"""Process start to window start: imports, weights, engines, warm-up
(compiles or cache loads) and the warm-up traffic."""


def read(run):
    return run.setup_s
