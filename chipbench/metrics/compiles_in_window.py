"""Programs compiled, or loaded from the persistent compile cache,
inside the window: ``jax.monitoring`` reports a backend compile for
each, a cache hit being one that was loaded."""


def read(run):
    return run.counters.get("compiles")
