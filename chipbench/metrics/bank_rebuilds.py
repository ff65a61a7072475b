"""Bank rebuilds of all engines inside the window
(``ServingEngine.bank_rebuilds``)."""


def read(run):
    return run.counters.get("bank_rebuilds")
