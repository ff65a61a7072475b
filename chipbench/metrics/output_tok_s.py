"""Output tokens the engines produced inside the window over its length:
all the work over all the time."""


def read(run):
    w0, w1 = run.window
    return run.tokens_in_window / (w1 - w0) if w1 > w0 else None
