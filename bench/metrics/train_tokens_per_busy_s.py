"""Documents' tokens (padding left out) of every step in the window over
the seconds in which an operation ran on the device (the profiler's trace,
mean over the chips): the window's rate with the device's idle time left
out, so a run whose host holds the device back reads like the others."""


def read(w):
    busy = w.busy_s
    if not busy or not w.fetches:
        return None
    return w.tokens / busy
