"""Share of the window in which no operation ran on the device, from the
profiler trace: 1 - union of the ``XLA Ops`` intervals / window, in %."""


def read(w):
    if w.busy_s is None:
        return None
    return 100.0 * (1.0 - w.busy_s / w.window_s)
