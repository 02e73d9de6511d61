"""Seconds from the start of the process to the window's first fetch:
importing, planning the batches, building the trainer, making the weights,
and compiling or loading every program the window runs (host clock)."""


def read(w):
    return w.setup_s
