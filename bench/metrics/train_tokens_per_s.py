"""Documents' tokens (padding left out) of every step in the window, over
the window's seconds (host clock, first fetch to the closing fetch)."""


def read(w):
    return w.tokens / w.window_s
