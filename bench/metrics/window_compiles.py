"""Programs compiled or loaded from the compile cache inside the window,
as JAX's backend-compile events count them (0 when set-up warmed all)."""


def read(w):
    return len(w.compiles) if w.traced else None
