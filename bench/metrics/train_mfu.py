"""Model FLOPs of the window's documents (forward and backward, attention
over each document's own length, nothing recomputed, padding not counted)
over the window's seconds times the chips' published bf16 peak, in %."""


def read(w):
    if w.peak is None or not w.fetches:
        return None
    flops = sum(w.flops([int(n) for n in f.lens]) for f in w.fetches)
    return 100.0 * flops / (w.window_s * w.chips * w.peak["bf16_flops_per_s"])
