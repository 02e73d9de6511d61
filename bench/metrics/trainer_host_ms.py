"""Host time of the trainer's loop per window step, from the program's own
spans: ``train/step`` less the ``train/block_until_ready`` inside it, so the
fetch, the host-to-device copy, the dispatch and the loss read-back."""


def read(w):
    if not w.traced:
        return None
    steps = [s for s in w.spans if s[0] == "train/step"]
    blocks = [s for s in w.spans if s[0] == "train/block_until_ready"]
    if not steps:
        return None
    host = 0.0
    for _, s, e, _ in steps:
        waited = sum(be - bs for _, bs, be, _ in blocks if bs >= s and be <= e)
        host += (e - s) - waited
    return 1e3 * host / len(steps)
