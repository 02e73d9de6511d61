"""95th percentile over the window of the device time of one run of the
jitted train step (its ``XLA Modules`` events in the profiler trace)."""

import statistics


def read(w):
    mods = w.chip_lines("XLA Modules")
    runs = [e - s for name, s, e in (mods[0] if mods else [])
            if "train_step" in name]
    if len(runs) < 20:
        return None
    return 1e3 * statistics.quantiles(runs, n=20)[-1]
