"""Share of the window in which the device idled while the host sat in the
trainer's ``train/block_until_ready`` span, in %: the step's launch latency
and a finished step noticed late. The spans are read from the profiler
trace's host plane (the program writes its spans there as TraceMe
annotations), so they and the device's ``XLA Ops`` share one clock. With
``device_idle_host_share`` it sums to ``device_idle_share``."""
from bisect import bisect_left

from bench.lib import trace as tr

SPAN = "train/block_until_ready"


def host_spans(w, name):
    """(start, end) of the host plane's events named ``name`` (TraceMe
    metadata after a ``#`` left out), in time order."""
    return sorted((s, e) for plane, lines in w.planes.items()
                  if plane.startswith(tr.HOST_PLANE)
                  for evs in lines.values()
                  for n, s, e in evs if n.split("#", 1)[0] == name)


def idle_inside(w, name=SPAN):
    """Per chip, the idle seconds inside each span ``name``; None when the
    trace has no device operations or no such span."""
    spans = host_spans(w, name)
    ops = w.chip_lines("XLA Ops")
    if not spans or not ops or not ops[0]:
        return None
    lo, hi = w.lo, w.lo + w.window_s
    out = []
    for chip_ops in ops:
        gaps = sorted(tr.gaps(chip_ops, lo, hi))
        starts = [g[0] for g in gaps]
        per_span = []
        for s, e in spans:
            idle, i = 0.0, bisect_left(starts, e) - 1
            while i >= 0 and gaps[i][1] > s:
                idle += min(e, gaps[i][1]) - max(s, gaps[i][0])
                i -= 1
            per_span.append(idle)
        out.append(per_span)
    return out


def read(w):
    idle = idle_inside(w)
    if idle is None:
        return None
    return 100.0 * sum(map(sum, idle)) / len(idle) / w.window_s
