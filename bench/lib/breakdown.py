"""The traced run's ``breakdown``: the device operations that took most of
the window, and the window's longest idle gaps, each labelled with the
innermost program span around its middle and its offset in the window
(device and host clocks agree to about a millisecond)."""
from __future__ import annotations

from typing import Optional

from bench.lib import trace as tr

TOP = 10


def _label(spans, t: float, lo: float) -> str:
    inside = [sp for sp in spans if sp[1] <= t <= sp[2]]
    what = max(inside, key=lambda sp: sp[3])[0] if inside \
        else "outside the trainer's spans"
    return f"{what} at +{t - lo:.3f}s"


def read(w) -> Optional[dict]:
    ops = w.chip_lines("XLA Ops")
    if not ops or not ops[0]:
        return None
    lo, hi = w.lo, w.lo + w.window_s
    return {"device_ops": tr.top_ops(ops[0], lo, hi, TOP),
            "idle_gaps": [[_label(w.spans, (s + e) / 2, lo), e - s]
                          for s, e in tr.gaps(ops[0], lo, hi)[:TOP]]}
