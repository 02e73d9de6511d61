"""Window steps in which the first chip idled 25 ms or more inside the
step's ``train/block_until_ready``: a finished step noticed that late is a
stall, not launch latency."""
from bench.metrics.device_idle_sync_share import idle_inside

STALL_S = 0.025


def read(w):
    idle = idle_inside(w)
    if idle is None:
        return None
    return sum(t >= STALL_S for t in idle[0])
