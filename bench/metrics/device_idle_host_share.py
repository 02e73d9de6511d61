"""Share of the window in which the device idled while the host was not in
``train/block_until_ready``, in %: the trainer's host loop (read-back,
bookkeeping, the next fetch, copy and dispatch). ``device_idle_share`` less
``device_idle_sync_share``."""
from bench.metrics.device_idle_sync_share import read as sync_share


def read(w):
    sync = sync_share(w)
    if sync is None:
        return None
    return 100.0 * (1.0 - w.busy_s / w.window_s) - sync
