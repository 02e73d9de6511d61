"""The readers that split device idle time by what the host was doing, on
hand-made windows: idle inside the trainer's ``train/block_until_ready``
spans (read from the trace's host plane) against the rest."""
import numpy as np
import pytest

from bench.lib.harness import Fetch, Window, load_reader

SYNC = ("device_idle_sync_share", "device_idle_host_share", "sync_stalls")


def _window(host_events, ops=None, chips=1, traced=True):
    """1 s on chip 0: busy but for a 50 ms gap at 0.30, a 30 ms one at
    0.60 and 100 ms at the end."""
    ops = ops or [("%fusion.1 = f", 0.0, 0.30), ("%fusion.2 = g", 0.35, 0.60),
                  ("%fusion.1 = f", 0.63, 0.90)]
    planes = {"/device:TPU:0": {"XLA Ops": ops},
              "/host:CPU": {"python": host_events,
                            "tf_XLA": [("x", 0.0, 1.0)]}}
    if chips == 2:
        planes["/device:TPU:1"] = {"XLA Ops": [("%fusion.1 = f", 0.0, 1.0)]}
    return Window(setup_s=1.0, window_s=1.0,
                  fetches=[Fetch(0.0, 16, np.array([16]))], chips=chips,
                  flops=len, peak=None, compiles=[], traced=traced,
                  planes=planes if traced else {})


def test_shares_split_idle_time_and_sum_to_the_idle_share():
    # a step's sync [0.28, 0.34] holds 40 ms of the first gap; TraceMe
    # metadata after '#' is not part of the name
    w = _window([("train/step#step_num=1,_r=1#", 0.0, 0.36),
                 ("train/block_until_ready", 0.28, 0.34),
                 ("train/block_until_ready#x=1#", 0.59, 0.605),
                 ("train/readback", 0.34, 0.345)])
    sync = load_reader("device_idle_sync_share")(w)
    host = load_reader("device_idle_host_share")(w)
    idle = load_reader("device_idle_share")(w)
    assert sync == pytest.approx(100 * (0.04 + 0.005))
    assert idle == pytest.approx(100 * (0.05 + 0.03 + 0.10))
    assert sync + host == pytest.approx(idle)


def test_shares_average_over_the_chips_as_the_idle_share_does():
    w = _window([("train/block_until_ready", 0.25, 0.40)], chips=2)
    sync = load_reader("device_idle_sync_share")(w)
    host = load_reader("device_idle_host_share")(w)
    assert sync == pytest.approx(100 * 0.05 / 2)
    assert sync + host == pytest.approx(load_reader("device_idle_share")(w))


def test_a_30ms_wait_is_a_stall_and_a_5ms_one_is_not():
    ops = [("%fusion.1 = f", 0.0, 0.30), ("%fusion.2 = g", 0.33, 0.60),
           ("%fusion.1 = f", 0.605, 1.0)]
    w = _window([("train/block_until_ready", 0.10, 0.331),
                 ("train/block_until_ready", 0.50, 0.61)], ops=ops)
    assert load_reader("sync_stalls")(w) == 1
    w = _window([("train/block_until_ready", 0.50, 0.61)], ops=ops)
    assert load_reader("sync_stalls")(w) == 0


def test_a_gap_across_two_spans_counts_in_each_only_its_part():
    w = _window([("train/block_until_ready", 0.29, 0.32),
                 ("train/block_until_ready", 0.33, 0.36)])
    sync = load_reader("device_idle_sync_share")(w)
    assert sync == pytest.approx(100 * (0.02 + 0.02))
    assert load_reader("sync_stalls")(w) == 0


@pytest.mark.parametrize("name", SYNC)
def test_nothing_without_a_trace_or_without_the_spans(name):
    read = load_reader(name)
    assert read(_window([], traced=False)) is None
    # a program that writes no annotations (its host plane lacks the span)
    assert read(_window([("bench/align", 0.0, 0.001)])) is None
    # a trace with the spans but no device operations
    w = _window([("train/block_until_ready", 0.1, 0.2)])
    w.planes["/device:TPU:0"] = {}
    assert read(w) is None
