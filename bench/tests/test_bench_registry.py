"""The harness finds everything by name: peaks by device kind, readers by
metric name, configuration and traffic files by cell."""
import json
import os

import numpy as np
import pytest

from bench.lib import breakdown
from bench.lib.harness import (ROOT, Fetch, Window, cell_files, load_reader,
                               peak_table)
from bench.lib.traffic import make_epoch


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        peak_table("TPU v99 imaginary")


def test_known_device_kind_has_its_peaks():
    assert peak_table("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_every_metric_and_cell_resolves():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(load_reader(m["name"]))
    for cell in bench["workloads"]:
        _, c, cfgspec, traffic = cell_files(cell["name"])
        assert cfgspec["name"] == c["config"]
        assert traffic["granularity"] > 0


def test_a_metric_is_added_by_a_file_alone(tmp_path):
    """A new per-layer metric is one reader file named after it."""
    (tmp_path / "bench" / "metrics").mkdir(parents=True)
    (tmp_path / "bench" / "metrics" / "steps.count.py").write_text(
        "def read(w):\n    return len(w.fetches)\n")
    read = load_reader("steps.count", root=str(tmp_path))
    w = Window(setup_s=1.0, window_s=2.0, fetches=[1, 2, 3], chips=1,
               flops=len, peak=None, compiles=[])
    assert read(w) == 3


def test_readers_return_nothing_without_a_trace():
    w = Window(setup_s=1.0, window_s=2.0, fetches=[], chips=1, flops=len,
               peak=None, compiles=[])
    for name in ("device_idle_share", "device_step_p95_ms",
                 "trainer_host_ms", "window_compiles", "train_mfu",
                 "train_tokens_per_busy_s"):
        assert load_reader(name)(w) is None


def _traced_window():
    """A window of 10 s on two chips: chip 0 runs a loop around two
    fusions and idles 4 s, chip 1 idles 6 s."""
    ops0 = [("%while.1 = loop", 0.0, 4.0), ("%fusion.1 = f", 0.5, 2.0),
            ("%fusion.2 = g", 2.0, 3.5), ("%fusion.1 = f", 8.0, 10.0)]
    ops1 = [("%fusion.1 = f", 0.0, 4.0)]
    return Window(setup_s=1.0, window_s=10.0,
                  fetches=[Fetch(0.0, 16, np.array([16]))], chips=2,
                  flops=len, peak=None, compiles=[], traced=True,
                  spans=[("train/step", 3.9, 9.0, 0),
                         ("train/block_until_ready", 5.0, 8.5, 1)],
                  planes={"/device:TPU:0": {"XLA Ops": ops0},
                          "/device:TPU:1": {"XLA Ops": ops1},
                          "/host:CPU": {"main/1": [("x", 0.0, 1.0)]}})


def test_tokens_per_busy_second_leave_the_idle_time_out():
    # chip 0 is busy 6 s of the 10, chip 1 4 s: 16 tokens over 5 s
    assert load_reader("train_tokens_per_busy_s")(_traced_window()) == 3.2


def test_a_device_op_metric_is_added_by_a_file_alone(tmp_path):
    """A reader of device operations needs its file and nothing else: the
    window hands it every line of the trace, per chip."""
    (tmp_path / "bench" / "metrics").mkdir(parents=True)
    (tmp_path / "bench" / "metrics" / "fusion_share.py").write_text(
        "def read(w):\n"
        "    ops = w.chip_lines('XLA Ops')\n"
        "    if not ops:\n"
        "        return None\n"
        "    t = sum(e - s for n, s, e in ops[0] if 'fusion' in n)\n"
        "    return 100.0 * t / w.window_s\n")
    read = load_reader("fusion_share", root=str(tmp_path))
    w = _traced_window()
    assert read(w) == pytest.approx(50.0)
    assert w.busy_s == pytest.approx((6.0 + 4.0) / 2)
    assert load_reader("device_idle_share")(w) == pytest.approx(50.0)


def test_breakdown_reads_the_window():
    b = breakdown.read(_traced_window())
    assert b["device_ops"] == [["%fusion.1", pytest.approx(3.5)],
                               ["%fusion.2", pytest.approx(1.5)],
                               ["%while.1", pytest.approx(1.0)]]
    assert b["idle_gaps"] == [
        ["train/block_until_ready at +6.000s", pytest.approx(4.0)]]
    w = _traced_window()
    w.planes = {}
    assert breakdown.read(w) is None


def test_a_traffic_kind_is_added_by_a_file_alone(tmp_path):
    (tmp_path / "bench" / "kinds").mkdir(parents=True)
    (tmp_path / "bench" / "kinds" / "fixed_len.py").write_text(
        "class Epoch:\n"
        "    def __init__(self, spec, vocab_size, seed):\n"
        "        self.padded = [spec['sl']] * spec['batches']\n")
    ep = make_epoch({"kind": "fixed_len", "sl": 64, "batches": 3}, 10, 1,
                    root=str(tmp_path))
    assert ep.padded == [64, 64, 64]
    with pytest.raises(KeyError, match="no generator"):
        make_epoch({"kind": "no_such_kind"}, 10, 1, root=str(tmp_path))
