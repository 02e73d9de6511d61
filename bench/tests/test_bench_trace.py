"""The trace reduction: busy union, idle share, gaps and top operations,
on hand-made intervals and on a small trace recorded on the CPU."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench.lib import trace as tr


def test_union_gaps_and_top_ops_by_hand():
    ev = [("a", 0.0, 10.0), ("b", 5.0, 15.0), ("a", 20.0, 30.0),
          ("c", 29.0, 31.0), ("b", 50.0, 60.0)]
    # window [2, 55]: busy [2,15] + [20,31] + [50,55] = 13 + 11 + 5
    assert tr.busy(ev, 2.0, 55.0) == pytest.approx(29.0)
    assert tr.gaps(ev, 2.0, 55.0) == [(31.0, 50.0), (15.0, 20.0)]
    assert tr.gaps(ev, 0.0, 70.0)[0] == (31.0, 50.0)
    assert tr.gaps(ev, 0.0, 70.0)[1] == (60.0, 70.0)
    seq = [("a", 0.0, 10.0), ("b", 10.0, 15.0), ("a", 20.0, 30.0),
           ("c", 30.0, 31.0)]
    top = tr.top_ops(seq, 0.0, 100.0, n=2)
    assert [n for n, _ in top] == ["a", "b"]
    assert top[0][1] == pytest.approx(20.0)


def test_enclosing_ops_keep_only_their_own_time():
    # a loop [0, 100] around two bodies; HLO text after " = " is dropped
    ev = [("%while.1 = (f32[8]) while(...)", 0.0, 100.0),
          ("%fusion.2 = f32[8] fusion(...)", 10.0, 40.0),
          ("%fusion.3 = f32[8] fusion(...)", 50.0, 90.0),
          ("%fusion.2 = f32[8] fusion(...)", 120.0, 130.0)]
    own = dict(tr.self_times(ev[:3]))
    assert own["%while.1 = (f32[8]) while(...)"] == pytest.approx(30.0)
    top = dict(tr.top_ops(ev, 0.0, 200.0))
    assert top == pytest.approx({"%fusion.3": 40.0, "%fusion.2": 40.0,
                                 "%while.1": 30.0})
    assert tr.busy(ev, 0.0, 200.0) == pytest.approx(110.0)


def test_reduction_of_a_recorded_cpu_trace(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench/align"):
        anchor = time.perf_counter_ns()
    t0 = time.perf_counter_ns()
    for _ in range(3):
        f(x).block_until_ready()
        time.sleep(0.02)
    t1 = time.perf_counter_ns()
    jax.profiler.stop_trace()

    prof = tr.load(str(tmp_path))
    off = anchor - tr.marker_ns(prof, "bench/align")
    lo, hi = t0 - off, t1 - off
    # the CPU runs the operations on the host's XLA threads
    host = tr.planes(prof, (tr.HOST_PLANE,))
    evs = [e for lines in host.values() for line, es in lines.items()
           if line.startswith("tf_XLA") for e in es]
    assert evs, "no operation events in the CPU trace"
    busy = tr.busy(evs, lo, hi)
    assert 0 < busy < hi - lo
    idle = 1 - busy / (hi - lo)
    assert 0.5 < idle < 1.0          # three sleeps of 20 ms dominate
    gaps = tr.gaps(evs, lo, hi)
    assert all(a[1] - a[0] >= b[1] - b[0] for a, b in zip(gaps, gaps[1:]))
    assert gaps[0][1] - gaps[0][0] > 15e6
    assert busy + sum(e - s for s, e in gaps) == pytest.approx(hi - lo)
    assert tr.top_ops(evs, lo, hi)[0][1] > 0
    assert tr.planes(prof, (tr.TPU_PLANE,)) == {}
    # clipped to a window and mapped to other times, every line at once
    clipped = tr.planes(prof, (tr.HOST_PLANE,), lo, hi,
                        to_time=lambda t: (t + off) * 1e-9)
    for lines in clipped.values():
        for es in lines.values():
            assert all(t0 * 1e-9 - 1e-6 <= s <= e <= t1 * 1e-9 + 1e-6
                       for _, s, e in es)


def test_missing_trace_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        tr.load(str(tmp_path))
