"""repro.obs: tracer, metrics, events, projection monitor, trainer wiring."""
import json
import os
import threading

import numpy as np
import pytest

from repro import obs
from repro.core.profile import EpochLog
from repro.core.seqpoint import select_seqpoints
from repro.obs.events import EventSink
from repro.obs.metrics import MetricsRegistry, bucket_bound
from repro.obs.trace import NULL_SPAN, Tracer


@pytest.fixture
def tracer():
    """Fresh enabled tracer installed as the global one."""
    t = Tracer(enabled=True)
    prev = obs.set_tracer(t)
    yield t
    obs.set_tracer(prev)


@pytest.fixture
def sink(tmp_path):
    s = EventSink(str(tmp_path / "events.jsonl"), flush_every=1)
    prev = obs.set_sink(s)
    yield s
    obs.set_sink(prev)
    s.close()


# -------------------------------------------------------------------- trace


def test_span_nesting_records_depth_and_containment(tracer):
    with obs.span("outer", sl=128):
        assert tracer.current_span() == "outer"
        with obs.span("inner"):
            assert tracer.current_span() == "inner"
    assert tracer.current_span() is None
    by_name = {e["name"]: e for e in tracer.events}
    assert set(by_name) == {"outer", "inner"}
    outer, inner = by_name["outer"], by_name["inner"]
    assert inner["args"]["depth"] == 1
    assert outer["args"]["sl"] == 128
    # child fully contained in parent
    assert inner["ts"] >= outer["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6


def test_disabled_tracer_is_zero_cost_noop():
    t = Tracer(enabled=False)
    prev = obs.set_tracer(t)
    try:
        s1 = obs.span("a", x=1)
        s2 = obs.span("b")
        # one shared null span object: no allocation, no clock reads
        assert s1 is s2 is NULL_SPAN
        with s1:
            pass
        assert t.events == []
        assert s1.set(y=2) is NULL_SPAN
    finally:
        obs.set_tracer(prev)


def test_chrome_trace_export_roundtrips(tracer, tmp_path):
    with obs.span("train/step", step=3):
        with obs.span("train/step_fn"):
            pass
    path = tracer.export_chrome_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)                      # must be valid JSON
    names = [e["name"] for e in doc["traceEvents"]]
    assert sorted(names) == ["train/step", "train/step_fn"]
    for e in doc["traceEvents"]:
        assert e["ph"] == "X" and e["dur"] >= 0 and "pid" in e and "tid" in e


def test_traced_decorator_and_threads(tracer):
    @obs.traced("worker/fn")
    def fn():
        return 7

    th = threading.Thread(target=fn)
    th.start()
    th.join()
    assert fn() == 7
    events = [e for e in tracer.events if e["name"] == "worker/fn"]
    assert len(events) == 2
    assert len({e["tid"] for e in events}) == 2   # distinct thread ids


# ------------------------------------------------------------------ metrics


def test_histogram_log2_bucket_boundaries():
    # exact powers of two land on their own bound; everything else rounds up
    assert bucket_bound(1.0) == 1.0
    assert bucket_bound(2.0) == 2.0
    assert bucket_bound(1.0001) == 2.0
    assert bucket_bound(0.5) == 0.5
    assert bucket_bound(0.51) == 1.0
    assert bucket_bound(0.0) == 0.0
    assert bucket_bound(-3.0) == 0.0

    reg = MetricsRegistry()
    h = reg.histogram("t", sl=64)
    for v in (0.5, 1.0, 1.5, 2.0, 3.0):
        h.observe(v)
    assert h.buckets == {0.5: 1, 1.0: 1, 2.0: 2, 4.0: 1}
    assert h.count == 5 and h.min == 0.5 and h.max == 3.0
    assert h.cumulative() == [(0.5, 1), (1.0, 2), (2.0, 4), (4.0, 5)]


def test_registry_snapshot_prometheus_and_type_conflict():
    reg = MetricsRegistry()
    reg.counter("steps", job="train").inc(3)
    reg.gauge("depth").set(2)
    reg.histogram("lat_s", sl=32).observe(0.25)
    snap = reg.snapshot()
    assert snap["steps"][0]["value"] == 3
    assert snap["steps"][0]["labels"] == {"job": "train"}
    assert snap["lat_s"][0]["buckets"] == {"0.25": 1}
    json.loads(reg.to_json())                   # JSON-serializable
    prom = reg.to_prometheus()
    assert 'steps{job="train"} 3' in prom
    assert 'lat_s_bucket{sl="32",le="+Inf"} 1' in prom
    assert 'lat_s_count{sl="32"} 1' in prom
    with pytest.raises(TypeError):
        reg.gauge("steps", job="train")


# ------------------------------------------------------------------- events


def test_event_sink_flush_and_sequencing(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    s = EventSink(path, flush_every=2)
    s.emit("a", x=1)
    assert not os.path.exists(path)             # buffered
    s.emit("b")
    recs = [json.loads(l) for l in open(path)]  # flushed at 2
    assert [r["kind"] for r in recs] == ["a", "b"]
    assert [r["seq"] for r in recs] == [0, 1]
    assert all("ts" in r for r in recs)
    s.emit("c")
    s.close()                                   # close flushes the tail
    recs = [json.loads(l) for l in open(path)]
    assert [r["kind"] for r in recs] == ["a", "b", "c"]


def test_event_sink_rotation(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    s = EventSink(path, flush_every=1, max_bytes=200)
    for i in range(20):
        s.emit("fill", i=i, pad="x" * 40)
    s.close()
    assert os.path.exists(path + ".1")          # rotated generation
    for p in (path, path + ".1"):
        for line in open(p):
            json.loads(line)                    # every line parses


def test_module_event_noop_without_sink():
    prev = obs.set_sink(None)
    try:
        assert obs.event("anything", x=1) is None
    finally:
        obs.set_sink(prev)


# --------------------------------------------------------------- projection


def _synthetic_log(scale=1.0):
    log = EpochLog()
    for sl, rt, n in ((16, 0.1, 30), (32, 0.2, 20), (64, 0.4, 10)):
        for _ in range(n):
            log.append(sl, rt * scale)
    return log


def test_projection_monitor_exact_on_selection_log():
    log = _synthetic_log()
    sp = select_seqpoints(log)                   # all-unique: exact
    mon = obs.ProjectionMonitor(sp)
    mon.observe_log(log)
    rep = mon.report()
    assert rep.iterations == 60
    assert rep.rel_error < 1e-9
    assert rep.eq1_predicted == pytest.approx(sp.predicted)
    assert len(rep.per_sl) == 3
    for r in rep.per_sl:
        assert abs(r.residual) < 1e-12


def test_projection_monitor_detects_drift():
    sp = select_seqpoints(_synthetic_log())
    mon = obs.ProjectionMonitor(sp)
    mon.observe_log(_synthetic_log(scale=1.25))  # hardware got 25% slower
    rep = mon.report()
    assert rep.rel_error == pytest.approx(0.2, abs=1e-6)  # 1/1.25 short
    worst = rep.worst_sl()
    assert worst is not None and worst.residual > 0
    # per-SL: measured mean exceeds prediction by exactly 25%
    for r in rep.per_sl:
        assert r.measured_mean == pytest.approx(r.predicted * 1.25)


def test_collective_projection_report_aggregates():
    from repro.obs.projection import collective_projection_report

    records = [
        {"arch": "a", "shape": "s", "mesh": "16x16", "status": "ok",
         "projection": {"rel_error": 0.1, "analytic_wire_bytes": 1.0,
                        "measured_wire_bytes": 1.1}},
        {"arch": "b", "shape": "s", "mesh": "16x16", "status": "error"},
        {"arch": "c", "shape": "s", "mesh": "16x16", "status": "ok",
         "projection": {"rel_error": 0.4, "analytic_wire_bytes": 2.0,
                        "measured_wire_bytes": 1.2}},
    ]
    rep = collective_projection_report(records, error_bound=0.5)
    assert rep["num_cells"] == 2
    assert rep["max_rel_error"] == pytest.approx(0.4)
    assert rep["within_bound"] is True
    assert not collective_projection_report(
        records, error_bound=0.2)["within_bound"]


def test_analytic_wire_bytes_decode_uses_single_token():
    from repro.configs import get_model_config, get_shape
    from repro.dist.sharding import tp_activation_wire_bytes
    from repro.obs.projection import analytic_wire_bytes

    cfg = get_model_config("starcoder2-3b")
    decode = get_shape("decode_32k")
    a = analytic_wire_bytes(cfg, decode, parallelism="tp", dp_degree=16,
                            tp_degree=16)
    assert a["dp_grad"] == 0.0                   # no grads when serving
    # one token through the stack, regardless of the 32k cache
    expected = tp_activation_wire_bytes(cfg, decode.global_batch, 1, 16,
                                        training=False)
    assert a["tp_activation"] == pytest.approx(expected)
    assert a["tp_activation"] > 0
    assert a["total"] == pytest.approx(a["tp_activation"])


def test_analytic_wire_bytes_grad_dtype_and_zero_micro_reduces():
    from repro.configs import get_model_config, get_shape
    from repro.obs.projection import analytic_wire_bytes

    cfg = get_model_config("starcoder2-3b")
    train = get_shape("train_4k")
    base = analytic_wire_bytes(cfg, train, parallelism="tp", dp_degree=4,
                               tp_degree=4)
    bf16 = analytic_wire_bytes(cfg, train, parallelism="tp", dp_degree=4,
                               tp_degree=4, grad_dtype_bytes=2.0)
    assert bf16["dp_grad"] == pytest.approx(base["dp_grad"] / 2)
    assert bf16["tp_activation"] == pytest.approx(base["tp_activation"])
    micro = analytic_wire_bytes(cfg, train, parallelism="tp", dp_degree=4,
                                tp_degree=4, micro_reduces=4)
    assert micro["dp_grad"] == pytest.approx(4 * base["dp_grad"])


def test_cell_projection_micro_counted_normalizes_rolled_scan():
    # compile-mode HLO rolls the microbatch scan: measured stats contain
    # one microbatch body, so the analytic dp term must not be multiplied
    # by the full microbatch count
    from repro.configs import MeshConfig, RunConfig, get_model_config, \
        get_shape
    from repro.obs.projection import cell_collective_projection
    from repro.perfmodel.hlo import CollectiveStats

    cfg = get_model_config("starcoder2-3b")
    train = get_shape("train_4k")
    run = RunConfig(model=cfg, shape=train,
                    mesh=MeshConfig(shape=(4, 4), axes=("data", "model")),
                    fsdp=True, microbatches=4)
    assert run.zero_stage >= 3 and run.compute_dtype == "bfloat16"
    measured = CollectiveStats()
    measured.count["all-reduce"] = 1
    measured.buffer_bytes["all-reduce"] = 10**9
    measured.count["all-gather"] = 4
    measured.buffer_bytes["all-gather"] = 10**9
    rolled = cell_collective_projection(cfg, train, run, measured,
                                        micro_counted=1)
    full = cell_collective_projection(cfg, train, run, measured)
    assert rolled["micro_reduces"] == 4 and rolled["micro_counted"] == 1
    assert full["micro_counted"] == 4
    assert full["analytic_dp_bytes"] == \
        pytest.approx(4 * rolled["analytic_dp_bytes"])
    assert rolled["grad_dtype_bytes"] == 2.0
    # the claimed residual compares against all-reduce wire only; the
    # ZeRO all-gather stays in measured_wire_bytes but not in claimed
    assert rolled["measured_claimed_wire_bytes"] < \
        rolled["measured_reduce_wire_bytes"] <= rolled["measured_wire_bytes"]
    assert "rel_error_claimed" in rolled
    # spec-derived DP ring size overrides the param-count assumption
    shrunk = cell_collective_projection(cfg, train, run, measured,
                                        micro_counted=1,
                                        dp_reduce_elems=1000.0)
    assert shrunk["dp_reduce_elems"] == 1000.0
    assert shrunk["analytic_dp_bytes"] < rolled["analytic_dp_bytes"]


# ------------------------------------------------------- end-to-end trainer

TRAIN_SPANS = ("train/pulse", "train/step", "train/data_fetch", "train/h2d",
               "train/step_fn", "train/block_until_ready", "train/readback",
               "train/accept")


def _tiny_run(arch="starcoder2-3b", **over):
    from repro.configs import MeshConfig, OptimizerConfig, RunConfig, \
        ShapeConfig, StepKind, smoke_config

    cfg = smoke_config(arch).with_overrides(
        num_layers=2, d_model=64, vocab_size=256, **over)
    shape = ShapeConfig("tiny", seq_len=32, global_batch=8,
                        step=StepKind.TRAIN)
    return RunConfig(model=cfg, shape=shape,
                     mesh=MeshConfig(shape=(1,), axes=("data",)),
                     optimizer=OptimizerConfig(lr=1e-3, warmup_steps=2),
                     param_dtype="float32", compute_dtype="float32")


def _tiny_trainer(**kw):
    from repro.data.batching import DataIterator
    from repro.data.synthetic import IWSLT_LIKE
    from repro.models import Runtime, build_model
    from repro.train.trainer import Trainer

    run = _tiny_run(d_ff=128)
    data = DataIterator(IWSLT_LIKE, samples_per_epoch=256, batch_size=8,
                        vocab_size=run.model.vocab_size, granularity=8,
                        seed=1)
    model = build_model(run.model, Runtime.from_run(run))
    return Trainer(model, run, data, **kw)


def _profiled(tmp_path, fn):
    """Run ``fn`` under the JAX profiler; the host plane's events as
    (name, start ns, duration ns, stats), in start order."""
    import glob

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    return sorted(((e.name, float(e.start_ns), float(e.duration_ns),
                    dict(e.stats) if e.name.startswith("train/") else {})
                   for plane in ProfileData.from_file(path).planes
                   if plane.name.startswith("/host:CPU")
                   for line in plane.lines for e in line.events),
                  key=lambda ev: ev[1])


def _warm_trainer(steps):
    """A tiny trainer that has compiled every program of its next
    ``steps`` steps."""
    tr = _tiny_trainer(total_steps=8)
    pos = tr.data.state()
    tr.train(steps)
    tr.data.restore(pos)
    return tr


def test_trainer_emits_spans_metrics_and_straggler_events(tracer, sink):
    obs.metrics.reset()
    tr = _tiny_trainer(straggler_factor=1e-9, total_steps=8)
    rep = tr.train(5)
    assert rep.steps == 5

    names = [e["name"] for e in tracer.events]
    for expected in TRAIN_SPANS:
        assert names.count(expected) == 5, expected
    # step spans carry the padded SL attribute
    step_evs = [e for e in tracer.events if e["name"] == "train/step"]
    assert all("sl" in e["args"] for e in step_evs)
    assert [e["args"]["step"] for e in step_evs] == list(range(5))

    sink.flush()
    evs = [json.loads(l) for l in open(sink.path)]
    kinds = [e["kind"] for e in evs]
    assert kinds[0] == "train_start" and kinds[-1] == "train_end"
    stragglers = [e for e in evs if e["kind"] == "straggler"]
    assert len(stragglers) == rep.stragglers >= 1
    assert all({"step", "sl", "dt", "baseline"} <= set(e) for e in
               stragglers)

    snap = obs.metrics.snapshot()
    assert snap["train_steps_total"][0]["value"] == 5
    hist = snap["train_step_time_s"]
    assert sum(h["count"] for h in hist) == 5
    assert all("sl" in h["labels"] for h in hist)     # SL-keyed
    obs.metrics.reset()


@pytest.mark.parametrize("ckpt_every, drains", [
    (None, [("end", 11)]),
    (4, [("checkpoint", 3), ("checkpoint", 7), ("end", 11)]),
])
def test_trainer_counts_overlapped_steps_and_drains(tracer, tmp_path,
                                                    ckpt_every, drains):
    """Every step but those after a drain is dispatched with another in
    flight; a drain settles one step (checkpoint drains before steps 4 and
    8, then the end) in its own spans, outside any ``train/step``."""
    obs.metrics.reset()
    kw = {} if ckpt_every is None else {"ckpt_dir": str(tmp_path / "ck"),
                                        "ckpt_every": ckpt_every}
    _tiny_trainer(total_steps=12, **kw).train(12)
    snap = obs.metrics.snapshot()
    assert snap["train_steps_overlapped_total"][0]["value"] == \
        12 - len(drains)
    by_reason = {r["labels"]["reason"]: r["value"]
                 for r in snap["train_pipeline_drains_total"]}
    assert by_reason == {r: sum(d[0] == r for d in drains) for r, _ in drains}
    evs = tracer.events
    drain_evs = [e for e in evs if e["name"] == "train/drain"]
    assert [(e["args"]["reason"], e["args"]["step"]) for e in drain_evs] \
        == drains

    def within(e, d):
        return d["ts"] <= e["ts"] and e["ts"] + e["dur"] <= d["ts"] + d["dur"]

    for d in drain_evs:
        assert sorted(e["name"] for e in evs if e is not d and within(e, d)) \
            == ["train/accept", "train/block_until_ready", "train/readback"]
        assert not any(within(d, e) for e in evs if e["name"] == "train/step")
    obs.metrics.reset()


def test_trainer_spans_reach_the_profiler_trace(tracer, tmp_path):
    """Enabled spans are also TraceMe annotations: each of the loop's spans
    sits on the profiler's host plane once per step, ``train/step`` with
    its step number, and the plane's spans are the tracer's own."""
    tr = _warm_trainer(4)
    tracer.clear()
    host = _profiled(tmp_path, lambda: tr.train(4))
    on_plane = [ev for ev in host if ev[0].startswith("train/")]
    for name in TRAIN_SPANS:
        assert sum(ev[0] == name for ev in on_plane) == 4, name
    assert [st["step_num"] for n, _, _, st in on_plane
            if n == "train/step"] == [0, 1, 2, 3]
    mine = sorted(tracer.events, key=lambda e: e["ts"])
    assert [e["name"] for e in mine] == [ev[0] for ev in on_plane]
    # the tracer's clock reads enclose the annotation, tightly
    for e, (_, _, dur_ns, _) in zip(mine, on_plane):
        assert dur_ns * 1e-3 <= 1.001 * e["dur"] + 5.0, e["name"]
        assert e["dur"] <= 1.1 * dur_ns * 1e-3 + 2e3, e["name"]


def test_disabled_tracer_writes_nothing_to_the_profiler_trace(tmp_path):
    assert not obs.tracing_enabled()
    assert obs.span("train/step") is NULL_SPAN
    assert obs.step_span("train/step", 3) is NULL_SPAN
    tr = _warm_trainer(2)
    host = _profiled(tmp_path, lambda: tr.train(2))
    assert not [ev for ev in host if ev[0].startswith("train/")]
    assert obs.get_tracer().events == []


@pytest.mark.parametrize("arch", ["starcoder2-3b", "rwkv6-3b"])
def test_train_step_ops_carry_the_layer_scopes(arch):
    """The compiled step names its ops by layer kind: embedding, mixer
    (attention or RWKV time-mix), FFN (or channel-mix), head and loss, and
    the optimizer, forward and backward alike."""
    import re

    import jax
    import jax.numpy as jnp

    from repro.models import Runtime, build_model
    from repro.train.train_step import build_train_step, init_train_state

    run = _tiny_run(arch)
    model = build_model(run.model, Runtime.from_run(run))
    state = init_train_state(model, run, jax.random.PRNGKey(0))
    batch = {k: jnp.zeros((8, 32), jnp.int32) for k in ("tokens", "labels")}
    hlo = jax.jit(build_train_step(model, run)).lower(
        state, batch).compile().as_text()
    paths = [p.split("/") for p in re.findall(r'op_name="([^"]*)"', hlo)]

    def bare(part):                  # transpose(jvp(ffn)) -> ffn
        while re.fullmatch(r"[\w-]+\(.*\)", part):
            part = part[part.index("(") + 1:-1]
        return part

    for scope in ("embed", "mixer", "ffn", "head_loss", "optimizer"):
        assert any(scope in map(bare, parts) for parts in paths), scope
    for scope in ("embed", "mixer", "ffn", "head_loss"):
        assert any(scope in map(bare, parts) and
                   any(p.startswith("transpose(") for p in parts)
                   for parts in paths), f"no backward op in {scope}"


def test_trainer_disabled_obs_keeps_log_identical():
    """With obs off (default), training still logs the epoch normally and
    no trace events or sink writes happen."""
    assert obs.get_sink() is None and not obs.tracing_enabled()
    tr = _tiny_trainer(total_steps=4)
    rep = tr.train(3)
    assert rep.steps == 3 and tr.epoch_log.num_iterations == 3
    assert obs.get_tracer().events == []


# ------------------------------------------------------- live scrape endpoint


def test_serve_http_scrapes_live_metrics():
    """The background endpoint renders a fresh to_prometheus() per scrape
    (live values, not snapshot-at-exit) and shuts down cleanly."""
    import urllib.request

    reg = MetricsRegistry()
    reg.counter("scrape_demo_total", sl=64).inc(2)
    with obs.serve_http(registry=reg) as srv:
        assert srv.port > 0
        body = urllib.request.urlopen(srv.url, timeout=5).read().decode()
        assert '# TYPE scrape_demo_total counter' in body
        assert 'scrape_demo_total{sl="64"} 2' in body
        # live: a later increment shows up on the next scrape
        reg.counter("scrape_demo_total", sl=64).inc()
        body = urllib.request.urlopen(srv.url, timeout=5).read().decode()
        assert 'scrape_demo_total{sl="64"} 3' in body
        # index points at /metrics; unknown paths 404
        idx = urllib.request.urlopen(
            f"http://{srv.addr}:{srv.port}/", timeout=5).read().decode()
        assert "/metrics" in idx
        with pytest.raises(Exception):
            urllib.request.urlopen(
                f"http://{srv.addr}:{srv.port}/nope", timeout=5)
