"""Bring-up smoke run of the system's main paths on a TPU.

    python chip_smoke.py             # one chip: phases A, B and C
    python chip_smoke.py --chips 4   # four chips: the sharded train step

Phase A trains starcoder2-3b at its published widths, cut to 2 of its 30
layers, through the launcher's own trainer (``repro.launch.train``):
variable padded SLs, per-iteration SL logging and a SeqPoint selection.
Phase B serves 8 requests on the full 30-layer model in bfloat16 through
``ServeEngine.serve()``. Phase C runs each Pallas kernel once, compiled
(``interpret=False``), at a real model's widths and compares it with its
reference. Weights and data are random, made from a fixed seed.

``--chips 4`` runs only the phase-A train step under the production
sharding rules on a (data 2, model 2) mesh and compares its loss and
gradient norm with the same step on one chip in this process.

Earlier lines report what each phase found. The last line of stdout is one
JSON object naming the device, printed only when every phase ran and every
check held. Without a TPU the script exits non-zero before any phase. It
runs in one process and starts no other.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
SEED = 0


class CompileLog:
    """Counts XLA compilations (and their seconds) per jitted function."""

    def __init__(self):
        self.events: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == BACKEND_COMPILE:
            self.events.append((kw.get("fun_name", "?"), duration))

    def mark(self) -> int:
        return len(self.events)

    def since(self, mark: int, name: str | None = None) -> tuple:
        ev = [d for n, d in self.events[mark:]
              if name is None or n == f"jit({name})"]
        return len(ev), float(sum(ev))


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    print(f"  check {'ok' if ok else 'FAILED'}: {what}", flush=True)
    if not ok:
        raise CheckFailed(what)


def peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def starcoder2():
    from repro.configs import get_model_config

    return get_model_config("starcoder2-3b")


# ---------------------------------------------------------------------------
# phase A: training through the launcher's trainer


def phase_train(cfg, full_layers: int, *, batch: int, seq: int, steps: int,
                log: CompileLog) -> None:
    from repro.launch.train import build_trainer

    trainer = build_trainer(cfg, seq=seq, batch=batch, steps=steps)
    shapes = jax.eval_shape(trainer.model.init, jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    n_outer = int(np.prod(shapes["embed"].shape)) + int(
        np.prod(shapes["lm_head"].shape))
    dev = jax.devices()[0]
    print(f"phase A: train {cfg.name} widths d_model={cfg.d_model} "
          f"d_ff={cfg.d_ff} heads={cfg.num_heads}/{cfg.num_kv_heads} "
          f"vocab={cfg.vocab_size}, num_layers cut {full_layers}->"
          f"{cfg.num_layers}, {n_params:,} params, float32, batch {batch}, "
          f"seq <= {seq}, on {dev.device_kind}", flush=True)
    print(f"  note: at {cfg.num_layers} layers the embedding and head hold "
          f"{n_outer / 1e6:.0f} M of {n_params / 1e6:.0f} M parameters, so "
          "host overhead is a larger share of a step than at full depth",
          flush=True)
    mark = log.mark()
    t0 = time.perf_counter()
    rep = trainer.train(steps)
    wall = time.perf_counter() - t0
    sls = [int(s) for s in trainer.epoch_log.seq_lens()]
    n_comp, comp_s = log.since(mark, "train_step")
    print(f"  steps={rep.steps} wall_s={wall:.3f} padded_sls={sls}",
          flush=True)
    print(f"  step_s={[round(t, 4) for t in rep.step_times]} (a step at a "
          "new SL includes its compile)", flush=True)
    print(f"  losses={[round(x, 4) for x in rep.losses]}", flush=True)
    print(f"  distinct_sls={len(set(sls))} train_step_compiles={n_comp} "
          f"compile_s={comp_s:.3f} peak_bytes_in_use={peak_bytes(dev)}",
          flush=True)
    sp = trainer.seqpoints(error_threshold=0.05)
    print(f"  seqpoints={sp.num_points} sls={sp.seq_lens} "
          f"weights={[round(float(w), 3) for w in sp.weights]} "
          f"error={100 * sp.error:.3f}%", flush=True)
    ln_v = math.log(cfg.vocab_size)
    check(rep.steps == steps and len(rep.losses) == steps,
          f"{steps} trainer steps ran")
    check(all(math.isfinite(x) for x in rep.losses), "losses finite")
    check(abs(rep.losses[0] - ln_v) < 1.0,
          f"first loss {rep.losses[0]:.4f} within 1.0 of ln(vocab) "
          f"{ln_v:.4f} (random init, logits of unit scale)")
    check(sp.num_points >= 1, "SeqPoint selection made")


# ---------------------------------------------------------------------------
# phase B: serving


def phase_serve(cfg, *, n_requests: int, prompt_range: tuple, new_tokens: int,
                batch: int, max_len: int, seed: int, log: CompileLog) -> None:
    from repro.data.synthetic import sample_tokens
    from repro.models import Runtime, build_model
    from repro.serve import Request, ServeEngine

    dev = jax.devices()[0]
    model = build_model(cfg, Runtime(param_dtype=jnp.bfloat16,
                                     compute_dtype=jnp.bfloat16))
    mark = log.mark()
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    rng = np.random.RandomState(seed)
    lens = rng.randint(prompt_range[0], prompt_range[1] + 1, size=n_requests)
    reqs = [Request(prompt=sample_tokens(rng, (int(n),), cfg.vocab_size
                                         ).astype(np.int32),
                    max_new_tokens=new_tokens) for n in lens]
    print(f"phase B: serve {cfg.name} {cfg.num_layers} layers, "
          f"{n_params:,} params, bfloat16, {n_requests} requests, prompts "
          f"{sorted(int(n) for n in lens)}, {new_tokens} new tokens each, "
          f"batch {batch}, max_len {max_len}, on {dev.device_kind}",
          flush=True)
    engine = ServeEngine(model, params, batch_size=batch, max_len=max_len)
    t0 = time.perf_counter()
    stats = engine.serve(reqs)
    wall = time.perf_counter() - t0
    n_pre, s_pre = log.since(mark, "prefill")
    n_dec, s_dec = log.since(mark, "decode_step")
    n_all, s_all = log.since(mark)
    print(f"  wall_s={wall:.3f} tokens_out={stats.tokens_out} "
          f"prefills={stats.prefills} decode_steps={stats.decode_steps} "
          f"finished={stats.n_finished}", flush=True)
    print(f"  compiles: prefill={n_pre} ({s_pre:.3f} s) decode={n_dec} "
          f"({s_dec:.3f} s) all={n_all} ({s_all:.3f} s), "
          f"peak_bytes_in_use={peak_bytes(dev)}", flush=True)
    check(stats.n_finished == n_requests and all(
        len(r.output) == new_tokens and not r.curtailed and not r.shed
        for r in reqs), f"all {n_requests} requests got {new_tokens} tokens")
    check(all(0 <= t < cfg.vocab_size for r in reqs for t in r.output),
          f"every token id in [0, {cfg.vocab_size})")


# ---------------------------------------------------------------------------
# phase C: the Pallas kernels, compiled

# Flash attention at starcoder2-3b (24 query / 2 KV heads, head dim 128),
# the LSTM cell at GNMT (batch 128, 1024 units), the selective scan at
# jamba-v0.1-52b (d_inner 8192, N 16), WKV at rwkv6-3b (40 heads x 64).
KERNEL_WIDTHS = {"seq": 2048, "flash_heads": (24, 2), "flash_dh": 128,
                 "lstm": (128, 1024, 1024), "mamba": (8192, 16),
                 "wkv": (40, 64)}


def _kernel_cases(w: dict, key):
    from repro.kernels.flash_attention.kernel import flash_attention_fwd
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.lstm_cell.kernel import lstm_cell_fwd
    from repro.kernels.lstm_cell.ref import lstm_cell_ref
    from repro.kernels.mamba_scan.kernel import mamba_scan_fwd
    from repro.kernels.mamba_scan.ref import mamba_scan_ref
    from repro.kernels.rwkv6_wkv.kernel import wkv6_fwd
    from repro.kernels.rwkv6_wkv.ref import wkv6_ref

    s = w["seq"]
    ks = iter(jax.random.split(key, 32))
    normal = lambda shape, dt=jnp.float32: jax.random.normal(  # noqa: E731
        next(ks), shape, jnp.float32).astype(dt)

    hq, hkv = w["flash_heads"]
    qkv = (normal((hq, s, w["flash_dh"]), jnp.bfloat16),
           normal((hkv, s, w["flash_dh"]), jnp.bfloat16),
           normal((hkv, s, w["flash_dh"]), jnp.bfloat16))
    yield ("flash_attention", qkv,
           lambda q, k, v: flash_attention_fwd(q, k, v, causal=True),
           lambda q, k, v: attention_ref(q, k, v, causal=True),
           2e-2, "bf16 in and out: one bf16 ulp is 2^-8..2^-7 of a value")

    b, d, h = w["lstm"]
    lstm = (normal((b, d + h)), normal((4, d + h, h)) / math.sqrt(d + h),
            normal((4, h)) * 0.1, normal((b, h)))
    yield ("lstm_cell", lstm, lstm_cell_fwd, lstm_cell_ref,
           2e-2, "f32 matmul over K=2048 may take one bf16 MXU pass "
           "(Mosaic's default precision): ~1e-2 worst-case gate error")

    dim, n = w["mamba"]
    mamba = (normal((1, s, dim)),
             jax.nn.softplus(normal((1, s, dim)) - 2.0),
             -jnp.exp(normal((dim, n)) * 0.3),
             normal((1, s, n)), normal((1, s, n)), normal((dim,)))
    yield ("mamba_scan", mamba, mamba_scan_fwd, mamba_scan_ref,
           1e-3, "f32 recurrence on both sides, no matmul; exp and "
           "summation order differ")

    heads, dh = w["wkv"]
    wkv = (normal((heads, s, dh)), normal((heads, s, dh)),
           normal((heads, s, dh)),
           -jnp.exp(jnp.clip(normal((heads, s, dh)), -8.0, 0.0)),
           normal((heads, dh)))
    yield ("wkv6", wkv, wkv6_fwd, wkv6_ref,
           1e-3, "fp32-precision matmuls on both sides; chunked vs "
           "sequential summation over S")


def phase_kernels(widths: dict, *, seed: int, log: CompileLog) -> None:
    print(f"phase C: Pallas kernels compiled (interpret=False) at {widths} "
          f"on {jax.devices()[0].device_kind}", flush=True)
    for name, args, kern, ref, tol, why in _kernel_cases(
            widths, jax.random.PRNGKey(seed)):
        mark = log.mark()
        out = jax.block_until_ready(jax.jit(kern)(*args))
        n_comp, comp_s = log.since(mark)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref)(*args)
        outs = out if isinstance(out, (tuple, list)) else (out,)
        wants = want if isinstance(want, (tuple, list)) else (want,)
        err = excess = 0.0
        for o, r in zip(outs, wants):
            o, r = np.asarray(o, np.float32), np.asarray(r, np.float32)
            diff = np.abs(o - r)
            err = max(err, float(diff.max()))
            excess = max(excess, float((diff - tol * np.abs(r)).max()))
        print(f"  {name}: shapes={[tuple(a.shape) for a in args]} "
              f"compile_s={comp_s:.3f} (compiles={n_comp}) "
              f"max_abs_err={err:.3e} tol=atol {tol:g} + rtol {tol:g} "
              f"({why})", flush=True)
        check(all(np.isfinite(np.asarray(o, np.float32)).all()
                  for o in outs), f"{name} output finite")
        check(excess <= tol, f"{name} within tolerance of its reference")


# ---------------------------------------------------------------------------
# four chips: the phase-A step under the production sharding rules


def phase_sharded(cfg, *, batch: int, seq: int, devices,
                  log: CompileLog) -> None:
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs import MeshConfig
    from repro.dist.sharding import batch_specs
    from repro.launch.mesh import make_mesh
    from repro.launch.train import build_trainer
    from repro.models import Runtime, build_model
    from repro.train.train_step import build_train_step, init_train_state, \
        train_state_specs

    trainer = build_trainer(cfg, seq=seq, batch=batch, steps=1)
    model, run = trainer.model, trainer.run
    tokens, labels, sl = next(iter(trainer.data))
    host_batch = {"tokens": tokens, "labels": labels}
    run4 = dataclasses.replace(
        run, mesh=MeshConfig(shape=(2, 2), axes=("data", "model")),
        fsdp=True)
    model4 = build_model(cfg, Runtime.from_run(run4))
    mesh = make_mesh(run4.mesh, devices)
    print(f"sharded: train step of {cfg.name} ({cfg.num_layers} layers, "
          f"float32) on a {dict(mesh.shape)} mesh of "
          f"{devices[0].device_kind}, fsdp=True, batch {batch} x SL {sl}, "
          "vs the same step on one chip", flush=True)

    def named(spec_tree):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                            is_leaf=lambda x: isinstance(x, P))

    rng = jax.random.PRNGKey(run.seed)
    state_sh = named(train_state_specs(
        jax.eval_shape(lambda r: init_train_state(model4, run4, r), rng),
        run4))
    batch_sh = named(batch_specs(host_batch, run4.mesh, run4.shape,
                                 run4.parallelism))
    mark = log.mark()
    with mesh:
        state = jax.jit(lambda r: init_train_state(model4, run4, r),
                        out_shardings=state_sh)(rng)
        host_state = jax.device_get(state)
        step4 = jax.jit(build_train_step(model4, run4),
                        out_shardings=(state_sh, None), donate_argnums=0)
        state, m4 = step4(state, jax.device_put(host_batch, batch_sh))
        loss4, gn4 = float(m4["loss"]), float(m4["grad_norm"])
    n_comp, comp_s = log.since(mark, "train_step")
    for d in devices:
        st = d.memory_stats() or {}
        print(f"  {d}: bytes_in_use={st.get('bytes_in_use')} "
              f"peak_bytes_in_use={st.get('peak_bytes_in_use')}", flush=True)
    del state, m4

    one = devices[0]
    step1 = jax.jit(build_train_step(model, run), donate_argnums=0)
    _, m1 = step1(jax.device_put(host_state, one),
                  jax.device_put(host_batch, one))
    loss1, gn1 = float(m1["loss"]), float(m1["grad_norm"])
    print(f"  sharded: loss={loss4!r} grad_norm={gn4!r} "
          f"(compile_s={comp_s:.3f}, compiles={n_comp})", flush=True)
    print(f"  one chip: loss={loss1!r} grad_norm={gn1!r}", flush=True)
    print(f"  peak_bytes_in_use per device: "
          f"{[peak_bytes(d) for d in devices]}", flush=True)
    rtol = 1e-3
    why = ("same params and batch; only the f32 summation order of the "
           "sharded partial sums differs")
    check(abs(loss4 - loss1) <= rtol * abs(loss1),
          f"loss matches one chip within rtol {rtol:g} ({why})")
    check(abs(gn4 - gn1) <= rtol * abs(gn1),
          f"grad norm matches one chip within rtol {rtol:g}")
    check(all(peak_bytes(d) > 0 for d in devices),
          "every device reports nonzero peak_bytes_in_use")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "nothing run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    log = CompileLog()
    try:
        full = starcoder2()
        cut = full.with_overrides(num_layers=2)
        if args.chips == 4:
            phase_sharded(cut, batch=8, seq=512, devices=devices[:4],
                          log=log)
        else:
            phase_train(cut, full.num_layers, batch=8, seq=512, steps=8,
                        log=log)
            phase_serve(full, n_requests=8,
                        prompt_range=(64, 512), new_tokens=16, batch=4,
                        max_len=1024, seed=SEED, log=log)
            phase_kernels(KERNEL_WIDTHS, seed=SEED, log=log)
    except CheckFailed as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
