"""Ahead-of-time compiles for a described TPU v5e, without a chip.

The TPU compiler is installed with JAX and compiles for a topology that is
described rather than attached, so these tests catch what interpret mode
cannot: Pallas kernels the Mosaic compiler refuses at real widths, and a
train step that does not fit one chip's memory. Nothing runs; they say
nothing about results or speed.

The topology is described only inside the module fixture below: loading
the TPU compiler while a module is imported would hand it to whichever
test worker imports first, and the others would fail to load it.
"""
import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 15.75 * 2**30   # what the compiler reports a v5e can hold


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        from jax.experimental.compilation_cache import compilation_cache

        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            jax.config.update("jax_enable_compilation_cache", enabled)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


# The widths chip_smoke.py's phase C runs: starcoder2-3b attention, GNMT's
# LSTM, jamba-v0.1-52b's selective scan, rwkv6-3b's WKV, at S = 2048.
def _flash(s):
    from repro.kernels.flash_attention.kernel import flash_attention_fwd

    return (lambda q, k, v: flash_attention_fwd(q, k, v, causal=True),
            [_sds(s, (24, 2048, 128), jnp.bfloat16),
             _sds(s, (2, 2048, 128), jnp.bfloat16),
             _sds(s, (2, 2048, 128), jnp.bfloat16)])


def _lstm(s):
    from repro.kernels.lstm_cell.kernel import lstm_cell_fwd

    return (lstm_cell_fwd, [_sds(s, (128, 2048)), _sds(s, (4, 2048, 1024)),
                            _sds(s, (4, 1024)), _sds(s, (128, 1024))])


def _mamba(s, dt=jnp.float32):
    from repro.kernels.mamba_scan.kernel import mamba_scan_fwd

    return (mamba_scan_fwd,
            [_sds(s, (1, 2048, 8192), dt), _sds(s, (1, 2048, 8192), dt),
             _sds(s, (8192, 16)), _sds(s, (1, 2048, 16), dt),
             _sds(s, (1, 2048, 16), dt), _sds(s, (8192,))])


def _mamba_bf16(s):       # per-step rows of packed bf16 activations
    return _mamba(s, jnp.bfloat16)


def _wkv(s):
    from repro.kernels.rwkv6_wkv.kernel import wkv6_fwd

    return (wkv6_fwd, [_sds(s, (40, 2048, 64))] * 4 + [_sds(s, (40, 64))])


@pytest.mark.parametrize("case", [_flash, _lstm, _mamba, _mamba_bf16, _wkv],
                         ids=["flash_attention", "lstm_cell", "mamba_scan",
                              "mamba_scan_bf16", "wkv6"])
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, args = case(one_chip)
    assert "tpu_custom_call" in _compiled_text(fn, *args)


def test_starcoder2_two_layer_train_step_fits_one_v5e(one_chip):
    """chip_smoke.py phase A: starcoder2-3b at published widths, 2 layers,
    float32 + AdamW, batch 8 x SL 512, as the launcher builds it."""
    from repro.configs import get_model_config
    from repro.launch.train import build_trainer
    from repro.train.train_step import build_train_step, init_train_state

    cfg = get_model_config("starcoder2-3b").with_overrides(num_layers=2)
    trainer = build_trainer(cfg, seq=512, batch=8, steps=8)
    state = jax.eval_shape(
        lambda r: init_train_state(trainer.model, trainer.run, r),
        jax.random.PRNGKey(0))
    state = jax.tree.map(lambda x: _sds(one_chip, x.shape, x.dtype), state)
    batch = {"tokens": _sds(one_chip, (8, 512), jnp.int32),
             "labels": _sds(one_chip, (8, 512), jnp.int32)}
    step = build_train_step(trainer.model, trainer.run, 8)
    compiled = jax.jit(step, donate_argnums=0).lower(state, batch).compile()
    mem = compiled.memory_analysis()
    n_params = sum(math.prod(x.shape) for x in jax.tree.leaves(state.params))
    assert 500e6 < n_params < 600e6
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES
