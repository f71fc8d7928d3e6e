"""Compile-only checks against one described TPU v5e chip.

The TPU compiler is installed even where no chip is attached: it
compiles for a described ``v5e:2x2`` topology, and refuses what the
chip's compiler would refuse (Pallas lowering gaps, tiling rules, VMEM
and HBM limits).  Nothing here runs; each case compiles the kernels the
serving and model paths select on TPU, at the widths of the configs that
use them.  The topology is described inside a fixture, never on import,
and the persistent compile cache is off while these compile: a program
compiled for a described chip cannot be read back without one.
"""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest

from repro.serve.scheduler import PREFILL_CHUNK

HBM_BYTES = 16 * 10**9      # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _wkv6(sh):
    """rwkv6-3b: 40 heads x 64, one 256-token prefill chunk."""
    from repro.kernels.rwkv6_scan import wkv6_pallas

    act = _spec(sh, (1, 256, 40, 64), jnp.bfloat16)
    w = _spec(sh, (1, 256, 40, 64))
    return jax.jit(lambda r, k, v, w, u: wkv6_pallas(r, k, v, w, u)).lower(
        act, act, act, w, _spec(sh, (40, 64)))


def _mamba2(sh):
    """zamba2-2.7b: 80 heads, P = N = 64, one 256-token prefill chunk."""
    from repro.kernels.mamba2_ssd import mamba2_pallas

    return jax.jit(lambda x, dt, a, b, c: mamba2_pallas(x, dt, a, b, c)).lower(
        _spec(sh, (1, 256, 80, 64), jnp.bfloat16), _spec(sh, (1, 256, 80)),
        _spec(sh, (80,)), _spec(sh, (1, 256, 64), jnp.bfloat16),
        _spec(sh, (1, 256, 64), jnp.bfloat16))


def _flash(sh):
    """phi3-mini-3.8b: 32 heads of 96, a 512-token prompt."""
    from repro.kernels.flash_attention import flash_attention_pallas

    qkv = _spec(sh, (1, 512, 32, 96), jnp.bfloat16)
    return jax.jit(lambda q, k, v: flash_attention_pallas(q, k, v)).lower(
        qkv, qkv, qkv)


def _xor(sh):
    """SCR XOR parity over 8 checkpoint fragments of 1 MiB each."""
    from repro.kernels.xor_parity import xor_reduce_pallas

    return jax.jit(xor_reduce_pallas).lower(
        _spec(sh, (8, 2048, 128), jnp.int32))


def _paged_step(tokens):
    def lower(sh):
        """phi3's paged decode step at published widths, cut to 2 layers,
        bf16 weights, the pool geometry of chip_smoke.py: the T=1 step
        over 4 lanes, or one admission-prefill chunk at batch 1."""
        from repro.configs import get_config
        from repro.models.registry import get_model

        cfg = dataclasses.replace(get_config("phi3-mini-3.8b"), n_layers=2,
                                  param_dtype="bfloat16")
        model = get_model(cfg)
        params = jax.tree_util.tree_map(
            lambda s: _spec(sh, s.shape, s.dtype),
            jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), cfg)))
        pool = _spec(sh, (cfg.n_layers, 129, 16, cfg.padded_kv_heads,
                          cfg.resolved_head_dim), jnp.bfloat16)
        batch = 4 if tokens == 1 else 1
        step = jax.jit(lambda p, pools, tables, pos, toks:
                       model.paged_decode_step(p, pools, tables, pos, toks,
                                               cfg))
        return step.lower(params, {"k": pool, "v": pool},
                          _spec(sh, (batch, 16), jnp.int32),
                          _spec(sh, (batch,), jnp.int32),
                          _spec(sh, (batch, tokens), jnp.int32))
    return lower


CASES = {
    "wkv6_pallas-rwkv6-3b": _wkv6,
    "mamba2_pallas-zamba2-2.7b": _mamba2,
    "flash_attention_pallas-phi3": _flash,
    "xor_reduce_pallas": _xor,
    "paged_decode_step-phi3-T1": _paged_step(1),
    "paged_decode_step-phi3-T128": _paged_step(PREFILL_CHUNK),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiles_for_v5e(case, one_chip):
    compiled = CASES[case](one_chip).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < used < HBM_BYTES, used
    if case.startswith(("wkv6", "mamba2", "flash", "xor")):
        assert "tpu_custom_call" in compiled.as_text()
