"""The main-path kernels compile for a TPU v5e at OPT-125m widths.

Interpret mode accepts kernels the TPU compiler refuses (block shapes
against the (8, 128) tiling rule, operand types the MXU does not take), so
these tests lower and compile the two kernels the serving tick reaches —
paged attention and the W8A8 matmul — with ``interpret=False`` for a
described, not attached, ``v5e:2x2`` chip. Nothing runs: a pass says the
chip's compiler takes the kernel, not that it is right or fast (the CPU
sweeps and ``chip_smoke.py`` say that).

The topology is described inside a module fixture, never at import time:
only one process may hold the TPU library, and under pytest-xdist every
worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_models import opt_125m
from repro.kernels.int8_matmul import int8_matmul
from repro.kernels.paged_attention import paged_mha

CFG = opt_125m()
B, BS, NB, W = 8, 16, 512, 32          # batch 8, 512-token tables, 512 blocks
HQ = HKV = CFG.n_kv_heads
DH = CFG.head_dim
D, FF = CFG.d_model, CFG.d_ff
VARIANTS = {                           # (gamma, gated)
    "vanilla": (0.0, False),
    "clipped": (-0.03, False),
    "gated": (0.0, True),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache off meanwhile
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("tq", [1, 64])
@pytest.mark.parametrize("kv_int8", [False, True])
@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_paged_attention_compiles(one_chip, variant, kv_int8, tq):
    gamma, gated = VARIANTS[variant]
    pool_dtype = jnp.int8 if kv_int8 else jnp.bfloat16
    args = [_sds((B, tq, HQ, DH), jnp.bfloat16, one_chip),
            _sds((NB, HKV, BS, DH), pool_dtype, one_chip),
            _sds((NB, HKV, BS, DH), pool_dtype, one_chip),
            _sds((B, W), jnp.int32, one_chip),
            _sds((B,), jnp.int32, one_chip)]
    if gated:
        args.append(_sds((B, tq, HQ), jnp.bfloat16, one_chip))
    if kv_int8:
        args += [_sds((NB, 1, BS), jnp.float32, one_chip)] * 2

    def fn(q, k_pool, v_pool, table, off, *rest):
        gate = rest[0] if gated else None
        ks, vs = rest[-2:] if kv_int8 else (None, None)
        return paged_mha(q, k_pool, v_pool, table, off, gate, gamma=gamma,
                         k_scale=ks, v_scale=vs, interpret=False)

    assert "tpu_custom_call" in _compiled_text(fn, *args)


def test_paged_attention_compiles_at_the_cells_prefill_tick(one_chip):
    """The benchmark cell's largest paged-attention call: its T=256 tick
    bucket over 64 rows at table width 128, gated, int8 pools of 8,192
    blocks, with per-row live widths. Every Pallas call of the module is
    named ``paged_attention*``, the prefix the benchmark's kernel time and
    roofline read."""
    b, t, w, nb = 64, 256, 128, 8192
    args = [_sds((b, t, HQ, DH), jnp.bfloat16, one_chip),
            _sds((nb, HKV, BS, DH), jnp.int8, one_chip),
            _sds((nb, HKV, BS, DH), jnp.int8, one_chip),
            _sds((b, w), jnp.int32, one_chip),
            _sds((b,), jnp.int32, one_chip),
            _sds((b, t, HQ), jnp.bfloat16, one_chip),
            _sds((nb, 1, BS), jnp.float32, one_chip),
            _sds((nb, 1, BS), jnp.float32, one_chip),
            _sds((b,), jnp.int32, one_chip)]

    def fn(q, k_pool, v_pool, table, off, gate, ks, vs, held):
        return paged_mha(q, k_pool, v_pool, table, off, gate, k_scale=ks,
                         v_scale=vs, live_widths=held, interpret=False)

    names = re.findall(
        r"%([\w.]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
        _compiled_text(fn, *args))
    assert names and all(n.startswith("paged_attention") for n in names)


# the q/k/v/o projections (D x D), a fused q/k/v projection (D x 3D), the
# MLP's up and down projections
@pytest.mark.parametrize("kn", [(D, D), (D, 3 * D), (D, FF), (FF, D)],
                         ids=lambda kn: f"{kn[0]}x{kn[1]}")
@pytest.mark.parametrize("m", [8, 256])
def test_int8_matmul_compiles(one_chip, m, kn):
    k, n = kn

    def fn(x, w_q, w_scale):
        return int8_matmul(x, w_q, w_scale, x_scale=0.05, x_zero=128.0,
                           interpret=False)

    text = _compiled_text(fn, _sds((m, k), jnp.bfloat16, one_chip),
                          _sds((k, n), jnp.int8, one_chip),
                          _sds((), jnp.float32, one_chip))
    assert "tpu_custom_call" in text
