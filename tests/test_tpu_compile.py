"""Compile-only checks: the main path's Pallas kernels at ChatGLM3-6B widths.

Each test lowers one kernel for a TPU v5e that is described, not attached
(``jax.experimental.topologies``), compiles it with the TPU compiler and
checks that the program holds the kernel (``tpu_custom_call``).  Nothing
runs, so these say nothing about results or times; they catch what interpret
mode cannot: block shapes the TPU layout rules refuse, unsupported vector
casts, VMEM overruns.

The topology is described inside a module fixture, never at import: only one
process may load the TPU compiler's library at a time, and every test worker
imports this file.
"""
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke
from repro.kernels import dispatch
from repro.kernels.int_attention import (int_attention_fused,
                                         int_paged_decode_attention)
from repro.kernels.qmatmul import qmatmul

D_MODEL, HEAD_DIM, KV_HEADS, GROUPS = 4096, 128, 2, 16   # ChatGLM3-6B


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _weight_pads(text, dtype, n):
    """Result shapes of the pads of a (>= n, ...) array of the weight's
    dtype: a copy of the weight to the tile."""
    shapes = re.findall(rf"= {dtype}\[(\d+),(\d+)\]\S* pad\(", text)
    return [s for s in shapes if int(s[0]) >= n]


@pytest.mark.parametrize("packed", [False, True], ids=["int8", "nibbles"])
@pytest.mark.parametrize("n,k", [(4608, D_MODEL), (13696, D_MODEL),
                                 (D_MODEL, 13696)],
                         ids=["qkv", "ffn", "down"])
def test_qmatmul_compiles(one_chip, n, k, packed):
    m = 128
    bm, bn, bk = dispatch.qmatmul_blocks(m, n, k, packed=packed)
    w = ((n, k // 2), jnp.uint8) if packed else ((n, k), jnp.int8)
    text = _compiled_text(
        one_chip,
        partial(qmatmul, bm=bm, bn=bn, bk=bk, packed=packed, interpret=False),
        ((m, k), jnp.int8), w, ((n,), jnp.float32), ((n,), jnp.float32),
        ((m,), jnp.float32))
    assert "tpu_custom_call" in text
    # ffn's N and down's K are not block multiples: the kernel reads the
    # ragged edge as a partial block, and the weight is never padded.
    assert _weight_pads(text, "u8" if packed else "s8", n) == []


def test_fused_attention_compiles(one_chip):
    h, s, d = 32, 512, HEAD_DIM
    bq, bk = dispatch.attention_blocks(s, s, d)
    nq = -(-s // bq)
    text = _compiled_text(
        one_chip,
        partial(int_attention_fused, bq=bq, bk=bk, interpret=False),
        ((h, s, d), jnp.int8), ((h, s, d), jnp.int8), ((h, s, d), jnp.int8),
        ((h, nq), jnp.float32), ((h,), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("packed", [False, True], ids=["kv8", "kv4"])
def test_paged_decode_compiles(one_chip, packed):
    ps = chip_smoke.PAGE_SIZE
    b = chip_smoke.BATCH
    pages = -(-(chip_smoke.MAX_PROMPT + chip_smoke.GEN) // ps)
    num_phys = b * pages + 1
    dk = HEAD_DIM // 2 if packed else HEAD_DIM
    pool = ((num_phys, KV_HEADS, ps, dk), jnp.uint8 if packed else jnp.int8)

    def step(q, k, v, sc, vs, pt, pos, kps, vps):
        return int_paged_decode_attention(
            q, k, v, sc, vs, pt, pos, k_page_scale=kps, v_page_scale=vps,
            packed=packed, interpret=False)

    text = _compiled_text(
        one_chip, step,
        ((b, KV_HEADS, GROUPS, HEAD_DIM), jnp.int8), pool, pool,
        ((b,), jnp.float32), ((b,), jnp.float32), ((b, pages), jnp.int32),
        ((b,), jnp.int32), ((num_phys,), jnp.float32),
        ((num_phys,), jnp.float32))
    assert "tpu_custom_call" in text
