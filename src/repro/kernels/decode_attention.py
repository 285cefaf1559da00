"""Flash-decode — single-query attention against a long KV cache.

Memory-bound by design (arithmetic intensity ≈ 1 flop/byte): the kernel's
job is to stream K/V through VMEM exactly once at full HBM bandwidth.  Grid
``(B, nq, S/bk)`` with the KV axis innermost; the query tile (one token per
batch×head) stays resident in VMEM scratch along with the online-softmax
state.  Positions beyond ``pos`` are masked with a length word passed as a
``[1,1]`` int32 operand (scalar-prefetch/SMEM is the further TPU
refinement; a VMEM scalar keeps interpret and Mosaic paths identical).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale, bk):
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[0, 0]

    @pl.when(j * bk <= pos)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale            # [1, hd]
        k = k_ref[0, 0].astype(jnp.float32)                    # [bk, hd]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                                       # [1, bk]
        kj = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        s = jnp.where(kj <= pos, s, NEG_INF)
        m_prev = m_ref[0, 0]
        l_prev = l_ref[0, 0]
        m_new = jnp.maximum(m_prev, s.max())
        p = jnp.exp(s - m_new)                                  # [1, bk]
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + p.sum()
        v = v_ref[0, 0].astype(jnp.float32)                    # [bk, hd]
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )                                                       # [1, hd]
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.full_like(m_ref, m_new)
        l_ref[...] = jnp.full_like(l_ref, l_new)

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_ref[0, 0]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)


def flash_decode_bhsd(
    q: jax.Array,            # [B, nq, 1, hd]
    k: jax.Array,            # [B, nkv, S, hd]
    v: jax.Array,            # [B, nkv, S, hd]
    pos: jax.Array,          # scalar int32 — last valid position
    *,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, nq, _, hd = q.shape
    nkv, sk = k.shape[1], k.shape[2]
    g = nq // nkv
    bk = min(block_k, sk)
    assert sk % bk == 0, (sk, bk)
    grid = (b, nq, sk // bk)
    scale = 1.0 / (hd ** 0.5)
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (1, 1))

    kernel = functools.partial(_kernel, scale=scale, bk=bk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, h, j: (0, 0)),
            pl.BlockSpec((1, 1, 1, hd), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, g=g: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, g=g: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, hd), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nq, 1, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, _LANES), jnp.float32),
            pltpu.VMEM((1, _LANES), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(pos_arr, q, k, v)
