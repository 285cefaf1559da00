"""Fused causal GQA flash attention — Pallas TPU kernel.

Grid ``(B, n_q_heads, S/bq, S/bk)`` with the key axis innermost (sequential);
online-softmax state (m, l, acc) lives in f32 VMEM scratch that persists
across the key axis.  GQA is free: the k/v BlockSpec index maps query head
``h`` to kv head ``h // group`` — no materialized head expansion.  Block
shapes keep the MXU dims at multiples of 128 (q/k tiles × head_dim) and the
working set ≈ (bq + 2·bk) · hd · 4 B + bq·bk·4 B ≤ a few MB of VMEM.

Causal blocks strictly above the diagonal are skipped via ``pl.when`` — with
bq = bk this halves the compute relative to a dense sweep.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale, bq, bk, causal):
    i = pl.program_id(2)
    j = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = (j * bk < (i + 1) * bq) if causal else (j >= 0)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale            # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)                    # [bk, hd]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                                       # [bq, bk]
        if causal:
            qi = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kj = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kj <= qi, s, NEG_INF)
        m_prev = m_ref[:, 0]                                    # [bq]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])                         # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)                         # [bq]
        l_new = alpha * l_prev + p.sum(axis=-1)
        v = v_ref[0, 0].astype(jnp.float32)                    # [bk, hd]
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_ref[:, 0]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)


def flash_attention_bhsd(
    q: jax.Array,            # [B, nq, S, hd]
    k: jax.Array,            # [B, nkv, S, hd]
    v: jax.Array,            # [B, nkv, S, hd]
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, nq, sq, hd = q.shape
    nkv, sk = k.shape[1], k.shape[2]
    assert nq % nkv == 0, (nq, nkv)
    g = nq // nkv
    bq, bk = min(block_q, sq), min(block_k, sk)
    assert sq % bq == 0 and sk % bk == 0, (sq, bq, sk, bk)
    grid = (b, nq, sq // bq, sk // bk)
    scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(_kernel, scale=scale, bq=bq, bk=bk, causal=causal)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, g=g: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, g=g: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nq, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
