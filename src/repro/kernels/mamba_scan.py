"""Chunked selective-scan (mamba1) — Pallas TPU kernel.

Layout puts ``d_inner`` on the lane axis and the small state dim ``N`` on
sublanes: the recurrent state ``h`` is an ``[N, bd]`` f32 VMEM scratch that
persists across the sequential chunk axis.  Grid ``(B, d_inner/bd, S/ck)``
— batch and channel blocks are embarrassingly parallel (the recurrence only
couples time), chunks run in order carrying ``h``.

Per time step inside a chunk (vector ops only, no MXU):
    h   = exp(Δ_t ⊗ A) ⊙ h + (Δ_t x_t) ⊗ B_t
    y_t = Σ_n C_t[n] · h[n, :]
VMEM working set ≈ (3·ck·bd + 2·ck·N + 2·N·bd) · 4 B — with ck = 256,
bd = 512 that is ~1.7 MB, well inside a v5e core's 16 MB budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, h_ref, *, ck):
    t0 = pl.program_id(2)

    @pl.when(t0 == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[...]                                           # [N, bd] f32

    def step(t, h):
        xt = x_ref[0, t]                                     # [bd]
        dtt = dt_ref[0, t]                                   # [bd]
        bt = b_ref[0, t]                                     # [N]
        ct = c_ref[0, t]                                     # [N]
        da = jnp.exp(dtt[None, :] * a)                       # [N, bd]
        h = da * h + bt[:, None] * (dtt * xt)[None, :]
        y_ref[0, t] = (h * ct[:, None]).sum(axis=0)
        return h

    h_ref[...] = jax.lax.fori_loop(0, ck, step, h_ref[...])


def mamba_scan_blocked(
    x: jax.Array,            # [B, S, d_in] f32 (post-conv, silu'd)
    dt: jax.Array,           # [B, S, d_in] f32
    a: jax.Array,            # [d_in, N] f32 (negative)
    b_mat: jax.Array,        # [B, S, N] f32
    c_mat: jax.Array,        # [B, S, N] f32
    *,
    block_d: int = 512,
    chunk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    bsz, s, d_in = x.shape
    n = a.shape[-1]
    bd = min(block_d, d_in)
    ck = min(chunk, s)
    assert d_in % bd == 0 and s % ck == 0, (d_in, bd, s, ck)
    a_t = a.T                                                # [N, d_in]
    grid = (bsz, d_in // bd, s // ck)

    kernel = functools.partial(_kernel, ck=ck)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, ck, bd), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((1, ck, bd), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((n, bd), lambda b, d, t: (0, d)),
            pl.BlockSpec((1, ck, n), lambda b, d, t: (b, t, 0)),
            pl.BlockSpec((1, ck, n), lambda b, d, t: (b, t, 0)),
        ],
        out_specs=pl.BlockSpec((1, ck, bd), lambda b, d, t: (b, t, d)),
        out_shape=jax.ShapeDtypeStruct((bsz, s, d_in), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, dt, a_t, b_mat, c_mat)
