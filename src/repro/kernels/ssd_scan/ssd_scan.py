"""Chunked Mamba2 SSD scan (TPU Pallas).

Grid: ``(batch, heads, n_chunks)`` with the chunk axis innermost and
*sequential* — the running state S (dh, ds) lives in VMEM scratch across chunk
iterations, so the recurrence never round-trips through HBM.  Each chunk does
three MXU contractions (CB^T, M @ dx, state outer-products) on
(chunk x chunk) and (chunk x dh/ds) tiles: with chunk = ds = 128 and dh = 64,
everything is MXU-shaped.

Layouts (contiguous in the model): x (b, l, h, dh), dt (b, l, h), A (h,),
B/C (b, l, ds) single SSM group, y (b, l, h, dh).  The wrapper lays heads out
before the sequence — x as (b, h, l, dh), dt as (b, h, 1, l), A as
(h, 1, 128) — so every block's last two dims are either (8, 128)-divisible or
the array's own, as the TPU lowering requires.  Inside the kernel ``dt`` is a
lane row; its column form and the cumulative log-decay are built with masked
reductions over the (chunk, chunk) iota grid (no in-kernel transposes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(chunk, x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, s_ref):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    x = x_ref[0, 0].astype(jnp.float32)  # (L, dh)
    dt_row = dt_ref[0, 0].astype(jnp.float32)  # (1, L)
    A = a_ref[0][:, :1].astype(jnp.float32)  # (1, 1)
    B = b_ref[0].astype(jnp.float32)  # (L, ds)
    C = c_ref[0].astype(jnp.float32)  # (L, ds)

    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = rows >= cols
    lam_row = A * dt_row  # (1, L) log-decay, <= 0
    # cum[t] = sum_{s<=t} lam[s] as a column; the same values as a row via
    # the diagonal; dt as a column likewise.
    cum_col = jnp.sum(jnp.where(lower, lam_row, 0.0), axis=1, keepdims=True)
    diag = rows == cols
    cum_row = jnp.sum(jnp.where(diag, cum_col, 0.0), axis=0, keepdims=True)
    dt_col = jnp.sum(jnp.where(diag, dt_row, 0.0), axis=1, keepdims=True)

    decay = jnp.where(lower, jnp.exp(cum_col - cum_row), 0.0)  # (t, s)
    CB = jax.lax.dot_general(
        C, B, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (t, s)
    M = CB * decay
    dx = dt_col * x  # (L, dh)
    y_intra = jax.lax.dot_general(
        M, dx, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (L, dh)

    S_in = s_ref[...]  # (dh, ds) state entering the chunk
    y_inter = jnp.exp(cum_col) * jax.lax.dot_general(
        C, S_in, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (L, ds) . (dh, ds)^T -> (L, dh)

    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: S_out = exp(cum_L) S_in + sum_s exp(cum_L - cum_s) dx_s B_s^T
    cum_last = cum_col[chunk - 1:, :]  # (1, 1)
    w = jnp.exp(cum_last - cum_col)  # (L, 1)
    s_ref[...] = jnp.exp(cum_last) * S_in + jax.lax.dot_general(
        (w * dx), B, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (dh, ds)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: jax.Array,  # (b, l, h, dh)
    dt: jax.Array,  # (b, l, h)
    A: jax.Array,  # (h,)
    B: jax.Array,  # (b, l, ds)
    C: jax.Array,  # (b, l, ds)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, l, h, dh = x.shape
    ds = B.shape[-1]
    chunk = min(chunk, l)
    assert l % chunk == 0
    nc = l // chunk
    grid = (b, h, nc)
    kernel = functools.partial(_kernel, chunk)
    xh = jnp.transpose(x, (0, 2, 1, 3))  # (b, h, l, dh)
    dth = jnp.transpose(dt, (0, 2, 1))[:, :, None, :]  # (b, h, 1, l)
    Ah = jnp.broadcast_to(A.astype(jnp.float32)[:, None, None], (h, 1, 128))
    y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, dh), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda ib, ih, ic: (ib, ih, 0, ic)),
            pl.BlockSpec((1, 1, 128), lambda ib, ih, ic: (ih, 0, 0)),
            pl.BlockSpec((1, chunk, ds), lambda ib, ih, ic: (ib, ic, 0)),
            pl.BlockSpec((1, chunk, ds), lambda ib, ih, ic: (ib, ic, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, dh),
                               lambda ib, ih, ic: (ib, ih, ic, 0)),
        out_shape=jax.ShapeDtypeStruct(xh.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((dh, ds), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(xh, dth, Ah, B, C)
    return jnp.transpose(y, (0, 2, 1, 3))
