"""Pallas TPU kernel: nearest-center assignment pass.

Drives (a) the data->center map alpha of §5 and (b) the inner absorption pass
of blocked shadow selection (DESIGN.md §3).  Grid (row tiles of X, center
tiles): the second axis sweeps the centers in ``block_m`` tiles, so only one
(block_m, d) center tile is in VMEM at a time, and a running (min d^2,
argmin) pair per row lives in VMEM scratch across that axis — any center
count fits the same kernel (the ingest budget of 32768 centers included).

Padding protocol: callers pad centers to a multiple of block_m and pass a
``valid`` (1, m_pad) float row (1 = real center); invalid slots are forced
to +inf so they can never win the argmin.  The mask is DATA, not a static
argument — blocked selection calls this kernel once per round with a
different mask and must not retrace (the round loop is host-driven).
Outputs are lane-dense (1, n) rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gram import sq_dists

Array = jax.Array


def _assign_kernel(x_ref, c_ref, v_ref, o_idx_ref, o_d2_ref, best_d2,
                   best_idx, *, block_m: int, m_steps: int):
    j = pl.program_id(1)
    # assignment always resolves in f32: a rounded argmin could flip centers
    d2 = jnp.maximum(sq_dists(x_ref[...].astype(jnp.float32),
                              c_ref[...].astype(jnp.float32)), 0.0)
    d2 = jnp.where(v_ref[...] > 0.0, d2, jnp.inf)                # (bn, bm)
    blk_d2 = jnp.min(d2, axis=1, keepdims=True)                  # (bn, 1)
    # first column attaining the tile minimum (argmin without a gather)
    col = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
    blk_idx = jnp.min(jnp.where(d2 == blk_d2, col, block_m), axis=1,
                      keepdims=True) + j * block_m

    @pl.when(j == 0)
    def _init():
        best_d2[...] = blk_d2
        best_idx[...] = blk_idx

    @pl.when(j > 0)
    def _fold():
        # strict <: on ties the earlier tile keeps the row, as argmin does
        take = blk_d2 < best_d2[...]
        best_d2[...] = jnp.where(take, blk_d2, best_d2[...])
        best_idx[...] = jnp.where(take, blk_idx, best_idx[...])

    @pl.when(j == m_steps - 1)
    def _emit():
        o_d2_ref[...] = best_d2[...].T
        o_idx_ref[...] = best_idx[...].T


def shadow_assign_pallas(x: Array, centers: Array, valid: Array, *,
                         block_n: int = 512, block_m: int = 512,
                         interpret: bool = False):
    """Returns (idx (1, n), d2min (1, n)) of the nearest valid center.

    ``valid`` is a (1, m_pad) float row; slots with valid <= 0 never win.  If
    NO center is valid, d2min is +inf and idx is 0 — callers gate on d2min.
    """
    n, d = x.shape
    m_pad, d2_ = centers.shape
    assert d == d2_ and n % block_n == 0 and m_pad % block_m == 0
    assert valid.shape == (1, m_pad), valid.shape
    m_steps = m_pad // block_m

    kernel = functools.partial(_assign_kernel, block_m=block_m,
                               m_steps=m_steps)
    row = pl.BlockSpec((1, block_n), lambda i, j: (0, i))
    return pl.pallas_call(
        kernel,
        name="shadow_assign",
        grid=(n // block_n, m_steps),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_m, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, block_m), lambda i, j: (0, j)),
        ],
        out_specs=[row, row],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_n, 1), jnp.float32),
                        pltpu.VMEM((block_n, 1), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, centers, valid)
