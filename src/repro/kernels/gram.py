"""Pallas TPU kernel: blocked (optionally weighted) Gram matrix.

The paper's O(mn)/O(n^2) hot spot.  TPU adaptation (DESIGN.md §3): the cross
term of ||x-y||^2 is a matmul -> MXU; the kernel nonlinearity exp(-d/sigma^p)
and the sqrt(w_i) sqrt(w_j) RSKPCA weighting (Algorithm 1's W K W) are fused
into the same VMEM block pass, so no n x m distance matrix ever touches HBM.

Grid: (ceil(n/bn), ceil(m/bm)) output tiles.  Per tile the working set is
  x_blk (bn, d) + y_blk (bm, d) + out (bn, bm)   [f32]
With bn = bm = 256 and d <= 8192 that is 256*8192*4*2 + 256*256*4 ~= 17 MB --
too big for v5e's 16 MB VMEM at the extreme, so ``ops.py`` picks the block
size from d to stay under a VMEM budget (default 8 MB) and keeps the matmul
dims multiples of the 128-lane MXU width.

Weight vectors travel as 2-D blocks: the row weights as an (n, 1) column cut
into (bn, 1) blocks, the column weights as a (1, m) row cut into (1, bm)
blocks.  A 1-D block's tiling must match the layout XLA gives the whole
vector, which the TPU compiler refuses for most tile sizes; the 2-D forms
broadcast against the (bn, bm) tile with no relayout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def cross(a: Array, b: Array) -> Array:
    """a @ b.T with f32 accumulation.  f32 operands take the full-precision
    MXU passes: the TPU's default f32 matmul rounds operands to bf16, and
    ||a||^2 + ||b||^2 - 2 a.b amplifies that rounding into distances."""
    prec = jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), precision=prec,
                               preferred_element_type=jnp.float32)


def contract(g: Array, v: Array) -> Array:
    """g @ v with f32 accumulation, full precision for f32 operands."""
    prec = jax.lax.Precision.HIGHEST if g.dtype == jnp.float32 else None
    return jax.lax.dot_general(g, v, (((1,), (0,)), ((), ())), precision=prec,
                               preferred_element_type=jnp.float32)


def sq_dists(x: Array, y: Array) -> Array:
    """(bn, bm) partial ||x_i - y_j||^2 over the feature columns given
    (unclamped, so K-chunks can be summed): f32 norms, MXU cross term."""
    xf = x.astype(jnp.float32)
    yf = y.astype(jnp.float32)
    xx = jnp.sum(xf * xf, axis=-1, keepdims=True)        # (bn, 1)
    yy = jnp.sum(yf * yf, axis=-1, keepdims=True).T      # (1, bm)
    return xx + yy - 2.0 * cross(x, y)


def kernel_of(d2: Array, sigma: float, p: int) -> Array:
    """phi(||.||^p / sigma^p) = exp(-d^p / sigma^p) from clamped squared
    distances, in f32."""
    if p == 2:
        s = d2 / (sigma * sigma)
    elif p == 1:
        s = jnp.sqrt(d2) / sigma
    else:
        s = d2 ** (p / 2.0) / sigma**p
    return jnp.exp(-s)


def _gram_kernel(x_ref, y_ref, wx_ref, wy_ref, o_ref, *, sigma: float, p: int,
                 weighted: bool, k_steps: int):
    """Grid step (i, j, k): accumulate the partial squared-distance for the
    (i, j) output tile over feature chunk k; apply the kernel nonlinearity
    (and the RSKPCA sqrt(w) weighting) on the LAST chunk.

    K-chunking keeps large-d working sets inside VMEM without shrinking the
    output tile — at d=4096 this raises arithmetic intensity from 31.5 (the
    128x128 fallback tile) to ~117 FLOP/byte (the P2 table in
    benchmarks/rskpca_scale.py).
    """
    k = pl.program_id(2)
    # mixed precision: bf16 inputs go to the MXU as-is (half the operand
    # bandwidth); norms, accumulation, and the nonlinearity stay f32
    partial = sq_dists(x_ref[...], y_ref[...])

    @pl.when(k == 0)
    def _init():
        o_ref[...] = partial.astype(o_ref.dtype)

    @pl.when(k > 0)
    def _accum():
        o_ref[...] = (o_ref[...].astype(jnp.float32) + partial
                      ).astype(o_ref.dtype)

    @pl.when(k == k_steps - 1)
    def _finish():
        g = kernel_of(jnp.maximum(o_ref[...].astype(jnp.float32), 0.0),
                      sigma, p)
        if weighted:
            g = g * jnp.sqrt(wx_ref[...]) * jnp.sqrt(wy_ref[...])
        o_ref[...] = g.astype(o_ref.dtype)


def _gram_row_kernel(x_ref, c_ref, w_ref, k_ref, d2_ref, *, sigma: float,
                     p: int, weighted: bool, k_steps: int):
    """Grid step (j, k): rank-one Gram-ROW pass for the streaming update path
    (repro/streaming): one new point against the center tile j, accumulating
    the partial squared distance over feature chunk k.  On the LAST chunk it
    emits BOTH the (optionally weight-fused) kernel row — the new row/column
    of the weighted Gram — and the raw squared distances (the online
    absorption decision of Algorithm 2 needs them in f32).  Rows are (1, bm)
    blocks of (1, m) outputs.
    """
    k = pl.program_id(1)
    # x is padded to the 8-row sublane minimum (row 0 real, the rest zero)
    partial = sq_dists(x_ref[...], c_ref[...])[0:1]      # (1, bm)

    @pl.when(k == 0)
    def _init():
        d2_ref[...] = partial

    @pl.when(k > 0)
    def _accum():
        d2_ref[...] = d2_ref[...] + partial

    @pl.when(k == k_steps - 1)
    def _finish():
        d2 = jnp.maximum(d2_ref[...], 0.0)
        d2_ref[...] = d2
        g = kernel_of(d2, sigma, p)
        if weighted:
            g = g * jnp.sqrt(w_ref[...])
        k_ref[...] = g.astype(k_ref.dtype)


def gram_row_pallas(x: Array, centers: Array, *, sigma: float, p: int = 2,
                    w: Array | None = None, block_m: int = 512,
                    block_k: int | None = None,
                    interpret: bool = False) -> tuple[Array, Array]:
    """(k_row, d2_row), each (1, m), of one point against all centers in one
    fused pass.

    x must be padded to (8, d) rows (row 0 real, the rest zero — the 8-row
    sublane minimum keeps the MXU happy); centers to (m % block_m == 0, d)
    and d % block_k == 0 (ops.gram_row handles the padding).  ``w`` is a
    (1, m) row of center weights; it fuses the sqrt(w_j) column weighting of
    Algorithm 1's W K W into the same pass.
    """
    m, d = centers.shape
    assert x.shape == (8, d), (x.shape, d)
    assert m % block_m == 0, (m, block_m)
    block_k = block_k or d
    assert d % block_k == 0, (d, block_k)
    k_steps = d // block_k
    weighted = w is not None
    if w is None:
        w = jnp.ones((1, m), jnp.float32)
    assert w.shape == (1, m), w.shape

    kernel = functools.partial(_gram_row_kernel, sigma=float(sigma),
                               p=int(p), weighted=weighted, k_steps=k_steps)
    row = pl.BlockSpec((1, block_m), lambda j, k: (0, j))
    return pl.pallas_call(
        kernel,
        name="gram_row",
        grid=(m // block_m, k_steps),
        in_specs=[
            pl.BlockSpec((8, block_k), lambda j, k: (0, k)),
            pl.BlockSpec((block_m, block_k), lambda j, k: (j, k)),
            row,
        ],
        out_specs=[row, row],
        out_shape=[
            jax.ShapeDtypeStruct((1, m), jnp.float32),
            jax.ShapeDtypeStruct((1, m), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, centers, w)


def _gram_matvec_kernel(x_ref, y_ref, wx_ref, wy_ref, v_ref, o_ref, d2_ref, *,
                        sigma: float, p: int, weighted: bool, k_steps: int):
    """Grid step (i, j, k): matrix-free K_w @ V, flash-attention style.

    For output row-tile i, column-tile j accumulates the partial squared
    distance over feature chunk k into the VMEM scratch ``d2_ref`` (the
    (bn, bm) Gram tile lives ONLY there — it is never written to HBM).  On
    the last feature chunk the kernel nonlinearity and the RSKPCA sqrt(w)
    weighting are applied in-register and the tile is immediately contracted
    against V's j-tile on the MXU, accumulating into the (bn, r) output
    tile.  f32 accumulation throughout; bf16 operands only feed the matmuls.
    """
    j = pl.program_id(1)
    k = pl.program_id(2)
    partial = sq_dists(x_ref[...], y_ref[...])

    @pl.when(k == 0)
    def _init():
        d2_ref[...] = partial

    @pl.when(k > 0)
    def _accum():
        d2_ref[...] = d2_ref[...] + partial

    @pl.when(k == k_steps - 1)
    def _contract():
        g = kernel_of(jnp.maximum(d2_ref[...], 0.0), sigma, p)
        if weighted:
            g = g * jnp.sqrt(wx_ref[...]) * jnp.sqrt(wy_ref[...])
        v = v_ref[...]                                   # (bm, r)
        pv = contract(g.astype(v.dtype), v)              # (bn, r) on the MXU

        @pl.when(j == 0)
        def _first():
            o_ref[...] = pv.astype(o_ref.dtype)

        @pl.when(j > 0)
        def _rest():
            o_ref[...] = (o_ref[...].astype(jnp.float32) + pv
                          ).astype(o_ref.dtype)


def _weight_blocks(wx, wy, n: int, m: int):
    """(n, 1) row-weight column and (1, m) column-weight row (ones when
    unweighted)."""
    wx = jnp.ones((n, 1), jnp.float32) if wx is None else wx
    wy = jnp.ones((1, m), jnp.float32) if wy is None else wy
    assert wx.shape == (n, 1) and wy.shape == (1, m), (wx.shape, wy.shape)
    return wx, wy


def gram_matvec_pallas(x: Array, y: Array, v: Array, *, sigma: float,
                       p: int = 2, wx: Array | None = None,
                       wy: Array | None = None, block_n: int = 256,
                       block_m: int = 256, block_k: int | None = None,
                       interpret: bool = False) -> Array:
    """out = K_w @ v without materializing K_w: out[i] = sum_j sqrt(wx_i)
    phi(||x_i-y_j||^p/sigma^p) sqrt(wy_j) v[j].

    Peak memory is O(n*r + tiles), never O(n*m) — the Gram tile exists only
    in the (block_n, block_m) VMEM scratch.  Shapes must be pre-padded:
    n % block_n == 0, m % block_m == 0, d % block_k == 0, and v's row count
    equal to m with zero rows on any padded tail (``ops.gram_matvec``
    handles all padding; zero v-rows make unweighted padding exact, and
    zero-weight padding already kills padded columns on the weighted path).
    ``wx`` is an (n, 1) column and ``wy`` a (1, m) row.
    """
    n, d = x.shape
    m, d2_ = y.shape
    assert d == d2_, (x.shape, y.shape)
    assert v.shape[0] == m, (v.shape, m)
    assert n % block_n == 0 and m % block_m == 0, (n, m, block_n, block_m)
    block_k = block_k or d
    assert d % block_k == 0, (d, block_k)
    k_steps = d // block_k
    r = v.shape[1]
    weighted = wx is not None
    wx, wy = _weight_blocks(wx, wy, n, m)

    grid = (n // block_n, m // block_m, k_steps)
    kernel = functools.partial(_gram_matvec_kernel, sigma=float(sigma),
                               p=int(p), weighted=weighted, k_steps=k_steps)
    return pl.pallas_call(
        kernel,
        name="gram_matvec",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (j, k)),
            pl.BlockSpec((block_n, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, block_m), lambda i, j, k: (0, j)),
            pl.BlockSpec((block_m, r), lambda i, j, k: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, r), lambda i, j, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, r), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_n, block_m), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(x, y, wx, wy, v)


def gram_pallas(x: Array, y: Array, *, sigma: float, p: int = 2,
                wx: Array | None = None, wy: Array | None = None,
                block_n: int = 256, block_m: int = 256,
                block_k: int | None = None,
                interpret: bool = False, out_dtype=jnp.float32) -> Array:
    """K[i, j] = sqrt(wx_i) phi(||x_i-y_j||^p/sigma^p) sqrt(wy_j).

    Shapes must already be padded: n % block_n == 0, m % block_m == 0,
    d % block_k == 0 (ops.gram handles padding/unpadding).  ``wx`` is an
    (n, 1) column and ``wy`` a (1, m) row.
    """
    n, d = x.shape
    m, d2_ = y.shape
    assert d == d2_, (x.shape, y.shape)
    assert n % block_n == 0 and m % block_m == 0, (n, m, block_n, block_m)
    block_k = block_k or d
    assert d % block_k == 0, (d, block_k)
    k_steps = d // block_k
    weighted = wx is not None
    wx, wy = _weight_blocks(wx, wy, n, m)

    grid = (n // block_n, m // block_m, k_steps)
    kernel = functools.partial(_gram_kernel, sigma=float(sigma), p=int(p),
                               weighted=weighted, k_steps=k_steps)
    return pl.pallas_call(
        kernel,
        name="gram",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (j, k)),
            pl.BlockSpec((block_n, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, block_m), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_n, block_m), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, y, wx, wy)
