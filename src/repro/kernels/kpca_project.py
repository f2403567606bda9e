"""Pallas TPU kernel: fused RSKPCA test-time projection.

z = phi(dists(x, C)) @ A with A = diag(sqrt(w)) U Lambda^{-1/2} (m x r).
This is the O(km) evaluation path the paper accelerates; fusing the Gram
block with the projection matmul keeps the kernel block in VMEM and writes
only the (bn x r) embedding to HBM — an (m/r)x reduction in output
bandwidth (m ~ thousands, r ~ 5-64).

Grid (row tiles of X, center tiles), flash-attention style: the second axis
sweeps ``block_m`` centers at a time, so VMEM holds one (bn, bm) Gram tile,
one (bm, d) center tile and one (bm, r) projector tile, and the (bn, r)
embedding accumulates in VMEM scratch across the sweep.  The operator size m
is then bounded by HBM, not by VMEM.  Both matmuls hit the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import quantize as _quant
from repro.kernels.gram import contract, kernel_of, sq_dists

Array = jax.Array


def _project_kernel(x_ref, c_ref, a_ref, o_ref, acc_ref, *, sigma: float,
                    p: int, m_steps: int):
    # mixed precision: bf16 x/c go to the MXU as-is; norms, the distance
    # accumulation, and the exp nonlinearity stay f32 (DESIGN.md §3)
    j = pl.program_id(1)
    x = x_ref[...]                       # (bn, d) f32 or bf16
    g = kernel_of(jnp.maximum(sq_dists(x, c_ref[...]), 0.0), sigma, p)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += contract(g.astype(x.dtype), a_ref[...].astype(x.dtype))

    @pl.when(j == m_steps - 1)
    def _emit():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _grid_specs(n, d, m, r, block_n, block_m):
    return dict(
        grid=(n // block_n, m // block_m),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_m, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_m, r), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, r), lambda i, j: (i, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )


def kpca_project_pallas(x: Array, centers: Array, projector: Array, *,
                        sigma: float, p: int = 2, block_n: int = 512,
                        block_m: int = 512, interpret: bool = False,
                        out_dtype=jnp.float32) -> Array:
    """Fused z = k(x, C) @ A.  Pad n to block_n, m to block_m and r to a
    lane multiple upstream (padded centers must carry zero projector rows)."""
    n, d = x.shape
    m, d2_ = centers.shape
    m2, r = projector.shape
    assert d == d2_ and m == m2 and n % block_n == 0 and m % block_m == 0

    kernel = functools.partial(_project_kernel, sigma=float(sigma), p=int(p),
                               m_steps=m // block_m)
    return pl.pallas_call(
        kernel,
        name="kpca_project",
        out_shape=jax.ShapeDtypeStruct((n, r), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_n, r), jnp.float32)],
        interpret=interpret,
        **_grid_specs(n, d, m, r, block_n, block_m),
    )(x, centers, projector)


# --------------------------------------------------------------------------
# quantized projector tier (int8 / fp8; kernels/quantize.py)
# --------------------------------------------------------------------------


def _project_kernel_quant(x_ref, c_ref, q_ref, s_ref, o_ref, acc_ref, *,
                          sigma: float, p: int, qmode: str, sg: float,
                          m_steps: int):
    # distances and the exp nonlinearity stay f32 — exactly the f32 kernel
    # above; ONLY the projector contraction drops precision (DESIGN.md §8)
    j = pl.program_id(1)
    g = kernel_of(jnp.maximum(sq_dists(x_ref[...].astype(jnp.float32),
                                       c_ref[...].astype(jnp.float32)), 0.0),
                  sigma, p)                      # (bn, bm) f32, in [0, kappa]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if qmode == "int8":
        # integer contraction with int32 accumulation: EXACT in any order,
        # so this path agrees bitwise with the dense quantized fallback
        gq = jnp.round(g * (1.0 / sg)).astype(jnp.int8)
        acc_ref[...] += jax.lax.dot_general(
            gq, q_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
    else:  # fp8: operands rounded to e4m3, f32 accumulation (the tier's
        # definition; ops refuses it on chips without an fp8 MXU)
        gq = g.astype(_quant.FP8_DTYPE)
        acc_ref[...] += contract(gq.astype(jnp.float32),
                                 q_ref[...].astype(jnp.float32))

    @pl.when(j == m_steps - 1)
    def _emit():
        scale = s_ref[...].astype(jnp.float32)   # (1, r) channel scales
        acc = acc_ref[...].astype(jnp.float32)
        if qmode == "int8":
            acc = acc * sg
        o_ref[...] = (acc * scale).astype(o_ref.dtype)


def kpca_project_quant_pallas(x: Array, centers: Array, q: Array,
                              scale: Array, *, sigma: float, p: int = 2,
                              qmode: str = "int8", block_n: int = 512,
                              block_m: int = 512, interpret: bool = False,
                              out_dtype=jnp.float32) -> Array:
    """Fused z ≈ k(x, C) @ A with the projector pre-quantized
    (kernels/quantize.py): ``q`` (m, r) int8|fp8, ``scale`` (1, r) f32.
    Padding contract as the f32 kernel: padded centers carry zero q rows,
    padded scale columns are 1 and stripped by the caller."""
    n, d = x.shape
    m, d2_ = centers.shape
    m2, r = q.shape
    assert d == d2_ and m == m2 and n % block_n == 0 and m % block_m == 0
    assert scale.shape == (1, r), scale.shape

    specs = _grid_specs(n, d, m, r, block_n, block_m)
    specs["in_specs"].append(pl.BlockSpec((1, r), lambda i, j: (0, 0)))
    kernel = functools.partial(
        _project_kernel_quant, sigma=float(sigma), p=int(p), qmode=str(qmode),
        sg=_quant.gram_scale(qmode), m_steps=m // block_m)
    acc_dtype = jnp.int32 if qmode == "int8" else jnp.float32
    return pl.pallas_call(
        kernel,
        name="kpca_project_quant",
        out_shape=jax.ShapeDtypeStruct((n, r), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_n, r), acc_dtype)],
        interpret=interpret,
        **specs,
    )(x, centers, q, scale)
