"""Pallas TPU kernel: fused random-Fourier-feature KPCA projection.

z = phi_D(x) @ U with phi_D(x) = sqrt(2/D) cos(x Omega^T + b) — the O(D(d+k))
test path of RFF-KPCA (Sriperumbudur & Sterge; DESIGN.md §15).  Fusing the
feature map with the component contraction keeps the (bn x D) feature block
in VMEM and writes only the (bn x r) embedding to HBM, the same bandwidth
argument as kpca_project.

Grid (row tiles of X, feature tiles): the second axis sweeps ``block_f``
random features at a time — Omega (bf x d), phase (1 x bf) and U (bf x r)
tiles — and the (bn x r) embedding accumulates in VMEM scratch, so the
feature count D (which plays the role m plays for the center-based methods)
is bounded by HBM, not VMEM.  Both matmuls hit the MXU; the cosine runs f32
regardless of operand precision.

Padding contract (enforced upstream in ops.rff_project): padded FEATURE rows
must carry zero Omega rows, zero phase, and zero U rows — cos(0 + 0) = 1
times a zero U row contributes nothing.  Padded data columns are zero in
both x and Omega (they don't move the inner product).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gram import contract, cross

Array = jax.Array


def _rff_kernel(x_ref, w_ref, b_ref, u_ref, o_ref, acc_ref, *, scale: float,
                f_steps: int):
    # mixed precision: bf16 x/Omega feed the MXU as-is with f32 accumulation;
    # the phase add and the cosine stay f32 (DESIGN.md §3 conventions)
    j = pl.program_id(1)
    x = x_ref[...]                        # (bn, d) f32 or bf16
    s = cross(x, w_ref[...])              # (bn, bf) f32
    feat = jnp.cos(s + b_ref[...]) * scale  # f32 feature tile, never to HBM

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += contract(feat.astype(x.dtype), u_ref[...].astype(x.dtype))

    @pl.when(j == f_steps - 1)
    def _emit():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def rff_project_pallas(x: Array, omega: Array, phase: Array, u: Array, *,
                       scale: float, block_n: int = 512, block_f: int = 512,
                       interpret: bool = False,
                       out_dtype=jnp.float32) -> Array:
    """Fused z = (scale * cos(x Omega^T + b)) @ U.  Pad n to block_n, D to
    block_f and r to a lane multiple upstream (padding contract in the
    module doc); ``scale`` is sqrt(2/D) with the TRUE (unpadded) feature
    count."""
    n, d = x.shape
    nfeat, d2 = omega.shape
    nfeat2, r = u.shape
    assert d == d2 and nfeat == nfeat2 and n % block_n == 0
    assert nfeat % block_f == 0, (nfeat, block_f)
    assert phase.shape == (1, nfeat), phase.shape

    kernel = functools.partial(_rff_kernel, scale=float(scale),
                               f_steps=nfeat // block_f)
    return pl.pallas_call(
        kernel,
        name="rff",
        grid=(n // block_n, nfeat // block_f),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_f, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, block_f), lambda i, j: (0, j)),
            pl.BlockSpec((block_f, r), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, r), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, r), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_n, r), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, omega, phase, u)
