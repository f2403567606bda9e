"""Distributed ShDE + RSKPCA (DESIGN.md §3 selection, §5 sharded
fit/transform — the TPU-pod adaptation).

The paper's Algorithm 2 is a greedy sequential scan — fine on one host,
hostile to a 256-chip pod.  We adapt it as a two-level blocked selection:

  level 1: each device runs Algorithm 2 on its local shard (shard_map);
  level 2: candidate centers are all-gathered and a single merge pass runs
           Algorithm 2 *on the centers*, summing absorbed weights.

Correctness: every data point is within eps of its level-1 center, and every
level-1 center is within eps of its level-2 center, so the two-level
quantization error is <= 2*eps (triangle inequality) — the paper's bounds hold
with ell -> ell/2 in the worst case.  Empirically the measured MMD sits far
below even the one-level bound (tests/test_distributed.py).

The Gram assembly and projection are embarrassingly parallel over ROWS: each
device computes the k(x_shard, C) block against the replicated (small) center
set — this is the O(mn) term and parallelizes perfectly, which is what makes
the probe (core/probe.py) cheap at pod scale.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.kernels_math import Kernel, gram_matrix, gram_matrix_dense
from repro.core.rsde import RSDE
from repro.core import shadow as shadow_mod
from repro.kernels import ops as kernel_ops
from repro.kernels.ops import _pad_rows

Array = jax.Array


def _local_shadow(x_loc: Array, eps: Array, max_centers: int,
                  valid_loc: Array):
    """Level-1 selection on one device's shard. Returns padded (c, w)."""
    centers, weights, _, _ = shadow_mod.shadow_select(
        x_loc, eps, max_centers=max_centers, valid=valid_loc
    )
    return centers, weights


@partial(jax.jit, static_argnames=("mesh", "axis", "max_local", "max_global"))
def _two_level_select(x: Array, valid: Array, eps: Array, mesh: Mesh,
                      axis: str, max_local: int, max_global: int):
    """shard_map level-1 + all-gather + replicated level-2 merge."""

    def level1(x_loc, valid_loc):
        c, w = _local_shadow(x_loc, eps, max_centers=max_local,
                             valid_loc=valid_loc)
        # gather every device's candidates (m_loc is data-dependent; padded)
        all_c = jax.lax.all_gather(c, axis, tiled=True)   # (ndev*max_local, d)
        all_w = jax.lax.all_gather(w, axis, tiled=True)   # (ndev*max_local,)
        return all_c, all_w

    all_c, all_w = jax.shard_map(
        level1, mesh=mesh, in_specs=(P(axis, None), P(axis)),
        out_specs=(P(None, None), P(None)), check_vma=False,
    )(x, valid)
    # level-2 merge is replicated (centers are tiny); weights>0 masks padding
    out_c, out_w, m = shadow_mod.two_level_merge(
        all_c, all_w, eps, max_centers=max_global
    )
    return out_c, out_w, m


@partial(jax.jit, static_argnames=("mesh", "axis", "block"))
def _chunk_select_sharded(xp: Array, valid: Array, eps2: Array, mesh: Mesh,
                          axis: str, block: int):
    """Level-1 BLOCKED selection on one ingest chunk, rows sharded over
    ``axis`` — the first device program of each chunk of the out-of-core
    pipeline (core/ingest_pipeline.py, DESIGN.md §9).

    Each device runs the fused blocked-selection while_loop on its local
    rows; the padded per-device (c, w) buffers stay row-sharded on the
    device for ``_merge_chunk_sharded``.  An all-invalid shard (ragged
    final chunk confined to few devices) exits its loop immediately with
    zero candidates — zero-weight rows are the merge's padding contract.
    """

    def level1(x_loc, v_loc):
        _, c, w, _, _ = shadow_mod._blocked_select_device(
            x_loc, eps2, block, v_loc, jnp.asarray(0, jnp.int32))
        return c, w

    return jax.shard_map(
        level1, mesh=mesh, in_specs=(P(axis, None), P(axis)),
        out_specs=(P(axis, None), P(axis)), check_vma=False,
    )(xp, valid)


@partial(jax.jit, static_argnames=("mesh", "axis", "block", "budget"))
def _merge_chunk_sharded(state: dict, c1: Array, w1: Array, eps2: Array,
                         mesh: Mesh, axis: str, block: int,
                         budget: int | None) -> dict:
    """The level-2 merge of one chunk on a mesh: every device all-gathers
    the devices' fixed-shape candidate buckets and folds them, in device
    order, into its replica of the merge state
    (``shadow.merge_chunk_local``), so the
    merged set, its int32 masses and the counters stay replicated on the
    devices and nothing comes back to the host."""
    spec = {k: P() for k in state}
    return jax.shard_map(
        lambda st, c, w: shadow_mod.merge_chunk_local(
            st, c, w, eps2, block, budget, axis),
        mesh=mesh, in_specs=(spec, P(axis, None), P(axis)), out_specs=spec,
        check_vma=False,
    )(state, c1, w1)


def distributed_shadow_rsde(x, kernel: Kernel, ell: float, mesh: Mesh,
                            axis: str = "data",
                            max_local: int | None = None,
                            max_global: int | None = None) -> RSDE:
    """Two-level distributed ShDE over a device mesh axis.

    n need not divide the axis: rows are padded to a device multiple and
    masked out of selection (they are never centers and carry no weight)."""
    ndev = mesh.shape[axis]
    x = jnp.asarray(x, jnp.float32)
    n = x.shape[0]
    xp = _pad_rows(x, ndev)
    valid = (jnp.arange(xp.shape[0]) < n)
    n_loc = xp.shape[0] // ndev
    max_local = max_local or n_loc
    max_global = max_global or min(xp.shape[0], ndev * max_local)
    sharding = NamedSharding(mesh, P(axis, None))
    xp = jax.device_put(xp, sharding)
    c, w, m = _two_level_select(
        xp, valid, jnp.float32(kernel.epsilon(ell)), mesh, axis, max_local,
        max_global
    )
    m = int(m)
    return RSDE(
        centers=np.asarray(c[:m]),
        weights=np.asarray(w[:m], np.float64),
        n=n,
        assign=None,  # assignment is recomputable in one blocked pass if needed
        scheme="shadow2",
    )


def blocked_gram_rows(x, centers, kernel: Kernel, mesh: Mesh,
                      axis: str = "data") -> Array:
    """k(x, C) with rows sharded over ``axis`` and C replicated — the O(mn)
    Gram-block assembly used by both training-side MMD checks and the probe.

    On TPU the per-device block is computed by the Pallas kernel
    (repro.kernels.gram); here sharding is expressed with explicit specs so
    XLA partitions it without any gather of x.
    """
    x = jnp.asarray(x, jnp.float32)
    c = jnp.asarray(centers, jnp.float32)

    def block(x_loc, c_rep):
        return gram_matrix(kernel, x_loc, c_rep)

    return jax.shard_map(
        block, mesh=mesh, in_specs=(P(axis, None), P(None, None)),
        out_specs=P(axis, None), check_vma=False,
    )(x, c)


@partial(jax.jit, static_argnames=("mesh", "axis"))
def _sharded_assign_jit(xp, c, v, mesh: Mesh, axis: str):
    def block(x_loc, c_rep, v_rep):
        return kernel_ops.shadow_assign(x_loc, c_rep, valid=v_rep)

    return jax.shard_map(
        block, mesh=mesh,
        in_specs=(P(axis, None), P(None, None), P(None)),
        out_specs=(P(axis), P(axis)), check_vma=False,
    )(xp, c, v)


def sharded_shadow_assign(x, centers, mesh: Mesh, axis: str = "data",
                          valid=None):
    """Nearest-valid-center pass with x ROWS sharded over ``axis`` and the
    center set replicated: each device runs the Pallas assignment kernel
    (repro.kernels.shadow_assign) on its shard.  Returns (idx, d2min) like
    ``kernel_ops.shadow_assign``; x is padded to a device multiple and
    stripped on the way out.  Jitted (mesh/axis static) so repeated serving
    calls at one shape reuse the compiled sharded program.
    """
    x = jnp.asarray(x, jnp.float32)
    c = jnp.asarray(centers, jnp.float32)
    n, m = x.shape[0], c.shape[0]
    ndev = mesh.shape[axis]
    xp = _pad_rows(x, ndev)
    v = jnp.ones((m,), jnp.float32) if valid is None \
        else jnp.asarray(valid, jnp.float32)
    idx, d2 = _sharded_assign_jit(xp, c, v, mesh, axis)
    return idx[:n], d2[:n]


def distributed_assign(x, centers, mesh: Mesh, axis: str = "data") -> Array:
    """Recover the data->center map alpha in one sharded pass (O(mn/devices)),
    routed through the Pallas assignment kernel per shard."""
    idx, _ = sharded_shadow_assign(x, centers, mesh, axis=axis)
    return idx


def sharded_weighted_gram(centers, weights, kernel: Kernel, mesh: Mesh,
                          axis: str = "data") -> Array:
    """Algorithm 1's K-tilde = W K^C W with center ROWS sharded over ``axis``
    and the center set replicated as columns — the fit-side O(m^2) assembly
    of DESIGN.md §5.  Callers pad (centers, weights) to a device multiple
    with zero-weight rows (sqrt(0) zeroes the padded rows/columns, so the
    padded spectrum gains only zeros)."""
    c = jnp.asarray(centers, jnp.float32)
    w = jnp.asarray(weights, jnp.float32)

    def block(c_loc, w_loc, c_rep, w_rep):
        if kernel.backend == "pallas":
            return kernel_ops.gram(c_loc, c_rep, sigma=kernel.sigma,
                                   p=kernel.p, wx=w_loc, wy=w_rep,
                                   precision=kernel.precision)
        g = gram_matrix_dense(kernel, c_loc, c_rep)
        return g * jnp.sqrt(w_loc)[:, None] * jnp.sqrt(w_rep)[None, :]

    return jax.shard_map(
        block, mesh=mesh,
        in_specs=(P(axis, None), P(axis), P(None, None), P(None)),
        out_specs=P(axis, None), check_vma=False,
    )(c, w, c, w)


@partial(jax.jit, static_argnames=("kernel", "mesh", "axis"))
def _sharded_wgram_jit(c, w, kernel: Kernel, mesh: Mesh, axis: str):
    return sharded_weighted_gram(c, w, kernel, mesh, axis=axis)


@partial(jax.jit, static_argnames=("kernel", "mesh", "axis", "chunk"))
def _sharded_project_jit(xp, c, a, kernel: Kernel, mesh: Mesh, axis: str,
                         chunk: int | None):
    def block(x_loc, c_rep, a_rep):
        if kernel.backend == "pallas":
            return kernel_ops.kpca_project(
                x_loc, c_rep, a_rep, sigma=kernel.sigma, p=kernel.p,
                chunk=chunk, precision=kernel.precision)
        return gram_matrix_dense(kernel, x_loc, c_rep) @ a_rep

    return jax.shard_map(
        block, mesh=mesh,
        in_specs=(P(axis, None), P(None, None), P(None, None)),
        out_specs=P(axis, None), check_vma=False,
    )(xp, c, a)


def sharded_kpca_project(x, centers, projector, kernel: Kernel, mesh: Mesh,
                         axis: str = "data", chunk: int | None = None):
    """Fused z = k(x, C) @ A with query ROWS sharded over ``axis`` and the
    (m, d) centers + (m, r) projector replicated (DESIGN.md §5).  Per device
    the fused Pallas projection kernel runs on the local shard (streamed in
    ``chunk`` rows if given); only the (n/ndev, r) embeddings travel back.
    Jitted (kernel/mesh/axis/chunk static) so repeated serving calls at one
    shape reuse the compiled sharded program.
    """
    x = jnp.asarray(x, jnp.float32)
    c = jnp.asarray(centers, jnp.float32)
    a = jnp.asarray(projector, jnp.float32)
    n = x.shape[0]
    ndev = mesh.shape[axis]
    # pad rows to a shape BUCKET, not just a device multiple: a ragged
    # serving stream then re-traces the sharded program once per
    # (chunk * ndev) bucket instead of once per distinct query size — the
    # mesh-side analogue of the single-device tail-chunk padding contract
    if chunk is not None and n > chunk * ndev:
        xp = _pad_rows(x, ndev * chunk)
        eff_chunk = chunk  # per-device rows are an exact chunk multiple
    else:
        xp = _pad_rows(x, ndev * 128)
        eff_chunk = None
    z = _sharded_project_jit(xp, c, a, kernel, mesh, axis, eff_chunk)
    return z[:n]


@partial(jax.jit,
         static_argnames=("kernel", "rank", "mesh", "axis", "lobpcg_min_m",
                          "matfree"))
def _fit_rskpca_sharded(c: Array, w: Array, n: Array, kernel: Kernel,
                        rank: int, mesh: Mesh, axis: str,
                        lobpcg_min_m: int, matfree: bool = False):
    """Algorithm 1 with the Gram assembly sharded over center rows and, for
    large m, the LOBPCG matvec distributed the same way — the m x m operator
    never needs to be replicated; only the (m, r) projector is.

    ``matfree=True`` (DESIGN.md §6) goes one step further: the sharded m x m
    operator is never ASSEMBLED either.  Each device runs the fused
    ``gram_matvec`` Pallas kernel on its row-tile of centers against the
    replicated center set, so per-device peak memory is O(m_loc * r + tiles)
    instead of O(m_loc * m) — the pod-scale analogue of the single-device
    matrix-free fit.
    """
    from repro.core.rskpca import _canonicalize_signs, _lobpcg_topk

    sw = jnp.sqrt(w)
    m_pad = c.shape[0]
    if matfree:
        # honored UNCONDITIONALLY: the caller asked for the memory contract,
        # so the sharded Gram is never assembled regardless of the wall-clock
        # crossover (the single-device matfree branch behaves the same way)
        def matvec(v):
            def blk(c_loc, w_loc, c_rep, w_rep, v_rep):
                return kernel_ops.gram_matvec(
                    c_loc, c_rep, v_rep, wx=w_loc, wy=w_rep,
                    sigma=kernel.sigma, p=kernel.p,
                    precision=kernel.precision, allow_dense=False)
            out = jax.shard_map(
                blk, mesh=mesh,
                in_specs=(P(axis, None), P(axis), P(None, None), P(None),
                          P(None, None)),
                out_specs=P(axis, None), check_vma=False,
            )(c, w, c, w, v)
            return out / n

        lam, u, _ = _lobpcg_topk(matvec, m_pad, rank)
    else:
        kt = sharded_weighted_gram(c, w, kernel, mesh, axis=axis) / n
        if m_pad > lobpcg_min_m and 5 * rank < m_pad:
            def matvec(v):
                def blk(k_loc, v_rep):
                    return jnp.dot(k_loc, v_rep,
                                   preferred_element_type=jnp.float32)
                return jax.shard_map(
                    blk, mesh=mesh, in_specs=(P(axis, None), P(None, None)),
                    out_specs=P(axis, None), check_vma=False,
                )(kt, v)

            lam, u, _ = _lobpcg_topk(matvec, m_pad, rank)
        else:
            lam, u = jnp.linalg.eigh(kt)  # ascending
            lam = lam[::-1][:rank]
            u = _canonicalize_signs(u[:, ::-1][:, :rank])
    lam = jnp.maximum(lam, 1e-12)
    proj = (sw[:, None] * u) / jnp.sqrt(lam)[None, :] / jnp.sqrt(n)
    return lam, proj


def fit_rskpca_sharded(centers, weights, n: int, kernel: Kernel, rank: int,
                       mesh: Mesh, axis: str = "data",
                       lobpcg_min_m: int | None = None,
                       matfree: bool | None = None, m: int | None = None):
    """Sharded Algorithm 1 core: returns (eigvals (rank,), projector (m, r)).

    ``centers``/``weights`` are an exact host (m, d) set, or a device buffer
    whose first ``m`` rows are live (the streaming merge's replicated
    state).  Either way they are cut or zero-padded to the power-of-two
    capacity bucket of m, as ``pipeline.fit_centers`` does (zero-weight
    rows contribute zero K-tilde rows/columns and zero projector rows), so
    one compile serves every m in a bucket; the padding is stripped on the
    host.  ``lobpcg_min_m`` is a test hook to force the distributed-matvec
    eigensolve at small m.  ``matfree`` (None = the bytes-budget policy of
    kernels.ops.matfree_fit) skips the sharded Gram assembly entirely and
    streams matvec row-tiles through the fused Pallas kernel per device
    (DESIGN.md §6).

    On CPU, small-m eigensolves hop to the same LAPACK subset driver the
    single-device fit uses (rskpca._host_subset_eigh) — same solver on both
    paths is what makes the 1e-5 sharded-vs-single parity hold.
    """
    from repro.core.rskpca import (_LOBPCG_MIN_M, _fold_projector,
                                   _host_subset_eigh, _use_matfree)

    m = int(np.shape(centers)[0] if m is None else m)
    ndev = mesh.shape[axis]
    cap = -(-shadow_mod._pow2_ceil(max(m, 128)) // ndev) * ndev
    if isinstance(centers, jax.Array) and centers.shape[0] >= cap:
        cp = centers[:cap].astype(jnp.float32)
        wp = weights[:cap].astype(jnp.float32)
    else:  # an exact host set: pad it on the host, one transfer
        cp = np.zeros((cap, np.shape(centers)[1]), np.float32)
        cp[:m] = np.asarray(centers)[:m]
        wp = np.zeros((cap,), np.float32)
        wp[:m] = np.asarray(weights)[:m]
        cp, wp = jnp.asarray(cp), jnp.asarray(wp)
    min_m = _LOBPCG_MIN_M if lobpcg_min_m is None else int(lobpcg_min_m)
    use_mf = _use_matfree(kernel, cap, rank, matfree)
    if (not use_mf and jax.default_backend() == "cpu"
            and cap <= min_m):
        kt = np.asarray(_sharded_wgram_jit(cp, wp, kernel, mesh, axis)) \
            / np.float32(n)
        top = _host_subset_eigh(kt, rank)
        if top is not None:
            lam, proj = _fold_projector(*top, np.asarray(wp), n)
            return np.asarray(lam), np.asarray(proj)[:m]
    lam, proj = _fit_rskpca_sharded(
        cp, wp, jnp.float32(n), kernel, rank, mesh, axis, min_m,
        matfree=use_mf)
    return np.asarray(lam), np.asarray(proj)[:m]


# --------------------------------------------------------------------------
# method zoo: sharded Nystrom extension + RFF covariance / projection
# (DESIGN.md §15 — the mesh= paths of fit_nystrom / fit_rff)
# --------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("kernel", "mesh", "axis"))
def _sharded_extend_jit(xp, lmk, bmat, kernel: Kernel, mesh: Mesh,
                        axis: str):
    def block(x_loc, l_rep, b_rep):
        if kernel.backend == "pallas":
            return kernel_ops.gram_matvec(
                x_loc, l_rep, b_rep, sigma=kernel.sigma, p=kernel.p,
                precision=kernel.precision)
        return gram_matrix_dense(kernel, x_loc, l_rep) @ b_rep

    return jax.shard_map(
        block, mesh=mesh,
        in_specs=(P(axis, None), P(None, None), P(None, None)),
        out_specs=P(axis, None), check_vma=False,
    )(xp, lmk, bmat)


def sharded_nystrom_extend(x, landmarks, bmat, kernel: Kernel, mesh: Mesh,
                           axis: str = "data") -> Array:
    """One chunk of the Nystrom extension proj = K_nm @ B with data ROWS
    sharded over ``axis`` and the (m, d) landmarks + (m, r) fold matrix
    replicated.  Per device the fused ``gram_matvec`` kernel streams K
    tiles through VMEM — the local rows x m Gram block never materializes
    (same contract as the single-device chunked extension)."""
    x = jnp.asarray(x, jnp.float32)
    n = x.shape[0]
    ndev = mesh.shape[axis]
    xp = _pad_rows(x, ndev * 128)
    out = _sharded_extend_jit(xp, jnp.asarray(landmarks, jnp.float32),
                              jnp.asarray(bmat, jnp.float32), kernel, mesh,
                              axis)
    return out[:n]


@partial(jax.jit, static_argnames=("mesh", "axis", "scale", "precision"))
def sharded_rff_cov(xd, ok, omega, phase, mesh: Mesh, axis: str = "data", *,
                    scale: float, precision: str = "f32") -> Array:
    """One chunk's feature-covariance contribution sum_i phi(x_i) phi(x_i)^T
    with the chunk's rows sharded over ``axis``: each device computes its
    local phi^T phi partial and a psum replicates the (D, D) result —
    only O(D^2) crosses the interconnect per chunk, never features."""
    def block(x_loc, ok_loc, w_rep, b_rep):
        z = kernel_ops.rff_features(x_loc, w_rep, b_rep, scale=scale,
                                    precision=precision)
        z = jnp.where(ok_loc[:, None], z, 0.0)
        cd = jnp.float32 if precision == "f32" else jnp.bfloat16
        part = jax.lax.dot_general(
            z.astype(cd), z.astype(cd), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return jax.lax.psum(part, axis)

    return jax.shard_map(
        block, mesh=mesh,
        in_specs=(P(axis, None), P(axis), P(None, None), P(None)),
        out_specs=P(None, None), check_vma=False,
    )(xd, ok, omega, phase)


@partial(jax.jit,
         static_argnames=("mesh", "axis", "chunk", "scale", "precision"))
def _sharded_rff_project_jit(xp, omega, phase, u, mesh: Mesh, axis: str,
                             chunk: int | None, scale: float,
                             precision: str):
    def block(x_loc, w_rep, b_rep, u_rep):
        return kernel_ops.rff_project(
            x_loc, w_rep, b_rep, u_rep, scale=scale, chunk=chunk,
            precision=precision)

    return jax.shard_map(
        block, mesh=mesh,
        in_specs=(P(axis, None), P(None, None), P(None), P(None, None)),
        out_specs=P(axis, None), check_vma=False,
    )(xp, omega, phase, u)


def sharded_rff_project(x, omega, phase, u, mesh: Mesh, axis: str = "data",
                        chunk: int | None = None,
                        precision: str = "f32") -> Array:
    """z = sqrt(2/D) cos(x Omega^T + b) @ U with query ROWS sharded and
    (Omega, b, U) replicated — the RFF analogue of sharded_kpca_project,
    with the same shape-bucket padding so ragged serving streams retrace
    once per (chunk * ndev) bucket."""
    x = jnp.asarray(x, jnp.float32)
    n = x.shape[0]
    ndev = mesh.shape[axis]
    scale = float(np.sqrt(2.0 / omega.shape[0]))
    if chunk is not None and n > chunk * ndev:
        xp = _pad_rows(x, ndev * chunk)
        eff_chunk = chunk
    else:
        xp = _pad_rows(x, ndev * 128)
        eff_chunk = None
    z = _sharded_rff_project_jit(
        xp, jnp.asarray(omega, jnp.float32), jnp.asarray(phase, jnp.float32),
        jnp.asarray(u, jnp.float32), mesh, axis, eff_chunk, scale, precision)
    return z[:n]
