"""Public jit'd wrappers around the Pallas kernels.

Responsibilities (DESIGN.md §3):
  * pad inputs to block multiples (and mask/strip on the way out);
  * pick a compute plan per call via the measured autotuner in
    ``repro.kernels.autotune``: the Pallas kernel (tuned tiles) above the
    crossover, a dense-jnp fallback below it so small problems stop paying
    Pallas interpret/grid overhead;
  * mixed precision: ``precision="bf16"`` feeds bf16 operands to the MXU
    matmuls while the distance accumulation and the exp nonlinearity stay
    f32;
  * dispatch: compiled Pallas on TPU, interpret=True elsewhere (the CPU
    test suite exercises the kernel bodies in interpret mode).

``plan=`` forces a path explicitly ("pallas" | "pallas_fat" | "dense");
tests use it to keep the kernel bodies exercised regardless of what the
autotuner would pick.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import autotune
from repro.obs.trace import profiled_span as _profiled_span

# NOTE on donation: the donated fit/transform entry points mark their
# scratch operands dead for the caller; XLA only ALIASES a donated buffer
# into an output of matching shape and emits a trace-time UserWarning
# otherwise.  Off-alias donation is the expected steady state here
# (projector outputs rarely match center-buffer shapes), and the warning is
# deliberately NOT suppressed: a global filter would swallow user code's own
# donation diagnostics and a per-call catch_warnings races across serving
# threads.  Python's default dedup shows it once per compiled shape;
# aliasing success is asserted where it matters, in tests/test_matfree.py.
from repro.kernels import gram as _gram
from repro.kernels import shadow_assign as _assign
from repro.kernels import kpca_project as _project
from repro.kernels import quantize as _quantize
from repro.kernels import rff as _rff

Array = jax.Array

_VMEM_BUDGET_BYTES = 8 * 1024 * 1024

#: "int8"/"fp8" are the SERVING tiers (DESIGN.md §8): they quantize only the
#: kpca_project projector contraction; every other Gram-shaped op (fit-side
#: gram/gram_matvec/gram_row) treats them as the bf16 MXU tier, and
#: shadow_assign always resolves distances in f32 regardless.
_PRECISIONS = ("f32", "bf16") + _quantize.QUANT_PRECISIONS


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


#: Center (or random-feature) tile of the kernels that sweep the operator
#: along a grid axis: assignment, projection and the RFF transform.
CENTER_TILE = 512


def center_tile(m: int) -> int:
    """Operator-axis tile for m centers: CENTER_TILE, shrunk to the
    128-padded m so a small operator pads no further than a lane multiple."""
    return min(CENTER_TILE, _round_up(max(m, 1), 128))


#: TPU generations whose MXU has no fp8 path.
_NO_FP8_MXU = ("TPU v2", "TPU v3", "TPU v4", "TPU v5", "TPU v6")


def fp8_mxu() -> bool:
    """Whether the default device contracts fp8 operands natively."""
    return not jax.devices()[0].device_kind.startswith(_NO_FP8_MXU)


def _pad_rows(a: Array, mult: int, value: float = 0.0) -> Array:
    n = a.shape[0]
    pad = _round_up(max(n, 1), mult) - n
    if pad == 0:
        return a
    widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, widths, constant_values=value)


def _compute_dtype(precision: str):
    if precision not in _PRECISIONS:
        raise ValueError(
            f"unknown precision {precision!r}; expected one of {_PRECISIONS}")
    # every reduced tier (bf16 AND the int8/fp8 serving tiers) feeds bf16
    # operands to the non-projector MXU matmuls; f32 stays f32
    return jnp.float32 if precision == "f32" else jnp.bfloat16


def pick_gram_blocks(d: int, budget: int = _VMEM_BUDGET_BYTES):
    """(bn, bm, bk): output tile + K-chunk so the working set
    (bn*bk + bm*bk + bn*bm) * 4B fits the VMEM budget.

    K-chunking (accumulating partial distances over feature chunks) keeps
    the 512x512 output tile at ANY d - without it d=4096 forced 128x128
    tiles and dropped arithmetic intensity to ~31 FLOP/byte (the P2 table in
    benchmarks/rskpca_scale.py reports the per-d numbers).

    This is the VMEM-safety baseline the autotuner starts from; the measured
    plan (repro.kernels.autotune) may instead pick fatter interpret-mode
    tiles or the dense fallback."""
    for b in (512, 256, 128):
        for bk in (min(d, 512), 256, 128):
            if bk > d:
                continue
            if (2 * b * bk + b * b) * 4 <= budget:
                return b, b, bk
    return 128, 128, 128


def _fat_gram_blocks(d: int):
    """Interpret-mode tiles: off-TPU there is no VMEM limit and the grid
    loop itself is the overhead, so take far fewer, fatter row tiles."""
    return 2048, 512, min(512, _round_up(d, 128))


# --------------------------------------------------------------------------
# dense-jnp fallbacks (the below-crossover plan; also honor bf16 operands)
# --------------------------------------------------------------------------


def _named(kernel: str):
    """Trace a dense plan under its kernel's name (``jax.named_scope``), the
    name its Pallas plan gives ``pallas_call``, so that a device trace can
    attribute the dense plan's ops to the kernel."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(kernel):
                return fn(*args, **kwargs)
        return scoped
    return wrap


def _dist_pow(d2: Array, p: int) -> Array:
    if p == 2:
        return d2
    if p == 1:
        return jnp.sqrt(d2)
    return d2 ** (p / 2.0)


def _dense_sq_dists(x: Array, y: Array, precision: str) -> Array:
    """f32 norms + (optionally bf16) MXU cross term, f32 accumulation."""
    cd = _compute_dtype(precision)
    xx = jnp.sum(x * x, axis=-1, keepdims=True)
    yy = jnp.sum(y * y, axis=-1, keepdims=True).T
    return jnp.maximum(xx + yy - 2.0 * _gram.cross(x.astype(cd), y.astype(cd)),
                       0.0)


@functools.partial(jax.jit,
                   static_argnames=("sigma", "p", "weighted", "precision"))
@_named("gram")
def _gram_dense(x, y, wx, wy, *, sigma, p, weighted, precision):
    d2 = _dense_sq_dists(x, y, precision)
    g = jnp.exp(-_dist_pow(d2, p) / sigma**p)
    if weighted:
        g = g * jnp.sqrt(wx)[:, None] * jnp.sqrt(wy)[None, :]
    return g


@functools.partial(jax.jit,
                   static_argnames=("sigma", "p", "weighted", "precision"))
@_named("gram_matvec")
def _gram_matvec_dense(x, y, wx, wy, v, *, sigma, p, weighted, precision):
    """Below-crossover fallback: materialize the (small) Gram, then matmul."""
    g = _gram_dense(x, y, wx, wy, sigma=sigma, p=p, weighted=weighted,
                    precision=precision)
    cd = _compute_dtype(precision)
    return _gram.contract(g.astype(cd), v.astype(cd))


@functools.partial(jax.jit, static_argnames=())
@_named("shadow_assign")
def _assign_dense(x, c, valid):
    d2 = _dense_sq_dists(x, c, "f32")  # assignment always resolves in f32
    d2 = jnp.where(valid[None, :] > 0.0, d2, jnp.inf)
    return jnp.argmin(d2, axis=1).astype(jnp.int32), jnp.min(d2, axis=1)


@functools.partial(jax.jit, static_argnames=("sigma", "p", "precision"))
@_named("kpca_project")
def _project_dense(x, c, a, *, sigma, p, precision):
    cd = _compute_dtype(precision)
    d2 = _dense_sq_dists(x, c, precision)
    g = jnp.exp(-_dist_pow(d2, p) / sigma**p)  # nonlinearity stays f32
    return _gram.contract(g.astype(cd), a.astype(cd))


@functools.partial(jax.jit, static_argnames=("sigma", "p", "qmode"))
@_named("kpca_project_quant")
def _project_dense_quant(x, c, q, s, *, sigma, p, qmode):
    # dense fallback of the quantized serving tier: IDENTICAL quantized
    # arithmetic to kernels/kpca_project._project_kernel_quant — the int8
    # contraction accumulates in int32 (integer-exact), so this path and
    # the Pallas path agree bitwise (asserted in tests/test_quantized.py)
    d2 = _dense_sq_dists(x, c, "f32")
    g = jnp.exp(-_dist_pow(d2, p) / sigma**p)
    sj = jnp.asarray(s, jnp.float32)[None, :]
    if qmode == "int8":
        sg = _quantize.gram_scale(qmode)
        gq = jnp.round(g * (1.0 / sg)).astype(jnp.int8)
        acc = jax.lax.dot_general(
            gq, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        return acc.astype(jnp.float32) * sg * sj
    gq = g.astype(_quantize.FP8_DTYPE)
    return _gram.contract(gq.astype(jnp.float32), q.astype(jnp.float32)) * sj


# --------------------------------------------------------------------------
# autotuned plan selection
# --------------------------------------------------------------------------


#: Plan measurement runs on shapes clamped to this many rows: beyond it the
#: relative ranking of candidates is stable, and an unclamped measurement at
#: a 64k-row bucket would cost a full Gram just to pick tiles.
_MEASURE_MAX_ROWS = 8192


def _bench_rows(n: int, d: int) -> np.ndarray:
    # deterministic synthetic operands for plan measurement (values are
    # irrelevant to timing).  Host arrays, so they stay concrete when the op
    # asking for a plan is itself being traced.  Plan functions build them in
    # a ``functools.cache``d thunk that only their candidates call, so a plan
    # the cache holds builds none and a miss builds them once.
    n = min(n, _MEASURE_MAX_ROWS)
    return (np.arange(n * d, dtype=np.float32) % np.float32(977.0)
            ).reshape(n, d) / np.float32(977.0)


def _gram_plan(n: int, m: int, d: int, precision: str, interpret: bool):
    """Returns ("dense", None) or ("pallas", (bn, bm, bk))."""
    nb, mb = autotune.bucket(n), autotune.bucket(m)
    db = autotune.bucket(d, lo=8, hi=8192)
    mode = "interp" if interpret else "tpu"
    if not autotune.measurement_enabled():
        kind = autotune.heuristic_plan(n, m, interpret)
        return ((kind, None) if kind == "dense"
                else ("pallas", pick_gram_blocks(d)))
    key = f"gram|n{nb}|m{mb}|d{db}|{precision}|{mode}"
    operands = functools.cache(
        lambda: (_bench_rows(nb, db), _bench_rows(mb, db)))

    def run(plan):
        return lambda: jax.block_until_ready(gram(
            *operands(), sigma=1.0, p=2, interpret=interpret,
            precision=precision, plan=plan))

    cands = {"pallas": run("pallas")}
    if interpret:
        cands["pallas_fat"] = run("pallas_fat")
    if nb * mb <= autotune.DENSE_MAX_CELLS:
        cands["dense"] = run("dense")
    winner = autotune.best(key, cands, default="pallas")
    if winner == "dense":
        return "dense", None
    blocks = _fat_gram_blocks(d) if winner == "pallas_fat" \
        else pick_gram_blocks(d)
    return "pallas", blocks


def _matvec_plan(n: int, m: int, d: int, r: int, precision: str,
                 interpret: bool, allow_dense: bool = True):
    """Returns ("dense", None) or ("pallas", (bn, bm, bk)) for gram_matvec.

    ``allow_dense=False`` keeps the dense (Gram-materializing) fallback out
    of the candidate set entirely — the matrix-free fit's memory guarantee
    must hold even where dense would win on wall-clock, so it tunes only
    over the streaming tile shapes (under its own cache key).
    """
    nb, mb = autotune.bucket(n), autotune.bucket(m)
    db = autotune.bucket(d, lo=8, hi=8192)
    rb = autotune.bucket(r, lo=8, hi=512)
    if not autotune.measurement_enabled():
        kind = autotune.heuristic_plan(n, m, interpret)
        return ((kind, None) if kind == "dense" and allow_dense
                else ("pallas", pick_gram_blocks(d)))
    mode = "interp" if interpret else "tpu"
    key = f"gmv|n{nb}|m{mb}|d{db}|r{rb}|{precision}|{mode}" \
        + ("" if allow_dense else "|nd")
    operands = functools.cache(
        lambda: (_bench_rows(nb, db), _bench_rows(mb, db),
                 _bench_rows(mb, rb)))

    def run(plan):
        return lambda: jax.block_until_ready(gram_matvec(
            *operands(), sigma=1.0, p=2, interpret=interpret,
            precision=precision, plan=plan))

    cands = {"pallas": run("pallas")}
    if interpret:
        cands["pallas_fat"] = run("pallas_fat")
    if allow_dense and nb * mb <= autotune.DENSE_MAX_CELLS:
        cands["dense"] = run("dense")
    winner = autotune.best(key, cands, default="pallas")
    if winner == "dense":
        return "dense", None
    blocks = _fat_gram_blocks(d) if winner == "pallas_fat" \
        else pick_gram_blocks(d)
    return "pallas", blocks


def _assign_plan(n: int, m: int, d: int, interpret: bool,
                 tag: str = "") -> str:
    """``tag`` namespaces the measured plan: the chunked ingest path
    (streaming merge + per-chunk assign, DESIGN.md §9) replays ONE shape
    thousands of times back-to-back, so its crossover is measured and
    cached under its own ``|<tag>`` key instead of sharing (and fighting
    over) the serving-shape entry."""
    nb, mb = autotune.bucket(n), autotune.bucket(m)
    db = autotune.bucket(d, lo=8, hi=8192)
    if not autotune.measurement_enabled():
        return autotune.heuristic_plan(n, m, interpret)
    mode = "interp" if interpret else "tpu"
    key = f"assign|n{nb}|m{mb}|d{db}|{mode}" + (f"|{tag}" if tag else "")
    operands = functools.cache(
        lambda: (_bench_rows(nb, db), _bench_rows(mb, db)))

    def run(plan):
        return lambda: jax.block_until_ready(shadow_assign(
            *operands(), interpret=interpret, plan=plan)[1])

    cands = {"pallas": run("pallas")}
    if nb * mb <= autotune.DENSE_MAX_CELLS:
        cands["dense"] = run("dense")
    return autotune.best(key, cands, default="pallas")


#: Row-tile candidates for the fused projection kernel.  Off-TPU the
#: interpret-mode grid loop dominates, so larger tiles (fewer grid steps)
#: tend to win; on hardware VMEM residency of the (bn, m) Gram block pulls
#: the other way.  The roofline tuner picks among these from measured
#: bytes/FLOPs crossovers, not raw time (autotune.best_roofline).
_PROJECT_TILES_TPU = (256, 512, 1024)
_PROJECT_TILES_INTERPRET = (512, 1024, 2048)


def _project_costs(n: int, m: int, d: int, r: int, bn: int, dense: bool,
                   precision: str) -> tuple[float, float]:
    """Analytic (flops, bytes) of one projection at the measured shape.

    FLOPs are plan-invariant: n rows x (distance matmul 2md + exp/dist
    pointwise ~4m + projection matmul 2mr).  Bytes are where plans differ —
    the fused kernel re-reads centers + projector from HBM once per grid
    step, the dense fallback streams each once but writes AND re-reads the
    materialized (n, m) Gram; a quantized projector moves 1 byte/element.
    """
    qb = 1.0 if precision in _quantize.QUANT_PRECISIONS else 4.0
    flops = float(n) * (2.0 * m * d + 4.0 * m + 2.0 * m * r)
    if dense:
        byts = 4.0 * (n * d + m * d + n * r + 2.0 * n * m) + qb * m * r
    else:
        tiles = max(1, -(-n // bn))
        byts = 4.0 * (n * d + n * r) + tiles * (4.0 * m * d + qb * m * r)
    return flops, byts


def _project_plan(n: int, m: int, d: int, r: int, precision: str,
                  interpret: bool) -> str:
    """Roofline-tuned plan: "dense" or "pallas:<row-tile>"."""
    nb, mb = autotune.bucket(n), autotune.bucket(m)
    db = autotune.bucket(d, lo=8, hi=8192)
    rb = autotune.bucket(r, lo=8, hi=512)
    if not autotune.measurement_enabled():
        return autotune.heuristic_plan(n, m, interpret)
    mode = "interp" if interpret else "tpu"
    key = f"project|n{nb}|m{mb}|d{db}|r{rb}|{precision}|{mode}"

    @functools.cache
    def operands():
        x, c = _bench_rows(nb, db), _bench_rows(mb, db)
        a = _bench_rows(mb, rb)
        # quantize the bench projector once, ahead of the timed calls: the
        # serving contract quantizes at snapshot publish, so per-call
        # quantization must not pollute the timing.  Built on the
        # measurement's thread, so it stays concrete under an outer trace
        aq = (_quantize.quantize_projector(a, precision)
              if precision in _quantize.QUANT_PRECISIONS else None)
        return x, c, a, aq

    def run(plan):
        def call():
            x, c, a, aq = operands()
            return jax.block_until_ready(kpca_project(
                x, c, a, sigma=1.0, p=2, interpret=interpret,
                precision=precision, plan=plan, projector_q=aq))
        return call

    neff, meff = min(nb, _MEASURE_MAX_ROWS), min(mb, _MEASURE_MAX_ROWS)
    tiles = _PROJECT_TILES_INTERPRET if interpret else _PROJECT_TILES_TPU
    cands, costs = {}, {}
    for t in tiles:
        name = f"pallas:{t}"
        bn_eff = min(t, _round_up(neff, 128))
        cands[name] = run(name)
        costs[name] = _project_costs(neff, meff, db, rb, bn_eff,
                                     dense=False, precision=precision)
    if nb * mb <= autotune.DENSE_MAX_CELLS:
        cands["dense"] = run("dense")
        costs["dense"] = _project_costs(neff, meff, db, rb, 0, dense=True,
                                        precision=precision)
    return autotune.best_roofline(key, cands, costs,
                                  default=f"pallas:{tiles[0]}")


# --------------------------------------------------------------------------
# gram
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("sigma", "p", "interpret",
                                             "bn", "bm", "bk"))
def _gram_call(xp, yp, wxp, wyp, *, sigma, p, interpret, bn, bm, bk):
    return _gram.gram_pallas(xp, yp, sigma=sigma, p=p, wx=wxp.reshape(-1, 1),
                             wy=wyp.reshape(1, -1),
                             block_n=bn, block_m=bm, block_k=bk,
                             interpret=interpret)


def gram(x, y, *, sigma: float, p: int = 2, wx=None, wy=None,
         interpret: bool | None = None, precision: str = "f32",
         plan: str | None = None) -> Array:
    """(Weighted) Gram matrix; pads and strips.

    ``plan=None`` consults the autotuner (Pallas with tuned tiles vs the
    dense fallback); ``precision="bf16"`` runs the cross-term matmul on bf16
    operands with f32 accumulation (parity tolerances documented in
    tests/test_precision.py).
    """
    if interpret is None:
        interpret = not _on_tpu()
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    n, m = x.shape[0], y.shape[0]
    blocks = None
    if plan is None:
        plan, blocks = _gram_plan(n, m, x.shape[1], precision, interpret)
    if plan == "dense":
        ones_n = jnp.ones((n,), jnp.float32)
        ones_m = jnp.ones((m,), jnp.float32)
        weighted = wx is not None or wy is not None
        return _gram_dense(
            x, y,
            jnp.asarray(wx, jnp.float32) if wx is not None else ones_n,
            jnp.asarray(wy, jnp.float32) if wy is not None else ones_m,
            sigma=float(sigma), p=int(p), weighted=weighted,
            precision=precision)
    if blocks is None:
        blocks = _fat_gram_blocks(x.shape[1]) if plan == "pallas_fat" \
            else pick_gram_blocks(x.shape[1])
    bn, bm, bk = blocks
    # shrink tiles toward small inputs so a 150-row Gram doesn't pad to 512
    bn = min(bn, _round_up(n, 128))
    bm = min(bm, _round_up(m, 128))
    bk = min(bk, _round_up(x.shape[1], 128))
    # pad the feature dim to the K-chunk (zero features don't move distances)
    dpad = _round_up(x.shape[1], bk) - x.shape[1]
    if dpad:
        x = jnp.pad(x, ((0, 0), (0, dpad)))
        y = jnp.pad(y, ((0, 0), (0, dpad)))
    cd = _compute_dtype(precision)
    xp = _pad_rows(x, bn).astype(cd)
    yp = _pad_rows(y, bm).astype(cd)
    wxp = _pad_rows(jnp.asarray(wx, jnp.float32), bn) if wx is not None \
        else jnp.ones((xp.shape[0],), jnp.float32)
    wyp = _pad_rows(jnp.asarray(wy, jnp.float32), bm) if wy is not None \
        else jnp.ones((yp.shape[0],), jnp.float32)
    out = _gram_call(xp, yp, wxp, wyp, sigma=float(sigma), p=int(p),
                     interpret=bool(interpret), bn=bn, bm=bm, bk=bk)
    return out[:n, :m]


def weighted_gram(centers, weights, *, sigma: float, p: int = 2,
                  interpret: bool | None = None, precision: str = "f32",
                  plan: str | None = None) -> Array:
    """Algorithm 1's K-tilde = W K^C W in one fused pass."""
    return gram(centers, centers, sigma=sigma, p=p, wx=weights, wy=weights,
                interpret=interpret, precision=precision, plan=plan)


# --------------------------------------------------------------------------
# gram_matvec (matrix-free fit operator)
# --------------------------------------------------------------------------


#: The materialized-Gram fit path is abandoned once the f32 m x m buffer
#: would exceed this many bytes (override with REPRO_GRAM_BYTES_BUDGET);
#: beyond it the LOBPCG matvec recomputes Gram tiles on-chip instead
#: (DESIGN.md §6).  128 MB puts the crossover at m_pad ~ 5793, so every
#: m <= 4096 fit stays bit-identical to the materialized path.
DEFAULT_GRAM_BYTES_BUDGET = 128 * 1024 * 1024


def gram_bytes_budget() -> int:
    env = os.environ.get("REPRO_GRAM_BYTES_BUDGET")
    return int(env) if env else DEFAULT_GRAM_BYTES_BUDGET


def matfree_fit(m: int) -> bool:
    """Crossover policy for the fit eigensolve: go matrix-free (LOBPCG
    through ``gram_matvec``) once materializing the m x m weighted Gram
    would blow the bytes budget.  ``REPRO_MATFREE_MIN_M`` forces an explicit
    threshold (tests use it to exercise the matfree path at small m)."""
    env = os.environ.get("REPRO_MATFREE_MIN_M")
    if env:
        return m >= int(env)
    return 4 * m * m > gram_bytes_budget()


@functools.partial(jax.jit, static_argnames=("sigma", "p", "interpret",
                                             "bn", "bm", "bk"))
def _gram_matvec_call(xp, yp, wxp, wyp, vp, *, sigma, p, interpret, bn, bm,
                      bk):
    return _gram.gram_matvec_pallas(xp, yp, vp, sigma=sigma, p=p,
                                    wx=wxp.reshape(-1, 1),
                                    wy=wyp.reshape(1, -1), block_n=bn,
                                    block_m=bm,
                                    block_k=bk, interpret=interpret)


def gram_matvec(x, y, v, *, sigma: float, p: int = 2, wx=None, wy=None,
                interpret: bool | None = None, precision: str = "f32",
                plan: str | None = None, allow_dense: bool = True) -> Array:
    """Matrix-free (weighted) Gram matvec: K_w @ v with K_w never leaving
    VMEM — peak memory O(n*r + m*r + tiles) instead of O(n*m).

    This is the fit-side operator of the matrix-free eigensolve (DESIGN.md
    §6): LOBPCG calls it once per sweep with v = the current (m, r) search
    block.  ``plan=None`` consults the autotuner (tuned Pallas tiles, fatter
    interpret-mode tiles, or — below the crossover — a dense fallback that
    materializes the small Gram); ``precision="bf16"`` feeds bf16 operands
    to BOTH fused matmuls (distance cross term and the tile-V contraction)
    with f32 accumulation.  ``allow_dense=False`` (the matrix-free fit)
    bars the materializing fallback no matter what the autotuner measures —
    the O(n*m)-free memory guarantee is part of the contract there.
    """
    if interpret is None:
        interpret = not _on_tpu()
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    v = jnp.asarray(v, jnp.float32)
    n, m, r = x.shape[0], y.shape[0], v.shape[1]
    assert v.shape[0] == m, (v.shape, y.shape)
    blocks = None
    if plan is None:
        plan, blocks = _matvec_plan(n, m, x.shape[1], r, precision,
                                    interpret, allow_dense=allow_dense)
    assert allow_dense or plan != "dense", \
        "dense plan forced where the matrix-free contract forbids it"
    ones_n = jnp.ones((n,), jnp.float32)
    ones_m = jnp.ones((m,), jnp.float32)
    weighted = wx is not None or wy is not None
    wxj = jnp.asarray(wx, jnp.float32) if wx is not None else ones_n
    wyj = jnp.asarray(wy, jnp.float32) if wy is not None else ones_m
    if plan == "dense":
        return _gram_matvec_dense(x, y, wxj, wyj, v, sigma=float(sigma),
                                  p=int(p), weighted=weighted,
                                  precision=precision)
    if blocks is None:
        blocks = _fat_gram_blocks(x.shape[1]) if plan == "pallas_fat" \
            else pick_gram_blocks(x.shape[1])
    bn, bm, bk = blocks
    bn = min(bn, _round_up(n, 128))
    bm = min(bm, _round_up(m, 128))
    bk = min(bk, _round_up(x.shape[1], 128))
    dpad = _round_up(x.shape[1], bk) - x.shape[1]
    if dpad:
        x = jnp.pad(x, ((0, 0), (0, dpad)))
        y = jnp.pad(y, ((0, 0), (0, dpad)))
    cd = _compute_dtype(precision)
    xp = _pad_rows(x, bn).astype(cd)
    yp = _pad_rows(y, bm).astype(cd)
    # weights pad with ZEROS (sqrt(0) kills padded columns on the weighted
    # path); v pads with zero rows so padded columns of the UNWEIGHTED
    # kernel — k(x, 0-pad) != 0 — contribute exactly nothing either way
    wxp = _pad_rows(wxj, bn) if weighted else jnp.ones((xp.shape[0],),
                                                       jnp.float32)
    wyp = _pad_rows(wyj, bm) if weighted else jnp.ones((yp.shape[0],),
                                                       jnp.float32)
    rp = _round_up(r, 128)
    vp = _pad_rows(v, bm).astype(cd)
    vp = jnp.pad(vp, ((0, 0), (0, rp - r)))
    out = _gram_matvec_call(xp, yp, wxp, wyp, vp, sigma=float(sigma),
                            p=int(p), interpret=bool(interpret), bn=bn,
                            bm=bm, bk=bk)
    return out[:n, :r]


def weighted_gram_matvec(centers, weights, v, *, sigma: float, p: int = 2,
                         interpret: bool | None = None,
                         precision: str = "f32",
                         plan: str | None = None,
                         allow_dense: bool = True) -> Array:
    """Algorithm 1's K-tilde @ v without ever materializing K-tilde."""
    return gram_matvec(centers, centers, v, sigma=sigma, p=p, wx=weights,
                       wy=weights, interpret=interpret, precision=precision,
                       plan=plan, allow_dense=allow_dense)


# --------------------------------------------------------------------------
# gram_row (streaming rank-one update)
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("sigma", "p", "weighted"))
@_named("gram_row")
def _gram_row_dense(x, c, w, *, sigma, p, weighted):
    d2 = _dense_sq_dists(x[None, :], c, "f32")[0]
    g = jnp.exp(-_dist_pow(d2, p) / sigma**p)
    if weighted:
        g = g * jnp.sqrt(w)
    return g, d2


@functools.partial(jax.jit, static_argnames=("sigma", "p", "interpret", "bm",
                                             "bk", "weighted"))
def _gram_row_call(xp, cp, wp, *, sigma, p, interpret, bm, bk, weighted):
    krow, d2 = _gram.gram_row_pallas(
        xp, cp, sigma=sigma, p=p, w=wp.reshape(1, -1) if weighted else None,
        block_m=bm, block_k=bk, interpret=interpret)
    return krow[0], d2[0]


def _gram_row_plan(m: int, d: int, interpret: bool) -> str:
    mb = autotune.bucket(m)
    db = autotune.bucket(d, lo=8, hi=8192)
    if not autotune.measurement_enabled():
        # a single row is always a tiny problem off-TPU; on TPU the fused
        # kernel avoids materializing intermediates
        return "dense" if interpret else "pallas"
    mode = "interp" if interpret else "tpu"
    key = f"gramrow|m{mb}|d{db}|{mode}"
    operands = functools.cache(
        lambda: (_bench_rows(8, db)[0], _bench_rows(mb, db)))

    def run(plan):
        return lambda: jax.block_until_ready(gram_row(
            *operands(), sigma=1.0, p=2, interpret=interpret, plan=plan)[1])

    return autotune.best(key, {"pallas": run("pallas"), "dense": run("dense")},
                         default="pallas")


def gram_row(x, centers, w=None, *, sigma: float, p: int = 2,
             interpret: bool | None = None, plan: str | None = None):
    """Rank-one Gram-row update: one fused pass computing the new row/column
    of the (optionally weighted) Gram against ALL centers, plus the raw
    squared distances the online absorption rule needs.

    Returns ``(k_row, d2_row)``, both (m,) f32: k_row[j] = k(x, c_j)
    (times sqrt(w_j) when ``w`` is given — Algorithm 1's W K W column
    factor); d2_row[j] = ||x - c_j||^2.  This is the streaming subsystem's
    per-update hot path (repro/streaming/updates.py): the full m x m Gram is
    never rebuilt — only this row is.
    """
    if interpret is None:
        interpret = not _on_tpu()
    x = jnp.asarray(x, jnp.float32).reshape(-1)
    centers = jnp.asarray(centers, jnp.float32)
    m, d = centers.shape
    assert x.shape == (d,), (x.shape, centers.shape)
    weighted = w is not None
    wj = jnp.asarray(w, jnp.float32) if weighted \
        else jnp.ones((m,), jnp.float32)
    if plan is None:
        plan = _gram_row_plan(m, d, interpret)
    if plan == "dense":
        return _gram_row_dense(x, centers, wj, sigma=float(sigma), p=int(p),
                               weighted=weighted)
    bm = min(512, _round_up(m, 128))
    bk = min(512, _round_up(d, 128))
    dpad = _round_up(d, bk) - d
    cp = centers if dpad == 0 else jnp.pad(centers, ((0, 0), (0, dpad)))
    xp = jnp.zeros((8, cp.shape[1]), jnp.float32).at[0, :d].set(x)
    cp = _pad_rows(cp, bm)
    wp = _pad_rows(wj, bm)
    krow, d2 = _gram_row_call(xp, cp, wp, sigma=float(sigma), p=int(p),
                              interpret=bool(interpret), bm=bm, bk=bk,
                              weighted=weighted)
    return krow[:m], d2[:m]


# --------------------------------------------------------------------------
# shadow_assign
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("bn", "bm", "interpret"))
def _assign_call(xp, cp, vp, *, bn, bm, interpret):
    idx, d2 = _assign.shadow_assign_pallas(xp, cp, vp.reshape(1, -1),
                                           block_n=bn, block_m=bm,
                                           interpret=interpret)
    return idx[0], d2[0]


def shadow_assign(x, centers, m_valid: int | None = None, *, valid=None,
                  interpret: bool | None = None, plan: str | None = None,
                  tag: str = ""):
    """Nearest-center (idx, d2min) via the Pallas assignment kernel.

    Validity can be given as a static prefix length ``m_valid`` or as a
    dynamic per-center ``valid`` mask (used by blocked shadow selection: the
    round loop reuses one compiled kernel with a fresh mask each round).
    Assignment always resolves distances in f32 — a bf16 argmin could flip
    nearest centers, so ``precision`` deliberately does not thread here.
    ``tag`` gives a caller its own autotune-key namespace (the chunked
    ingest path passes ``tag="ingest"`` — see ``_assign_plan``).
    """
    if interpret is None:
        interpret = not _on_tpu()
    x = jnp.asarray(x, jnp.float32)
    centers = jnp.asarray(centers, jnp.float32)
    n, m = x.shape[0], centers.shape[0]
    if plan is None:
        plan = _assign_plan(n, m, x.shape[1], interpret, tag=tag)
    if valid is None:
        m_valid = m if m_valid is None else int(m_valid)
        valid = (jnp.arange(m) < m_valid).astype(jnp.float32)
    else:
        valid = jnp.asarray(valid, jnp.float32)
    if plan == "dense":
        return _assign_dense(x, centers, valid)
    # off-TPU the grid loop itself is the overhead (no VMEM limit to respect),
    # so take far fewer, fatter row tiles: 8192 rows ~2.3x faster than 512 at
    # n=32k in interpret mode
    block_n = 8192 if interpret else 512
    block_m = center_tile(m)
    # split the 128-padded row count into equal fat tiles rather than padding
    # up to a block_n multiple (that would waste up to block_n-1 rows of
    # distance work per call, ~2x for n just above a multiple)
    npad = _round_up(n, 128)
    tiles = -(-npad // block_n)
    bn = min(block_n, _round_up(-(-npad // tiles), 128))
    xp = _pad_rows(x, bn)
    cp = _pad_rows(centers, block_m)
    vp = _pad_rows(valid, block_m)  # zero: padded slots are invalid
    idx, d2 = _assign_call(xp, cp, vp, bn=bn, bm=block_m,
                           interpret=bool(interpret))
    return idx[:n], d2[:n]


# --------------------------------------------------------------------------
# kpca_project
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("sigma", "p", "bn", "interpret"),
                   donate_argnums=(0,))
def _project_call(xp, cp, ap, *, sigma, p, bn, interpret):
    # xp (the padded query chunk) is donated: it is a serving-loop temporary
    # (kpca_project guarantees ownership before calling), so XLA reuses its
    # storage instead of holding chunk x d alive across the kernel
    return _project.kpca_project_pallas(xp, cp, ap, sigma=sigma, p=p,
                                        block_n=bn,
                                        block_m=center_tile(cp.shape[0]),
                                        interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("sigma", "p", "bn", "qmode", "interpret"),
                   donate_argnums=(0,))
def _project_call_quant(xp, cp, qp, sp, *, sigma, p, bn, qmode, interpret):
    # same donation contract as _project_call: xp is an owned padded chunk
    return _project.kpca_project_quant_pallas(
        xp, cp, qp, sp, sigma=sigma, p=p, qmode=qmode, block_n=bn,
        block_m=center_tile(cp.shape[0]), interpret=interpret)


def projection_compile_count() -> int:
    """Total jit traces of the projection entry points (test hook for the
    recompile-free serving contract) — the quantized tier included."""
    return int(_project_call._cache_size() + _project_dense._cache_size()
               + _project_call_quant._cache_size()
               + _project_dense_quant._cache_size())


def kpca_project(x, centers, projector, *, sigma: float, p: int = 2,
                 chunk: int | None = None,
                 interpret: bool | None = None, precision: str = "f32",
                 plan: str | None = None, projector_q=None) -> Array:
    """Fused z = k(x, C) @ A.  Pads m with zero projector rows (harmless:
    padded centers contribute k(x, 0-pad)*0).

    ``chunk`` streams query rows through the kernel in fixed-size slices, so
    arbitrarily large query sets never materialize more than a
    (chunk, m_pad) working set on device (the fused kernel never writes the
    q x m Gram to HBM either way — this bounds the padded INPUT residency).
    The tail slice is padded UP to the same fixed chunk and stripped after,
    so a ragged query stream compiles exactly once — the recompile-free
    serving contract (asserted in tests/test_kernels.py).

    ``precision`` "int8"/"fp8" runs the quantized projector contraction
    (kernels/quantize.py) — distances and the exp nonlinearity stay f32.
    ``projector_q`` optionally supplies the pre-quantized ``(Aq, s)`` pair
    (snapshot-publish caching, streaming/swap.py); when omitted the
    projector is quantized here per call.

    ``plan`` forces a compute plan: "dense", "pallas" (default row tile) or
    "pallas:<row-tile>"; ``None`` asks the roofline autotuner.
    """
    # spans (DESIGN.md §16): ``project.prep`` is everything before the
    # kernel call (operand conversion, the plan, a Pallas plan's padding),
    # ``project.launch`` the call(s) of the jitted projection
    with _profiled_span("project.prep"):
        if interpret is None:
            interpret = not _on_tpu()
        x = jnp.asarray(x, jnp.float32)
        centers = jnp.asarray(centers, jnp.float32)
        projector = jnp.asarray(projector, jnp.float32)
        n, r = x.shape[0], projector.shape[1]
        m, d = centers.shape
        quant = precision in _quantize.QUANT_PRECISIONS
        if projector_q is not None and not quant:
            raise ValueError(
                f"projector_q only applies to {_quantize.QUANT_PRECISIONS}, "
                f"got precision={precision!r}")
        if precision == "fp8" and not interpret and not fp8_mxu():
            raise ValueError(
                f"precision='fp8' needs an fp8 MXU; "
                f"{jax.devices()[0].device_kind!r} has none (serve int8 or "
                "bf16 on this chip)")
        if plan is None:
            plan = _project_plan(min(n, chunk or n), m, d, r, precision,
                                 interpret)
        if quant:
            if projector_q is None:
                projector_q = _quantize.quantize_projector(projector,
                                                           precision)
            qv, qs = projector_q
        if plan != "dense":
            # the kernel's padded operands; the dense plan reads the
            # caller's arrays as they are, so it builds none of them.
            # The quantized tier keeps distance operands f32 (only the
            # projector contraction drops precision); f32/bf16 tiers cast
            cd = jnp.float32 if quant else _compute_dtype(precision)
            # pad m to the center tile; padded projector rows are zero so
            # padded centers cannot contribute
            cp = _pad_rows(centers, center_tile(m)).astype(cd)
            rp = _round_up(r, 128)
            if quant:
                # padded q rows/cols are zero (can't contribute); padded
                # scale columns are 1 (never divide/NaN) and stripped with
                # the output
                qp = jnp.pad(qv, ((0, cp.shape[0] - m), (0, rp - r)))
                sp = jnp.pad(jnp.asarray(qs, jnp.float32), (0, rp - r),
                             constant_values=1.0).reshape(1, rp)
            else:
                ap = _pad_rows(projector, cp.shape[0])
                ap = jnp.pad(ap, ((0, 0), (0, rp - r)))
            tile = int(plan.split(":", 1)[1]) if plan.startswith("pallas:") \
                else 512

    def run(xs, owned):
        if plan == "dense":
            if quant:
                return _project_dense_quant(xs, centers, qv, qs,
                                            sigma=float(sigma), p=int(p),
                                            qmode=precision)
            return _project_dense(xs, centers, projector,
                                  sigma=float(sigma), p=int(p),
                                  precision=precision)
        bn = min(tile, _round_up(xs.shape[0], 128))
        xsp = _pad_rows(xs, bn).astype(cd)
        if xsp is xs and not owned:
            # nothing was padded or cast, so xsp still IS the caller's
            # buffer; _project_call donates its first argument, and donating
            # memory we do not own would consume it out from under the
            # caller — copy first (the owned chunked slices skip this)
            xsp = jnp.array(xsp, copy=True)
        if quant:
            out = _project_call_quant(xsp, cp, qp, sp, sigma=float(sigma),
                                      p=int(p), bn=bn, qmode=precision,
                                      interpret=bool(interpret))
        else:
            out = _project_call(xsp, cp, ap, sigma=float(sigma), p=int(p),
                                bn=bn, interpret=bool(interpret))
        return out[: xs.shape[0], :r]

    if chunk is None or n <= chunk:
        with _profiled_span("project.launch", chunks=1):
            return run(x, owned=False)
    chunk = _round_up(chunk, 128)
    with _profiled_span("project.launch", chunks=-(-n // chunk)):
        # fixed-shape streaming: pad the row count to a chunk multiple so
        # EVERY slice (the ragged tail included) traces with one shape; each
        # slice is a fresh buffer this function owns, so donation needs no
        # copy
        xpad = _pad_rows(x, chunk)
        pieces = [run(xpad[s : s + chunk], owned=True)  # fresh buffers
                  for s in range(0, xpad.shape[0], chunk)]
        return jnp.concatenate(pieces, axis=0)[:n]


# --------------------------------------------------------------------------
# rff_project (random-Fourier-feature transform; kernels/rff.py)
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("scale", "precision"))
def rff_features(x, omega, phase, *, scale, precision="f32"):
    """Dense feature map phi_D(x) = scale * cos(x Omega^T + b), f32 out.

    The RFF fit accumulates the D x D feature covariance phi^T phi
    chunk-by-chunk off this (core/random_features.py), so the (n, D) feature
    matrix never materializes beyond one chunk.  bf16 runs the x Omega^T
    matmul on bf16 operands with f32 accumulation; the cosine stays f32.
    """
    cd = _compute_dtype(precision)
    s = _gram.cross(jnp.asarray(x, jnp.float32).astype(cd),
                    jnp.asarray(omega, jnp.float32).astype(cd))
    return jnp.cos(s + jnp.asarray(phase, jnp.float32)[None, :]) * scale


@functools.partial(jax.jit, static_argnames=("scale", "precision"))
@_named("rff")
def _rff_dense(x, omega, phase, u, *, scale, precision):
    z = rff_features(x, omega, phase, scale=scale, precision=precision)
    cd = _compute_dtype(precision)
    return _gram.contract(z.astype(cd), jnp.asarray(u, jnp.float32).astype(cd))


_RFF_TILES_TPU = (256, 512, 1024)
_RFF_TILES_INTERPRET = (512, 1024, 2048)


def _rff_costs(n: int, nfeat: int, d: int, r: int, bn: int,
               dense: bool) -> tuple[float, float]:
    """Analytic (flops, bytes): n rows x (feature matmul 2Dd + cosine ~2D +
    component matmul 2Dr).  The fused kernel re-reads Omega/phase/U per grid
    step; the dense fallback writes AND re-reads the (n, D) feature block."""
    flops = float(n) * (2.0 * nfeat * d + 2.0 * nfeat + 2.0 * nfeat * r)
    if dense:
        byts = 4.0 * (n * d + nfeat * d + n * r + 2.0 * n * nfeat
                      + nfeat * r)
    else:
        tiles = max(1, -(-n // bn))
        byts = 4.0 * (n * d + n * r) \
            + tiles * 4.0 * (nfeat * d + nfeat + nfeat * r)
    return flops, byts


def _rff_plan(n: int, nfeat: int, d: int, r: int, precision: str,
              interpret: bool) -> str:
    """Roofline-tuned plan for rff_project: "dense" or "pallas:<row-tile>"."""
    nb, fb = autotune.bucket(n), autotune.bucket(nfeat)
    db = autotune.bucket(d, lo=8, hi=8192)
    rb = autotune.bucket(r, lo=8, hi=512)
    if not autotune.measurement_enabled():
        return autotune.heuristic_plan(n, nfeat, interpret)
    mode = "interp" if interpret else "tpu"
    key = f"rffproj|n{nb}|D{fb}|d{db}|r{rb}|{precision}|{mode}"
    neff, feff = min(nb, _MEASURE_MAX_ROWS), min(fb, _MEASURE_MAX_ROWS)
    scale = (2.0 / feff) ** 0.5

    @functools.cache
    def operands():
        w = _bench_rows(fb, db)
        return _bench_rows(nb, db), w, w[:, 0], _bench_rows(fb, rb)

    def run(plan):
        return lambda: jax.block_until_ready(rff_project(
            *operands(), scale=scale, interpret=interpret,
            precision=precision, plan=plan))

    tiles = _RFF_TILES_INTERPRET if interpret else _RFF_TILES_TPU
    cands, costs = {}, {}
    for t in tiles:
        name = f"pallas:{t}"
        bn_eff = min(t, _round_up(neff, 128))
        cands[name] = run(name)
        costs[name] = _rff_costs(neff, feff, db, rb, bn_eff, dense=False)
    if nb * fb <= autotune.DENSE_MAX_CELLS:
        cands["dense"] = run("dense")
        costs["dense"] = _rff_costs(neff, feff, db, rb, 0, dense=True)
    return autotune.best_roofline(key, cands, costs,
                                  default=f"pallas:{tiles[0]}")


@functools.partial(jax.jit, static_argnames=("scale", "bn", "interpret"),
                   donate_argnums=(0,))
def _rff_call(xp, wp, bp, up, *, scale, bn, interpret):
    # xp (the padded query chunk) is donated under the same ownership
    # contract as _project_call
    return _rff.rff_project_pallas(xp, wp, bp, up, scale=scale, block_n=bn,
                                   block_f=center_tile(wp.shape[0]),
                                   interpret=interpret)


def rff_project(x, omega, phase, u, *, scale: float | None = None,
                chunk: int | None = None, interpret: bool | None = None,
                precision: str = "f32", plan: str | None = None) -> Array:
    """Fused z = sqrt(2/D) cos(x Omega^T + b) @ U — the RFF-KPCA transform.

    Pads the feature count D to a lane multiple with zero Omega/phase/U rows
    (cos(0+0)=1 times a zero U row contributes nothing); ``chunk`` streams
    query rows in fixed-size slices exactly like kpca_project, so a ragged
    query stream compiles once.  ``scale`` defaults to sqrt(2/D) with the
    true (unpadded) D.
    """
    if interpret is None:
        interpret = not _on_tpu()
    x = jnp.asarray(x, jnp.float32)
    omega = jnp.asarray(omega, jnp.float32)
    phase_j = jnp.asarray(phase, jnp.float32)
    u = jnp.asarray(u, jnp.float32)
    n, r = x.shape[0], u.shape[1]
    nfeat, d = omega.shape
    assert u.shape[0] == nfeat and phase_j.shape == (nfeat,), \
        (omega.shape, phase_j.shape, u.shape)
    if scale is None:
        scale = (2.0 / nfeat) ** 0.5
    if plan is None:
        plan = _rff_plan(min(n, chunk or n), nfeat, d, r, precision,
                         interpret)
    cd = _compute_dtype(precision)
    ft = center_tile(nfeat)
    fpad = _round_up(nfeat, ft) - nfeat
    wp = _pad_rows(omega, ft).astype(cd)
    bp = jnp.pad(phase_j, (0, fpad)).reshape(1, -1)
    rp = _round_up(r, 128)
    up = _pad_rows(u, ft)
    up = jnp.pad(up, ((0, 0), (0, rp - r)))
    tile = int(plan.split(":", 1)[1]) if plan.startswith("pallas:") else 512

    def run(xs, owned):
        if plan == "dense":
            return _rff_dense(xs, omega, phase_j, u, scale=float(scale),
                              precision=precision)
        bn = min(tile, _round_up(xs.shape[0], 128))
        xsp = _pad_rows(xs, bn).astype(cd)
        if xsp is xs and not owned:
            # same ownership guard as kpca_project: _rff_call donates its
            # first argument, never donate a buffer the caller still owns
            xsp = jnp.array(xsp, copy=True)
        out = _rff_call(xsp, wp, bp, up, scale=float(scale), bn=bn,
                        interpret=bool(interpret))
        return out[: xs.shape[0], :r]

    if chunk is None or n <= chunk:
        return run(x, owned=False)
    chunk = _round_up(chunk, 128)
    xpad = _pad_rows(x, chunk)
    pieces = [run(xpad[s : s + chunk], owned=True)
              for s in range(0, xpad.shape[0], chunk)]
    return jnp.concatenate(pieces, axis=0)[:n]
