"""ISSUE 8: the paper's method comparison (Table 2 / Figs. 4-5) at 100x the
paper's n, every method on the optimized stack.

Three measurement groups, all writing ``mode="methods"`` rows to
BENCH_rskpca.json (the rows ``core.methods.select_method`` reads as the
measured accuracy-vs-time-vs-memory Pareto):

  1. ``bench_gate`` — the CI gate point (n=262144, m=2048, pendigits):
     the NEW ``fit_nystrom`` (jax.random landmarks, solver-ladder eigensolve,
     streamed ``gram_matvec`` extension) against the PRE-PR dense
     implementation replicated verbatim, interleaved min-of-reps; gates
     ``fit_speedup >= 5`` and knn accuracy within 1pt of the dense oracle.
     Also rows for wnystrom / rff at the same n for the Pareto.
  2. ``bench_structural`` — no-dense-Gram certificates: the matrix-free
     landmark eigensolve lowers with NO m x m buffer at m=8192 (XLA
     memory-analysis, PR-5 style), and the gate-point nystrom fit's peak
     live-buffer bytes stay far below one n x m Gram.
  3. ``bench_scale`` — out-of-core certificates at n=1M: each method fits
     from a ChunkedDataset in a subprocess with peak live-buffer bytes
     < 25% of the materialized dataset (ChunkedDataset has no labels, so
     1M rows record perf + residency; accuracy parity lives at the gate
     point where labels exist).

Method knobs at the gate point: nystrom/wnystrom share m=2048; rff gets
D=512 (n x D^2 covariance flops dominate its fit — D=512 holds the smoke
budget while landing knn accuracy in the same band).  At n=1M the children
use m=1024 / D=256 for the same reason.
"""
from __future__ import annotations

import os
import subprocess
import sys
import time

import numpy as np

from benchmarks.common import RssSampler, emit, pin_autotune_cache
from benchmarks.rskpca_scale import (BENCH_JSON, _merge_into_bench,
                                     _timed_interleaved)

GATE_N = 262144
GATE_M = 2048
GATE_D = 512
RANK = 8
KNN_SUB = 4096  # train and test subset size for the accuracy columns


def _dense_nystrom_fit(x, ker, rank: int, m: int, seed: int = 0):
    """The PRE-PR ``fit_nystrom`` replicated verbatim as the perf/accuracy
    baseline: host np.random landmarks, fully materialized n x m and m x m
    dense Grams, unfused extension arithmetic."""
    import jax
    import jax.numpy as jnp
    from repro.core.kernels_math import gram_matrix
    from repro.core.rskpca import _top_eigh

    xj = jnp.asarray(x, jnp.float32)
    n = xj.shape[0]
    rng = np.random.default_rng(seed)
    idx = jnp.asarray(rng.choice(n, size=m, replace=False))
    landmarks = xj[idx]
    dker = ker.with_backend("dense")
    k_nm = gram_matrix(dker, xj, landmarks)           # (n, m) materialized
    k_mm = gram_matrix(dker, landmarks, landmarks)    # (m, m) materialized
    lam_m, u_m, _ = _top_eigh(k_mm / m, rank)
    lam_m = jnp.maximum(lam_m, 1e-12)
    v = jnp.sqrt(m / n) * (k_nm / m) @ (u_m / lam_m[None, :])
    proj = v / jnp.sqrt(lam_m)[None, :] / np.sqrt(n)
    jax.block_until_ready(proj)
    return np.asarray(proj), np.asarray(lam_m)


def _model_bytes(model) -> int:
    """f32 bytes the fitted model retains (paper Table 2 storage row)."""
    extra = model.phase.size if getattr(model, "phase", None) is not None \
        else 0
    return 4 * (model.centers.size + model.projector.size + extra)


def _knn_accs(models: dict, x, y, k: int) -> dict:
    """knn accuracy per model on a fixed train/test subsample (one draw for
    every model, so the accuracy columns differ only through the fits)."""
    from repro.data import knn_classify

    rng = np.random.default_rng(0)
    perm = rng.permutation(len(x))
    tr, te = perm[:KNN_SUB], perm[KNN_SUB : 2 * KNN_SUB]
    accs = {}
    for name, model in models.items():
        tr_emb = model.transform(x[tr])
        te_emb = model.transform(x[te])
        accs[name] = float((knn_classify(tr_emb, y[tr], te_emb, k)
                            == y[te]).mean())
    return accs


def bench_gate(fast: bool = True) -> list:
    """The n=262144 comparison rows + the nystrom speedup/accuracy gate."""
    from repro.core import (KPCAModel, fit_nystrom, fit_rff, fit_stream,
                            gaussian)
    from repro.data import DATASETS, make_dataset
    from repro.core.ingest_pipeline import pad_block

    x, y, sigma = make_dataset("pendigits", seed=0, n=GATE_N)
    ker = gaussian(sigma)
    k = DATASETS["pendigits"].knn_k

    box = {}

    def dense_fit():
        box["dense"] = _dense_nystrom_fit(x, ker, RANK, GATE_M, seed=0)
        return box["dense"]

    def new_fit():
        box["new"] = fit_nystrom(x, ker, RANK, GATE_M, seed=0)
        return box["new"]

    best, _ = _timed_interleaved(
        {"fit_dense": dense_fit, "fit_new": new_fit}, 1 if fast else 2)

    # peak live-buffer bytes of one fresh new-path fit (warm): the runtime
    # no-n x m certificate — one n x m f32 Gram would be 4*n*m bytes
    samp = RssSampler().start()
    new_fit()
    samp.stop()
    nm_bytes = 4 * GATE_N * GATE_M
    peak_live_frac_nm = samp.peak_live / nm_bytes

    # wnystrom: streaming mini-batch k-means + Algorithm-1 fit (the resident
    # scan-based k-means would materialize an (n, m) one-hot per iteration
    # at this n; the stream path is the optimized-stack route being gated)
    def wn_chunks():
        for s in range(0, GATE_N, 65536):
            xb, ok = pad_block(x[s : s + 65536], 65536)
            yield xb, int(ok.sum())

    fit_stream(wn_chunks(), ker, RANK, method="wnystrom", m=GATE_M)  # warm
    t0 = time.perf_counter()
    wn_model, _ = fit_stream(wn_chunks(), ker, RANK, method="wnystrom",
                             m=GATE_M)
    wn_s = time.perf_counter() - t0

    fit_rff(x, ker, RANK, n_features=GATE_D)  # warm
    t0 = time.perf_counter()
    rff_model = fit_rff(x, ker, RANK, n_features=GATE_D)
    rff_s = time.perf_counter() - t0

    proj_dense, lam_dense = box["dense"]
    oracle = KPCAModel(kernel=ker, centers=np.asarray(x, np.float32),
                       projector=proj_dense, eigvals=lam_dense,
                       method="nystrom-dense")
    ny_model = box["new"]
    accs = _knn_accs({"dense": oracle, "nystrom": ny_model,
                      "wnystrom": wn_model, "rff": rff_model}, x, y, k)

    speedup = best["fit_dense"] / best["fit_new"]
    rows = [
        dict(mode="methods", n=GATE_N, method="nystrom", m=GATE_M, rank=RANK,
             fit_s=round(best["fit_new"], 4),
             dense_fit_s=round(best["fit_dense"], 4),
             fit_speedup=round(speedup, 2),
             knn_acc=round(accs["nystrom"], 4),
             knn_acc_dense=round(accs["dense"], 4),
             model_bytes=_model_bytes(ny_model),
             peak_live_frac_nm=round(peak_live_frac_nm, 4)),
        dict(mode="methods", n=GATE_N, method="wnystrom", m=GATE_M,
             rank=RANK, fit_s=round(wn_s, 4),
             knn_acc=round(accs["wnystrom"], 4),
             model_bytes=_model_bytes(wn_model)),
        dict(mode="methods", n=GATE_N, method="rff", m=GATE_D, rank=RANK,
             fit_s=round(rff_s, 4), knn_acc=round(accs["rff"], 4),
             model_bytes=_model_bytes(rff_model)),
    ]
    for r in rows:
        emit(f"methods_{r['method']}_n{r['n']}", r["fit_s"] * 1e6,
             **{k_: v for k_, v in r.items()
                if k_ not in ("mode", "n", "fit_s")})
    return rows


def bench_structural(m: int = 8192) -> None:
    """No-dense-Gram certificates (PR-5 memory-analysis idiom): the
    matrix-free landmark eigensolve must lower with no m x m tensor and a
    peak temp far below one materialized Gram."""
    import jax.numpy as jnp
    from repro.core import gaussian
    from repro.core.nystrom import _landmark_eigs_matfree
    from repro.kernels import ops as kernel_ops

    assert kernel_ops.matfree_fit(m), \
        f"m={m} sits below the matrix-free crossover; raise m"
    ker = gaussian(1.0)
    lowered = _landmark_eigs_matfree.lower(
        jnp.zeros((m, 16), jnp.float32), ker, RANK)
    assert f"{m}x{m}" not in lowered.as_text(), \
        "matrix-free landmark eigensolve lowered an m x m tensor"
    temp = lowered.compile().memory_analysis().temp_size_in_bytes
    assert temp < 4 * m * m, \
        f"matfree landmark solve peak temp {temp} ~ a dense m x m Gram"
    emit(f"methods_structural_m{m}", 0.0, temp_bytes=int(temp),
         gram_bytes=4 * m * m, ok=True)


_SCALE_CHILD = """
import os, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from benchmarks.common import RssSampler, pin_autotune_cache
pin_autotune_cache()
from benchmarks.methods_bench import _model_bytes
from repro.core import fit_stream, gaussian
from repro.data import ChunkedDataset

method, n, mknob = {method!r}, {n}, {mknob}
sigma = ChunkedDataset("pendigits", n=4096, chunk=4096, seed=0).bandwidth()
ker = gaussian(sigma)
# compile warmup at the production chunk shape so the timed 1M pass
# measures the pipeline, not tracing
warm = ChunkedDataset("pendigits", n=131072, chunk=65536, seed=0)
fit_stream(warm, ker, {rank}, method=method, m=mknob)
ds = ChunkedDataset("pendigits", n=n, chunk=65536, seed=0)
samp = RssSampler().start()
t0 = time.perf_counter()
model, stats = fit_stream(ds, ker, {rank}, method=method, m=mknob)
wall = time.perf_counter() - t0
samp.stop()
frac = samp.peak_live / ds.nbytes_f32
print(f"SCALE method={{method}} n={{n}} m={{stats.m}} wall_s={{wall:.3f}} "
      f"rows_per_s={{stats.rows / wall:.0f}} "
      f"peak_live={{samp.peak_live}} peak_live_frac={{frac:.4f}} "
      f"model_bytes={{_model_bytes(model)}}")
"""


def bench_scale(n: int = 1_048_576, methods=("nystrom", "wnystrom", "rff")
                ) -> list:
    """Out-of-core fits at n=1M, one subprocess per method (fresh process =
    honest peak-residency accounting).  ``peak_live_frac`` is the out-of-core
    certificate run.py gates at < 0.25: device-resident bytes never approach
    the materialized dataset.  (nystrom's O(nd) retained model is a HOST
    numpy buffer — the method's honest Table-2 storage — and deliberately
    not counted as device residency.)"""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(repo, "src") + os.pathsep + repo
    # a CPU rehearsal on forced host devices: it must never reach for a
    # chip that this process may hold
    env["JAX_PLATFORMS"] = "cpu"
    rows = []
    for method in methods:
        mknob = 256 if method == "rff" else 1024
        child = _SCALE_CHILD.format(method=method, n=n, mknob=mknob,
                                    rank=RANK)
        r = subprocess.run([sys.executable, "-c", child], env=env,
                           capture_output=True, text=True, timeout=1800)
        if r.returncode != 0:
            print(r.stderr[-3000:])
            raise SystemExit(f"bench_scale child failed for {method}")
        for line in r.stdout.splitlines():
            if not line.startswith("SCALE"):
                continue
            kv = dict(p.split("=") for p in line.split()[1:])
            row = dict(
                mode="methods", n=int(kv["n"]), method=kv["method"],
                m=int(kv["m"]), rank=RANK,
                fit_s=round(float(kv["wall_s"]), 3),
                rows_per_s=int(float(kv["rows_per_s"])),
                peak_live_bytes=int(kv["peak_live"]),
                peak_live_frac=round(float(kv["peak_live_frac"]), 4),
                model_bytes=int(kv["model_bytes"]),
                out_of_core=True,
            )
            rows.append(row)
            emit(f"methods_{method}_n{row['n']}", row["fit_s"] * 1e6,
                 **{k: v for k, v in row.items()
                    if k not in ("mode", "n", "fit_s")})
    return rows


def main(fast: bool = True):
    pin_autotune_cache()
    bench_structural()
    rows = bench_gate(fast=fast)
    rows += bench_scale()
    _merge_into_bench(rows)
    print(f"# appended methods rows to {BENCH_JSON}", flush=True)
    return rows


if __name__ == "__main__":
    main()
