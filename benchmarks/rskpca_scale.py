"""Beyond-paper §Perf: scaling the paper's own pipeline (ShDE + RSKPCA).

The headline benchmark (``bench_fit``, also the ``--smoke`` target) compares
the SEED fit/transform path — sequential Algorithm 2, dense Gram, full eigh —
against the current default pipeline — blocked selection, fused Pallas
kernels, top-r LOBPCG — at n in {2k, 8k, 32k}, and writes the results to
``BENCH_rskpca.json`` so successive PRs accumulate a perf trajectory.

Two further measurable-on-CPU optimizations of the paper's technique:

  P1. two-level (distributed) shadow selection vs the paper's sequential
      Algorithm 2 — wall-clock speedup at growing n (8 host devices stand in
      for 8 data-parallel workers) and the MMD cost of the 2-eps cover.
  P2. Pallas gram-kernel arithmetic-intensity table: the VMEM block-size
      rule (kernels/ops.pick_gram_blocks) keeps the MXU fed; we report
      AI(block) = flops/bytes per tile vs the v5e ridge point
      (197e12 / 819e9 ~= 240 flops/byte).

Run inside an 8-device subprocess (the harness keeps the main process at 1
device per the brief).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

# merge_rows/_row_key live in common.py now (they stamp fresh rows with
# run provenance — git SHA + timestamp — installed by run.py); re-exported
# here because every bench writer historically imported them from this
# module.
from benchmarks.common import _row_key, emit, merge_rows  # noqa: F401

BENCH_JSON = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_rskpca.json")


def _merge_into_bench(fresh_rows: list) -> None:
    """Shared read -> merge -> write for the mode= bench writers
    (bench_sharded / bench_stream / bench_matfree).

    Surviving old rows of the SAME mode as this run's fresh rows were NOT
    re-measured (e.g. a stream row at an m outside the current sweep), so
    they are stale-marked — the perf gates must never read a number this
    run did not take.  bench_fit applies the same rule to every mode= row
    when it rewrites the whole file.
    """
    try:
        with open(BENCH_JSON) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        doc = {"bench": "rskpca_fit_transform", "rows": []}
    modes = {r.get("mode") for r in fresh_rows}
    old = [dict(r, stale=True) if r.get("mode") in modes else r
           for r in doc.get("rows", [])]
    doc["rows"] = merge_rows(old, fresh_rows)
    with open(BENCH_JSON, "w") as f:
        json.dump(doc, f, indent=2)


def _seed_fit(x, ker, rank, ell):
    """The seed PR's fit path, replicated verbatim for the perf baseline:
    sequential selection + dense Gram + full O(m^3) eigh."""
    import numpy as np
    import jax.numpy as jnp
    from repro.core import shadow_select_host
    from repro.core.kernels_math import gram_matrix_dense

    c, w, _, m = shadow_select_host(x, ker.epsilon(ell))
    cj = jnp.asarray(c, jnp.float32)
    sw = jnp.sqrt(jnp.asarray(w, jnp.float32))
    kt = gram_matrix_dense(ker, cj, cj) * sw[:, None] * sw[None, :] / len(x)
    lam, v = jnp.linalg.eigh(kt)
    lam = jnp.maximum(lam[::-1][:rank], 1e-12)
    proj = (sw[:, None] * v[:, ::-1][:, :rank]) / jnp.sqrt(lam)[None, :] \
        / np.sqrt(len(x))
    return np.asarray(c), np.asarray(proj)


def _seed_transform(ker, centers, proj, q):
    """Seed transform: dense q x m Gram materialized, then the matmul."""
    import numpy as np
    import jax.numpy as jnp
    from repro.core.kernels_math import gram_matrix_dense

    k_qc = gram_matrix_dense(ker, jnp.asarray(q, jnp.float32),
                             jnp.asarray(centers))
    return np.asarray(k_qc @ jnp.asarray(proj))


def _timed_interleaved(fns: dict, reps: int):
    """min-of-reps wall clock for several thunks, measured INTERLEAVED.

    The container's CPU is share-throttled, so multi-hundred-ms slowdown
    windows come and go; timing path A fully and then path B would let one
    window hit only one side and invert a speedup ratio.  Interleaving the
    passes (A, B, A, B, ...) makes a window hit adjacent samples of both
    paths, and min-of-reps then keeps each path's cleanest sample.
    """
    outs = {k: fn() for k, fn in fns.items()}          # compile warmup
    best = {k: float("inf") for k in fns}
    for _ in range(reps):
        for k, fn in fns.items():
            t0 = time.perf_counter()
            outs[k] = fn()
            best[k] = min(best[k], time.perf_counter() - t0)
    return best, outs


def bench_fit(fast: bool = True):
    """fit + transform wall-clock, seed path vs current default, ->JSON.

    ``fast`` (the --smoke / default mode) takes the interleaved min of 3
    timed passes for the small points and a single pass at n=32768 to keep
    the smoke fast; --full takes min-of-3 everywhere.
    """
    from repro.core import gaussian, fit
    from repro.data import make_dataset

    rank, ell = 8, 4.0

    rows = []
    for n in (2048, 8192, 32768):
        # small points are noise-dominated: min-of-3 even in fast mode
        reps = 3 if (not fast or n <= 8192) else 1
        x, _, sigma = make_dataset("pendigits", seed=0, n=n)
        ker = gaussian(sigma)

        # transforms need fitted models: the fit thunks stash their outputs
        # in `box`, and _timed_interleaved's warmup pass (insertion order)
        # populates it before the transform thunks first run
        box = {}

        def seed_fit():
            box["seed"] = _seed_fit(x, ker, rank, ell)
            return box["seed"]

        def new_fit():
            box["mdl"] = fit(x, ker, rank, method="shadow", ell=ell)
            return box["mdl"]

        best, outs = _timed_interleaved({
            "fit_seed": seed_fit,
            "fit_new": new_fit,
            "tr_seed": lambda: _seed_transform(ker, *box["seed"], x),
            "tr_new": lambda: box["mdl"].transform(x),
        }, reps)
        mdl = outs["fit_new"]

        row = dict(
            n=n, m=mdl.m,
            fit_seed_s=round(best["fit_seed"], 4),
            fit_s=round(best["fit_new"], 4),
            fit_speedup=round(best["fit_seed"] / best["fit_new"], 2),
            transform_seed_s=round(best["tr_seed"], 4),
            transform_s=round(best["tr_new"], 4),
            transform_speedup=round(best["tr_seed"] / best["tr_new"], 2),
        )
        rows.append(row)
        emit(f"rskpca_fit_n{n}", best["fit_new"] * 1e6, **{
            k: v for k, v in row.items() if k not in ("n",)})
    # preserve any mode= rows a previous bench_sharded/bench_stream/
    # bench_matfree appended — a plain --smoke refresh must not silently
    # delete them — but mark them stale: their numbers were NOT re-measured
    # this run, so the perf gate must not treat them as fresh evidence
    # either way.  merge_rows drops a stale row the moment its (scale, mode)
    # pair is re-measured.
    try:
        with open(BENCH_JSON) as f:
            old = [dict(r, stale=True)
                   for r in json.load(f)["rows"] if "mode" in r]
    except (OSError, ValueError, KeyError):
        old = []
    rows = merge_rows(old, rows)
    with open(BENCH_JSON, "w") as f:
        json.dump({"bench": "rskpca_fit_transform", "rank": rank, "ell": ell,
                   "backend_default": "pallas(interpret on CPU)",
                   "rows": rows}, f, indent=2)
    print(f"# wrote {BENCH_JSON}", flush=True)
    return rows


_SHARD_CHILD = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
from repro.core import gaussian, fit
from repro.data import make_dataset
from repro.launch.mesh import smoke_mesh
from benchmarks.rskpca_scale import (_seed_fit, _seed_transform,
                                     _timed_interleaved)

precision = {precision!r}
for n in (8192, 32768):
    # shard count matched to the problem (~4096 rows/shard floor) so the
    # per-shard work amortizes host shard_map overhead; a pod scales the axis
    ndev = max(2, min(8, n // 4096))
    mesh = smoke_mesh(ndev)
    x, _, sigma = make_dataset("pendigits", seed=0, n=n)
    ker = gaussian(sigma)
    reps = 3 if n <= 8192 else 1
    # the child re-measures the SEED baseline itself, interleaved with the
    # sharded path, so each speedup compares samples taken seconds apart in
    # one process (a baseline recorded minutes earlier in another process
    # is a different machine-state); fit thunks stash outputs for the
    # transform thunks, populated by the warmup pass
    box = {{}}

    def seed_fit():
        box["seed"] = _seed_fit(x, ker, 8, 4.0)
        return box["seed"]

    def new_fit():
        box["mdl"] = fit(x, ker, 8, method="shadow", ell=4.0, mesh=mesh,
                         precision=precision)
        return box["mdl"]

    best, outs = _timed_interleaved({{
        "fit_seed": seed_fit,
        "fit_new": new_fit,
        "tr_seed": lambda: _seed_transform(ker, *box["seed"], x),
        "tr_new": lambda: box["mdl"].transform(x, mesh=mesh),
    }}, reps)
    print(f"SHARD n={{n}} m={{outs['fit_new'].m}} ndev={{ndev}} "
          f"fit_seed_s={{best['fit_seed']:.4f}} fit_s={{best['fit_new']:.4f}} "
          f"tr_seed_s={{best['tr_seed']:.4f}} tr_s={{best['tr_new']:.4f}}")
"""


def bench_sharded(precision: str = "bf16"):
    """Sharded (+mixed-precision) fit/transform rows appended to the JSON.

    Runs ``fit(..., mesh=...)`` / ``transform(..., mesh=...)`` in a
    multi-host-device subprocess; the child re-measures the seed baseline
    in-process (interleaved) so its speedups are same-machine-state ratios.
    """
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(repo, "src") + os.pathsep + repo
    # a CPU rehearsal on forced host devices: it must never reach for a
    # chip that this process may hold
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c", _SHARD_CHILD.format(precision=precision)],
        env=env, capture_output=True, text=True, timeout=1800)
    if r.returncode != 0:
        print(r.stderr[-3000:])
        raise SystemExit("bench_sharded child failed")
    fresh = []
    for line in r.stdout.splitlines():
        if not line.startswith("SHARD"):
            continue
        kv = dict(p.split("=") for p in line.split()[1:])
        n = int(kv["n"])
        seed_fit_s, fit_s = float(kv["fit_seed_s"]), float(kv["fit_s"])
        seed_tr_s, tr_s = float(kv["tr_seed_s"]), float(kv["tr_s"])
        row = dict(
            n=n, m=int(kv["m"]), mode=f"sharded+{precision}",
            ndev=int(kv["ndev"]),
            fit_seed_s=round(seed_fit_s, 4), fit_s=round(fit_s, 4),
            fit_speedup=round(seed_fit_s / fit_s, 2),
            transform_seed_s=round(seed_tr_s, 4),
            transform_s=round(tr_s, 4),
            transform_speedup=round(seed_tr_s / tr_s, 2),
        )
        fresh.append(row)
        emit(f"rskpca_shard_{precision}_n{n}", fit_s * 1e6, **{
            k: v for k, v in row.items() if k != "n"})
    _merge_into_bench(fresh)
    print(f"# appended sharded rows to {BENCH_JSON}", flush=True)
    return fresh

def bench_stream(fast: bool = True, ms=(256, 1024, 4096), rank: int = 8):
    """Streaming scenario: per-update cost of the incremental operator
    patch (rank-one Gram row + Rayleigh-Ritz eigen-update, DESIGN.md §7)
    vs a FULL refit on the equivalent center set, at m live centers.

    Appends ``mode="stream"`` rows to BENCH_rskpca.json; run.py --stream
    gates on ``update_speedup >= 1.0`` for every freshly-measured row.
    """
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.core import gaussian, fit_rskpca
    from repro.core.rsde import RSDE
    from repro import streaming
    from repro.streaming import updates as supdates

    rng = np.random.default_rng(0)
    d = 16
    batch = 16
    rows = []
    for m in ms:
        c = (rng.normal(size=(m, d)) * 3.0).astype(np.float32)
        w = rng.integers(1, 8, m).astype(np.float64)
        rsde = RSDE(c, w, n=float(w.sum()), scheme="bench")
        ker = gaussian(1.0)
        # budget=inf measures the steady-state PATCH path (the refit column
        # is exactly what the budget check falls back to)
        st = streaming.from_rsde(rsde, ker, rank, eps=0.5, cap=2 * m,
                                 budget=float("inf"))
        # half of every batch lands inside existing shadows (absorb), half
        # in FRESH far-out territory (insert): both rank-one update flavors
        # in every measured step — each rep gets its own far points, or the
        # warmup's inserts would turn later reps absorb-only
        reps = 2 if fast else 3

        def fresh_batch(k):
            near = c[rng.integers(0, m, batch // 2)] \
                + 0.1 * rng.normal(size=(batch // 2, d))
            far = rng.normal(size=(batch - batch // 2, d)) * 3.0 \
                + 25.0 * (k + 1)
            return jnp.asarray(np.concatenate([near, far]).astype(np.float32))

        st = supdates.ingest_batch(st, fresh_batch(0))  # compile warmup
        jax.block_until_ready(st.eigvals)
        best_up = float("inf")
        for rep in range(reps):
            xb = fresh_batch(rep + 1)
            jax.block_until_ready(xb)
            t0 = time.perf_counter()
            st = supdates.ingest_batch(st, xb)
            jax.block_until_ready(st.eigvals)
            best_up = min(best_up, time.perf_counter() - t0)
        update_s = best_up / batch

        rs = st.as_rsde()
        fit_rskpca(rs, ker, rank)  # compile warmup
        best_refit = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fit_rskpca(rs, ker, rank)
            best_refit = min(best_refit, time.perf_counter() - t0)

        row = dict(
            m=m, mode="stream", cap=st.cap, batch=batch,
            update_s=round(update_s, 6), refit_s=round(best_refit, 4),
            update_speedup=round(best_refit / update_s, 1),
        )
        rows.append(row)
        emit(f"rskpca_stream_m{m}", update_s * 1e6,
             **{k: v for k, v in row.items() if k != "m"})

    _merge_into_bench(rows)
    print(f"# appended stream rows to {BENCH_JSON}", flush=True)
    return rows


def bench_matfree(m: int = 8192, d: int = 16, rank: int = 8):
    """Matrix-free fit at m centers (DESIGN.md §6): wall-clock vs the SEED
    dense fit path (dense Gram + full eigh) on the same synthetic center
    set, plus the structural no-m x m-buffer assertions.

    Appends a ``mode="matfree"`` row to BENCH_rskpca.json; run.py gates on
    ``fit_speedup >= 1.0`` and on the peak-memory ratio.  Centers are
    synthesized directly (as bench_stream does) because growing a REAL
    m=8192 cover through sequential seed selection would take the smoke far
    past its budget — the fit-path comparison is identical either way.
    """
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.core import gaussian
    from repro.core.rskpca import _fit_rskpca_device
    from repro.core.kernels_math import gram_matrix_dense
    from repro.kernels import ops as kernel_ops

    assert kernel_ops.matfree_fit(m), \
        f"m={m} sits below the matrix-free crossover; raise m"
    rng = np.random.default_rng(0)
    c = (rng.normal(size=(m, d)) * 3.0).astype(np.float32)
    w = rng.integers(1, 8, m).astype(np.float32)
    n = float(w.sum())
    ker = gaussian(1.0)

    # --- structural assertion: the compiled matfree fit holds NO (m, m)
    # buffer; the materialized path's peak temp is dominated by exactly one.
    # memory_analysis() needs only compilation, never an execution.
    def lower(matfree):
        return _fit_rskpca_device.lower(
            jnp.asarray(c), jnp.asarray(w), jnp.float32(n), ker, rank,
            matfree=matfree)

    mf_lowered = lower(True)
    assert f"{m}x{m}" not in mf_lowered.as_text(), \
        "matrix-free fit lowered an m x m tensor"
    mf_temp = mf_lowered.compile().memory_analysis().temp_size_in_bytes
    gram_temp = lower(False).compile().memory_analysis().temp_size_in_bytes
    ratio = gram_temp / max(mf_temp, 1)
    assert gram_temp >= 4 * m * m, (gram_temp, m)   # sanity: Gram is there
    assert ratio >= 4.0, \
        f"matfree peak temp only {ratio:.1f}x below the materialized path"

    # --- seed dense path (one timed pass: LAPACK eigh dominates at ~m^3,
    # so compile noise is irrelevant and a warmup pass would double a
    # minutes-long measurement for nothing)
    t0 = time.perf_counter()
    cj = jnp.asarray(c)
    sw = jnp.sqrt(jnp.asarray(w))
    kt = gram_matrix_dense(ker, cj, cj) * sw[:, None] * sw[None, :] \
        / jnp.float32(n)
    lam_s, v_s = jnp.linalg.eigh(kt)
    lam_s = jnp.maximum(lam_s[::-1][:rank], 1e-12)
    proj_s = (sw[:, None] * v_s[:, ::-1][:, :rank]) \
        / jnp.sqrt(lam_s)[None, :] / np.sqrt(n)
    jax.block_until_ready(proj_s)
    seed_s = time.perf_counter() - t0
    lam_s = np.asarray(lam_s)
    del kt, v_s, proj_s

    # --- matrix-free fit: warmup (compile + autotune), then min-of-2
    def run_mf():
        lam, proj, _ = _fit_rskpca_device(jnp.asarray(c), jnp.asarray(w),
                                          jnp.float32(n), ker, rank,
                                          matfree=True)
        jax.block_until_ready(proj)
        return lam, proj

    run_mf()
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        lam_mf, _ = run_mf()
        best = min(best, time.perf_counter() - t0)

    # eigenvalue agreement with the seed solve (the row is meaningless if
    # the fast path computed a different operator)
    rel = float(np.max(np.abs(np.asarray(lam_mf) - lam_s) / lam_s))
    assert rel < 5e-3, f"matfree eigenvalues off by {rel:.2e}"

    row = dict(
        m=m, mode="matfree", d=d, rank=rank,
        fit_seed_s=round(seed_s, 4), fit_s=round(best, 4),
        fit_speedup=round(seed_s / best, 2),
        temp_bytes_matfree=int(mf_temp), temp_bytes_gram=int(gram_temp),
        peak_mem_ratio=round(ratio, 1),
    )
    emit(f"rskpca_matfree_m{m}", best * 1e6,
         **{k: v for k, v in row.items() if k != "m"})
    _merge_into_bench([row])
    print(f"# appended matfree row to {BENCH_JSON}", flush=True)
    return [row]


_CHILD = """
import os, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
from repro.core import gaussian, shadow_rsde
from repro.core import mmd as M
from repro.data import make_dataset

from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))
for n in (4096, 16384):
    x, _, sigma = make_dataset("pendigits", seed=0, n=n)
    ker = gaussian(sigma)
    # warmup both paths (compile)
    shadow_rsde(x[:512], ker, 4.0)
    shadow_rsde(x[:1024], ker, 4.0, mesh=mesh)
    t0 = time.perf_counter(); r1 = shadow_rsde(x, ker, 4.0)
    t1 = time.perf_counter(); r2 = shadow_rsde(x, ker, 4.0, mesh=mesh)
    t2 = time.perf_counter()
    m1 = M.mmd_weighted(ker, x, r1.centers, r1.weights)
    m2 = M.mmd_weighted(ker, x, r2.centers, r2.weights)
    print(f"RESULT n={n} seq_s={t1-t0:.3f} two_s={t2-t1:.3f} "
          f"speedup={(t1-t0)/max(t2-t1,1e-9):.2f} "
          f"m1={r1.m} m2={r2.m} mmd1={m1:.5f} mmd2={m2:.5f} "
          f"bound={ker.mmd_bound(4.0):.5f}")
"""


def main(fast: bool = True):
    bench_fit(fast=fast)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "src")
    # a CPU rehearsal on forced host devices: it must never reach for a
    # chip that this process may hold
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                       capture_output=True, text=True, timeout=1800)
    for line in r.stdout.splitlines():
        if line.startswith("RESULT"):
            kv = dict(p.split("=") for p in line.split()[1:])
            emit(f"rskpca_scale_shadow_n{kv['n']}",
                 float(kv["seq_s"]) * 1e6,
                 two_level_us=round(float(kv["two_s"]) * 1e6, 1),
                 speedup=kv["speedup"], m_seq=kv["m1"], m_two=kv["m2"],
                 mmd_seq=kv["mmd1"], mmd_two=kv["mmd2"], bound=kv["bound"])
    if r.returncode != 0:
        print(r.stderr[-2000:])

    # P2: gram-kernel arithmetic intensity vs block size (structural).
    # K-chunked kernel (current) vs the pre-hillclimb square-block fallback.
    from repro.kernels.ops import pick_gram_blocks
    for d in (64, 256, 1024, 4096):
        bn, bm, bk = pick_gram_blocks(d)
        flops = 2 * bn * bm * d
        bytes_ = 4 * (bn * d + bm * d + bn * bm)   # HBM traffic per tile
        old_b = next(b for b in (512, 256, 128)
                     if (2 * b * d + b * b) * 4 <= 8 * 1024 * 1024)             if (2 * 128 * d + 128 * 128) * 4 <= 8 * 1024 * 1024 else 128
        old_bytes = 4 * (2 * old_b * d + old_b * old_b)
        old_ai = 2 * old_b * old_b * d / old_bytes
        emit(f"rskpca_gram_ai_d{d}", 0.0, block=f"{bn}x{bm}x{bk}",
             arith_intensity=round(flops / bytes_, 1),
             pre_hillclimb_ai=round(old_ai, 1),
             v5e_ridge=240.5,
             bound=("compute" if flops / bytes_ > 240.5 else "memory"))


if __name__ == "__main__":
    main()
