"""Chip smoke: one RSKPCA deployment end to end on a TPU.

    python chip_smoke.py             # one chip: phases A and B
    python chip_smoke.py --chips 4   # the sharded path on four chips, and
                                     # the same work on one device

This is a smoke, not a benchmark: it proves that the main path runs on the
chip, compiled, and gives the reference's answers.  Its times are printed
for orientation and are no measurement of speed.

* Phase A (pendigits-shaped: d=16, 10 classes): 2**20 rows are generated
  chunk by chunk from ``--seed`` and ingested out of core
  (``select_streaming`` + ``fit_centers``, the two stages of
  ``ingest_fit``); the selected reduced set seeds a streaming operator,
  published through ``HotSwapServer`` and served through
  ``BatchingFrontEnd``; then fresh rows stream in, the operator is
  republished, and served again.
* Phase B (usps-shaped: d=256): 262144 rows in memory through shadow
  selection, ``fit_rskpca`` and ``model.transform``.

The shadow radius is sigma/ell with ell=3.0 in phase A and ell=2.75 in
phase B, chosen so m lands in the thousands: at ell=4 these mixtures keep
m=25074 (phase A, measured on the chip) and ~60% of the rows (phase B),
and the dense reference of such an operator does not fit the run.
* ``--chips 4``: phase A's fit and transform through ``mesh=``, and
  ``ingest_fit(mesh=...)``, each against the same call on one device.

Each phase checks its results against a plain reference on the same data:
the dense Gram and ``eigh`` at the highest matmul precision (top-r
eigenvalues, and embeddings up to sign), exact weight mass, the cover
radius on a sample, and each Pallas kernel of the path against its dense
plan at the phase's shapes.  Any failed check raises, and the script exits
non-zero.  The last line of a passing run is one JSON object naming the
device.  Without a TPU the script stops before any work.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import streaming  # noqa: E402
from repro.core import (fit_centers, fit_rskpca, gaussian,  # noqa: E402
                        gram_matrix_dense, ingest_fit, select_streaming,
                        shadow_rsde)
from repro.data import make_dataset  # noqa: E402
from repro.data.kpca_datasets import ChunkedDataset  # noqa: E402
from repro.kernels import autotune, ops  # noqa: E402
from repro.serving import BatchingFrontEnd  # noqa: E402

#: Relative tolerance of the fitted operator's top-r eigenvalues against the
#: dense eigh (LOBPCG stops near 1e-4 relative on these spectra).
EIG_RTOL = 1e-3
#: Embeddings against the reference transform, relative Frobenius error
#: after each column's sign is aligned.
EMB_RTOL = 2e-2
#: A Pallas kernel against its dense plan on the same operands.
KERNEL_RTOL = 1e-4
#: A streaming operator patched by Rayleigh-Ritz steps under its error
#: budget, against the exact eigensystem of its current reduced set.
PATCHED_EIG_RTOL = 1e-2

RANK = 8


class SmokeFailure(AssertionError):
    pass


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(name: str, ok: bool, detail: str) -> None:
    log(f"check {name}: {'pass' if ok else 'FAIL'} ({detail})")
    if not ok:
        raise SmokeFailure(f"{name}: {detail}")


# --------------------------------------------------------------------------
# compile and plan telemetry
# --------------------------------------------------------------------------


class CompileLog:
    """Backend compiles and persistent-cache hits seen by this process."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def line(self) -> str:
        return (f"backend_compiles={self.compiles} "
                f"backend_compile_s={self.compile_s:.3f} "
                f"persistent_cache_hits={self.cache_hits}")


def log_plans(seen: set) -> None:
    """Print every autotune decision for this device made since the last
    call (a plan cache on disk may also hold other devices' plans)."""
    for key, entry in sorted(autotune._MEM.items()):
        if key in seen or not key.endswith(autotune.env_tag()):
            continue
        seen.add(key)
        log(f"plan {key} -> {entry['winner']} "
            f"(us per candidate: {entry.get('us')})")
    log(f"autotune plan_hits={autotune._M_HITS.value} "
        f"plan_misses={autotune._M_MISSES.value}")


# --------------------------------------------------------------------------
# the plain reference
# --------------------------------------------------------------------------


def reference_eig(centers, weights, n, kernel, rank):
    """Top-``rank`` eigenpairs of K-tilde/n from the dense Gram (jnp, f32,
    highest matmul precision) and LAPACK's f32 ``eigh`` on the host, which
    shares no code with the device eigensolvers under test.  Returns
    (eigvals, projector)."""
    from scipy.linalg import eigh

    with jax.default_matmul_precision("highest"):
        c = jnp.asarray(centers, jnp.float32)
        sw = jnp.sqrt(jnp.asarray(weights, jnp.float32))
        kt = np.asarray(sw[:, None] * gram_matrix_dense(kernel, c, c)
                        * sw[None, :] / jnp.float32(n))
    m = kt.shape[0]
    lam, u = eigh(kt, subset_by_index=[m - rank, m - 1])  # ascending
    lam, u = lam[::-1], u[:, ::-1]
    sw = np.sqrt(np.asarray(weights, np.float64))
    proj = sw[:, None] * u / np.sqrt(lam)[None, :] / np.sqrt(float(n))
    return lam, proj


def reference_transform(x, centers, projector, kernel, chunk=8192):
    out = []
    with jax.default_matmul_precision("highest"):
        c = jnp.asarray(centers, jnp.float32)
        a = jnp.asarray(projector, jnp.float32)
        for s in range(0, x.shape[0], chunk):
            xs = jnp.asarray(x[s : s + chunk], jnp.float32)
            out.append(np.asarray(gram_matrix_dense(kernel, xs, c) @ a))
    return np.concatenate(out)


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                   1e-30))


def eig_rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) / np.abs(want)))


def emb_rel_err(z, z_ref) -> float:
    """Relative Frobenius error with each column's sign aligned."""
    z, z_ref = np.asarray(z, np.float64), np.asarray(z_ref, np.float64)
    s = np.sign(np.sum(z * z_ref, axis=0))
    s[s == 0] = 1.0
    return float(np.linalg.norm(z * s - z_ref) / np.linalg.norm(z_ref))


def check_cover(name, sample, centers, radius):
    """Every sampled row lies within ``radius`` of some center (dense
    distances at the highest precision)."""
    worst = 0.0
    with jax.default_matmul_precision("highest"):
        c = jnp.asarray(centers, jnp.float32)
        cc = jnp.sum(c * c, axis=1)
        for s in range(0, sample.shape[0], 1024):
            x = jnp.asarray(sample[s : s + 1024], jnp.float32)
            d2 = jnp.sum(x * x, axis=1)[:, None] + cc[None, :] \
                - 2.0 * x @ c.T
            worst = max(worst, float(jnp.max(jnp.min(d2, axis=1))))
    worst = float(np.sqrt(max(worst, 0.0)))
    check(name, worst <= radius * (1 + 1e-4),
          f"max distance to nearest center {worst:.6g} <= {radius:.6g} "
          f"over {sample.shape[0]} rows")


def check_mass(name, weights, n):
    total = float(np.sum(np.asarray(weights, np.float64)))
    check(name, total == float(n), f"sum of weights {total!r} == n {n}")


def check_kernels(tag, x, centers, weights, projector, sigma):
    """Each Pallas kernel of the path, compiled, against its dense plan on
    the phase's own operands."""
    x = jnp.asarray(x, jnp.float32)
    c = jnp.asarray(centers, jnp.float32)
    w = jnp.asarray(weights, jnp.float32)
    v = jnp.asarray(np.random.default_rng(0).normal(
        size=(c.shape[0], RANK)), jnp.float32)
    pairs = {
        "shadow_assign": lambda plan: ops.shadow_assign(x, c, plan=plan)[1],
        "gram": lambda plan: ops.weighted_gram(c, w, sigma=sigma, plan=plan),
        "gram_matvec": lambda plan: ops.weighted_gram_matvec(
            c, w, v, sigma=sigma, plan=plan),
        "gram_row": lambda plan: ops.gram_row(x[0], c, w, sigma=sigma,
                                              plan=plan)[0],
        "kpca_project": lambda plan: ops.kpca_project(
            x, c, projector, sigma=sigma, plan=plan),
    }
    for name, run in pairs.items():
        got, want = run("pallas"), run("dense")
        err = rel_err(got, want)
        check(f"{tag} kernel {name}", err <= KERNEL_RTOL,
              f"pallas vs dense plan rel err {err:.3g}, x {tuple(x.shape)} "
              f"centers {tuple(c.shape)}")


# --------------------------------------------------------------------------
# phase A: out-of-core ingest, fit, publish, serve, update, serve
# --------------------------------------------------------------------------


def serve_requests(fe, rows, requests, max_rows, rng):
    """Submit ``requests`` requests of 1..max_rows rows from 8 client
    threads; returns (queries, embeddings) in submission order."""
    sizes = rng.integers(1, max_rows + 1, size=requests)
    starts = rng.integers(0, rows.shape[0] - max_rows, size=requests)
    qs = [rows[s : s + k] for s, k in zip(starts, sizes)]
    with ThreadPoolExecutor(8) as pool:
        futs = list(pool.map(fe.submit, qs))
    zs = [f.result(timeout=300) for f in futs]
    for q, z in zip(qs, zs):
        assert z.shape == (q.shape[0], RANK), (q.shape, z.shape)
    return np.concatenate(qs), np.concatenate(zs)


def phase_a(n=1 << 20, chunk=65536, budget=32768, requests=200,
            max_rows=512, updates=1024, ell=3.0, seed=0):
    log(f"phase A: pendigits-shaped d=16, n={n}, chunk={chunk}, "
        f"budget={budget}, ell={ell}, rank={RANK}")
    nq = 4 * max_rows
    # rows depend only on (name, seed, i): rows past n are fresh draws of
    # the same mixture, used as queries and as the streamed update
    ds = ChunkedDataset("pendigits", n, chunk, seed)
    fresh = ChunkedDataset("pendigits", n + nq + updates, chunk, seed)
    queries = fresh.rows(n, n + nq)
    stream = fresh.rows(n + nq, n + nq + updates)
    kernel = gaussian(ds.bandwidth())
    eps = kernel.epsilon(ell)
    out = {"n": n, "d": ds.d}

    t0 = time.perf_counter()
    rsde, stats = select_streaming(ds, eps, budget=budget)
    out["select_s"] = time.perf_counter() - t0
    log(f"phase A ingest: m={rsde.m} chunks={stats.chunks} "
        f"spilled={stats.spilled} select_s={out['select_s']:.3f} "
        f"overlap={stats.overlap_fraction:.3f}")
    t0 = time.perf_counter()
    model = fit_centers(rsde.centers, rsde.weights, rsde.n, kernel, RANK,
                        method="rskpca+shadow-ingest")
    out["fit_s"] = time.perf_counter() - t0
    out["m"] = rsde.m
    log(f"phase A fit: m={rsde.m} fit_s={out['fit_s']:.3f}")

    check_mass("A weight mass", rsde.weights, n)
    # two-level selection covers at 2*eps; a budget spill loosens it by the
    # recorded spill distance
    sample = ds.rows(0, min(n, 8192))
    check_cover("A cover", sample, rsde.centers,
                2 * eps + stats.max_spill_dist)
    lam_ref, proj_ref = reference_eig(rsde.centers, rsde.weights, n,
                                      kernel, RANK)
    err = eig_rel_err(model.eigvals, lam_ref)
    check("A fit eigenvalues", err <= EIG_RTOL,
          f"max rel err {err:.3g} vs dense eigh, top {RANK}: "
          f"{np.array2string(lam_ref, precision=5)}")

    t0 = time.perf_counter()
    state = streaming.from_rsde(rsde, kernel, RANK, ell=ell)
    server = streaming.HotSwapServer(state)
    out["publish_s"] = time.perf_counter() - t0
    err = eig_rel_err(np.asarray(state.eigvals[:RANK]), lam_ref)
    check("A streaming eigenvalues", err <= EIG_RTOL,
          f"max rel err {err:.3g} vs dense eigh (cap {state.cap})")
    check_kernels("A", queries[:512], rsde.centers, rsde.weights,
                  model.projector, kernel.sigma)

    rng = np.random.default_rng(seed)
    with BatchingFrontEnd(server, max_batch=max_rows, slo_ms=200.0) as fe:
        t0 = time.perf_counter()
        for k in range(0, int(np.log2(max_rows)) + 1):  # every pow2 bucket
            fe.submit(queries[: 1 << k]).result(timeout=600)
        out["serve_warmup_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        xq, zq = serve_requests(fe, queries, requests, max_rows, rng)
        out["serve_s"] = time.perf_counter() - t0
        stats_fe = fe.snapshot()
    log(f"phase A serve: {requests} requests, {xq.shape[0]} rows, "
        f"{stats_fe.batches} batches, largest {stats_fe.max_batch_rows} rows, "
        f"warmup_s={out['serve_warmup_s']:.3f} serve_s={out['serve_s']:.3f}")
    err = emb_rel_err(zq, reference_transform(xq, rsde.centers, proj_ref,
                                              kernel))
    check("A served embeddings", err <= EMB_RTOL,
          f"rel err {err:.3g} vs reference transform, up to sign")

    t0 = time.perf_counter()
    state = streaming.ingest(state, stream, batch=256)
    server.publish(state)
    out["update_s"] = time.perf_counter() - t0
    live = state.as_rsde()
    check_mass("A weight mass after update", live.weights, n + updates)
    lam_upd, _ = reference_eig(live.centers, live.weights, live.n, kernel,
                               RANK)
    err = eig_rel_err(np.asarray(state.eigvals[:RANK]), lam_upd)
    check("A updated eigenvalues", err <= PATCHED_EIG_RTOL,
          f"max rel err {err:.3g} vs dense eigh of the updated reduced set "
          f"(m={live.m}, err_est={float(state.err_est):.3g})")
    with BatchingFrontEnd(server, max_batch=max_rows, slo_ms=200.0) as fe:
        xq, zq = serve_requests(fe, queries, max(requests // 4, 1),
                                max_rows, rng)
    z_ref = reference_transform(xq, np.asarray(state.centers),
                                np.asarray(state.projector), kernel)
    err = rel_err(zq, z_ref)
    check("A served after republish", err <= KERNEL_RTOL,
          f"rel err {err:.3g} vs the published operator, densely")
    log(f"phase A update: {updates} rows in, m={live.m}, "
        f"update_s={out['update_s']:.3f}")
    return out


# --------------------------------------------------------------------------
# phase B: wide features in memory
# --------------------------------------------------------------------------


def phase_b(n=262144, ell=2.75, queries=16384, seed=0):
    log(f"phase B: usps-shaped d=256, n={n}, ell={ell}, rank={RANK}")
    x, _, sigma = make_dataset("usps", seed=seed, n=n + queries)
    x, xq = x[:n], x[n:]
    kernel = gaussian(sigma)
    out = {"n": n, "d": x.shape[1]}
    t0 = time.perf_counter()
    rsde = shadow_rsde(x, kernel, ell)
    out["select_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    model = fit_rskpca(rsde, kernel, RANK)
    out["fit_s"] = time.perf_counter() - t0
    out["m"] = rsde.m
    t0 = time.perf_counter()
    z = model.transform(xq)
    out["transform_s"] = time.perf_counter() - t0
    log(f"phase B: m={rsde.m} select_s={out['select_s']:.3f} "
        f"fit_s={out['fit_s']:.3f} transform_s={out['transform_s']:.3f} "
        f"({queries} rows)")

    check_mass("B weight mass", rsde.weights, n)
    check_cover("B cover", x[:8192], rsde.centers, kernel.epsilon(ell))
    lam_ref, proj_ref = reference_eig(rsde.centers, rsde.weights, n,
                                      kernel, RANK)
    err = eig_rel_err(model.eigvals, lam_ref)
    check("B fit eigenvalues", err <= EIG_RTOL,
          f"max rel err {err:.3g} vs dense eigh, top {RANK}: "
          f"{np.array2string(lam_ref, precision=5)}")
    err = emb_rel_err(z, reference_transform(xq, rsde.centers, proj_ref,
                                             kernel))
    check("B transform", err <= EMB_RTOL,
          f"rel err {err:.3g} vs reference transform, up to sign")
    check_kernels("B", xq[:512], rsde.centers, rsde.weights,
                  model.projector, kernel.sigma)
    return out


# --------------------------------------------------------------------------
# four chips: the sharded path against one device
# --------------------------------------------------------------------------


def phase_sharded(mesh, n=1 << 20, chunk=65536, budget=32768, ell=3.0,
                  queries=16384, seed=0):
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import distributed as dist

    ndev = mesh.shape["data"]
    log(f"sharded: pendigits-shaped d=16, n={n}, mesh data={ndev}")
    ds = ChunkedDataset("pendigits", n, chunk, seed)
    kernel = gaussian(ds.bandwidth())
    eps = kernel.epsilon(ell)
    xq = ChunkedDataset("pendigits", n + queries, chunk, seed).rows(
        n, n + queries)
    out = {"n": n, "d": ds.d}

    t0 = time.perf_counter()
    model_1, st_1 = ingest_fit(ds, kernel, RANK, ell=ell, budget=budget)
    out["ingest_1dev_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    model_n, st_n = ingest_fit(ds, kernel, RANK, ell=ell, budget=budget,
                               mesh=mesh)
    out["ingest_mesh_s"] = time.perf_counter() - t0
    out["m"] = model_n.m
    log(f"sharded ingest_fit: m 1dev={model_1.m} mesh={model_n.m} "
        f"1dev_s={out['ingest_1dev_s']:.3f} "
        f"mesh_s={out['ingest_mesh_s']:.3f}")
    stats = [d.memory_stats() for d in mesh.devices.flat]
    if all(stats):  # the TPU runtime reports them; the CPU backend does not
        peaks = [st["peak_bytes_in_use"] for st in stats]
        rest = sorted(peaks[1:])
        log(f"sharded ingest peak bytes per device: {peaks}; device 0 / "
            f"median of the others = "
            f"{peaks[0] / max(rest[len(rest) // 2], 1):.3f}")
        floor = chunk // ndev * ds.d * 4
        check("sharded ingest spans devices", min(peaks) >= floor,
              f"peak bytes per device {peaks} >= one chunk shard {floor}")
    check("sharded ingest rows", st_n.rows == st_1.rows == n,
          f"rows mesh={st_n.rows} 1dev={st_1.rows}")
    # per-device selection merges different candidate sets, so the two
    # operators are two reduced sets of one density, each a 2*eps cover:
    # Theorem 5.2 bounds each spectrum's l2 distance from the full
    # operator's by sqrt(eigenvalue_bound(ell / 2))
    dist_l2 = float(np.linalg.norm(np.asarray(model_n.eigvals, np.float64)
                                   - np.asarray(model_1.eigvals)))
    bound = 2 * np.sqrt(kernel.eigenvalue_bound(ell / 2))
    check("sharded ingest eigenvalues", dist_l2 <= bound,
          f"l2 distance {dist_l2:.3g} <= Theorem 5.2 bound {bound:.3g} "
          f"(max rel err {eig_rel_err(model_n.eigvals, model_1.eigvals):.3g})")

    # fit and transform through mesh= on ONE reduced set: exact parity
    rsde = shadow_rsde(ds.rows(0, n // 16), kernel, ell)
    model_1 = fit_rskpca(rsde, kernel, RANK)
    model_n = fit_rskpca(rsde, kernel, RANK, mesh=mesh)
    err = eig_rel_err(model_n.eigvals, model_1.eigvals)
    check("sharded fit eigenvalues", err <= EIG_RTOL,
          f"max rel err {err:.3g} mesh vs one device (m={rsde.m})")
    z_1 = model_1.transform(xq)
    z_n = model_n.transform(xq, mesh=mesh)
    err = emb_rel_err(z_n, z_1)
    check("sharded transform", err <= EMB_RTOL,
          f"rel err {err:.3g} mesh vs one device, up to sign")
    z_dev = dist.sharded_kpca_project(xq, model_n.centers, model_n.projector,
                                      kernel, mesh)
    spans = len(z_dev.sharding.device_set)
    check("sharded transform spans devices", spans == ndev,
          f"output rows on {spans} devices")
    xs = jax.device_put(xq, NamedSharding(mesh, P("data", None)))
    check("row sharding spans devices", len(xs.sharding.device_set) == ndev,
          f"{len(xs.sharding.device_set)} devices")
    return out


# --------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX default device is "
              f"{dev.platform}); nothing was run", file=sys.stderr)
        return 1
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    cache = autotune.enable_compilation_cache()
    compiles = CompileLog()
    from repro import obs
    obs.enable()  # plan hit/miss counters
    log(f"SMOKE, not a benchmark: device {dev.device_kind} x{len(devices)}, "
        f"jax {jax.__version__}, interpret={not ops._on_tpu()}, "
        f"compile cache {cache}")
    plans: set = set()
    t_start = time.perf_counter()
    if args.chips == 4:
        from repro.launch.mesh import data_mesh
        res = {"sharded": phase_sharded(data_mesh(4), seed=args.seed)}
    else:
        res = {"A": phase_a(seed=args.seed)}
        log_plans(plans)
        res["B"] = phase_b(seed=args.seed)
    log_plans(plans)
    log(f"compile: {compiles.line()}")
    log(f"total_s={time.perf_counter() - t_start:.3f} phases="
        + json.dumps(res, sort_keys=True))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
