"""Out-of-core ingestion bench — ``mode="ingest"`` rows of BENCH_rskpca.json.

Measures the end-to-end select -> fit pipeline of core/ingest_pipeline.py on
the deterministic chunked source (data never materializes): wall time,
ingest throughput (rows/s), the measured copy/compute overlap fraction of
the async double-buffered feed, and peak memory via ``common.RssSampler`` —
both the sampled peak of LIVE buffer bytes (``peak_live_bytes``, what the
pipeline actually holds resident; on the CPU backend device buffers are
host memory) and the raw RSS growth (``rss_delta_bytes``, informational:
on CPU it additionally counts XLA's per-execution interpret-mode scratch
high-water, which lives in device HBM on real hardware and plateaus at a
shape-dependent constant unrelated to n).

Two scales share one child template:

  * smoke (CI, ``run.py --ingest``): n=1M rows, center budget 4096, one
    device — gated on the throughput floor and ``overlap_fraction >= 0.5``;
  * full (``run.py --ingest --full``): n=10M rows, budget 32768, chunk rows
    sharded over an 8-device mesh — additionally gated on
    ``peak_live_bytes`` < 25% of the 640MB the dataset would occupy
    resident (the out-of-core certificate: a materialized dataset would
    appear as a live 640MB array; the pipeline's window is O(chunk)).
    ``mem_gated`` marks which rows the gate reads.

The timed region includes chunk generation (``common.timeit_stream``
semantics: feeding the pipeline IS the workload) and the Algorithm 1 fit.
Warmup runs a 2-chunk source of the same chunk shape (compiles the
selection/feed/fit programs) and then drives a throwaway ``StreamingMerge``
through every pow2 bucket up to the center budget, so the merge-path
compilations and allocator high-water land BEFORE the RSS baseline — the
sampled peak measures data-path growth, not one-time jit arenas.
"""
from __future__ import annotations

import os
import subprocess
import sys

from benchmarks.common import emit
from benchmarks.rskpca_scale import BENCH_JSON, _merge_into_bench

#: CI throughput floor (rows/s) for the n=1M smoke — measured ~31k rows/s
#: on the dev box (CPU, interpret-mode Pallas); ~4x headroom for slower
#: runners.  Real accelerators clear it by orders of magnitude.
INGEST_ROWS_PER_S_FLOOR = 8000.0

_INGEST_CHILD = """
import os
if {ndev} > 1:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ndev}"
from benchmarks.common import RssSampler, timeit_stream
from repro.core import gaussian
from repro.core.ingest_pipeline import ingest_fit
from repro.data.kpca_datasets import ChunkedDataset

n, chunk, budget = {n}, {chunk}, {budget}
block, ell, ndev = {block}, {ell}, {ndev}
mesh = None
if ndev > 1:
    from repro.launch.mesh import smoke_mesh
    mesh = smoke_mesh(ndev)
sigma = ChunkedDataset("pendigits", n=n, chunk=chunk, seed=0).bandwidth()
ker = gaussian(sigma)
box = {{}}

def run(src):
    box["out"] = ingest_fit(src, ker, 8, ell=ell, block=block,
                            budget=budget, mesh=mesh)

# warmup 1: 2 chunks of the same shape compile the selection/feed/fit
# programs and autotune plans; the timed run then measures the pipeline
timeit_stream(
    lambda: ChunkedDataset("pendigits", n=2 * chunk, chunk=chunk, seed=0),
    run, repeat=1, warmup=0)
# warmup 2: merge shape sweep.  The host merge recompiles (and the XLA CPU
# allocator grows) at every pow2 bucket the merged set passes through on
# its way to ``budget``; drive a throwaway merge through the whole bucket
# ladder NOW — widely-spread random candidates all survive selection — so
# the RSS baseline below sits above the one-time compilation high-water
# and the sampled delta measures the DATA path, not jit arenas.
import numpy as np
from repro.core.shadow import StreamingMerge
sweep = StreamingMerge(16, ker.epsilon(ell), budget=budget, block=block)
rng = np.random.default_rng(0)
while sweep.m < budget:
    sweep.update(rng.uniform(0, 1e3, (8192, 16)).astype(np.float32),
                 np.ones(8192))
for _ in range(2):  # and the over-budget spill path
    sweep.update(rng.uniform(0, 1e3, (8192, 16)).astype(np.float32),
                 np.ones(8192))
del sweep
import gc
gc.collect()
rss = RssSampler().start()
timeit_stream(
    lambda: ChunkedDataset("pendigits", n=n, chunk=chunk, seed=0),
    run, repeat=1, warmup=0)
peak_rss = rss.stop()
model, st = box["out"]
ds_bytes = 4 * n * model.centers.shape[1]
print(f"INGEST n={{n}} m={{st.m}} ndev={{ndev}} chunk={{chunk}} "
      f"budget={{budget}} wall_s={{st.wall_s:.3f}} "
      f"select_s={{st.select_s:.3f}} fit_s={{st.fit_s:.3f}} "
      f"rows_per_s={{st.rows_per_s:.1f}} "
      f"overlap_fraction={{st.overlap_fraction:.4f}} "
      f"feed_s={{st.feed_s:.3f}} stall_s={{st.stall_s:.3f}} "
      f"spilled={{st.spilled}} peak_live_bytes={{rss.peak_live}} "
      f"rss_delta_bytes={{peak_rss}} dataset_bytes={{ds_bytes}}")
"""


def _run_child(n: int, chunk: int, budget: int, block: int, ell: float,
               ndev: int, timeout: int) -> dict:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(repo, "src") + os.pathsep + repo
    if ndev > 1:
        # a CPU rehearsal on forced host devices: it must never reach for a
        # chip that this process may hold
        env["JAX_PLATFORMS"] = "cpu"
    code = _INGEST_CHILD.format(n=n, chunk=chunk, budget=budget, block=block,
                                ell=ell, ndev=ndev)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=timeout)
    if r.returncode != 0:
        print(r.stderr[-3000:])
        raise SystemExit("ingest bench child failed")
    line = next(l for l in r.stdout.splitlines() if l.startswith("INGEST"))
    return dict(p.split("=") for p in line.split()[1:])


def bench_ingest(full: bool = False) -> list:
    """Appends mode="ingest" row(s) to BENCH_rskpca.json.

    ``full=False`` measures only the CI smoke point; ``full=True`` also runs
    the n=10M mesh row (several minutes end to end) — both carry distinct
    (mode, n) identities, so ``merge_rows`` refreshes each independently.
    """
    points = [dict(n=1_000_000, chunk=65536, budget=4096, block=512,
                   ell=3.0, ndev=1, mem_gated=False, timeout=1800)]
    if full:
        points.append(dict(n=10_000_000, chunk=262144, budget=32768,
                           block=512, ell=3.0, ndev=8, mem_gated=True,
                           timeout=7200))
    fresh = []
    for p in points:
        kv = _run_child(p["n"], p["chunk"], p["budget"], p["block"],
                        p["ell"], p["ndev"], p["timeout"])
        live, ds = int(kv["peak_live_bytes"]), int(kv["dataset_bytes"])
        row = dict(
            n=int(kv["n"]), m=int(kv["m"]), mode="ingest",
            ndev=int(kv["ndev"]), chunk=int(kv["chunk"]),
            budget=int(kv["budget"]), block=p["block"], ell=p["ell"],
            wall_s=float(kv["wall_s"]), select_s=float(kv["select_s"]),
            fit_s=float(kv["fit_s"]),
            rows_per_s=round(float(kv["rows_per_s"]), 1),
            overlap_fraction=float(kv["overlap_fraction"]),
            feed_s=float(kv["feed_s"]), stall_s=float(kv["stall_s"]),
            spilled=int(kv["spilled"]),
            peak_live_bytes=live,
            rss_delta_bytes=int(kv["rss_delta_bytes"]), dataset_bytes=ds,
            peak_live_frac=round(live / ds, 4),
            mem_gated=p["mem_gated"],
        )
        fresh.append(row)
        emit(f"rskpca_ingest_n{row['n']}", row["wall_s"] * 1e6, **{
            k: v for k, v in row.items() if k not in ("n", "mode")})
    _merge_into_bench(fresh)
    print(f"# appended ingest rows to {BENCH_JSON}", flush=True)
    return fresh
