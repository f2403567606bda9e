"""Measured compute-plan autotuner for the Gram-shaped kernels (DESIGN.md §3).

``pick_gram_blocks`` in ``ops.py`` is a static VMEM heuristic; it knows
nothing about the Pallas interpret/grid overhead that dominates small
problems off-TPU (the n=2048 fit regression in BENCH_rskpca.json), nor about
which tile shape actually wins on a given backend.  This module replaces the
heuristic with a tiny measured tuner:

  * each op asks for a plan under a key ``(op, n-bucket, m-bucket, d,
    precision, backend, device-kind, jax-version)`` — buckets are
    power-of-two ceilings so nearby shapes share one measurement, and the
    device/runtime qualifier (plus a schema version on the disk envelope)
    keeps a cache measured on one machine from being replayed on another;
  * the first request per key times every legal candidate (one warmup for
    compile, then best-of-``_REPS``) and records the winner;
  * winners are cached in-process and persisted to disk (JSON), so a process
    pays each measurement at most once and a machine at most once.

Candidates always include the Pallas kernel (tuned tiles) and, below a size
cap, a dense-jnp fallback — the crossover that stops small problems from
paying Pallas interpret/grid overhead.  ``REPRO_AUTOTUNE=0`` disables
measurement entirely and falls back to a deterministic size heuristic
(useful for tests that assert compile counts).  ``REPRO_AUTOTUNE_CACHE``
overrides the on-disk cache location; under pytest the disk layer defaults
OFF (hermetic runs) unless that variable is set explicitly.
"""
from __future__ import annotations

import concurrent.futures
import json
import os
import threading
import time
from typing import Callable

import jax

from repro.obs import metrics as _om
from repro.obs.trace import span as _span

# plan-cache telemetry: a "miss" pays a measurement (warmup + reps per
# candidate) inside the request, so the hit/miss ratio is the difference
# between a warm serving process and one paying autotune latency on live
# traffic.
_M_HITS = _om.counter("autotune.plan_hits")
_M_MISSES = _om.counter("autotune.plan_misses")

_LOCK = threading.RLock()
_MEM: dict[str, dict] = {}     # key -> {"winner": name, "us": {name: micros}}
_DISK_LOADED = False

#: On-disk cache format version.  Bumping it orphans every older cache file
#: (schema 1 was a bare key->plan dict with no environment qualifier, so a
#: plan measured on one device kind / jax version could be replayed on
#: another — exactly the staleness this versioned envelope prevents).
_SCHEMA = 2

_ENV_TAG = None


def env_tag() -> str:
    """Hardware + software qualifier appended to every plan key: a plan is
    only ever replayed on the device kind and jax version that measured it."""
    global _ENV_TAG
    if _ENV_TAG is None:
        kind = jax.devices()[0].device_kind.replace(" ", "_").replace("|", "_")
        _ENV_TAG = f"{kind}|jax{jax.__version__}"
    return _ENV_TAG


def qualified(key: str) -> str:
    """The full cache key ``best`` stores measurements under."""
    return f"{key}|{env_tag()}"

#: Dense fallback is only a candidate (and the heuristic only picks it) below
#: this many output cells — beyond it the dense path's n x m intermediates
#: stop fitting comfortably in memory and the blocked kernel always wins.
DENSE_MAX_CELLS = 1 << 25

#: Deterministic crossover used when measurement is disabled or fails:
#: off-TPU (interpret mode) the grid loop overhead makes dense win far later
#: than on real hardware.
HEURISTIC_DENSE_CELLS_INTERPRET = 1 << 22
HEURISTIC_DENSE_CELLS_TPU = 1 << 14

_REPS = 2


def measurement_enabled() -> bool:
    return os.environ.get("REPRO_AUTOTUNE", "1") != "0"


def repo_root() -> str:
    """The checkout root: src/repro/kernels/autotune.py -> four levels up."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))


def _cache_path() -> str:
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return env
    return os.path.join(repo_root(), ".autotune_cache.json")


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone.  Otherwise the cache lives at ``<repo>/.jax_cache``: a fixed
    path, because the directory is part of what a later process must find.
    Entry points call this before their first jit; importing the library
    never does."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(repo_root(), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _disk_enabled() -> bool:
    """Disk persistence is OFF under pytest unless a cache path is set
    explicitly: a test run must neither inherit a developer's measured
    plans nor pollute the repo with its own (hermetic CI runs point
    ``REPRO_AUTOTUNE_CACHE`` at a temp file instead).  The in-process
    cache is unaffected — each test process still measures at most once
    per key."""
    if os.environ.get("REPRO_AUTOTUNE_CACHE"):
        return True
    return "PYTEST_CURRENT_TEST" not in os.environ


def bucket(v: int, lo: int = 128, hi: int = 1 << 17) -> int:
    """Power-of-two ceiling clipped to [lo, hi]: nearby shapes share a key."""
    v = max(int(v), 1)
    b = 1 << (v - 1).bit_length()
    return max(lo, min(b, hi))


def _load_disk() -> None:
    global _DISK_LOADED
    if _DISK_LOADED:
        return
    _DISK_LOADED = True
    if not _disk_enabled():
        return
    try:
        with open(_cache_path()) as f:
            disk = json.load(f)
        if not isinstance(disk, dict) or disk.get("schema") != _SCHEMA:
            return  # pre-versioned or foreign cache: invalidate wholesale
        for k, v in disk.get("plans", {}).items():
            _MEM.setdefault(k, v)
    except (OSError, ValueError):
        pass


def _save_disk() -> None:
    if not _disk_enabled():
        return
    path = _cache_path()
    try:
        # merge with whatever is on disk (a concurrent process may have
        # persisted other keys since we loaded) — our measurements win ties;
        # an old-schema file is dropped, not merged
        merged: dict[str, dict] = {}
        try:
            with open(path) as f:
                disk = json.load(f)
            if isinstance(disk, dict) and disk.get("schema") == _SCHEMA:
                merged.update(disk.get("plans", {}))
        except (OSError, ValueError):
            pass
        merged.update(_MEM)
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = path + f".tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"schema": _SCHEMA, "plans": merged}, f, indent=1,
                      sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass  # read-only FS: in-process cache still works


def clear(in_memory_only: bool = True) -> None:
    """Drop cached plans (tests)."""
    global _DISK_LOADED
    with _LOCK:
        _MEM.clear()
        _DISK_LOADED = in_memory_only  # True: don't re-read disk either


class CandidateFailed(RuntimeError):
    """A plan candidate raised while being measured.  Every candidate is a
    plan the op may run, so a failure is a fault to fix (or a candidate to
    prune), never a reason to quietly pick another plan."""


def _time_candidates(key: str, candidates: dict[str, Callable[[], object]]
                     ) -> dict[str, float]:
    times: dict[str, float] = {}
    for name, thunk in candidates.items():
        try:
            thunk()  # compile warmup
            t = []
            for _ in range(_REPS):
                t0 = time.perf_counter()
                thunk()
                t.append(time.perf_counter() - t0)
        except Exception as e:
            raise CandidateFailed(
                f"autotune candidate {name!r} for {key} raised "
                f"{type(e).__name__}: {e}") from e
        times[name] = min(t)
    return times


def _measure(key: str, candidates: dict[str, Callable[[], object]]
             ) -> dict[str, float]:
    """Best-of-``_REPS`` seconds per candidate after one compile warmup.

    The timing runs on a fresh thread: JAX keeps its trace state per
    thread, so the candidates execute for real even when the op asking for
    a plan is being traced inside an outer ``jit`` — timing that trace
    would rank candidates by tracing cost, not by what the device does."""
    with concurrent.futures.ThreadPoolExecutor(1) as pool:
        return pool.submit(_time_candidates, key, candidates).result()


def best(key: str, candidates: dict[str, Callable[[], object]],
         default: str) -> str:
    """Winner for ``key``: cached if known, else measured once and persisted.

    ``candidates`` maps name -> thunk running that plan on bucket-shaped
    synthetic data (the thunk must block until the result is ready).  A thunk
    that raises aborts the call with ``CandidateFailed`` naming the key and
    the candidate.  With a single candidate, or measurement disabled, no
    timing happens.

    Keys are qualified with the device kind and jax version (``env_tag``)
    before lookup/storage, so a persisted plan can never be replayed on
    hardware or a runtime that did not measure it.
    """
    if not measurement_enabled():
        return default
    key = qualified(key)
    with _LOCK:
        _load_disk()
        hit = _MEM.get(key)
        if hit is not None and hit.get("winner") in candidates:
            _M_HITS.inc()
            return hit["winner"]
        if len(candidates) == 1:
            return next(iter(candidates))
        _M_MISSES.inc()
        with _span("autotune.measure", key=key, n_candidates=len(candidates)):
            times = _measure(key, candidates)
        winner = min(times, key=times.get)
        _MEM[key] = {"winner": winner,
                     "us": {k: round(v * 1e6, 1) for k, v in times.items()}}
        _save_disk()
        return winner


def best_roofline(key: str, candidates: dict[str, Callable[[], object]],
                  costs: dict[str, tuple[float, float]], default: str) -> str:
    """Roofline-driven winner: measured bytes/FLOPs crossover, not raw time.

    ``costs`` maps each candidate to its analytic ``(flops, bytes)`` for the
    measured shape (the caller's cost model — e.g. per-tile HBM re-reads of
    the centers/projector for the transform kernel).  Every candidate is
    timed once (same warmup + best-of-``_REPS`` as ``best``); the
    measurements are then used to estimate the device's achieved compute
    peak ``P = max flops/t`` and bandwidth ``B = max bytes/t`` ACROSS the
    candidate fleet, and the winner minimizes the roofline-predicted time

        t_pred(c) = max(flops_c / P, bytes_c / B)

    with measured time breaking near-ties (within 10%).  Unlike time-only
    search, one noisy sample cannot crown a tile shape whose byte traffic
    is strictly worse — the prediction uses analytic costs with fleet-level
    peaks, so a slowdown window hitting one candidate perturbs P/B a little
    rather than that candidate's ranking entirely.  The measured peaks, the
    ridge point, and the per-candidate predictions are recorded alongside
    the winner in the same schema-2 cache envelope as ``best``'s entries.
    """
    if not measurement_enabled():
        return default
    key = qualified(key)
    with _LOCK:
        _load_disk()
        hit = _MEM.get(key)
        if hit is not None and hit.get("winner") in candidates:
            _M_HITS.inc()
            return hit["winner"]
        if len(candidates) == 1:
            return next(iter(candidates))
        _M_MISSES.inc()
        with _span("autotune.measure_roofline", key=key,
                   n_candidates=len(candidates)):
            times = _measure(key, candidates)
        peak_flops = max(costs[c][0] / t for c, t in times.items())
        peak_bytes = max(costs[c][1] / t for c, t in times.items())
        pred = {c: max(costs[c][0] / peak_flops, costs[c][1] / peak_bytes)
                for c in times}
        t_best = min(pred.values())
        near = [c for c in pred if pred[c] <= 1.10 * t_best]
        winner = min(near, key=times.get)
        _MEM[key] = {
            "winner": winner,
            "us": {c: round(t * 1e6, 1) for c, t in times.items()},
            "roofline": {
                "peak_gflops": round(peak_flops / 1e9, 2),
                "peak_gbs": round(peak_bytes / 1e9, 2),
                "ridge_flop_per_byte": round(peak_flops / peak_bytes, 2),
                "pred_us": {c: round(t * 1e6, 1) for c, t in pred.items()},
            },
        }
        _save_disk()
        return winner


def roofline_entry(key: str) -> dict | None:
    """The full recorded entry ({winner, us, roofline}) for an unqualified
    key, if ``best_roofline`` measured it — benchmarks/roofline.py reads
    these to report the transform crossover."""
    with _LOCK:
        _load_disk()
        hit = _MEM.get(qualified(key))
        return None if hit is None or "roofline" not in hit else hit


def heuristic_plan(n: int, m: int, interpret: bool) -> str:
    """Deterministic dense/pallas crossover for when measurement is off."""
    cells = n * m
    cap = (HEURISTIC_DENSE_CELLS_INTERPRET if interpret
           else HEURISTIC_DENSE_CELLS_TPU)
    return "dense" if cells <= min(cap, DENSE_MAX_CELLS) else "pallas"
