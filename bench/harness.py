"""What every cell shares: finding a cell and its files by name, the
compile cache, compile counting, the measured window, the check against
the reference, and the result line.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Its
configuration is ``bench/configs/<config>.json``, its traffic mix
``bench/traffic/<traffic>.json`` (whose ``kind`` names its load,
``bench/kinds/<kind>.py``), its correctness limits
``bench/limits/<cell>.json``,
each per-layer metric's reader ``bench/metrics/<base>.py`` (``base`` is the
metric's name up to its first dot), and each kernel's operation count and
trace names ``bench/costs/<kernel>.py``.  Adding a cell or a metric adds
files; no existing file changes.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


# --------------------------------------------------------------------------
# finding a cell by name
# --------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    limits: dict
    end_to_end: list
    per_layer: list
    root: str = ROOT


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(os.path.join(root, configs[w["config"]]["file"]))
    from bench import traffic
    mix = traffic.load(os.path.join(root, "bench"), w["traffic"])
    limits = _json(os.path.join(root, "bench", "limits", f"{name}.json"))
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", ())
                 or ("workloads" not in m and m["moves"] in moved)]
    return Cell(name, int(w["chips"]), config, mix, limits, e2e, per_layer,
                root)


def module(path: str, modname: str):
    """The Python file ``path``, loaded as the module ``modname``."""
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, root: str = ROOT):
    """The ``read(ctx)`` function of a per-layer metric."""
    base = metric.split(".", 1)[0]
    path = os.path.join(root, "bench", "metrics", f"{base}.py")
    return module(path, f"bench_metric_{base}").read


def load_class(kind: str, root: str = ROOT):
    """The load of a traffic ``kind``: ``LOAD`` of ``bench/kinds/<kind>.py``."""
    path = os.path.join(root, "bench", "kinds", f"{kind}.py")
    return module(path, f"bench_kind_{kind}").LOAD


def cost(kernel: str, root: str = ROOT):
    """The count module of a kernel (operations, bytes, trace names)."""
    path = os.path.join(root, "bench", "costs", f"{kernel}.py")
    return module(path, f"bench_cost_{kernel}")


def peaks(device_kind: str, root: str = ROOT) -> dict:
    """Published peaks of ``device_kind``; an unknown kind is an error."""
    table = _json(os.path.join(root, "bench", "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table['devices'])})")
    return table["devices"][device_kind]


# --------------------------------------------------------------------------
# compile cache and compile counting
# --------------------------------------------------------------------------


def enable_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` when set,
    else at ``<checkout>/.jax_cache`` (a fixed path: the path is part of
    the cache key); every compile is written, however short, so that a
    later run of the cell compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileLog:
    """Programs loaded for the backend, and how many of them came from the
    persistent cache, with the seconds spent, split by phase."""

    def __init__(self):
        import jax

        self.phase = "setup"
        self.counts: dict[str, list] = {}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _slot(self):
        return self.counts.setdefault(self.phase, [0, 0.0, 0])

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            s = self._slot()
            s[0] += 1
            s[1] += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self._slot()[2] += 1

    def line(self) -> str:
        return " ".join(
            f"{p}: programs={c} compiled={c - h} from_cache={h} "
            f"seconds={s:.3f}" for p, (c, s, h) in self.counts.items()) \
            or "none"


class GcLog:
    """Pauses of Python's garbage collector, by generation, while open: the
    load generator shares the served process, so its pauses are the
    server's too."""

    def __init__(self):
        self.pauses: dict[int, list] = {0: [], 1: [], 2: []}
        self._t0 = None
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses[info["generation"]].append(
                time.perf_counter() - self._t0)

    def close(self) -> str:
        gc.callbacks.remove(self._cb)
        return " ".join(
            f"gen{g}: n={len(p)} max_ms={1e3 * max(p, default=0.0):.3f} "
            f"total_ms={1e3 * sum(p):.3f}" for g, p in self.pauses.items())


def log_plans() -> None:
    """Every plan the program's autotuner holds for this device."""
    from repro.kernels import autotune

    for key, entry in sorted(autotune._MEM.items()):
        if key.endswith(autotune.env_tag()):
            log(f"plan {key} -> {entry['winner']} (us per candidate: "
                f"{entry.get('us')})")


# --------------------------------------------------------------------------
# device
# --------------------------------------------------------------------------


def device_info() -> dict:
    import jax

    devs = jax.devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


# --------------------------------------------------------------------------
# the check
# --------------------------------------------------------------------------


def compare(values: dict, limits: dict) -> tuple[bool, dict]:
    """Each number against its limit (``value <= limit``); a number that is
    missing or not finite fails."""
    import math

    out, ok = {}, True
    for name, spec in limits.items():
        v = values.get(name)
        good = v is not None and math.isfinite(v) and v <= spec["limit"]
        ok &= good
        out[name] = {"value": v, "limit": spec["limit"]}
    return ok, out


def print_checks(checks: dict) -> None:
    for name, c in checks.items():
        v, lim = c["value"], c["limit"]
        ok = v is not None and v <= lim
        print(f"[check] {name} = {v!r} limit {lim!r} "
              f"{'pass' if ok else 'FAIL'}", file=sys.stderr, flush=True)


def clock() -> float:
    return time.perf_counter()
