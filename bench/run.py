"""Run one benchmark cell on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix, limits and metric readers are
found by name (``bench/harness.py``).  Set-up builds the inputs from
``--seed`` and warms every shape the cell uses; the window then runs for
``--seconds``; the program's state is freed and what the window produced
is checked against the plain reference (``bench/reference.py``).  The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics read from a profiler trace of the
window), ``device``, with ``--trace 1`` ``breakdown``, and last ``checks``,
each compared number beside its limit.  Without a TPU, or with fewer chips
than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402
from bench.harness import log  # noqa: E402

#: Where a traced run writes its profile (inside the checkout, cleared each
#: run).
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chips(chips: int) -> str | None:
    """Why this machine cannot run a cell of ``chips`` TPU chips, or None."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        return (f"no TPU: JAX's default device is {devs[0].platform}; "
                "nothing was run")
    if len(devs) < chips:
        return f"the cell needs {chips} TPU chips, found {len(devs)}"
    return None


def per_layer(cell, ctx: dict, root: str = ROOT) -> dict:
    """Each per-layer metric of the cell that its reader finds."""
    out = {}
    for m in cell.per_layer:
        v = harness.reader(m["name"], root)(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        load_cls=None) -> tuple[dict, dict]:
    """Set-up, window, check.  Returns (result line, context)."""
    import jax

    compiles = harness.CompileLog()
    drv = (load_cls or harness.load_class(cell.mix["kind"], cell.root))(
        cell, seed)
    drv.setup()
    # the seconds the plain reference spent making the served operator are
    # the benchmark's, not the program's set-up
    setup_s = harness.clock() - t_start - drv.reference_s
    log(f"set-up {setup_s:.3f} s, besides {drv.reference_s:.3f} s in the "
        "reference")
    compiles.phase = "window"
    spans = None
    if trace:
        from repro import obs
        from repro.obs import trace as otrace
        otrace.set_ring(1 << 20)
        obs.enable()
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    gclog = harness.GcLog()
    anchor = harness.clock()
    with jax.profiler.TraceAnnotation("bench.window"):
        t_open, t_end = drv.window(seconds)
    log(f"gc in the window: {gclog.close()}")
    if trace:
        jax.profiler.stop_trace()
        obs.disable()
        spans = [e for e in otrace.events()
                 if t_open - otrace._T0 <= e["t_s"] <= t_end - otrace._T0]
    compiles.phase = "check"
    device = harness.device_info()
    log(f"compiles {compiles.line()}")
    harness.log_plans()
    ctx = {"cell": cell.name, "kind": cell.mix["kind"],
           "window_s": t_end - t_open, "seconds": seconds,
           "peaks": None, "spans": spans, "trace": None,
           "reference_s": drv.reference_s}
    ctx.update(drv.layer_ctx())
    drv.close()
    values = drv.check()
    log("check values: " + " ".join(f"{k}={v!r}" for k, v in values.items()))
    correct, checks = harness.compare(values, cell.limits)
    if trace:
        from bench import trace as btrace
        ctx["peaks"] = harness.peaks(device["kind"])
        extra = [(e["name"], e["t_s"] + otrace._T0,
                  e["t_s"] + otrace._T0 + e["dur_s"]) for e in spans]
        red = btrace.reduce_dir(TRACE_DIR, harness.ROOT, extra, anchor)
        ctx["trace"] = red
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        metrics = per_layer(cell, ctx)
    else:
        e2e = dict(drv.e2e(), setup_s=setup_s)
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    result = {"correct": bool(correct), "attempted": int(drv.attempted),
              "failed": int(drv.failed), "metrics": metrics,
              "device": device}
    if trace:
        result["breakdown"] = ctx["trace"]["breakdown"]
    result["checks"] = checks
    return result, ctx


def main(argv=None) -> int:
    args = parse(argv)
    cell = harness.load_cell(args.workload)
    harness.enable_compile_cache()
    why = require_chips(cell.chips)
    if why:
        print(f"bench: {why}", file=sys.stderr)
        return 2
    log(f"cell={cell.name} kind={cell.mix['kind']} seed={args.seed} "
        f"seconds={args.seconds} trace={args.trace}")
    from bench.trace import TraceCutShort
    try:
        result, _ = run(cell, args.seed, args.seconds, bool(args.trace),
                        T_START)
    except TraceCutShort as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 3
    harness.print_checks(result["checks"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
