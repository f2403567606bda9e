"""From a profiler trace to the numbers per-layer metrics read.

A traced run records the measured window with ``jax.profiler``; the
window is the host annotation ``bench.window``.  Per TPU device plane:

* busy time: the union of the intervals of its ``XLA Ops`` events,
  clipped to the window, averaged over the devices used (those with an op
  in the window);
* a kernel's device time: the summed duration, inside the window, of the
  events its count module (``bench/costs/<kernel>.py``) names, either
  whole XLA modules (``MODULES``, on the ``XLA Modules`` line) or single
  ops (``OPS``, on the ``XLA Ops`` line);
* the breakdown: the ops that took most device time, and the longest idle
  gaps, each labelled by what the host was doing in it.

A trace whose device events stop well before the window closes (the
profiler dropped the rest: it keeps a bounded number of events) would read
the lost time as idle; ``reduce`` refuses it (``TraceCutShort``).
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE = re.compile(r"^/device:TPU:\d+$")

#: A device plane whose last operation in the window ends more than this
#: many seconds, and more than this share of the window, before the window
#: closes has lost its last events.
CUT_TAIL_S = 1.0
CUT_TAIL_SHARE = 0.02


class TraceCutShort(ValueError):
    """The device's events stop before the window closes."""


def load(trace_dir: str):
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(max(files, key=os.path.getmtime))


def _events(line):
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for ev in line.events]


def _bench_events(pd) -> list[tuple[str, float, float]]:
    """The harness's own annotations (``bench.*``), from every host line."""
    out = []
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                n = ev.name
                if n.startswith("bench."):
                    out.append((n, ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def window(pd, bench_events=None) -> tuple[float, float]:
    """(start, end) in ns of the host annotation ``bench.window``."""
    for name, s, e in bench_events or _bench_events(pd):
        if name == WINDOW:
            return float(s), float(e)
    raise ValueError(f"no {WINDOW!r} annotation in the trace")


def devices(pd) -> list:
    return [p for p in pd.planes if _DEVICE.match(p.name)]


def _line(plane, name: str):
    for line in plane.lines:
        if line.name == name:
            return line
    return None


def _arrays(plane, name: str):
    """(names, starts, ends) of a line's events, read once."""
    line = _line(plane, name)
    names, st, du = [], [], []
    for ev in (line.events if line is not None else ()):
        names.append(ev.name)
        st.append(ev.start_ns)
        du.append(ev.duration_ns)
    st = np.asarray(st, np.float64)
    return names, st, st + np.asarray(du, np.float64)


def union(intervals, lo: float, hi: float) -> np.ndarray:
    """Disjoint sorted (k, 2) intervals covering ``intervals`` within
    [lo, hi]."""
    iv = np.clip(np.asarray(intervals, np.float64).reshape(-1, 2), lo, hi)
    iv = iv[iv[:, 1] > iv[:, 0]]
    if iv.size == 0:
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    s, e = iv[:, 0], np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > e[:-1]
    first = np.flatnonzero(new)
    last = np.r_[first[1:] - 1, len(s) - 1]
    return np.stack([s[first], e[last]], axis=1)


def _seconds(u: np.ndarray) -> float:
    return float(np.sum(u[:, 1] - u[:, 0])) * 1e-9 if u.size else 0.0


def _matching(names, patterns) -> np.ndarray:
    """Mask of the events whose name matches a pattern (each distinct name
    is matched once)."""
    pats = [re.compile(p) for p in patterns]
    hit = {n: any(p.search(n) for p in pats) for n in set(names)}
    return np.fromiter((hit[n] for n in names), bool, len(names))


def _label(spans, s: float, e: float) -> str:
    """The host span that overlaps [s, e] most, or 'no host span'."""
    best, name = 0.0, "no host span"
    for n, a, b in spans:
        ov = min(b, e) - max(a, s)
        if ov > best:
            best, name = ov, n
    return name


def _check_tail(plane_name: str, st, en, lo: float, hi: float,
                tail_s: float, tail_share: float) -> None:
    inside = (en > lo) & (st < hi)
    last = min(float(np.max(en[inside])), hi)
    tail = (hi - last) * 1e-9
    if tail > tail_s and tail > tail_share * (hi - lo) * 1e-9:
        raise TraceCutShort(
            f"{plane_name}: the last device op ends {tail:.3f} s before the "
            f"{(hi - lo) * 1e-9:.3f} s window closes ({int(np.sum(inside))} "
            "ops read): the trace was cut short")


def reduce(pd, kernels: dict, extra_spans=(), tail_s: float = CUT_TAIL_S,
           tail_share: float = CUT_TAIL_SHARE) -> dict:
    """Busy and window seconds, each kernel's device seconds, and the
    breakdown, from a loaded trace.  ``kernels`` maps a kernel's name to
    its count module; ``extra_spans`` are more host spans (name, start ns,
    end ns) on the trace's clock to label idle gaps with.  A kernel's time
    is the union of its matched modules and ops, so an op inside a matched
    module counts once.  A device plane in use whose ops end more than
    ``tail_s`` seconds and ``tail_share`` of the window before it closes
    raises ``TraceCutShort``."""
    bench = _bench_events(pd)
    lo, hi = window(pd, bench)
    devs = devices(pd)
    if not devs:
        raise ValueError("no TPU device plane in the trace")
    used = []
    for plane in devs:
        ops = _arrays(plane, OPS_LINE)
        if np.any((ops[2] > lo) & (ops[1] < hi)):
            used.append((plane, ops))
    used = used or [(devs[0], _arrays(devs[0], OPS_LINE))]
    busy, op_time, gaps = 0.0, {}, []
    ksec = {k: 0.0 for k in kernels}
    for i, (plane, (names, st, en)) in enumerate(used):
        if st.size and np.any((en > lo) & (st < hi)):
            _check_tail(plane.name, st, en, lo, hi, tail_s, tail_share)
        u = union(np.stack([st, en], axis=1), lo, hi)
        busy += _seconds(u)
        dur = np.clip(np.minimum(en, hi) - np.maximum(st, lo), 0.0, None)
        for n, t in zip(names, dur):
            if t > 0:
                op_time[n] = op_time.get(n, 0.0) + t * 1e-9
        mods = _arrays(plane, MODULES_LINE)
        for k, mod in kernels.items():
            iv = []
            for (ns, s_, e_), pats in ((mods, getattr(mod, "MODULES", ())),
                                       ((names, st, en),
                                        getattr(mod, "OPS", ()))):
                if pats and ns:
                    m = _matching(ns, pats)
                    iv.append(np.stack([s_[m], e_[m]], axis=1))
            if iv:
                ksec[k] += _seconds(union(np.concatenate(iv), lo, hi))
        if i == 0:
            edges = np.concatenate([[lo], u.ravel(), [hi]])
            gaps = [(s_, e_) for s_, e_ in edges.reshape(-1, 2) if e_ > s_]
    ndev = len(used)
    spans = [(n, s_, e_) for n, s_, e_ in bench
             if n != WINDOW and e_ > lo and s_ < hi] + list(extra_spans)
    top_gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    return {
        "busy_s": busy / ndev,
        "window_s": (hi - lo) * 1e-9,
        "devices": ndev,
        "kernel_s": {k: v / ndev for k, v in ksec.items()},
        "breakdown": {
            "device_ops": [[n, t / ndev] for n, t in
                           sorted(op_time.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": [[_label(spans, s_, e_), float(e_ - s_) * 1e-9]
                          for s_, e_ in top_gaps],
        },
    }


def reduce_dir(trace_dir: str, root: str, extra_spans=(),
               anchor=None) -> dict:
    """``reduce`` of the trace under ``trace_dir`` against every count
    module under ``<root>/bench/costs``.  ``extra_spans`` are (name, start
    s, end s) on the host's ``perf_counter`` clock, and ``anchor`` the
    ``perf_counter`` reading taken as the window annotation opened: they
    are shifted onto the trace's clock by it."""
    from bench import harness

    pd = load(trace_dir)
    names = sorted(os.path.splitext(os.path.basename(p))[0] for p in
                   glob.glob(os.path.join(root, "bench", "costs", "*.py")))
    kernels = {k: harness.cost(k, root) for k in names}
    shifted = []
    if anchor is not None and extra_spans:
        lo, _ = window(pd)
        shifted = [(n, lo + (s - anchor) * 1e9, lo + (e - anchor) * 1e9)
                   for n, s, e in extra_spans]
    return reduce(pd, kernels, shifted)
