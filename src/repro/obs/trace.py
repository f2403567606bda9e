"""Nestable tracing spans with a lock-free ring buffer (DESIGN.md §16).

A span is one timed region of a hot path::

    with span("ingest.select_chunk", chunk=i, rows=n_valid):
        ...

Spans NEST: each thread keeps a stack of its open spans, so every span
records an integer ``id`` and the ``parent`` id of the span open on its
thread when it began (0 for none); a Chrome-trace viewer renders
``serve.batch`` containing ``project.launch`` as stacked bars, and a reader
can attribute a parent's time to its children.  Completed spans land in a
bounded ``deque`` (``maxlen`` ring semantics: CPython's deque
append/popleft are atomic under the GIL, so producers on the dispatcher,
producer-feed, and client threads never take a lock on the hot path and the
buffer can never grow without bound).

While enabled and while ``jax.profiler`` takes a trace, each span also
enters a ``jax.profiler.TraceAnnotation`` of its name and the attributes
known at entry, so it lands on its thread's host line of the ``.xplane.pb``,
on the same clock as the device ops.  With no trace being taken the
annotation would record nothing, so it is not made (one C++ flag read).

Timing is wall-clock (``time.perf_counter``) by default.  JAX dispatch is
asynchronous — a wall-clock exit can close a span whose device work is still
in flight — so a span whose duration must include device completion passes
its result through :meth:`Span.sync`, which blocks until the arrays are
ready and records the synced fraction of the span separately::

    with span("serve.transform", rows=r) as sp:
        z = sp.sync(server.transform(x))   # dur now covers device work

The layer spans inside one served batch use :func:`profiled_span`, which
records only while a profiler trace is being taken as well.

Everything is OFF by default: ``span()`` returns a shared no-op object
(one module-global check, no allocation beyond the kwargs dict) until
``repro.obs.enable()`` flips the flag.  Exporters:

  * :func:`export_chrome` — ``chrome://tracing`` / Perfetto "X" complete
    events, one track per thread;
  * :func:`export_jsonl` — one flat JSON object per line, for ad-hoc
    ``jq``/pandas digestion.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque

from jax.profiler import TraceAnnotation

#: Flipped by repro.obs.enable()/disable(); every hot-path check reads this
#: module global directly (one dict lookup — the disabled-mode cost).
_ENABLED = False

_DEFAULT_RING = 65536
_EVENTS: deque = deque(maxlen=_DEFAULT_RING)
_TLS = threading.local()
#: Span ids, process-wide; ``next`` on a C iterator is atomic under the GIL.
_IDS = itertools.count(1)
#: True while a ``jax.profiler`` trace is being taken (one C++ flag read).
_profiler_on = TraceAnnotation.is_enabled

#: Process-epoch for relative timestamps: every event shares this origin so
#: cross-thread ordering in the exported trace is meaningful.
_T0 = time.perf_counter()


class Span:
    """One live timed region; use via the :func:`span` factory."""

    __slots__ = ("name", "attrs", "t0", "sync_s", "id", "parent", "_ann")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs
        self.t0 = 0.0
        self.sync_s = 0.0
        self.id = 0
        self.parent = 0
        self._ann = None

    def __enter__(self) -> "Span":
        try:  # this thread's open spans, innermost last
            stack = _TLS.stack
        except AttributeError:
            stack = _TLS.stack = []
        self.parent = stack[-1].id if stack else 0
        self.id = next(_IDS)
        stack.append(self)
        if _profiler_on():
            self._ann = TraceAnnotation(self.name, **self.attrs)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def sync(self, value):
        """Block until ``value``'s device work is done; the blocked wall time
        accrues to the span (reported as ``sync_s``).  Returns ``value``."""
        import jax

        t0 = time.perf_counter()
        jax.block_until_ready(value)
        self.sync_s += time.perf_counter() - t0
        return value

    def set(self, **attrs) -> "Span":
        """Attach attributes discovered mid-span (e.g. an output shape)."""
        self.attrs.update(attrs)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        stack = _TLS.stack
        stack.pop()  # spans are context managers: this one is innermost
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        # attrs are flattened to a tuple of pairs: a ring of dicts keeps
        # 64k tracked containers alive and every GC pass pays for them,
        # whereas tuples of atoms get UNTRACKED after one young-gen scan —
        # the buffered trace then costs the collector nothing (this is
        # measurable: the serve-dispatch overhead in benchmarks/
        # obs_overhead.py was ~3% GC amplification before the flattening)
        _EVENTS.append((
            self.name, threading.get_ident(), self.id, self.parent,
            len(stack), self.t0 - _T0, t1 - self.t0, self.sync_s,
            tuple(self.attrs.items()),
        ))
        return False


class _NullSpan:
    """Shared do-nothing span handed out while tracing is disabled."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def sync(self, value):
        return value

    def set(self, **attrs):
        return self


_NULL = _NullSpan()


def span(name: str, **attrs):
    """A nestable timed region; no-op (shared null object) while disabled."""
    if not _ENABLED:
        return _NULL
    return Span(name, attrs)


def profiling() -> bool:
    """True while enabled and while a ``jax.profiler`` trace is being
    taken: when :func:`profiled_span` records."""
    return _ENABLED and _profiler_on()


def profiled_span(name: str, **attrs):
    """A :func:`span` recorded only while a ``jax.profiler`` trace is also
    being taken.  For the layer boundaries inside one served batch: they
    exist to attribute a profile's device gaps, and outside a profile four
    more spans a batch would cost more than the enabled budget allows."""
    if not profiling():
        return _NULL
    return Span(name, attrs)


def enabled() -> bool:
    return _ENABLED


def set_ring(maxlen: int) -> None:
    """Resize the event ring (drops buffered events)."""
    global _EVENTS
    _EVENTS = deque(maxlen=int(maxlen))


def clear() -> None:
    _EVENTS.clear()


def events() -> list[dict]:
    """Snapshot of the buffered spans, oldest first, as plain dicts."""
    return [
        {"name": n, "tid": tid, "id": sid, "parent": parent, "depth": depth,
         "t_s": round(t, 6), "dur_s": round(dur, 6),
         "sync_s": round(sync_s, 6), **dict(attrs)}
        for n, tid, sid, parent, depth, t, dur, sync_s, attrs
        in list(_EVENTS)
    ]


def _atomic_write(path: str, text: str) -> None:
    tmp = f"{path}.tmp{os.getpid()}"
    with open(tmp, "w") as f:
        f.write(text)
    os.replace(tmp, path)


def export_chrome(path: str) -> int:
    """Write the buffered spans as Chrome-trace JSON ("X" complete events,
    one track per thread); returns the number of events written."""
    evs = list(_EVENTS)
    out = []
    for name, tid, sid, parent, _depth, t, dur, sync_s, attrs in evs:
        args = dict(attrs)  # ring stores flattened (k, v) pairs
        args["id"] = sid
        args["parent"] = parent
        if sync_s:
            args["sync_ms"] = round(sync_s * 1e3, 3)
        out.append({
            "name": name, "ph": "X", "pid": 0, "tid": tid,
            "ts": round(t * 1e6, 1), "dur": round(dur * 1e6, 1),
            "args": args,
        })
    _atomic_write(path, json.dumps(
        {"traceEvents": out, "displayTimeUnit": "ms"}, indent=1))
    return len(out)


def export_jsonl(path: str) -> int:
    """Write the buffered spans as one flat JSON object per line."""
    evs = events()
    _atomic_write(path, "".join(json.dumps(e) + "\n" for e in evs))
    return len(evs)
