"""Tiny cells for the CPU tests: the committed configurations and mixes,
shrunk to a size a test run holds."""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from bench import harness  # noqa: E402

#: kind -> the committed cell it shrinks
CELLS = {
    "serve_closed": "pendigits-d16.serve-bulk",
    "fit_inmem": "usps-d256.fit-inmem",
}

SEED = 2 ** 33 + 12345  # past 32 bits, as large seeds are


def cell(kind: str, limits: dict | None = None) -> harness.Cell:
    """The committed cell of ``kind`` at a tiny size."""
    c = harness.load_cell(CELLS[kind])
    c.config = json.loads(json.dumps(c.config))
    c.config["n"] = 4096 if kind == "fit_inmem" else 8192
    c.mix.update(pool_rows=4096, check_requests=16, check_rows=256)
    if kind == "serve_closed":
        c.mix.update(clients=4, max_requests=4000)
    if limits is not None:
        c.limits = limits
    return c
