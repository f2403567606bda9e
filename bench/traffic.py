"""The one traffic generator: it reads a mix's parameters from
``bench/traffic/<mix>.json`` and turns them, with ``--seed``, into the
requests a load replays.  The mix's ``kind`` names its load,
``bench/kinds/<kind>.py``: a new kind is a new file, and an unknown one is
refused when the mix is loaded.
"""
from __future__ import annotations

import json
import os

import numpy as np


def load(bench_dir: str, name: str) -> dict:
    """The mix ``name``; the load of its kind must exist."""
    with open(os.path.join(bench_dir, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    kind = mix.get("kind")
    if not isinstance(kind, str) or not os.path.isfile(
            os.path.join(bench_dir, "kinds", f"{kind}.py")):
        have = sorted(f[:-3] for f in os.listdir(os.path.join(bench_dir,
                                                              "kinds"))
                      if f.endswith(".py"))
        raise ValueError(f"traffic {name!r}: no load bench/kinds/{kind}.py "
                         f"(have {have})")
    return mix


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed) & (2**63 - 1),
                                                         stream]))


def closed_offsets(mix: dict, seed: int, count: int,
                   pool_rows: int) -> np.ndarray:
    """First pool row of each of ``count`` closed-loop requests."""
    return rng(seed, 12).integers(0, pool_rows - int(mix["rows"]) + 1,
                                  size=count)


def sample(seed: int, population: int, count: int) -> np.ndarray:
    """Indices of the answers the check compares, drawn from the seed."""
    count = min(count, population)
    return np.sort(rng(seed, 13).choice(population, size=count,
                                        replace=False))
