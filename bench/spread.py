"""Spread of a cell's runs: per metric, the distance between the first and
third quartiles as a share of the median (``statistics.quantiles(n=4)``),
per set of runs, and the bound five times the wider spread would give.

    python3 bench/spread.py set_a.jsonl set_b.jsonl

Each file holds the last lines of one set's runs (one JSON object a line).
"""
from __future__ import annotations

import json
import statistics
import sys


def spread(values: list) -> float:
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med


def read(path: str) -> dict:
    out: dict[str, list] = {}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line.startswith("{"):
                continue
            r = json.loads(line)
            if not r.get("correct"):
                print(f"{path}: a run with correct false: {r.get('checks')}")
            for name, m in r["metrics"].items():
                out.setdefault(name, []).append(m["value"])
    return out


def main(paths) -> int:
    sets = [read(p) for p in paths]
    for name in sorted(set().union(*sets)):
        vals = [s[name] for s in sets if len(s.get(name, ())) >= 2]
        spreads = [spread(v) for v in vals]
        meds = [statistics.median(v) for v in vals]
        print(json.dumps({"metric": name, "medians": meds,
                          "spreads": spreads,
                          "bound_5x": 5 * max(spreads) if spreads else None,
                          "values": vals}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
