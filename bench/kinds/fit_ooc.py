"""Back-to-back out-of-core fit jobs: ``ingest_fit`` over rows staged once
in host memory, streamed in fixed chunks through the prefetch feed, on one
chip or, with rows split over the chips, on a mesh of the cell's chips."""
from __future__ import annotations

import numpy as np

from bench import data, reference, reference_ooc
from bench.harness import clock, log
from bench.loads import Fit, annotate


class HostRows:
    """The staged rows as the program's chunk source: ``(chunk, n_valid)``
    views of one host array, the last padded with zero rows."""

    def __init__(self, x: np.ndarray, chunk: int):
        self.x, self.chunk = x, int(chunk)

    @property
    def d(self) -> int:
        return self.x.shape[1]

    def chunks(self, start: int = 0):
        n = self.x.shape[0]
        for lo in range(start * self.chunk, n, self.chunk):
            blk = self.x[lo: lo + self.chunk]
            k = blk.shape[0]
            if k < self.chunk:
                blk = np.concatenate(
                    [blk, np.zeros((self.chunk - k, self.d), np.float32)])
            yield blk, k


class FitOoc(Fit):
    def setup(self):
        from repro.core.ingest_pipeline import IngestStats

        # the cell measures the on-device merge; a program that merges on
        # the host compiles per seed past a run's time limit, so it stops
        # here
        if not hasattr(IngestStats(), "gathered"):
            raise RuntimeError("this program has no on-device streaming "
                               "merge (IngestStats.gathered): nothing run")
        self.chunk = int(self.mix["chunk"])
        self.budget = int(self.cfg["center_budget"])
        self.mesh = None
        if self.cell.chips > 1:
            from repro.launch.mesh import data_mesh
            self.mesh = data_mesh(self.cell.chips)
        self.x = np.asarray(data.rows(self.ds, self.n, self.seed, 0))
        self.warm = self.job()
        log(f"sizes n={self.n} d={self.d} chunk={self.chunk} "
            f"chips={self.cell.chips} m={self.warm['m']} rank={self.rank} "
            f"sigma={self.sigma:.6g} eps={self.eps:.6g} budget={self.budget} "
            f"warm job {self.warm['wall_s']:.3f} s, merge counters "
            f"{self.warm['merge']}")

    def rows_for_check(self):
        return self.x

    def radius(self) -> float:
        return 2.0 * self.eps

    def job(self) -> dict:
        from repro.core import ingest_fit

        t0 = clock()
        with annotate("bench.job"):
            model, st = ingest_fit(
                HostRows(self.x, self.chunk), self.kernel(), self.rank,
                ell=float(self.cfg["ell"]), block=int(self.mix["block"]),
                budget=self.budget, prefetch=int(self.mix["prefetch"]),
                mesh=self.mesh)
        wall = clock() - t0
        return {"select_s": st.select_s, "fit_s": st.fit_s, "wall_s": wall,
                "m": int(st.m),
                "merge": {k: getattr(st, k) for k in (
                    "gathered", "absorbed", "kept", "spilled", "cap")},
                "out": {"centers": np.asarray(model.centers, np.float32),
                        "weights": np.asarray(model.weights, np.float64),
                        "assign": None,
                        "eigvals": np.asarray(model.eigvals),
                        "projector": np.asarray(model.projector),
                        "transform": model.transform}}

    def control(self) -> dict:
        """The check with the two-level reference at Precision.HIGH, over
        the same chunks and the same split over the chips, in the
        program's place."""
        c, w = reference_ooc.select(
            self.x, self.eps, self.chunk, self.cell.chips,
            block=int(self.mix["block"]), budget=self.budget,
            precision="high")
        lam, proj = reference.eig(c, w, self.n, self.sigma, self.rank,
                                  precision="high")

        def transform(xq):
            return reference.transform(xq, c, proj, self.sigma,
                                       precision="high")

        out = {"centers": c, "weights": w, "assign": None, "eigvals": lam,
               "projector": proj, "transform": transform}
        vals = {"failed": 0.0}
        vals.update(self.check_job(out, self.radius(), None))
        return vals


LOAD = FitOoc
