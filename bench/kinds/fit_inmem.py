"""Back-to-back in-memory fit jobs: ``shadow_rsde`` (blocked selection
over rows in memory), then the capacity-bucketed fit ``fit_centers``."""
from __future__ import annotations

import numpy as np

from bench import data
from bench.harness import clock, log
from bench.loads import Fit, annotate, answer


class FitInMem(Fit):
    def setup(self):
        self.x = np.asarray(data.rows(self.ds, self.n, self.seed, 0))
        self.warm = self.job()
        log(f"sizes n={self.n} d={self.d} m={self.warm['m']} "
            f"rank={self.rank} sigma={self.sigma:.6g} eps={self.eps:.6g} "
            f"warm job {self.warm['wall_s']:.3f} s")

    def rows_for_check(self):
        return self.x

    def radius(self) -> float:
        return self.eps

    def job(self) -> dict:
        from repro.core import shadow_rsde

        t0 = clock()
        with annotate("bench.job.select"):
            rsde = shadow_rsde(self.x, self.kernel(), float(self.cfg["ell"]))
        t1 = clock()
        with annotate("bench.job.fit"):
            model = self._fit(rsde, "rskpca+shadow")
        t2 = clock()
        return {"select_s": t1 - t0, "fit_s": t2 - t1, "wall_s": t2 - t0,
                "m": int(rsde.m), "out": answer(rsde, model, rsde.assign)}


LOAD = FitInMem
