"""What the loads share.  A load is the code of one traffic ``kind``, in
``bench/kinds/<kind>.py`` (its class ``LOAD``): it builds its cell's inputs
and program objects from the seed (set-up), runs the measured window, frees
the program's state, and checks what the window produced against the plain
reference.  ``Serve`` and ``Fit`` hold what the serving and the fitting
kinds have in common.

Sizes come from the configuration and the mix, so the CPU tests drive the
same functions at a tiny size.
"""
from __future__ import annotations

import jax
import numpy as np

from bench import data, reference, traffic
from bench.harness import clock, log


def annotate(name: str):
    return jax.profiler.TraceAnnotation(name)


def pow2(v: int) -> int:
    return 1 << max(int(v) - 1, 0).bit_length()


class Load:
    """State shared by every kind: configuration, mix, seed, kernel."""

    def __init__(self, cell, seed: int):
        self.cell = cell
        self.cfg = cell.config
        self.mix = cell.mix
        self.seed = int(seed)
        self.ds = self.cfg["dataset"]
        self.sigma = data.bandwidth(self.ds)
        self.eps = self.sigma / float(self.cfg["ell"])
        self.rank = int(self.cfg["rank"])
        self.n = int(self.cfg["n"])
        self.d = int(self.ds["d"])
        self.attempted = 0
        self.failed = 0
        #: seconds of set-up spent in the plain reference, which
        #: ``setup_s`` leaves out
        self.reference_s = 0.0

    def kernel(self):
        from repro.core import gaussian
        return gaussian(self.sigma)


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------


class Operator:
    """A published operator in the shape ``HotSwapServer.publish`` reads:
    the centers and projector padded to a power-of-two capacity with zero
    projector rows, as a streaming state holds them."""

    def __init__(self, centers, weights, projector, kernel):
        m, cap = centers.shape[0], max(128, pow2(centers.shape[0]))
        self.centers = np.zeros((cap, centers.shape[1]), np.float32)
        self.centers[:m] = centers
        self.weights = np.zeros((cap,), np.float64)
        self.weights[:m] = weights
        self.projector = np.zeros((cap, projector.shape[1]), np.float32)
        self.projector[:m] = projector
        self.kernel = kernel


class Serve(Load):
    """The operator is the deployment's reduced-set KPCA of n rows of the
    mixture, made by the plain reference from the seed (it is the served
    model's weights, and its seconds are not the program's set-up); the
    program serves it through ``HotSwapServer`` behind
    ``BatchingFrontEnd``."""

    def setup(self):
        from repro import streaming
        from repro.serving import BatchingFrontEnd

        t0 = clock()
        x = data.rows(self.ds, self.n, self.seed, 0)
        self.op = reference.fit(x, self.eps, self.sigma, self.rank)
        del x
        self.reference_s = clock() - t0
        self.m = self.op["centers"].shape[0]
        self.pool = np.asarray(data.rows(self.ds, int(self.mix["pool_rows"]),
                                         self.seed, 1))
        self.server = streaming.HotSwapServer(
            chunk=int(self.cfg["serving"]["chunk"]))
        self.server.publish(Operator(self.op["centers"], self.op["weights"],
                                      self.op["projector"], self.kernel()))
        self.fe = BatchingFrontEnd(self.server,
                                   max_batch=int(self.mix["max_batch"]),
                                   slo_ms=float(self.mix["slo_ms"]))
        for b in self.buckets():
            for _ in range(2):
                self.fe.submit(self.pool[:b]).result(timeout=900)
        log(f"sizes n={self.n} d={self.d} m={self.m} rank={self.rank} "
            f"cap={self.server._snapshot[0].shape[0]} sigma={self.sigma:.6g} "
            f"eps={self.eps:.6g} warm buckets={self.buckets()} operator "
            f"made by the reference in {self.reference_s:.3f} s")

    def _request(self, k: int):
        o, r = int(self.offset[k]), int(self.rows[k])
        return self.pool[o: o + r]

    def serve_counts(self):
        s1 = self.fe.snapshot()
        return {"rows": s1.rows - self.snap0.rows,
                "batches": s1.batches - self.snap0.batches,
                "requests": s1.requests - self.snap0.requests}

    def close(self):
        self.fe.close()
        self.fe = self.server = None

    def _sample(self, count: int) -> list:
        """The requests the check compares: a sample of the ``count`` sent,
        drawn from the seed, with the longest request in it."""
        pick = [int(i) for i in traffic.sample(
            self.seed, count, int(self.mix["check_requests"]))]
        if count:
            pick.append(int(np.argmax(self.rows[:count])))
        return sorted(set(pick))

    def check(self, served=None) -> dict:
        """The sampled requests' served rows against the reference
        transform of the same operator.  ``served`` maps the sampled rows
        to what stands in the program's place (the control).  A sampled
        request with no answer counts in ``failed``."""
        pick = sorted(self.results)
        if not pick:
            return {"failed": float(self.failed), "served_rel_err": None}
        x = np.concatenate([self._request(k) for k in pick])
        z = served(x) if served else \
            np.concatenate([np.asarray(self.results[k]) for k in pick])
        z_ref = reference.transform_exact(x, self.op["centers"],
                                          self.op["projector"], self.sigma)
        log(f"check: {len(pick)} requests, {x.shape[0]} rows against the "
            f"reference transform in float64")
        return {"failed": float(self.failed),
                "served_rel_err": reference.rel_err(z, z_ref)}

    def control(self) -> dict:
        """The check with the reference at Precision.HIGH in the program's
        place."""
        return self.check(lambda x: reference.transform(
            x, self.op["centers"], self.op["projector"], self.sigma,
            precision="high"))

    def layer_ctx(self) -> dict:
        return {"serve": self.counts,
                "op": {"m": self.m, "d": self.d, "r": self.rank}}


# --------------------------------------------------------------------------
# fit jobs
# --------------------------------------------------------------------------


class Fit(Load):
    """Back-to-back fit jobs over the same rows: jobs start until the
    window's seconds have passed, and the one in flight finishes."""

    def window(self, seconds: float):
        self.jobs = []
        t_open = clock()
        while clock() - t_open < seconds:
            self.jobs.append(self.job())
        t_end = clock()
        self.elapsed = t_end - t_open
        self.attempted = len(self.jobs)
        self.rows_per_s = self.n * len(self.jobs) / self.elapsed
        log(f"fit jobs: {len(self.jobs)} in {self.elapsed:.3f} s, "
            f"m={[j['m'] for j in self.jobs]}, select_s="
            f"{[round(j['select_s'], 3) for j in self.jobs]}, fit_s="
            f"{[round(j['fit_s'], 3) for j in self.jobs]}, "
            f"rows/s={self.rows_per_s:.3f}")
        return t_open, t_end

    def e2e(self) -> dict:
        return {"fit_rows_per_s": self.rows_per_s}

    def layer_ctx(self) -> dict:
        return {"jobs": [{k: v for k, v in j.items() if k != "out"}
                         for j in self.jobs],
                "fit_elapsed_s": self.elapsed, "n": self.n, "d": self.d}

    def close(self):
        pass

    def _fit(self, rsde, method: str):
        from repro.core import fit_centers
        return fit_centers(rsde.centers, rsde.weights, rsde.n,
                           self.kernel(), self.rank, method=method)

    def check_job(self, out: dict, radius: float, assign) -> dict:
        """The numbers of one job's answer against the reference: exact
        mass, the cover and the separation the configuration states, the
        eigenvalues against ``eigh`` of the same reduced set, and held-out
        rows through the fitted model against the reference's transform,
        with the model's own operator (``transform_err``) and with the
        exact eigenvectors (``emb_err``)."""
        x = self.rows_for_check()
        excess, outside = reference.cover(x, out["centers"], radius, assign)
        deficit, close = reference.separation(out["centers"], self.eps)
        lam, proj = reference.eig(out["centers"], out["weights"], self.n,
                                  self.sigma, self.rank)
        xq = np.asarray(data.rows(self.ds, int(self.mix["check_rows"]),
                                  self.seed, 2))
        z = out["transform"](xq)
        return {
            "mass_err": reference.mass_err(out["weights"], self.n),
            "count_err": reference.count_err(out["weights"], assign),
            "cover_excess": excess, "cover_outside": outside,
            "sep_deficit": deficit, "sep_close": close,
            "eig_err": reference.eig_err(out["eigvals"], lam),
            "transform_err": reference.rel_err(z, reference.transform_exact(
                xq, out["centers"], out["projector"], self.sigma)),
            "emb_err": reference.emb_err(z, reference.transform(
                xq, out["centers"], proj, self.sigma)),
        }

    def control(self) -> dict:
        """The check with the reference's own fit at Precision.HIGH, over
        the same rows, in the program's place."""
        x = self.rows_for_check()
        ref = reference.fit(x, self.eps, self.sigma, self.rank,
                            precision="high")

        def transform(xq):
            return reference.transform(xq, ref["centers"], ref["projector"],
                                       self.sigma, precision="high")

        ref["transform"] = transform
        vals = {"failed": 0.0}
        vals.update(self.check_job(ref, self.radius(), ref["assign"]))
        return vals

    def check(self) -> dict:
        vals = {"failed": float(self.failed)}
        if not self.jobs:
            return vals
        mass = max(reference.mass_err(j["out"]["weights"], self.n)
                   for j in self.jobs)
        k = int(traffic.sample(self.seed, len(self.jobs), 1)[0])
        job = self.jobs[k]["out"]
        vals.update(self.check_job(job, self.radius(), job.get("assign")))
        vals["mass_err"] = max(mass, vals["mass_err"])
        log(f"check: job {k} of {len(self.jobs)} against the reference "
            f"(m={job['centers'].shape[0]}), mass of every job")
        return vals


def answer(rsde, model, assign=None) -> dict:
    return {"centers": np.asarray(rsde.centers, np.float32),
            "weights": np.asarray(rsde.weights, np.float64),
            "assign": assign, "eigvals": np.asarray(model.eigvals),
            "projector": np.asarray(model.projector),
            "transform": model.transform}
