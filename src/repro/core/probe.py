"""RSKPCA activation probe — the paper's technique as a first-class training
feature (DESIGN.md §4).

During LM training, pooled hidden states are reservoir-sampled into a host
buffer.  Every ``period`` steps the probe runs distributed ShDE + RSKPCA on
the buffer and reports:

  * the top-k kernel spectrum of the representation (effective dimensionality
    of the feature manifold — collapse shows up as spectral concentration);
  * retention m/n (how redundant the representation is at bandwidth sigma);
  * eigen-embedding drift vs the previous probe (aligned Frobenius distance —
    how fast the representation is rotating).

Cost per probe is O(mn/devices + m^3) instead of O(n^2) — this is exactly the
paper's speedup applied to a production monitoring loop.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.kernels_math import Kernel, gaussian
from repro.core.rskpca import fit_rskpca, embedding_alignment_error
from repro.core.rsde import shadow_rsde
from repro.data.kpca_datasets import median_sigma


@dataclasses.dataclass
class ProbeReport:
    step: int
    spectrum: np.ndarray       # top-k eigenvalues of the reduced operator
    retention: float           # m / n
    m: int
    drift: float | None        # aligned embedding drift vs previous probe
    sigma: float

    def summary(self) -> str:
        top = ", ".join(f"{v:.4f}" for v in self.spectrum[:5])
        drift = f"{self.drift:.4f}" if self.drift is not None else "n/a"
        return (f"[probe step {self.step}] m={self.m} "
                f"retention={self.retention:.3f} drift={drift} "
                f"spectrum=[{top}...]")


class ReservoirBuffer:
    """Classic reservoir sampling of activation rows (host-side, O(cap) mem)."""

    def __init__(self, capacity: int, dim: int, seed: int = 0):
        self.capacity = capacity
        self.buf = np.zeros((capacity, dim), np.float32)
        self.seen = 0
        self.rng = np.random.default_rng(seed)

    def add(self, rows: np.ndarray) -> None:
        rows = np.asarray(rows, np.float32).reshape(-1, self.buf.shape[1])
        for r in rows:
            if self.seen < self.capacity:
                self.buf[self.seen] = r
            else:
                j = self.rng.integers(0, self.seen + 1)
                if j < self.capacity:
                    self.buf[j] = r
            self.seen += 1

    @property
    def data(self) -> np.ndarray:
        return self.buf[: min(self.seen, self.capacity)]


class RSKPCAProbe:
    """Attachable representation monitor for the training loop."""

    def __init__(self, dim: int, capacity: int = 2048, rank: int = 8,
                 ell: float = 4.0, period: int = 50, seed: int = 0,
                 mesh=None):
        self.buffer = ReservoirBuffer(capacity, dim, seed)
        self.rank = rank
        self.ell = ell
        self.period = period
        self.mesh = mesh
        self._prev_embedding: np.ndarray | None = None
        self._anchor: np.ndarray | None = None  # fixed query set for drift
        self.reports: list[ProbeReport] = []

    def observe(self, hidden: np.ndarray) -> None:
        """Feed pooled hidden states, shape (batch, dim)."""
        self.buffer.add(hidden)

    def maybe_probe(self, step: int) -> ProbeReport | None:
        if step % self.period or self.buffer.seen < 64:
            return None
        return self.probe(step)

    def probe(self, step: int) -> ProbeReport:
        x = self.buffer.data
        sigma = max(median_sigma(x), 1e-6)
        kernel = gaussian(sigma)
        rsde = shadow_rsde(x, kernel, self.ell, mesh=self.mesh)
        rank = min(self.rank, rsde.m)
        model = fit_rskpca(rsde, kernel, rank=rank)
        if self._anchor is None:
            self._anchor = x[: min(256, x.shape[0])].copy()
        emb = model.transform(self._anchor)
        drift = None
        if self._prev_embedding is not None:
            k = min(emb.shape[1], self._prev_embedding.shape[1])
            denom = np.linalg.norm(self._prev_embedding[:, :k]) + 1e-12
            drift = embedding_alignment_error(
                self._prev_embedding[:, :k], emb[:, :k]
            ) / denom
        self._prev_embedding = emb
        report = ProbeReport(
            step=step, spectrum=model.eigvals, retention=rsde.retention,
            m=rsde.m, drift=drift, sigma=sigma,
        )
        self.reports.append(report)
        return report
