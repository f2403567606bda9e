"""Windowed MMD drift detection + the partial refit it triggers.

The substitute density p-tilde = (1/n) sum_j w_j k(c_j, .) was fitted to
yesterday's stream; when the stream drifts, the windowed MMD between the
last W raw samples (uniform mass) and p-tilde grows past what center-level
quantization alone can explain — Theorem 5.1 bounds the latter by
``kernel.mmd_bound(ell)``, so that bound (times a slack factor) is the
natural trigger threshold, exactly the spectral/projection-error acceptance
signal the Francis & Raimond comparisons motivate.

The refresh is a PARTIAL refit in the paper's reduced-set sense: it needs
only the live centers (with their masses, optionally decayed) and the raw
window — never the historical stream — because the RSDE weight structure
carries all surviving mass.  Window points are shadow-selected at the same
eps and merged with the decayed centers by ``two_level_merge``, the
float-mass shadow pass over weighted centers (cover radius 2*eps, i.e. the
§5 bounds with ell -> ell/2, as in every two-level selection), and the
eigensystem is re-solved exactly.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kernels_math import Kernel, gram_matrix
from repro.core.rsde import RSDE
from repro.core import shadow as shadow_mod
from repro.streaming.state import StreamingRSKPCA, from_rsde

Array = jax.Array


@partial(jax.jit, static_argnames=("kernel",))
def stream_mmd(kernel: Kernel, window: Array, centers: Array,
               weights: Array, n: Array) -> Array:
    """MMD between the uniform window distribution (1/W each) and the
    substitute density (w_j / n); dead slots carry w = 0 and drop out.
    Jittable, backend-dispatched through ``gram_matrix``."""
    xw = jnp.asarray(window, jnp.float32)
    wgt = jnp.asarray(weights, jnp.float32)
    wn = xw.shape[0]
    kxx = gram_matrix(kernel, xw, xw).sum() / (wn * wn)
    kcc = (wgt[:, None] * gram_matrix(kernel, centers, centers)
           * wgt[None, :]).sum() / (n * n)
    kxc = (gram_matrix(kernel, xw, centers) * wgt[None, :]).sum() / (wn * n)
    return jnp.sqrt(jnp.maximum(kxx + kcc - 2.0 * kxc, 0.0))


class DriftDetector:
    """Ring buffer over the last ``window`` raw samples + the MMD trigger.

    ``factor`` scales the Theorem 5.1 quantization bound: MMD below
    ``factor * kernel.mmd_bound(ell)`` is indistinguishable from the
    quantization the operator was BUILT with, so only excursions above it
    count as drift.  The detector never holds device state — ``push`` is
    pure numpy, the MMD evaluation is one jitted call.
    """

    def __init__(self, kernel: Kernel, ell: float, window: int = 512,
                 factor: float = 1.0):
        self.kernel = kernel
        self.ell = float(ell)
        self.factor = float(factor)
        self.size = int(window)
        self._buf: np.ndarray | None = None
        self._pos = 0
        self._count = 0

    def push(self, xb) -> None:
        xb = np.asarray(xb, np.float32)
        if self._buf is None:
            self._buf = np.zeros((self.size, xb.shape[1]), np.float32)
        for row in xb:  # ring write; windows are small, this is not hot
            self._buf[self._pos] = row
            self._pos = (self._pos + 1) % self.size
            self._count += 1

    @property
    def full(self) -> bool:
        return self._count >= self.size

    def window(self) -> np.ndarray:
        assert self._buf is not None, "push() before window()"
        return self._buf[: min(self._count, self.size)].copy()

    @property
    def threshold(self) -> float:
        return self.factor * self.kernel.mmd_bound(self.ell)

    def mmd(self, state: StreamingRSKPCA) -> float:
        return float(stream_mmd(self.kernel, jnp.asarray(self.window()),
                                state.centers, state.weights, state.n))

    def should_refresh(self, state: StreamingRSKPCA) -> bool:
        """Trigger only on a FULL window (early small windows are noisy)."""
        return self.full and self.mmd(state) > self.threshold


def refresh(state: StreamingRSKPCA, window, decay: float = 1.0
            ) -> StreamingRSKPCA:
    """Drift-triggered partial refit from (decayed centers + raw window).

    ``decay`` < 1 forgets the pre-drift density geometrically (decay=1
    keeps all surviving mass).  The buffer capacity is preserved when the
    merged center set still fits, so a HotSwapServer republish after a
    refresh stays recompile-free.
    """
    window = np.asarray(window, np.float32)
    cw, ww, _, _ = shadow_mod.shadow_select_blocked(window, state.eps)
    live = np.asarray(state.weights) > 0
    all_c = np.concatenate([np.asarray(state.centers)[live], cw])
    all_w = np.concatenate(
        [decay * np.asarray(state.weights)[live], ww.astype(np.float32)])
    out_c, out_w, m = shadow_mod.two_level_merge(
        jnp.asarray(all_c), jnp.asarray(all_w, jnp.float32),
        jnp.float32(state.eps), max_centers=all_c.shape[0])
    m = int(m)
    n_new = float(np.asarray(out_w[:m]).sum())
    rsde = RSDE(np.asarray(out_c[:m]), np.asarray(out_w[:m], np.float64),
                n=n_new, scheme="streaming-refresh")
    cap = state.cap if m <= state.cap else None  # keep the serving bucket
    return from_rsde(rsde, state.kernel, state.rank, eps=state.eps,
                     cap=cap, budget=state.budget)
