"""The plain reference of out-of-core two-level selection, written from the
deployment's description in jnp and numpy, sharing no code with the
program.

* Level 1: each chip's share of each chunk (the chunk's rows split evenly
  over the chips, in order) runs blocked Algorithm 2, ``reference.select``.
* Level 2, one chunk's candidates at a time (the chips' in chip order): a
  candidate strictly within eps of its nearest merged center hands its mass
  to it; the survivors run weighted blocked selection among themselves;
  the keepers append to the merged set while the center budget allows, and
  the rest hand their mass to the nearest retained center.  Masses are
  float64 on the host.

Every distance product takes ``precision``, like ``reference``: "highest"
is the reference, "high" the control that the limits are set against.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference


@partial(jax.jit, static_argnames=("block", "precision"))
def _select_weighted(x, mass, eps2, block: int, precision: str):
    """Blocked Algorithm 2 over the rows with positive ``mass``, a
    keeper's weight being the masses it absorbs.  Returns (keeper row
    indices padded with n, their masses, m)."""
    n = x.shape[0]

    def body(state):
        alive, idx, weights, m = state
        (cand,) = jnp.nonzero(alive, size=block, fill_value=n)
        cand_ok = cand < n
        cx = x[jnp.minimum(cand, n - 1)]
        d2c = jnp.sum((cx[:, None, :] - cx[None, :, :]) ** 2, axis=-1)

        def pick(j, keep):
            sep = jnp.all(jnp.where(keep, d2c[:, j] >= eps2, True))
            return keep.at[j].set(cand_ok[j] & sep)

        keep = jax.lax.fori_loop(0, block, pick, jnp.zeros((block,), bool))
        d2 = jnp.where(keep[None, :], reference.sq_dists(x, cx, precision),
                       jnp.inf)
        d2 = d2.at[jnp.where(cand_ok, cand, n)].set(
            jnp.where(keep[None, :], d2c, jnp.inf), mode="drop")
        near = jnp.argmin(d2, axis=1)
        absorbed = alive & (jnp.min(d2, axis=1) < eps2)
        rank = jnp.cumsum(keep) - 1
        got = jnp.zeros((block,), jnp.int32).at[near].add(
            jnp.where(absorbed, mass, 0))
        slot = jnp.where(keep, m + rank, n)
        idx = idx.at[slot].set(cand, mode="drop")
        weights = weights.at[slot].set(got, mode="drop")
        return (alive & ~absorbed, idx, weights,
                m + jnp.sum(keep, dtype=jnp.int32))

    state = (mass > 0, jnp.full((n,), n, jnp.int32),
             jnp.zeros((n,), jnp.int32), jnp.int32(0))
    _, idx, weights, m = jax.lax.while_loop(
        lambda s: jnp.any(s[0]), body, state)
    return idx, weights, m


@partial(jax.jit, static_argnames=("precision",))
def _nearest(x, c, precision: str):
    d2 = reference.sq_dists(x, c, precision)
    return jnp.argmin(d2, axis=1), jnp.min(d2, axis=1)


def _near(x, c, precision: str):
    """Each row's nearest center of ``c`` and the squared distance (both
    padded to buckets, so the programs compile once per bucket)."""
    k = x.shape[0]
    b = reference._bucket(k)
    xs = jnp.asarray(reference._pad_rows(np.asarray(x, np.float32), b))
    cs = jnp.asarray(reference._pad_far(c, reference._bucket(len(c))))
    i, d2 = _nearest(xs, cs, precision)
    return np.asarray(i)[:k], np.asarray(d2)[:k]


class Merge:
    """The level-2 merge: merged centers (float32) and masses (float64)."""

    def __init__(self, d: int, eps: float, block: int, budget: int | None,
                 precision: str):
        self.c = np.zeros((0, d), np.float32)
        self.w = np.zeros((0,), np.float64)
        self.eps, self.block, self.budget = eps, block, budget
        self.precision = precision
        self.spilled = 0

    def fold(self, cand: np.ndarray, mass: np.ndarray) -> None:
        """Fold one chunk's candidates (k, d) with integer masses (k,)."""
        eps2 = np.float32(self.eps) ** 2
        if len(self.c) and len(cand):
            i, d2 = _near(cand, self.c, self.precision)
            hit = d2 < eps2
            np.add.at(self.w, i[hit], mass[hit])
            cand, mass = cand[~hit], mass[~hit]
        if not len(cand):
            return
        b = reference._bucket(len(cand))
        xs = reference._pad_far(cand, b)
        ms = reference._pad_rows(np.asarray(mass, np.int32), b)
        idx, got, m = _select_weighted(jnp.asarray(xs), jnp.asarray(ms),
                                       jnp.float32(eps2), self.block,
                                       self.precision)
        m = int(m)
        idx, got = np.asarray(idx)[:m], np.asarray(got)[:m]
        room = m if self.budget is None else max(0, self.budget - len(self.c))
        kept = min(m, room)
        self.c = np.concatenate([self.c, xs[idx[:kept]]])
        self.w = np.concatenate([self.w, got[:kept].astype(np.float64)])
        if kept < m:
            i, _ = _near(xs[idx[kept:]], self.c, self.precision)
            np.add.at(self.w, i, got[kept:].astype(np.float64))
            self.spilled += m - kept


def select(x, eps: float, chunk: int, chips: int, block: int = 256,
           budget: int | None = None, precision: str = "highest"):
    """Two-level selection over the rows ``x`` (host (n, d)), streamed in
    chunks of ``chunk`` rows split over ``chips``.  Returns (centers (m, d)
    float32, weights (m,) float64)."""
    x = np.asarray(x, np.float32)
    n, d = x.shape
    per = chunk // chips
    b = max(1, min(block, per))
    merge = Merge(d, eps, b, budget, precision)
    for lo in range(0, n, chunk):
        cand = [np.zeros((0, d), np.float32)]
        mass = [np.zeros((0,), np.int64)]
        for j in range(chips):
            rows = x[lo + j * per: min(lo + (j + 1) * per, n)]
            if not len(rows):
                continue
            if len(rows) < per:  # one program for every share
                idx, w, _ = reference.select(
                    reference._pad_far(rows, per), eps, b, precision)
                keep = idx < len(rows)
                idx, w = idx[keep], w[keep]
            else:
                idx, w, _ = reference.select(rows, eps, b, precision)
            cand.append(rows[idx])
            mass.append(np.asarray(w, np.int64))
        merge.fold(np.concatenate(cand), np.concatenate(mass))
    return merge.c, merge.w
