"""Seeded inputs: rows of a configuration's Gaussian mixture, made on the
device.

The mixture family is the one the program's synthetic datasets use (the
paper's Table 1 geometry: ``classes * clusters_per_class`` cluster means
uniform in the unit box, each cluster's scale ``cluster_std`` times a
lognormal(0, ``std_jitter``) draw).  The geometry and the kernel bandwidth
belong to the deployment and come from the configuration's
``geometry_seed``; ``--seed`` draws the rows, so every seed gives the same
density and about the same reduced set, and only the sample changes.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

#: Rows of the median-distance bandwidth sample.
BANDWIDTH_SAMPLE = 2048


def geometry(ds: dict) -> tuple[np.ndarray, np.ndarray]:
    """Cluster means (k, d) and scales (k,) of the mixture, from the
    configuration's ``dataset`` block."""
    rng = np.random.default_rng(np.random.SeedSequence([ds["geometry_seed"]]))
    k = ds["classes"] * ds["clusters_per_class"]
    means = rng.uniform(0.0, 1.0, size=(k, ds["d"]))
    stds = ds["cluster_std"] * rng.lognormal(0.0, ds["std_jitter"], size=k)
    return means.astype(np.float32), stds.astype(np.float32)


def host_rows(ds: dict, n: int, seed: int) -> np.ndarray:
    """``n`` rows drawn on the host (float64 maths, float32 out)."""
    means, stds = geometry(ds)
    rng = np.random.default_rng(np.random.SeedSequence([ds["geometry_seed"],
                                                        seed, 1]))
    c = rng.integers(0, means.shape[0], size=n)
    x = means[c] + rng.normal(size=(n, ds["d"])) * stds[c][:, None]
    return x.astype(np.float32)


def bandwidth(ds: dict) -> float:
    """Median pairwise distance of a fixed sample (the median heuristic),
    fixed by the geometry: the deployment's kernel does not move with the
    run's seed."""
    x = host_rows(ds, BANDWIDTH_SAMPLE, seed=0).astype(np.float64)
    sq = np.sum(x * x, axis=1)
    d2 = np.maximum(sq[:, None] + sq[None, :] - 2.0 * x @ x.T, 0.0)
    iu = np.triu_indices(x.shape[0], k=1)
    return float(np.sqrt(np.median(d2[iu])))


def key(seed: int, stream: int) -> jax.Array:
    """A PRNG key for one input stream of a run; any integer seed (past
    32 bits too) maps to a distinct key."""
    seed = int(seed)
    k = jax.random.PRNGKey(stream)
    k = jax.random.fold_in(k, seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


@partial(jax.jit, static_argnames=("n",))
def _rows(k, means, stds, n: int):
    kc, kx = jax.random.split(k)
    c = jax.random.randint(kc, (n,), 0, means.shape[0])
    z = jax.random.normal(kx, (n, means.shape[1]), jnp.float32)
    return means[c] + z * stds[c][:, None]


def rows(ds: dict, n: int, seed: int, stream: int) -> jax.Array:
    """``n`` rows of the mixture on the default device, in one jitted call."""
    means, stds = geometry(ds)
    return _rows(key(seed, stream), jnp.asarray(means), jnp.asarray(stds),
                 n=int(n))
