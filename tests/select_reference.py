"""The host cascade of blocked selection (DESIGN.md §3): the halving
phases as ``shadow_select_blocked`` ran them before the working set moved
onto the device.  Each phase pads its rows on the host, sends them to the
device, runs the same device rounds (``shadow._blocked_select_device``)
and compacts the survivors on the host.  The tests hold the device
cascade to it, bit for bit."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.shadow import _blocked_select_device, _pow2_ceil


def _pad_pow2(x: np.ndarray, orig: np.ndarray, w: np.ndarray | None):
    """Pad a working set with dead zero rows to a power of two, so the
    number of distinct jit shapes stays logarithmic in n.  Returns
    (x, padded-row -> original-row map, alive mask, masses or None)."""
    k = x.shape[0]
    npad = _pow2_ceil(k)
    xp = np.zeros((npad, x.shape[1]), np.float32)
    xp[:k] = x
    op = np.zeros((npad,), np.int64)
    op[:k] = orig
    alive = np.zeros((npad,), bool)
    alive[:k] = True
    if w is not None:
        wp = np.zeros((npad,), np.float32)
        wp[:k] = w
        w = wp
    return xp, op, alive, w


def shadow_select_blocked(x, eps: float, block: int | None = None,
                          weights=None):
    """Blocked Algorithm 2 with the host compacting each phase's
    survivors.  Returns (centers (m, d), weights (m,), assign (n,), m)."""
    x_np = np.asarray(x, np.float32)
    n = x_np.shape[0]
    block = 256 if block is None else block
    eps2 = jnp.asarray(eps, jnp.float32) ** 2
    assign = np.full((n,), -1, np.int64)
    centers_out, weights_out = [], []
    m = 0
    cur_x, cur_orig = x_np, np.arange(n)
    cur_w = None if weights is None else np.asarray(weights, np.float32)
    while cur_x.shape[0]:
        k = cur_x.shape[0]
        xp, orig, alive0, wp = _pad_pow2(cur_x, cur_orig, cur_w)
        b = max(1, min(block, xp.shape[0]))
        xd, alive_d, wd = (jnp.asarray(xp), jnp.asarray(alive0),
                           None if wp is None else jnp.asarray(wp))
        alive, c, w, a, mr = _blocked_select_device(
            xd, eps2, b, alive_d, jnp.asarray(k // 2, jnp.int32), wd)
        mm = int(np.asarray(mr)[0])
        a = np.asarray(a)
        absorbed = a >= 0
        assign[orig[absorbed]] = m + a[absorbed]
        centers_out.append(np.asarray(c[:mm]))
        weights_out.append(np.asarray(w[:mm]))
        m += mm
        still = np.flatnonzero(np.asarray(alive))
        cur_x, cur_orig = xp[still], orig[still]
        cur_w = None if wp is None else wp[still]
    return (np.concatenate(centers_out),
            np.concatenate(weights_out).astype(np.float64),
            assign, m)
