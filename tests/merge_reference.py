"""The plain host reference of the streaming merge (DESIGN.md §9): the
level-2 merge as the out-of-core ingest ran it before it moved onto the
device, one candidate batch at a time in numpy with float64 masses.  The
tests hold the on-device merge (``shadow.merge_chunk_local``) to it, batch
for batch."""
from __future__ import annotations

import numpy as np

from repro.core.shadow import shadow_select_blocked
from repro.kernels import ops as kernel_ops


class StreamingMerge:
    """Weight-exact streaming extension of ``two_level_merge`` (DESIGN.md
    §9): reconcile candidate-center batches ONE BATCH AT A TIME instead of
    requiring every level-1 center in memory at once.

    Per ``update(cand_c, cand_w)``:

    1. **Absorb** — one assignment pass of the candidates against the
       current merged set; any candidate strictly within eps of a merged
       center hands its mass to that center.  Duplicate centers across
       chunk/shard boundaries land here (d2 == 0 < eps^2), so they merge
       instead of accumulating.
    2. **Select** — survivors (all >= eps from every merged center) run
       WEIGHTED blocked selection among themselves, restoring pairwise
       eps-separation; the kept centers append to the merged set.
    3. **Spill** — if appending would exceed ``budget`` centers, the
       over-budget keepers are instead absorbed into their nearest
       retained center (merged set + kept prefix) regardless of distance;
       ``spilled``/``max_spill_dist`` record how much cover quality the
       budget cost.

    Mass bookkeeping is float64 on host, so for integer point masses the
    invariant ``weights.sum() == total ingested mass`` holds EXACTLY up to
    2^53 (the one-shot device merge is only exact to f32's 2^24).  Cover
    radius of the merged set is 2*eps (triangle inequality), exactly like
    ``two_level_merge`` — the §5 bounds hold with ell -> ell/2.
    """

    def __init__(self, d: int, eps: float, budget: int | None = None,
                 block: int | None = 256):
        self.d = int(d)
        self.eps = float(eps)
        self.budget = None if budget is None else int(budget)
        self.block = 256 if block is None else int(block)
        self._c = np.zeros((0, self.d), np.float32)
        self._w = np.zeros((0,), np.float64)
        self.spilled = 0
        self.max_spill_dist = 0.0

    @property
    def m(self) -> int:
        return self._c.shape[0]

    @property
    def centers(self) -> np.ndarray:
        return self._c

    @property
    def weights(self) -> np.ndarray:
        return self._w

    def _absorb_into(self, target_c, target_w, cand_c, cand_w, spill: bool):
        """Assign candidates to nearest target center; within-eps (or ALL,
        when ``spill``) hand over their mass.  Returns the survivor mask."""
        idx, d2 = kernel_ops.shadow_assign(cand_c, target_c, tag="ingest")
        idx, d2 = np.asarray(idx), np.asarray(d2)
        hit = np.ones_like(idx, dtype=bool) if spill \
            else d2 < np.float32(self.eps) ** 2
        np.add.at(target_w, idx[hit], cand_w[hit])
        if spill and hit.any():
            self.spilled += int(hit.sum())
            self.max_spill_dist = max(self.max_spill_dist,
                                      float(np.sqrt(d2[hit].max())))
        return ~hit

    def update(self, cand_c, cand_w) -> None:
        """Fold one batch of candidate centers (zero-weight rows are
        padding and ignored) into the merged set."""
        cand_c = np.asarray(cand_c, np.float32)
        cand_w = np.asarray(cand_w, np.float64)
        live = cand_w > 0
        cand_c, cand_w = cand_c[live], cand_w[live]
        if cand_c.shape[0] == 0:          # empty shard / all-padding batch
            return
        if self.m:
            keep = self._absorb_into(self._c, self._w, cand_c, cand_w,
                                     spill=False)
            cand_c, cand_w = cand_c[keep], cand_w[keep]
            if cand_c.shape[0] == 0:
                return
        c_new, w_new, _, m_new = shadow_select_blocked(
            cand_c, self.eps, block=self.block, weights=cand_w)
        room = m_new if self.budget is None else max(0, self.budget - self.m)
        kept = min(m_new, room)
        kept_c = np.asarray(c_new[:kept], np.float32)
        kept_w = np.asarray(w_new[:kept], np.float64)
        if kept < m_new:                  # center-budget spill
            target_c = np.concatenate([self._c, kept_c]) if self.m else kept_c
            target_w = np.concatenate([self._w, kept_w]) if self.m else kept_w
            if target_c.shape[0] == 0:
                raise ValueError("center budget is 0: nowhere to spill")
            self._absorb_into(target_c, target_w, c_new[kept:], w_new[kept:],
                              spill=True)
            self._c, self._w = target_c, target_w
        else:
            self._c = np.concatenate([self._c, kept_c]) if self.m else kept_c
            self._w = np.concatenate([self._w, kept_w]) if self.m else kept_w
