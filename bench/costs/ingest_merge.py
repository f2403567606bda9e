"""Operations and bytes of the out-of-core ingest's streaming merge (the
level-2 fold of each chunk's candidate centers), and the names under
which it appears in a device trace.

The count is the merge's live work, read from the program's per-stream
counters: the absorb pass compares each live candidate with each live
merged center (``absorb_pairs``, summed over the folds), and the weighted
reselection compares each survivor with each new keeper
(``reselect_pairs``); 2*d operations a pair, the cross term of a squared
distance.  Bytes: each live candidate's row and mass read once.  Padding,
dead rows and the capacity beyond m are not counted, so the share of
the roofline cannot pass 100%.
"""

#: XLA modules whose device time is the merge's: the one-device and the
#: mesh merge programs (``core/shadow.py``, ``core/distributed.py``); on a
#: mesh this includes the all-gather of the candidate buckets.
MODULES = (r"^jit__merge_chunk",)


def flops(absorb_pairs: float, reselect_pairs: float, d: int) -> float:
    return 2.0 * d * (float(absorb_pairs) + float(reselect_pairs))


def bytes_moved(gathered: float, d: int) -> float:
    return 4.0 * (d + 1) * float(gathered)


def least_time(absorb_pairs: float, reselect_pairs: float, gathered: float,
               d: int, peaks: dict) -> float:
    """Seconds the chip needs at least: the larger of operations over the
    bfloat16 peak and bytes over HBM bandwidth."""
    return max(flops(absorb_pairs, reselect_pairs, d)
               / peaks["bf16_flops_per_s"],
               bytes_moved(gathered, d) / peaks["hbm_bytes_per_s"])
