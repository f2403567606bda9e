"""Operations and bytes of the served transform z = k(x, C) A, and the
names under which it appears in a device trace.

The count is the least work of the call, the same for any plan: each
served row is compared with every center (2*m*d for the distance cross
term) and projected (2*m*r); each row's input and output are read and
written once, and each dispatched batch reads the operator (centers and
projector) once.  It counts the rows users sent, not rows padded to a
bucket, and the live centers m, not the padded capacity.
"""

#: XLA modules whose device time is the transform's: the Pallas plan's
#: jitted call and the dense plan's (``kernels/ops.py``).
MODULES = (r"^jit__project_call", r"^jit__project_dense")


def flops(rows: int, m: int, d: int, r: int) -> float:
    return float(rows) * (2.0 * m * d + 2.0 * m * r)


def bytes_moved(rows: int, batches: int, m: int, d: int, r: int) -> float:
    return 4.0 * rows * (d + r) + 4.0 * batches * m * (d + r)


def least_time(rows: int, batches: int, m: int, d: int, r: int,
               peaks: dict) -> float:
    """Seconds the chip needs at least: the larger of operations over the
    bfloat16 peak and bytes over HBM bandwidth."""
    return max(flops(rows, m, d, r) / peaks["bf16_flops_per_s"],
               bytes_moved(rows, batches, m, d, r) / peaks["hbm_bytes_per_s"])
