"""The all-gather collectives of a device trace: the ops by which each
chip of a mesh gathers the others' data (on a mesh the ingest merge
gathers the chips' candidate buckets).  It has no roofline: only its
device time is read."""

#: Op names of an all-gather, synchronous or split into start and done.
OPS = (r"all-gather",)
