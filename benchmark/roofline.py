"""The least work the fused lane can do for a layer, in bytes moved.

Counted from the TAR's size alone — never from the program's shapes, kernel
names or chunk counts — so it is the same work whatever implements the lane:
every byte has to be read from HBM once to be judged for a cut (gear hash)
and once more to be digested, because a chunk's digest cannot start before
its cut is known. Bytes-bound only: sha256 and the gear hash are 32-bit
integer work on the vector unit, for which no peak of a v5e is published, so
no operations bound is claimed and the share says how far the lane is from
the memory roof, not from the chip's best.
"""

PASSES_OVER_THE_BYTES = 2  # once to cut, once to digest


def lane_bytes(tar_bytes: int) -> int:
    return PASSES_OVER_THE_BYTES * tar_bytes


def least_seconds(tar_bytes: int, hbm_bytes_per_s: float) -> float:
    return lane_bytes(tar_bytes) / hbm_bytes_per_s
