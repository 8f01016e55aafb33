"""Operations and bytes that the measured work needs, from shapes alone.

These are the yardstick of the roofline and utilization metrics: they
count what the algorithm must do, not what an implementation happens to
do, so a faster implementation of the same work moves the share up and a
wasteful one cannot hide behind its own counts.
"""

from __future__ import annotations

F32 = 4


def omp_scoring_bytes(rounds: int, valid_rows: int, d: int) -> float:
    """Least bytes OMP scoring must read: every candidate row of the
    problem, f32, once per round."""
    return float(rounds) * valid_rows * d * F32


def omp_scoring_flops(rounds: int, valid_rows: int, d: int) -> float:
    return 2.0 * rounds * valid_rows * d


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline: the larger of the compute and the memory bound."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
