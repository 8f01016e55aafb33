"""Pool scoring's share of its roofline in the library loop: the least
time to read every valid row of each class problem once per OMP round,
over the device's busy time in the window."""

import work


def read(ctx):
    tr, w = ctx["trace"], ctx["window"].work
    if tr is None or not tr.busy_s or "scored_rows" not in w:
        return None
    nbytes = work.omp_scoring_bytes(1, w["scored_rows"], w["d"])
    flops = work.omp_scoring_flops(1, w["scored_rows"], w["d"])
    return 100.0 * work.least_seconds(flops, nbytes, ctx["peaks"]) / tr.busy_s
