"""Device milliseconds per OMP round spent outside the pool-scoring
kernels (the Gram and NNLS update), in the library loop.

The scoring kernels are found by name: the Pallas kernels ``_corr_*``,
which a TPU trace may list by their kernel name or as custom calls (the
only custom calls on this path).  A traced window that ran OMP rounds but
holds no such operation is an error, not a reading: the kernels were
renamed or left the path, and the split between scoring and update is
unknown."""

SCORING = ("corr", "custom-call", "custom_call")


def read(ctx):
    tr, w = ctx["trace"], ctx["window"].work
    if tr is None or not w.get("rounds_run"):
        return None
    scoring = tr.ops_matching(*SCORING)
    if scoring <= 0:
        top = [n for n, _ in tr.breakdown()["device_ops"]]
        raise RuntimeError(f"update_ms.select: no device operation named "
                           f"like {SCORING} in the window; the largest "
                           f"are {top}")
    return 1000.0 * (tr.busy_s - scoring) / w["rounds_run"]
