"""The comparisons that decide ``correct``: each returns plain numbers,
and ``run.py`` sets them beside their limits."""

from __future__ import annotations

import numpy as np


def weight_gap(weights, ref_weights) -> float:
    """The largest difference between two weight vectors over the
    reference's largest weight."""
    w = np.asarray(weights, np.float64)
    r = np.asarray(ref_weights, np.float64)
    return float(np.max(np.abs(w - r)) / max(float(np.max(np.abs(r))),
                                              1e-30))


def normalise(w, mask) -> np.ndarray:
    """Weights on the live slots scaled to sum to 1, 0 elsewhere."""
    w = np.where(mask, np.asarray(w, np.float64), 0.0)
    s = w.sum()
    return w / s if s > 1e-12 else np.where(mask, 1.0 / max(mask.sum(), 1),
                                            0.0)

