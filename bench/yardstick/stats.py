"""Exact order statistics of the harness's own per-request and per-token
clock readings (not the program's bucketed histograms)."""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile by linear interpolation between order
    statistics; None for no values."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))
