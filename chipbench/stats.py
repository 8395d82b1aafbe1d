"""Order statistics of the readers."""
from __future__ import annotations

from typing import Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (q in 0..100) over all values."""
    v = np.sort(np.asarray(values, np.float64))
    if not len(v):
        raise ValueError("percentile of no values")
    return float(v[max(0, int(np.ceil(q / 100.0 * len(v))) - 1)])

