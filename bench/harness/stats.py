"""Order statistics the metric readers share."""
from __future__ import annotations

import math
from typing import Optional, Sequence


def quantile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank quantile: the ceil(q * n)-th smallest value; None for
    no values."""
    if not values:
        return None
    xs = sorted(values)
    return xs[max(math.ceil(q * len(xs)) - 1, 0)]
