"""The device trace's clock against the host's monotonic clock.

The feeder annotates each submit with a ``feeder`` span in the trace
and stamps the same submit's ``Record.release`` on the monotonic clock,
a few microseconds apart. The offset between the clocks is the one that
most (feeder span start, release) pairs share."""
from __future__ import annotations

from typing import Optional

import numpy as np

TOL_S = 1e-3        # a pair matches when this close once shifted
MIN_SHARE = 0.8     # of the window's feeder spans that must match


def offset(ctx) -> Optional[float]:
    """Seconds to add to a trace time to get the monotonic time of the
    same instant; None without a trace, where fewer than ``MIN_SHARE`` of
    the traced window's feeder spans find a release within ``TOL_S``, or
    where two offsets further apart than that match as many."""
    t = ctx.trace
    if t is None:
        return None
    feeds = np.sort([s for s, _ in t.spans.get("feeder", ())
                     if 0.0 <= s <= t.window_s])
    rel = np.sort([r.release for r in ctx.records])
    if not len(feeds) or not len(rel):
        return None
    cands = (rel[None, :] - feeds[:, None]).ravel()
    counts = _nearest(rel, feeds[None, :] + cands[:, None])[1].sum(axis=1)
    best = counts.max()
    tied = cands[counts == best]
    if best < MIN_SHARE * len(feeds) or tied.max() - tied.min() > 2 * TOL_S:
        return None
    near, hit = _nearest(rel, feeds + float(np.median(tied)))
    return float(np.median(near[hit] - feeds[hit]))


def _nearest(sorted_vals: np.ndarray, at: np.ndarray):
    """The value of ``sorted_vals`` nearest each of ``at``, and whether it
    lies within ``TOL_S``."""
    i = np.searchsorted(sorted_vals, at)
    lo = sorted_vals[np.clip(i - 1, 0, len(sorted_vals) - 1)]
    hi = sorted_vals[np.clip(i, 0, len(sorted_vals) - 1)]
    near = np.where(np.abs(lo - at) <= np.abs(hi - at), lo, hi)
    return near, np.abs(near - at) <= TOL_S
