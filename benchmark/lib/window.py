"""Window and percentile arithmetic of the serving cells.

A delivery is one item of ``handle.stream(...)``: a list of new token
ids, stamped by the client's monotonic clock on arrival.

The window is aligned to deliveries. It opens at the first delivery at or
after the nominal opening time and closes at the first delivery at or
after ``seconds`` later; a delivery belongs to it when its stamp lies in
(open, close]. Deliveries come in bursts (one decode chunk hands tokens
to every active slot at once), so a window cut at fixed times counts a
burst more or less by chance; one cut on deliveries holds a whole number
of bursts and is as long as they took. Every rate is over all tokens and
all the time of that window.
"""

from __future__ import annotations

import bisect
import math
from typing import Dict, List, Optional, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear interpolation between order statistics (numpy's default)."""
    if not values:
        return None
    xs = sorted(values)
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def aligned_window(stamps: Sequence[float], nominal_open: float,
                   seconds: float) -> Tuple[float, float]:
    """``stamps``: every delivery's arrival time, sorted."""
    i = bisect.bisect_left(stamps, nominal_open)
    if i >= len(stamps):
        raise ValueError("no delivery at or after the window's opening")
    t_open = stamps[i]
    j = bisect.bisect_left(stamps, t_open + seconds)
    if j >= len(stamps):
        raise ValueError("no delivery at or after the window's close: "
                         "the load stopped too early")
    return t_open, stamps[j]


def in_window(t: float, window: Tuple[float, float]) -> bool:
    return window[0] < t <= window[1]


def tokens_in_window(deliveries: Sequence[Tuple[float, int]],
                     window: Tuple[float, float]) -> int:
    return sum(n for t, n in deliveries if in_window(t, window))


def summarize(requests: List[Dict], window: Tuple[float, float]) -> Dict:
    """``requests``: one dict per request sent, with ``sent`` (s),
    ``stamps`` (arrival time of each delivery), ``counts`` (tokens in
    each), ``done`` (bool), ``tag``. Each sample belongs to the window by
    the stamp of the event it times."""
    out_tokens = 0
    itl, ttft, tpot = [], [], []
    for r in requests:
        st, ct = r["stamps"], r["counts"]
        for i, (t, n) in enumerate(zip(st, ct)):
            if not in_window(t, window):
                continue
            out_tokens += n
            if i == 0:
                ttft.append(t - r["sent"])
            else:
                itl.append(t - st[i - 1])
        if r["done"] and st and in_window(st[-1], window):
            n_out = sum(ct)
            if n_out > 1 and len(st) > 1:
                tpot.append((st[-1] - st[0]) / (n_out - ct[0]))
    return {"window_s": window[1] - window[0], "out_tokens": out_tokens,
            "itl_s": itl, "ttft_s": ttft, "tpot_s": tpot}
