"""End-to-end metrics from one run's window, by the host clock.

Every metric is over all the work and all the time of the window
[open, close):

* ``ttft_p<q>_ms``: q-th percentile, over every request due in the window,
  of the time from its due time to its first token.  A request with no
  first token when the window closes counts with its wait so far, so a
  stall cannot drop out of the tail.
* ``itl_p<q>_ms``: q-th percentile of every gap between successive tokens
  of one request whose later token falls in the window.
* ``tokens_per_s``: tokens emitted in the window over its length.
* ``setup_s``: process start to window open (passed in).

Percentiles interpolate linearly between order statistics (numpy's
default).
"""
from __future__ import annotations

import re

import numpy as np


def ttft_samples(requests, open_t: float, close_t: float) -> list[float]:
    out = []
    for r in requests:
        if not open_t <= r.due < close_t:
            continue
        first = r.token_times[0] if r.token_times else None
        if first is None or first >= close_t:
            first = close_t
        out.append(first - r.due)
    return out


def gap_samples(requests, open_t: float, close_t: float) -> list[float]:
    out = []
    for r in requests:
        t = r.token_times
        out.extend(b - a for a, b in zip(t, t[1:]) if open_t <= b < close_t)
    return out


def tokens_in(requests, open_t: float, close_t: float) -> int:
    return sum(1 for r in requests for t in r.token_times
               if open_t <= t < close_t)


_PCT = re.compile(r"^(ttft|itl)_p(\d+(?:\.\d+)?)_ms$")


def end_to_end(name: str, requests, open_t: float, close_t: float,
               setup_s: float) -> float:
    """The value of end-to-end metric ``name`` over the window."""
    if name == "setup_s":
        return setup_s
    if name == "tokens_per_s":
        return tokens_in(requests, open_t, close_t) / (close_t - open_t)
    m = _PCT.match(name)
    if m is None:
        raise KeyError(f"no end-to-end metric {name!r}")
    samples = (ttft_samples if m.group(1) == "ttft" else gap_samples)(
        requests, open_t, close_t)
    if not samples:
        raise ValueError(f"{name}: no samples in the window")
    return 1e3 * float(np.percentile(samples, float(m.group(2))))
