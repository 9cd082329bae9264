"""Window arithmetic: per-request records in, end-to-end metrics out.

The harness keeps its own clock (``time.perf_counter``).  A request is
due when its burst's ``serve_requests`` call starts; its first token is
the first ``on_token`` commit, its last token the last one.  The window
runs from the first burst's start to the last burst's end, and every
rate divides by that whole length.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence


@dataclasses.dataclass
class Record:
    due: float  # burst start (s, harness clock)
    first: Optional[float]  # first committed token
    last: Optional[float]  # last committed token
    n_out: int  # tokens committed
    ok: bool  # finished "length" or "stop"


def percentile(xs: Sequence[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    if not xs:
        raise ValueError("percentile of no samples")
    s = sorted(xs)
    pos = (len(s) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def ttfts(records: List[Record], window_end: float) -> List[float]:
    """Seconds from due to first token.  A request that failed or never
    produced a token waited at least until the window closed: it counts
    with that wait, so failures raise the tail."""
    out = []
    for r in records:
        if r.ok and r.first is not None:
            out.append(r.first - r.due)
        else:
            out.append(window_end - r.due)
    return out


def tpots(records: List[Record]) -> List[float]:
    """Seconds per output token after the first, per request that
    committed at least two tokens."""
    return [
        (r.last - r.first) / (r.n_out - 1)
        for r in records
        if r.ok and r.first is not None and r.n_out > 1
    ]


def end_to_end(records: List[Record], start: float, end: float) -> dict:
    """``tokens_per_s``, ``ttft_p90_s`` and ``tpot_p90_ms`` of a window,
    with the sample counts behind the tails."""
    length = end - start
    tt, tp = ttfts(records, end), tpots(records)
    return {
        "tokens_per_s": sum(r.n_out for r in records) / length,
        "ttft_p90_s": percentile(tt, 90),
        "tpot_p90_ms": percentile(tp, 90) * 1e3,
        "window_s": length,
        "n_requests": len(records),
        "n_tpot": len(tp),
    }
