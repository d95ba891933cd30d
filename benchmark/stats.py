"""The reduction from records to metrics (host clock)."""

from __future__ import annotations

import math
from typing import Any, Dict, List, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Linear interpolation between order statistics (NumPy's default)."""
    if not values:
        raise ValueError("percentile of nothing")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def whole(rec: Dict[str, Any]) -> bool:
    """The request came back whole: the stream ended properly with
    exactly ``max_tokens`` ids, all inside the vocabulary."""
    return (rec["done"] and rec["error"] is None and rec["bad"] == 0
            and rec["n"] == rec["max_tokens"])


def ttft_ms(records: List[Dict[str, Any]]) -> List[float]:
    """From the instant the request was due (open loop) or its client
    was free (closed loop) to its first streamed token."""
    return [1e3 * (r["first"] - r["due"]) for r in records]


def tpot_ms(records: List[Dict[str, Any]]) -> List[float]:
    """Per request, (last token - first token) / (tokens - 1)."""
    return [1e3 * (r["last"] - r["first"]) / (r["n"] - 1)
            for r in records if r["n"] > 1]


def itl_ms(records: List[Dict[str, Any]]) -> List[float]:
    """Every gap between two consecutive tokens of one request, all
    requests pooled: some ten thousand readings where a window has a
    hundred requests, so one stalled second moves a percentile of them
    far less than it moves a percentile over requests."""
    return [1e3 * (b - a) for r in records
            for a, b in zip(r["t"], r["t"][1:])]

