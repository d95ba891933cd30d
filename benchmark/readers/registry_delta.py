"""A ratio of deltas of the program's metrics registry over the window.

``num`` and ``den`` name samples (``<metric>_sum``, ``<metric>_count``
or a counter's own name); label sets are summed. The value is
``scale * sum(delta num) / (sum(delta den) * engine[den_times])``.
Bucket percentiles are never read: the registry's bounds are coarse.
"""

from typing import List, Optional


def read(ctx, num: List[str], den: List[str], scale: float = 1.0,
         den_times: Optional[str] = None) -> Optional[float]:
    before, after = ctx.get("registry_before"), ctx.get("registry_after")
    if before is None or after is None:
        return None

    def delta(names):
        return sum(after.get(n, 0.0) - before.get(n, 0.0) for n in names)

    below = delta(den)
    if den_times is not None:
        below *= ctx["engine"][den_times]
    if below <= 0:
        return None
    return scale * delta(num) / below
