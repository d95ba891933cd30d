"""A share of device time from the reduced profiler trace, in percent:
``part`` over ``whole``, both keys of ``trace_reduce.reduce``'s result
(``mosaic_s``, ``collective_exposed_s``, ``busy_s``, ``window_s``)."""

from typing import Optional


def read(ctx, part: str, whole: str) -> Optional[float]:
    trace = ctx.get("trace")
    if not trace or not trace.get(whole):
        return None
    return 100.0 * trace[part] / trace[whole]
