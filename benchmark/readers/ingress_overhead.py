"""Mean client-side TTFT minus the engine's own mean TTFT (registry
``ray_tpu_serve_request_ttft_seconds`` sum/count delta over the window):
what HTTP ingress, router and the hop to the replica add, in ms."""

from typing import Optional

NAME = "ray_tpu_serve_request_ttft_seconds"


def read(ctx) -> Optional[float]:
    before, after = ctx.get("registry_before"), ctx.get("registry_after")
    ttft = [r["first"] - r["sent"] for r in ctx.get("measured") or []
            if r["first"] is not None]
    if before is None or after is None or not ttft:
        return None
    count = after.get(NAME + "_count", 0) - before.get(NAME + "_count", 0)
    if count <= 0:
        return None
    engine = (after.get(NAME + "_sum", 0) - before.get(NAME + "_sum", 0)) / count
    return 1e3 * (sum(ttft) / len(ttft) - engine)
