"""Resident K/V bytes of a model with sliding-window layers over what
ONE block table for every layer would hold, in percent: the window
layers' rings (``ray_tpu_cb_window_kv_bytes``) plus the arena of the
layers that keep every key (``ray_tpu_cb_full_kv_bytes``), over that
arena's bytes a layer times all the layers. Gauges, fixed at
construction: read at the window's close. A program that books neither
(the parent commit, a model without a window) reads nothing."""

from typing import Optional

WINDOW = "ray_tpu_cb_window_kv_bytes"
FULL = "ray_tpu_cb_full_kv_bytes"


def read(ctx) -> Optional[float]:
    after = ctx.get("registry_after") or {}
    kinds = (ctx.get("config") or {}).get("layer_types") or ()
    full_layers = sum(k != "sliding_attention" for k in kinds)
    if not after.get(WINDOW) or not after.get(FULL) or not full_layers:
        return None
    one_table = after[FULL] / full_layers * len(kinds)
    return 100.0 * (after[WINDOW] + after[FULL]) / one_table
