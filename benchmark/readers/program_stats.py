"""Compilations inside the measured window: the delta of
``xla_monitor.all_program_stats()`` compile counts, all programs, plus
the persistent cache's lookups (a jit outside the monitor still looks
the cache up when it compiles). Must read 0."""

from typing import Optional


def read(ctx) -> Optional[float]:
    before, after = ctx.get("compiles_before"), ctx.get("compiles_after")
    if before is None or after is None:
        return None
    return float(sum(after.values()) - sum(before.values()))
