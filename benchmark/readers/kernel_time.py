"""One named Mosaic kernel's own device time, from the by-kernel part of
the trace reduction that ``runners/serve_moe.py`` adds (``kernels``:
``{kernel: {program: [own seconds, calls]}}`` and ``programs``:
``{program: [seconds, calls]}``, both inside the traced seconds). A
trace reduced without them, as every other runner's is, reads nothing.

``stat`` ``time_share``: the kernel's own time, all programs, over
device busy time, in percent. ``tick_roofline_share``: the least time
one decode tick's grouped multiplications could take
(``benchmark/flops_moe.py`` over ``benchmark/peaks.json``, with the
share of experts touched a tick from the registry's
``ray_tpu_cb_moe_experts_touched_share``) over the kernel's measured own
time a call of ``program``, in percent.
"""

from typing import Optional

from benchmark import flops_moe, peaks

TOUCHED = "ray_tpu_cb_moe_experts_touched_share"


def read(ctx, kernel: str, stat: str,
         program: Optional[str] = None) -> Optional[float]:
    trace = ctx.get("trace") or {}
    by_program = (trace.get("kernels") or {}).get(kernel)
    if not by_program or not trace.get("busy_s"):
        return None
    if stat == "time_share":
        return (100.0 * sum(s for s, _ in by_program.values())
                / trace["busy_s"])
    if stat != "tick_roofline_share":
        raise ValueError(f"unknown kernel statistic {stat!r}")
    calls = (trace.get("programs") or {}).get(program, (0.0, 0))[1]
    before, after = ctx.get("registry_before"), ctx.get("registry_after")
    if not calls or program not in by_program or not before or not after:
        return None
    ticks = after.get(TOUCHED + "_count", 0) - before.get(TOUCHED + "_count", 0)
    if ticks <= 0:
        return None
    touched = (after.get(TOUCHED + "_sum", 0.0)
               - before.get(TOUCHED + "_sum", 0.0)) / ticks
    least = flops_moe.tick_gmm_seconds(
        ctx["config"], ctx["engine"]["num_slots"], touched,
        peaks.for_device(ctx["device"]["kind"]))
    return 100.0 * least / (by_program[program][0] / calls)
