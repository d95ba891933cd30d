"""The ``ssm_step`` kernel's share of its roofline in a decode tick, in
percent: the least time one tick's state updates could take
(``benchmark/flops_ssm.py`` over ``benchmark/peaks.json``, for every
slot of the engine: the kernel updates them all) over the kernel's
measured own time a call of ``program``, from the by-kernel part of the
trace reduction (``runners/serve_moe.py::by_kernel``). A trace without
that part, a program that never ran the kernel (one without state-space
layers), or a configuration without them reads nothing.
"""

from typing import Optional

from benchmark import flops_ssm, peaks


def read(ctx, kernel: str, program: str) -> Optional[float]:
    trace = ctx.get("trace") or {}
    by_program = (trace.get("kernels") or {}).get(kernel) or {}
    calls = (trace.get("programs") or {}).get(program, (0.0, 0))[1]
    config = ctx.get("config") or {}
    if (not calls or program not in by_program
            or "mamba" not in config.get("layer_types", ())):
        return None
    least = flops_ssm.tick_step_seconds(
        config, ctx["engine"]["num_slots"],
        peaks.for_device(ctx["device"]["kind"]))
    return 100.0 * least / (by_program[program][0] / calls)
