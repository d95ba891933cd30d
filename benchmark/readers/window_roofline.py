"""A kernel's share of its roofline in a decode tick of a model with
sliding-window layers and a held share of its experts, in percent: the
least time the tick's work could take (``benchmark/flops_window.py``
over ``benchmark/peaks.json``) over the kernel's measured own time a
call of ``program``, from the by-kernel part of the trace reduction
(``runners/serve_moe.py::by_kernel``). What a tick's work WAS comes from
the registry's deltas over the window, per tick:

``paged_attn``: the blocks the full-attention layers' kernel visited
(``ray_tpu_cb_paged_live_block_share`` x slots x table width) and the
share of them a sliding-window layer's visited
(``ray_tpu_cb_window_live_block_share``), as tokens, the window layers'
capped at ``sliding_window`` a slot (a block's overhang is the
program's, not needed).

``moe_gmm``: the assignments that fell on held experts
(``ray_tpu_cb_moe_local_assignments_total``) and the held experts they
touched (``ray_tpu_cb_moe_experts_touched_share``).

A trace without the by-kernel part, a program that never ran the kernel
or books none of these (the parent commit), or a configuration without
a window reads nothing.
"""

from typing import Optional

from benchmark import flops_window, peaks

LIVE = "ray_tpu_cb_paged_live_block_share"
WINDOW_LIVE = "ray_tpu_cb_window_live_block_share"
LOCAL = "ray_tpu_cb_moe_local_assignments_total"
TOUCHED = "ray_tpu_cb_moe_experts_touched_share"


def _mean(ctx, name: str) -> Optional[float]:
    """Mean of a histogram's observations over the window."""
    before, after = ctx["registry_before"], ctx["registry_after"]
    n = after.get(name + "_count", 0) - before.get(name + "_count", 0)
    if n <= 0:
        return None
    return (after.get(name + "_sum", 0.0) - before.get(name + "_sum", 0.0)) / n


def read(ctx, kernel: str, program: str, stat: str) -> Optional[float]:
    trace = ctx.get("trace") or {}
    by_program = (trace.get("kernels") or {}).get(kernel) or {}
    calls = (trace.get("programs") or {}).get(program, (0.0, 0))[1]
    config = ctx.get("config") or {}
    if (not calls or program not in by_program
            or not config.get("sliding_window")
            or not ctx.get("registry_before") or not ctx.get("registry_after")):
        return None
    engine = ctx["engine"]
    peak = peaks.for_device(ctx["device"]["kind"])
    if stat == "paged_attn":
        live, window_live = _mean(ctx, LIVE), _mean(ctx, WINDOW_LIVE)
        if live is None or window_live is None:
            return None
        bs, slots = engine["block_size"], engine["num_slots"]
        full_tokens = live * slots * -(-engine["max_len"] // bs) * bs
        window_tokens = min(window_live * full_tokens,
                            slots * config["sliding_window"])
        least = flops_window.tick_attn_seconds(config, window_tokens,
                                               full_tokens, peak)
    elif stat == "moe_gmm":
        touched = _mean(ctx, TOUCHED)
        ticks = (ctx["registry_after"].get(TOUCHED + "_count", 0)
                 - ctx["registry_before"].get(TOUCHED + "_count", 0))
        local = (ctx["registry_after"].get(LOCAL, 0.0)
                 - ctx["registry_before"].get(LOCAL, 0.0))
        if touched is None or local <= 0:
            return None
        least = flops_window.tick_gmm_seconds(
            config, local / ticks / flops_window.routed_layers(config),
            touched * config["num_experts"], peak)
    else:
        raise ValueError(f"unknown statistic {stat!r}")
    return 100.0 * least / (by_program[program][0] / calls)
