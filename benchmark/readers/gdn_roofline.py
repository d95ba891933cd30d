"""Three readings of a cell whose model keeps a recurrent state a slot in
Gated DeltaNet layers (``qwen3_next``), ``stat``:

``step``: the ``gdn_step`` kernel's share of its roofline in a decode
tick, in percent: the least time the tick's state updates could take
(``benchmark/flops_gdn.py`` over ``benchmark/peaks.json``, for the slots
that held a LIVE request: the mean of ``ray_tpu_cb_state_live_slots``
over the window) over ``kernel``'s measured own time a call of
``program``, from the by-kernel part of the trace reduction
(``runners/serve_moe.py::by_kernel``). The kernel advances every slot's
row, live or not, so at an occupancy under 100% it reads that much under
its ceiling.

``gmm``: the same for the ``moe_gmm`` kernel: the assignments that fell
on held experts (``ray_tpu_cb_moe_local_assignments_total``) and the
held experts they touched (``ray_tpu_cb_moe_experts_touched_share``),
by ``flops_window.tick_gmm_seconds``'s count with every layer routed.

``state_resident``: the recurrent-state bytes the live requests keep
(``flops_gdn.state_bytes`` of the mean live slots) over those plus the
K/V bytes of their live blocks in the full-attention layers
(``ray_tpu_cb_paged_live_block_share`` x slots x table width x block),
in percent: what share of a request's memory does NOT grow with its
context.

A trace without the by-kernel part, a program that never ran the kernel
or books none of these series (the parent commit), or a configuration
without ``linear_num_value_heads`` reads nothing.
"""

from typing import Optional

from benchmark import flops_gdn, flops_window, peaks
from benchmark.readers.mla_roofline import _delta, _mean

LIVE_SLOTS = "ray_tpu_cb_state_live_slots"
LIVE_BLOCKS = "ray_tpu_cb_paged_live_block_share"
LOCAL = "ray_tpu_cb_moe_local_assignments_total"
TOUCHED = "ray_tpu_cb_moe_experts_touched_share"


def read(ctx, stat: str, kernel: Optional[str] = None,
         program: Optional[str] = None) -> Optional[float]:
    config = ctx.get("config") or {}
    if (not config.get("linear_num_value_heads")
            or not ctx.get("registry_before")
            or not ctx.get("registry_after")):
        return None
    engine = ctx["engine"]
    live = _mean(ctx, LIVE_SLOTS)
    if stat == "state_resident":
        blocks = _mean(ctx, LIVE_BLOCKS)
        if not live or blocks is None:
            return None
        bs = engine["block_size"]
        tokens = blocks * engine["num_slots"] * -(-engine["max_len"] // bs) * bs
        state = flops_gdn.state_bytes(config, live)
        return 100.0 * state / (state
                                + tokens * flops_gdn.kv_token_bytes(config))
    trace = ctx.get("trace") or {}
    by_program = (trace.get("kernels") or {}).get(kernel) or {}
    calls = (trace.get("programs") or {}).get(program, (0.0, 0))[1]
    if not calls or program not in by_program:
        return None
    peak = peaks.for_device(ctx["device"]["kind"])
    if stat == "step":
        if not live:
            return None
        least = flops_gdn.tick_step_seconds(config, live, peak)
    elif stat == "gmm":
        touched, ticks = _mean(ctx, TOUCHED), _delta(ctx, TOUCHED + "_count")
        local = _delta(ctx, LOCAL)
        if touched is None or local <= 0:
            return None
        routed = dict(config, num_dense_layers=0)       # every layer's MLP
        least = flops_window.tick_gmm_seconds(
            routed, local / ticks / config["num_hidden_layers"],
            touched * config["num_experts"], peak)
    else:
        raise ValueError(f"unknown statistic {stat!r}")
    return 100.0 * least / (by_program[program][0] / calls)
