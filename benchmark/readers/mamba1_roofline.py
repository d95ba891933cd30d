"""Readings of a cell whose model keeps a recurrent state a slot in
Mamba-1 layers (``jamba``: a config with ``mamba_dt_rank``), ``stat``:

``step``: the ``mamba1_step`` kernel's share of its roofline in a decode
tick, in percent: the least time the tick's state updates could take
(``benchmark/flops_mamba1.py`` over ``benchmark/peaks.json``, for the
slots that held a LIVE request: the mean of
``ray_tpu_cb_state_live_slots`` over the window) over ``kernel``'s
measured own time a call of ``program``, from the by-kernel part of the
trace reduction (``runners/serve_moe.py::by_kernel``). The kernel
advances every slot's row, live or not, so at an occupancy under 100% it
reads that much under its ceiling.

``scan``: the same for the prefill's ``mamba1_scan``: the least time ONE
``cb_prefill`` call's scans could take for its REAL prompt tokens
(``ray_tpu_cb_prefill_tokens_total`` over the calls,
``ray_tpu_cb_prefill_chunk_ms_count``; a state written once a row and
read once where a chunk carried one:
``ray_tpu_cb_state_installs_total``,
``ray_tpu_cb_prefill_state_carries_total``) over the kernel's own time a
``jit_prefill`` call. Its ceiling is bytes; the kernel also does 6
vector operations and one ``exp`` a state element a token, which is what
paces it: expect a low reading.

``tick``: the share of the whole step: the least time one tick's bytes
and operations could take (``flops_mamba1.tick_seconds``: the LARGER of
total operations over the bf16 peak and total bytes over HBM's rate)
over the measured DEVICE time of one call of ``program`` (``jit_tick``).

``paged_attn``: the least time the live tokens' K/V of the attention
layers could be read over ``kernel``'s (``paged_decode_attn``) own time
a tick: MQA 20/1 at head size 128.

``state_resident``: the state bytes the live requests keep
(``flops_mamba1.state_bytes`` of the mean live slots) over those plus
the K/V bytes of their live blocks, in percent: what share of a
request's memory does NOT grow with its context.

Live tokens: ``ray_tpu_cb_paged_live_block_share`` (the table entries
that hold a key) less half a block a live row. A trace without the
parts, a program that never ran the kernel or books none of these series
(the parent commit), or a configuration without ``mamba_dt_rank`` reads
nothing.
"""

from typing import Optional

from benchmark import flops_mamba1, peaks
from benchmark.readers.mla_roofline import _delta, _mean

LIVE_SLOTS = "ray_tpu_cb_state_live_slots"
LIVE_BLOCKS = "ray_tpu_cb_paged_live_block_share"
TOKENS = "ray_tpu_cb_prefill_tokens_total"
CALLS = "ray_tpu_cb_prefill_chunk_ms_count"
INSTALLS = "ray_tpu_cb_state_installs_total"
CARRIES = "ray_tpu_cb_prefill_state_carries_total"


def read(ctx, stat: str, kernel: Optional[str] = None,
         program: Optional[str] = None) -> Optional[float]:
    config = ctx.get("config") or {}
    if (not config.get("mamba_dt_rank") or not ctx.get("registry_before")
            or not ctx.get("registry_after")):
        return None
    engine = ctx["engine"]
    rows, blocks = _mean(ctx, LIVE_SLOTS), _mean(ctx, LIVE_BLOCKS)
    if not rows or blocks is None:
        return None
    bs = engine["block_size"]
    live = max(blocks * engine["num_slots"] * -(-engine["max_len"] // bs) * bs
               - rows * bs / 2, 0.0)
    if stat == "state_resident":
        state = flops_mamba1.state_bytes(config, rows)
        return 100.0 * state / (
            state + live * flops_mamba1.kv_token_bytes(config))
    trace = ctx.get("trace") or {}
    seconds, calls = (trace.get("programs") or {}).get(program, (0.0, 0))
    if not calls:
        return None
    peak = peaks.for_device(ctx["device"]["kind"])
    if stat == "tick":
        return 100.0 * flops_mamba1.tick_seconds(config, live, rows, peak) / (
            seconds / calls)
    by_program = (trace.get("kernels") or {}).get(kernel) or {}
    if program not in by_program:
        return None
    if stat == "step":
        least = flops_mamba1.tick_step_seconds(config, rows, peak)
    elif stat == "paged_attn":
        least = flops_mamba1.tick_attn_seconds(config, live, rows, peak)
    elif stat == "scan":
        batches, carried = _delta(ctx, CALLS), _delta(ctx, CARRIES)
        if batches <= 0:
            return None
        least = flops_mamba1.scan_seconds(
            config, _delta(ctx, TOKENS) / batches,
            (carried + _delta(ctx, INSTALLS)) / batches, carried / batches,
            peak)
    else:
        raise ValueError(f"unknown statistic {stat!r}")
    return 100.0 * least / (by_program[program][0] / calls)
