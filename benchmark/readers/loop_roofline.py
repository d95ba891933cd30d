"""Readings of a cell whose model applies its layer stack several times
(``ouro``: a config with ``total_ut_steps``), ``stat``:

``tick``: the share of the whole tick's roofline, in percent: the least
time one tick's bytes and operations could take
(``benchmark/flops_loop.py`` over ``benchmark/peaks.json``: the stack's
weights once a step, the head, the live tokens' K/V of every (step,
layer) pair, queries in and outputs out) over the measured DEVICE time
of one call of ``program`` (``jit_tick``), from the by-program part of
the trace reduction (``runners/serve_moe.py::by_kernel``).

``paged_attn``: the same for the attention alone: the least time the
live tokens' K/V could be read over ``kernel``'s measured own time a
call of ``program`` (one call a (step, layer) pair).

Both take a tick's live tokens and rows over the CAPTURE, between the
profiler's start and stop (``runners/serve_loop.py::Trace`` keeps those
deltas): live tokens from ``ray_tpu_cb_paged_live_block_share`` (the
table entries that hold a key) less half a block a live row, rows from
``ray_tpu_cb_loop_rows_total`` a tick.

``resident``: the arena's bytes (gauge ``ray_tpu_cb_loop_kv_bytes``,
fixed at construction) over the chip's memory (``peaks.json``'s
``hbm_bytes``), in percent.

A program that books none of these series (the parent commit), a trace
without the parts, or a configuration without ``total_ut_steps`` reads
nothing.
"""

from typing import Optional

from benchmark import flops_loop, peaks

LIVE = "ray_tpu_cb_paged_live_block_share"
ROWS = "ray_tpu_cb_loop_rows_total"
KV_BYTES = "ray_tpu_cb_loop_kv_bytes"
# What a capture keeps (``runners/serve_loop.py::Trace``).
CAPTURED = (LIVE + "_sum", LIVE + "_count", ROWS)


def read(ctx, stat: str, kernel: Optional[str] = None,
         program: Optional[str] = None) -> Optional[float]:
    config = ctx.get("config") or {}
    after = ctx.get("registry_after")
    if (not config.get("total_ut_steps") or not after
            or ctx["device"]["platform"] != "tpu"):     # (a rehearsal)
        return None
    peak = peaks.for_device(ctx["device"]["kind"])
    if stat == "resident":
        return (100.0 * after[KV_BYTES] / peak["hbm_bytes"]
                if after.get(KV_BYTES) else None)
    trace = ctx.get("trace") or {}
    capture = trace.get("loop_capture") or {}
    seconds, calls = (trace.get("programs") or {}).get(program, (0.0, 0))
    ticks = capture.get(LIVE + "_count", 0)
    if not calls or ticks <= 0 or not capture.get(ROWS):
        return None
    engine = ctx["engine"]
    bs = engine["block_size"]
    rows = capture[ROWS] / ticks
    blocks = (capture[LIVE + "_sum"] / ticks * engine["num_slots"]
              * -(-engine["max_len"] // bs))
    live = max(blocks * bs - rows * bs / 2, 0.0)
    if stat == "tick":
        return 100.0 * flops_loop.tick_seconds(config, live, rows, peak) / (
            seconds / calls)
    if stat != "paged_attn":
        raise ValueError(f"unknown statistic {stat!r}")
    by_program = (trace.get("kernels") or {}).get(kernel) or {}
    if program not in by_program:
        return None
    return 100.0 * flops_loop.tick_attn_seconds(config, live, rows, peak) / (
        by_program[program][0] / calls)
