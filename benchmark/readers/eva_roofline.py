"""Readings of a cell whose model keeps its context under EVA attention
(``evabyte``: summaries of closed windows beside the open window's raw
keys, in one arena that rewrites itself), ``stat``:

``attn``: the decode attention kernel's share of its roofline in a tick,
in percent: the least time the tick's attention could take
(``benchmark/flops_eva.py`` over ``benchmark/peaks.json``, for the keys
the tick's queries attended: ``ray_tpu_cb_eva_summary_keys`` and
``ray_tpu_cb_eva_window_keys`` a tick, and the live slots from
``ray_tpu_cb_decode_tokens_total`` a tick, all three over the CAPTURE,
between the profiler's start and stop: ``runners/serve_eva.py::Trace``
keeps those deltas, because the open windows' fill swings by a tenth
over half a minute and the kernel's time is the capture's) over
``kernel``'s measured own time a call of ``program``, from the by-kernel
part of the trace reduction (``runners/serve_moe.py::by_kernel``).
HBM-bound; the kernel streams whole blocks of 64, so a context's last
block reads under the ceiling by its overhang.

``compress``: device time the decode ticks spent closing windows (the
loop over the rows whose window filled: gather its blocks, pool, write
the summaries; ``runners/serve_eva.py::compress_seconds``) over device
busy time, in percent, inside the traced seconds. A prefill chunk's
pooling is not in it.

``summary_keys``: summaries among the keys a tick attends, in percent.

``resident``: the bytes the live slots hold
(``ray_tpu_cb_eva_cache_bytes``) over what the same contexts would hold
with every key kept (``ray_tpu_cb_eva_uncompressed_bytes``), in percent,
as the window closed (gauges: a reading, not a delta).

``closed_in_tick``: windows closed by decode ticks among all closed, in
percent: the ticks' from ``ray_tpu_cb_eva_blocks_retired_total`` (a tick
that closes a window retires ``(window - summaries) / block`` blocks and
a prefill retires none), all of them from
``ray_tpu_cb_eva_windows_closed_total`` (its ``phase`` label is summed
where the harness reads the registry).

A program that books none of these series (the parent commit), a trace
without the parts, or a configuration without ``window_size`` reads
nothing.
"""

from typing import Optional

from benchmark import flops_eva, peaks
from benchmark.readers.mla_roofline import _delta, _mean

SUMMARY = "ray_tpu_cb_eva_summary_keys"
WINDOW = "ray_tpu_cb_eva_window_keys"
CLOSED = "ray_tpu_cb_eva_windows_closed_total"
RETIRED = "ray_tpu_cb_eva_blocks_retired_total"
RESIDENT = "ray_tpu_cb_eva_cache_bytes"
WHOLE = "ray_tpu_cb_eva_uncompressed_bytes"
DECODED = "ray_tpu_cb_decode_tokens_total"


def read(ctx, stat: str, kernel: Optional[str] = None,
         program: Optional[str] = None) -> Optional[float]:
    config = ctx.get("config") or {}
    if (not config.get("window_size") or not ctx.get("registry_before")
            or not ctx.get("registry_after")):
        return None
    if stat == "resident":
        after = ctx["registry_after"]
        return (100.0 * after[RESIDENT] / after[WHOLE]
                if after.get(RESIDENT) and after.get(WHOLE) else None)
    if stat == "closed_in_tick":
        closed = _delta(ctx, CLOSED)
        per = ((config["window_size"] - flops_eva.summaries_per_window(config))
               // ctx["engine"]["block_size"])
        return (100.0 * _delta(ctx, RETIRED) / per / closed
                if closed > 0 else None)
    summaries, raw = _mean(ctx, SUMMARY), _mean(ctx, WINDOW)
    if summaries is None or raw is None:
        return None
    if stat == "summary_keys":
        return 100.0 * summaries / (summaries + raw)
    trace = ctx.get("trace") or {}
    if stat == "compress":
        if "eva_compress_tick_s" not in trace or not trace.get("busy_s"):
            return None
        return 100.0 * trace["eva_compress_tick_s"] / trace["busy_s"]
    if stat != "attn":
        raise ValueError(f"unknown statistic {stat!r}")
    by_program = (trace.get("kernels") or {}).get(kernel) or {}
    calls = (trace.get("programs") or {}).get(program, (0.0, 0))[1]
    during = trace.get("eva_capture") or {}
    ticks = during.get(SUMMARY + "_count", 0)
    if not calls or program not in by_program or ticks <= 0:
        return None
    keys = (during[SUMMARY + "_sum"] + during[WINDOW + "_sum"]) / ticks
    least = flops_eva.tick_attn_seconds(
        config, keys, during[DECODED] / ticks,
        peaks.for_device(ctx["device"]["kind"]))
    return 100.0 * least / (by_program[program][0] / calls)
