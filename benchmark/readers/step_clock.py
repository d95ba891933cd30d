"""The trainer's steps on the host clock, each window of steps closed by
a fetch that waits for the device: ``step_ms`` (median over windows of
window time / steps) or ``mfu`` (the benchmark's FLOPs per token x
tokens per step / that median step time / (chips x peak bf16 FLOP/s),
in percent; the median, because the traced run's profiler stalls a few
windows)."""

from typing import Optional

from benchmark import flops, peaks, stats


def read(ctx, stat: str) -> Optional[float]:
    windows = ctx.get("step_windows")
    if not windows:
        return None
    step_s = stats.percentile([w / n for w, n in windows], 50)
    if stat == "step_ms":
        return 1e3 * step_s
    if stat == "mfu":
        if ctx["device"]["platform"] != "tpu":
            return None       # the rehearsal: no chip, no utilisation
        job = ctx["traffic"]
        per_step = (flops.train_flops_per_token(
            ctx["config"], job["sequence_tokens"])
            * job["batch_sequences"] * job["sequence_tokens"])
        peak = peaks.for_device(ctx["device"]["kind"])["bf16_flops_per_s"]
        return 100.0 * per_step / step_s / (ctx["chips"] * peak)
    raise ValueError(f"unknown step statistic {stat!r}")
