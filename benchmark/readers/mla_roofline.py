"""Three readings of a cell whose model keeps a LATENT cache (MLA),
``stat``:

``attn``: the latent-attention kernel's share of its roofline in a decode
tick, in percent: the least time the tick's attention could take
(``benchmark/flops_mla.py`` over ``benchmark/peaks.json``, for the cache
rows the tick's queries attended: the mean of
``ray_tpu_cb_mla_live_tokens`` over the window) over ``kernel``'s
measured own time a call of ``program``, from the by-kernel part of the
trace reduction (``runners/serve_moe.py::by_kernel``). It counts 576
values a row; a program that pads a row to 640 lanes reads those too and
cannot pass 90%.

``gmm``: the same for the ``moe_gmm`` kernel: the assignments that fell
on held experts (``ray_tpu_cb_moe_local_assignments_total``) and the
held experts they touched (``ray_tpu_cb_moe_experts_touched_share``).

``kv_resident``: resident bytes of the latent cache
(``ray_tpu_cb_latent_kv_bytes``, a gauge fixed at construction) over what
per-head K and V of the same tokens would take, in percent.

A trace without the by-kernel part, a program that never ran the kernel
or books none of these series (the parent commit), or a configuration
without ``kv_lora_rank`` reads nothing.
"""

from typing import Optional

from benchmark import flops_mla, peaks

LIVE = "ray_tpu_cb_mla_live_tokens"
LATENT = "ray_tpu_cb_latent_kv_bytes"
LOCAL = "ray_tpu_cb_moe_local_assignments_total"
TOUCHED = "ray_tpu_cb_moe_experts_touched_share"


def _delta(ctx, name: str) -> float:
    return (ctx["registry_after"].get(name, 0.0)
            - ctx["registry_before"].get(name, 0.0))


def _mean(ctx, name: str) -> Optional[float]:
    """Mean of a histogram's observations over the window."""
    n = _delta(ctx, name + "_count")
    return _delta(ctx, name + "_sum") / n if n > 0 else None


def read(ctx, stat: str, kernel: Optional[str] = None,
         program: Optional[str] = None) -> Optional[float]:
    config = ctx.get("config") or {}
    if (not config.get("kv_lora_rank") or not ctx.get("registry_before")
            or not ctx.get("registry_after")):
        return None
    engine = ctx["engine"]
    if stat == "kv_resident":
        held = ctx["registry_after"].get(LATENT)
        if not held:
            return None
        bs = engine["block_size"]
        blocks = engine.get("num_blocks") or (
            engine["num_slots"] * -(-engine["max_len"] // bs) + 1)
        return 100.0 * held / (
            blocks * bs * config["num_hidden_layers"]
            * flops_mla.per_head_token_bytes(config))
    trace = ctx.get("trace") or {}
    by_program = (trace.get("kernels") or {}).get(kernel) or {}
    calls = (trace.get("programs") or {}).get(program, (0.0, 0))[1]
    if not calls or program not in by_program:
        return None
    peak = peaks.for_device(ctx["device"]["kind"])
    if stat == "attn":
        live = _mean(ctx, LIVE)
        if live is None:
            return None
        least = flops_mla.tick_attn_seconds(config, live,
                                            engine["num_slots"], peak)
    elif stat == "gmm":
        touched, ticks = _mean(ctx, TOUCHED), _delta(ctx, TOUCHED + "_count")
        local = _delta(ctx, LOCAL)
        if touched is None or local <= 0:
            return None
        least = flops_mla.tick_gmm_seconds(
            config, local / ticks / flops_mla.routed_layers(config),
            touched * config["n_routed_experts"], peak)
    else:
        raise ValueError(f"unknown statistic {stat!r}")
    return 100.0 * least / (by_program[program][0] / calls)
