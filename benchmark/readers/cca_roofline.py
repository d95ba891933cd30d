"""Three readings of a cell whose model attends in a compressed latent
behind convolutions (``zaya``: a config with ``cca_time0``), ``stat``:

``paged_attn``: the ``paged_decode_attn`` kernel's share of its roofline
in a decode tick, in percent: the least time the tick's attention could
take (``benchmark/flops_cca.py`` over ``benchmark/peaks.json``) over
``kernel``'s measured own time a call of ``program``, from the by-kernel
part of the trace reduction (``runners/serve_moe.py::by_kernel``). The
tokens a tick attended are the live blocks
(``ray_tpu_cb_paged_live_block_share`` x slots x table width x block)
LESS HALF A BLOCK A LIVE ROW (``ray_tpu_cb_decode_tokens_total`` a
tick): a row's last block is half full on average, and its overhang is
the program's, not needed.

``moe_gmm``: the same for the ``moe_gmm`` kernel: the assignments
(``ray_tpu_cb_moe_local_assignments_total``: every expert is held here)
and the experts they touched
(``ray_tpu_cb_moe_experts_touched_share``).

Both take the registry's deltas over the CAPTURE where the runner kept
them (``trace.cca_capture``: the profiler's few seconds, the same as the
kernel time's), else over the window.

``kv_resident``: resident bytes of the model's two stores (the K/V arena
in the latent, gauge ``ray_tpu_cb_cca_kv_bytes``, plus the tail cache,
``ray_tpu_cb_cca_tail_bytes``; both fixed at construction) over what
per-head K and V at the hidden width would take in the same blocks, in
percent. Needs no trace.

A trace without the by-kernel part, a program that never ran the kernel
or books none of these series (the parent commit), or a configuration
without ``cca_time0`` reads nothing.
"""

from typing import Optional

from benchmark import flops_cca, peaks

LIVE = "ray_tpu_cb_paged_live_block_share"
DECODED = "ray_tpu_cb_decode_tokens_total"
LOCAL = "ray_tpu_cb_moe_local_assignments_total"
KV_BYTES = "ray_tpu_cb_cca_kv_bytes"
TAIL_BYTES = "ray_tpu_cb_cca_tail_bytes"
TOUCHED = "ray_tpu_cb_moe_experts_touched_share"
# What a capture keeps (``runners/serve_cca.py::Trace``).
CAPTURED = (LIVE + "_sum", LIVE + "_count", DECODED, LOCAL,
            TOUCHED + "_sum", TOUCHED + "_count")


def _deltas(ctx):
    captured = (ctx.get("trace") or {}).get("cca_capture")
    if captured:
        return captured
    before, after = ctx["registry_before"], ctx["registry_after"]
    return {name: after.get(name, 0.0) - before.get(name, 0.0)
            for name in CAPTURED}


def read(ctx, stat: str, kernel: Optional[str] = None,
         program: Optional[str] = None) -> Optional[float]:
    config = ctx.get("config") or {}
    if (not config.get("cca_time0") or not ctx.get("registry_before")
            or not ctx.get("registry_after")):
        return None
    if stat == "kv_resident":
        after = ctx["registry_after"]
        if not after.get(KV_BYTES):
            return None
        # Per-head K and V at the hidden width: 2 x hidden bf16 values a
        # token a layer where the latent keeps ``kv_token_bytes``.
        per_head = (after[KV_BYTES] * 2 * config["hidden_size"] * 2
                    / flops_cca.kv_token_bytes(config))
        return 100.0 * (after[KV_BYTES] + after.get(TAIL_BYTES, 0.0)) / per_head
    engine = ctx["engine"]
    bs = engine["block_size"]
    trace = ctx.get("trace") or {}
    by_program = (trace.get("kernels") or {}).get(kernel) or {}
    calls = (trace.get("programs") or {}).get(program, (0.0, 0))[1]
    if not calls or program not in by_program:
        return None
    peak = peaks.for_device(ctx["device"]["kind"])
    d = _deltas(ctx)
    if stat == "paged_attn":
        ticks = d[LIVE + "_count"]
        if ticks <= 0:
            return None
        rows = d[DECODED] / ticks
        blocks = (d[LIVE + "_sum"] / ticks * engine["num_slots"]
                  * -(-engine["max_len"] // bs))
        least = flops_cca.tick_attn_seconds(
            config, max(blocks * bs - rows * bs / 2, 0.0), rows, peak)
    elif stat == "moe_gmm":
        ticks = d[TOUCHED + "_count"]
        if ticks <= 0 or d[LOCAL] <= 0:
            return None
        least = flops_cca.tick_gmm_seconds(
            config, d[LOCAL] / ticks / config["num_hidden_layers"],
            d[TOUCHED + "_sum"] / ticks * config["num_experts"], peak)
    else:
        raise ValueError(f"unknown statistic {stat!r}")
    return 100.0 * least / (by_program[program][0] / calls)
