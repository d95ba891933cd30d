"""A serving cell of the Granite 4.0-H family (Mamba-2 layers beside
attention layers in one stack; a config with ``layer_types``).

The served path is ``runners/serve.py``'s to the letter (the same
deployment class on the same route, replica wait, warm-up, load
generator and window), and the trace reduction BY KERNEL, the count of
``tokens_per_s`` for a closed loop of long answers and the metrics
printed under ``detail`` are ``runners/serve_moe.py``'s. This runner
replaces what those tie to their families: how the program's config
object is made from the published keys (FIRST, before the device is
opened: a program without the family fails there, at once), and the
reference the outputs are held to
(``benchmark/reference_granite_hybrid.py``). The check prompts pad to
the 128, 512 and 1024 buckets, so a multi-chunk, right-padded prefill
and 32 ticks through the state cache are held to the reference's full
forward in every run. ``detail`` also carries ``tick_overlap_share`` and
the resident bytes of the state cache.
"""

from __future__ import annotations

import asyncio
import importlib
import json
import os
from typing import Any, Dict, Tuple

import numpy as np

from benchmark import (client, harness, manifest, reference_granite_hybrid,
                       stats)
from benchmark.harness import say
from benchmark.runners.serve import (_measured, _offer, _prompts,
                                     _replica_up, _warm_up)
from benchmark.runners.serve_moe import (DETAIL_METRICS, Trace,
                                         tokens_in_window)

STATE_BYTES = "ray_tpu_cb_state_cache_bytes"
# Per-layer metrics the benchmark lists for other cells only: read here
# through their own metric files and printed under ``detail``.
DETAIL = DETAIL_METRICS + ("tick_overlap_share",)


def granite_hybrid_config(config: Dict[str, Any], **extra):
    """The program's config object from the published ``config.json``
    keys. What the program does not implement is refused here, not
    ignored."""
    from ray_tpu.models import llama

    inner = config["mamba_n_heads"] * config["mamba_d_head"]
    unsupported = {
        "mamba_expand x hidden_size != mamba_n_heads x mamba_d_head":
            config["mamba_expand"] * config["hidden_size"] != inner,
        "mamba_proj_bias": config["mamba_proj_bias"],
        "attention_bias": config["attention_bias"],
        "no mamba_conv_bias": not config["mamba_conv_bias"],
        "hidden_act": config["hidden_act"] != "silu",
        "position_embedding_type": config["position_embedding_type"]
        not in ("nope", "rope"),
        "untied head": not config["tie_word_embeddings"],
    }
    if any(unsupported.values()):
        raise ValueError("granitemoehybrid config the program does not run: "
                         f"{[k for k, bad in unsupported.items() if bad]}")
    return llama.LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        num_experts=config["num_local_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        # A softmax over the top-k logits IS the softmax over all,
        # renormalised over the top k.
        norm_topk_prob=True,
        layer_types=tuple(config["layer_types"]),
        mamba_n_heads=config["mamba_n_heads"],
        mamba_d_head=config["mamba_d_head"],
        mamba_d_state=config["mamba_d_state"],
        mamba_n_groups=config["mamba_n_groups"],
        mamba_d_conv=config["mamba_d_conv"],
        rope=config["position_embedding_type"] == "rope",
        attention_multiplier=float(config["attention_multiplier"]),
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        shared_intermediate_size=config["shared_intermediate_size"],
        tie_word_embeddings=True,
        **extra)


def _check_against_reference(cell, config, checks) -> Tuple[bool, float]:
    """After the replica is gone: rebuild the engine's weights (the
    served path always seeds them with 0) and hold each token the
    engine chose to the reference's logits."""
    import jax

    from ray_tpu.models import llama

    # The replica's tick thread never exits, so its engine is never
    # collected: free the chip by deleting every buffer the process has.
    for array in jax.live_arrays():
        array.delete()
    params = jax.jit(lambda key: llama.init_params(config, key))(
        jax.random.PRNGKey(0))
    worst = 0.0
    # One padded length, so one compilation of each of the reference's
    # functions serves every check (a causal model: padding behind a
    # position cannot change it).
    longest = max(len(req["prompt"]) + len(rec["tokens"])
                  for req, rec in checks)
    for req, rec in checks:
        gaps = reference_granite_hybrid.chosen_gaps(
            params, req["prompt"], rec["tokens"], config, pad_to=longest)
        worst = max(worst, float(np.max(np.asarray(gaps))))
    tol = cell["config"]["tolerance"]["serve_logit_gap_sd"]
    say(f"reference: worst chosen-token gap {worst:.4f} logit standard "
        f"deviations under the reference maximum (tolerance {tol})")
    return worst <= tol, worst


def _listed_elsewhere(ctx) -> Dict[str, Any]:
    out = {}
    for name in DETAIL:
        spec = manifest.metric_file(name)
        reader = importlib.import_module("benchmark.readers." + spec["reader"])
        out[name] = reader.read(ctx, **spec.get("args", {}))
    return out


def run(cell: Dict[str, Any], opts) -> Dict[str, Any]:
    work, mix = cell["workload"], cell["traffic"]
    os.environ.update(work.get("env", {}))
    # First of all: a program without the family fails here, at once.
    config = granite_hybrid_config(cell["config"],
                                   max_seq_len=work["engine"]["max_len"])
    info = harness.open_device(cell["chips"], opts.rehearse)
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import build_continuous_llama_app

    vocab = config.vocab_size
    detail: Dict[str, Any] = {}
    trace = Trace(opts.trace, opts.keep_trace, detail)
    ray_tpu.init(**({"num_tpus": 1} if opts.rehearse else {}))
    try:
        serve.run(build_continuous_llama_app(
            config=config, num_replicas=1, **work["engine"]))
        port = serve.start_http(port=0)
        _replica_up()
        say(f"replica up, HTTP on port {port}")
        _warm_up(port, cell, vocab, opts.seed)
        records, setup_s, ctx = _offer(port, cell, opts, vocab, trace)
        rng = np.random.default_rng([opts.seed, 0xc4ec])
        check_reqs = _prompts(rng, vocab, work["check"]["prompt_tokens"],
                              work["check"]["max_tokens"])
        check_recs = asyncio.run(client.wave(port, check_reqs, vocab))
        peak = harness.memory_peak_bytes(cell["chips"])
        say("check prompts answered")
    finally:
        serve.stop_http()
        serve.shutdown()
        ray_tpu.shutdown()
    say("serve and runtime shut down")

    measured = _measured(records, mix, opts.seconds)
    good = [r for r in measured if stats.whole(r)]
    failed = len(measured) - len(good)
    checks_whole = all(stats.whole(r) for r in check_recs)
    ref_ok, worst_gap = (_check_against_reference(
        cell, config, list(zip(check_reqs, check_recs)))
        if checks_whole else (False, float("nan")))
    ttft = stats.ttft_ms(good)
    tokens = tokens_in_window(records, opts.seconds)
    ctx.update(measured=good, engine=work["engine"])
    if opts.keep_records:
        os.makedirs(opts.keep_records, exist_ok=True)
        with open(os.path.join(opts.keep_records,
                               f"{cell['name']}.{opts.seed}.json"), "w") as f:
            json.dump({"records": measured, "seconds": opts.seconds}, f)
    detail.update(
        _listed_elsewhere(ctx), worst_logit_gap_sd=worst_gap,
        state_cache_bytes=ctx["registry_after"].get(STATE_BYTES),
        requests_whole=len(good),
        tokens_per_s_whole_requests=sum(
            r["prompt_tokens"] + r["n"] for r in good) / opts.seconds,
        generated_tokens=sum(r["n"] for r in good),
        prompt_tokens=sum(r["prompt_tokens"] for r in good),
        ttft_p50_ms=stats.percentile(ttft, 50) if ttft else None,
        ttft_p90_ms=stats.percentile(ttft, 90) if ttft else None,
        itl_p50_ms=(stats.percentile(stats.itl_ms(good), 50)
                    if good else None),
        last_finished_s=max((r["last"] for r in good), default=None))
    return {
        "correct": bool(failed == 0 and checks_whole and ref_ok),
        "attempted": len(measured), "failed": failed,
        "end_to_end": {"setup_s": setup_s,
                       "tokens_per_s": tokens / opts.seconds},
        "device": dict(info, memory_peak_bytes=peak),
        "trace": trace, "ctx": ctx, "detail": detail,
    }
