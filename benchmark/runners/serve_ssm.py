"""A serving cell of the Jamba family at ``num_experts`` 1
(``model_type`` "jamba": Mamba-1 mixers beside MQA attention without
positions, a dense SwiGLU on every layer; a config with
``mamba_dt_rank``).

The served path is ``runners/serve.py``'s to the letter (the same
deployment class on the same route, replica wait, warm-up, load
generator and window); the trace reduction BY KERNEL and by program is
``runners/serve_moe.py``'s; the window opens on a PRIMED closed loop and
``tokens_per_s`` counts by ``serve_mla.tokens_in_service``, as
``runners/serve_mla.py``'s does and by its code; all imported, not
repeated. This runner replaces what those tie to their families: how the
program's config object is made from the published keys (FIRST, before
the device is opened: a program without the family fails there, at
once); the warm-up's last step, the check prompts' own programs (a
second chunk of ONE token, a second chunk of half a chunk), each asked
alone as the check asks them (:func:`ask_in_turn`), so that no program
of the check compiles under the ingress's 60 s a stream item; and the
reference the outputs are held to (``benchmark/reference_jamba.py``: ONE
limit, the chosen tokens' mean logit gap, as EvaByte's and Ouro's). The
mix carries no state over a chunk (every prompt is one chunk); the check
does, in every run.
"""

from __future__ import annotations

import asyncio
import json
import os
from typing import Any, Dict, List

import numpy as np

from benchmark import client, harness, reference_jamba, stats
from benchmark.harness import say
from benchmark.runners.serve import _prompts, _replica_up, _warm_up
from benchmark.runners.serve_loop import _listed_elsewhere
from benchmark.runners.serve_mla import (LEAD_IN_SERIES, _offer_after_lead_in,
                                         tokens_between, tokens_in_service)
from benchmark.runners.serve_moe import Trace

GAUGES = ("ray_tpu_cb_state_cache_bytes", "ray_tpu_cb_kv_blocks_used",
          "ray_tpu_cb_kv_blocks_total")
# Metrics the benchmark lists for other cells only: read through their
# own files and printed under ``detail``.
DETAIL_METRICS = ("engine_queue_ms", "prefill_ms", "prefill_batch_ms",
                  "prefill_chunk_ms", "tick_thread_host_share",
                  "paged_visit_fill_share", "paged_live_block_share",
                  "tick_overlap_share", "decode_stall_share",
                  "fetch_wait_share", "stall_excess_share",
                  "ready_at_fetch_share", "stream_handoff_ms")


def jamba_config(config: Dict[str, Any], **extra):
    """The program's config object from the published ``config.json``
    keys. What the program does not implement is refused here, not
    ignored. The layer order is ``transformers``' ``layers_block_type``
    (the configuration file's ``assumed``)."""
    from ray_tpu.models import llama

    layers = config["num_hidden_layers"]
    unsupported = {
        "model_type": config["model_type"] != "jamba",
        "hidden_act": config["hidden_act"] != "silu",
        "num_experts": config["num_experts"] != 1,
        "mamba_conv_bias": not config["mamba_conv_bias"],
        "mamba_proj_bias": config["mamba_proj_bias"],
        "untied head": not config["tie_word_embeddings"],
        "sliding_window": config["sliding_window"] is not None,
        "head_dim": (config["head_dim"] * config["num_attention_heads"]
                     != config["hidden_size"]),
    }
    if any(unsupported.values()):
        raise ValueError("jamba config the program does not run: "
                         f"{[k for k, bad in unsupported.items() if bad]}")
    return llama.LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=layers,
        layer_types=tuple(
            "attention" if i % config["attn_layer_period"]
            == config["attn_layer_offset"] else "mamba1"
            for i in range(layers)),
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        rms_eps=float(config["rms_norm_eps"]),
        mamba_n_heads=1,
        mamba_d_head=config["mamba_expand"] * config["hidden_size"],
        mamba_d_state=config["mamba_d_state"],
        mamba_d_conv=config["mamba_d_conv"],
        mamba_dt_rank=config["mamba_dt_rank"],
        rope=False, tie_word_embeddings=True,
        **extra)


def ask_in_turn(port: int, reqs: List[Dict[str, Any]],
                vocab: int) -> List[Dict[str, Any]]:
    """Each request alone, one after another: every prefill is a batch
    of ONE row, so the programs it runs are named by its length alone."""
    return [asyncio.run(client.wave(port, [req], vocab))[0] for req in reqs]


def warm_up(port: int, cell: Dict[str, Any], vocab: int, seed: int) -> None:
    """``serve._warm_up`` (a wave for every batch bucket of the mix's one
    length bucket), then the check prompts' lengths asked in turn."""
    _warm_up(port, cell, vocab, seed)
    check = cell["workload"]["check"]
    rng = np.random.default_rng([seed, 0x3b])
    bad = [r for r in ask_in_turn(port, _prompts(
        rng, vocab, check["prompt_tokens"], 2), vocab) if not stats.whole(r)]
    if bad:
        raise RuntimeError(f"warm-up request failed: {bad[0]}")
    say(f"warm: the check's programs too ({check['prompt_tokens']})")


def hold_to_reference(params, config, checks, tolerance) -> Dict[str, Any]:
    """Each check request's chosen tokens against the reference's
    teacher-forced pass over the same tokens (``reference_jamba.gaps``),
    by the ONE LIMIT of the configuration file's ``tolerance_why``:
    ``serve_mean_logit_gap_sd``, how far the chosen tokens lie under the
    reference's maximum, in standard deviations of a position's logits,
    on average over every checked position. The worst gap is printed and
    not held."""
    longest = max(len(req["prompt"]) + len(rec["tokens"])
                  for req, rec in checks)
    gaps = np.concatenate([np.asarray(reference_jamba.gaps(
        params, req["prompt"], rec["tokens"], config, pad_to=longest))
        for req, rec in checks])
    out = {"worst_logit_gap_sd": float(gaps.max()),
           "mean_logit_gap_sd": float(gaps.mean()),
           "tokens_not_the_argmax": int(np.count_nonzero(gaps))}
    say(f"reference: over {gaps.size} chosen tokens, mean gap "
        f"{out['mean_logit_gap_sd']:.5f} (tolerance "
        f"{tolerance['serve_mean_logit_gap_sd']}) and worst gap "
        f"{out['worst_logit_gap_sd']:.4f} logit standard deviations under "
        f"the reference maximum, {out['tokens_not_the_argmax']} tokens not "
        f"its argmax")
    out["ok"] = bool(out["mean_logit_gap_sd"]
                     <= tolerance["serve_mean_logit_gap_sd"])
    return out


def _check_against_reference(cell, config, checks) -> Dict[str, Any]:
    """After the replica is gone: rebuild the engine's weights (the
    served path always seeds them with 0) and hold the check requests to
    the reference."""
    import jax

    from ray_tpu.models import llama

    # The replica's tick thread never exits, so its engine is never
    # collected: free the chip by deleting every buffer the process has.
    for array in jax.live_arrays():
        array.delete()
    params = jax.jit(lambda key: llama.init_params(config, key))(
        jax.random.PRNGKey(0))
    return hold_to_reference(params, config, checks,
                             cell["config"]["tolerance"])


def run(cell: Dict[str, Any], opts) -> Dict[str, Any]:
    work = cell["workload"]
    os.environ.update(work.get("env", {}))
    # First of all: a program without the family fails here, at once.
    config = jamba_config(cell["config"],
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
        warm_up(port, cell, vocab, opts.seed)
        records, primed, setup_s, ctx = _offer_after_lead_in(
            port, cell, opts, vocab, trace)
        rng = np.random.default_rng([opts.seed, 0xc4ec])
        check_reqs = _prompts(rng, vocab, work["check"]["prompt_tokens"],
                              work["check"]["max_tokens"])
        check_recs = ask_in_turn(port, check_reqs, vocab)
        peak = harness.memory_peak_bytes(cell["chips"])
        say("check prompts answered")
    finally:
        serve.stop_http()
        serve.shutdown()
        ray_tpu.shutdown()
    say("serve and runtime shut down")

    # Those that ended inside the window, whole or not, and every
    # request that failed, whenever: in the lead-in and the drain too,
    # and a primer as well.
    measured = [r for r in records if r["error"] is not None
                or (r["done"] and 0 < r["last"] <= opts.seconds)]
    measured += [r for r in primed if not stats.whole(r)]
    if len(primed) != work["engine"]["num_slots"]:
        raise RuntimeError(f"{len(primed)} primers came back")
    good = [r for r in measured if stats.whole(r)]
    failed = len(measured) - len(good)
    checks_whole = all(stats.whole(r) for r in check_recs)
    held = (_check_against_reference(
        cell, config, list(zip(check_reqs, check_recs)))
        if checks_whole else {"ok": False})
    ref_ok = held.pop("ok")
    prompt_tokens, generated = tokens_between(records, opts.seconds)
    ctx.update(measured=good, engine=work["engine"])
    if opts.keep_records:
        os.makedirs(opts.keep_records, exist_ok=True)
        with open(os.path.join(opts.keep_records,
                               f"{cell['name']}.{opts.seed}.json"), "w") as f:
            json.dump({"records": records, "primers": primed,
                       "seconds": opts.seconds}, f)
    whole = [r for r in records if stats.whole(r)]
    after = ctx["registry_after"]
    detail.update(
        held, **_listed_elsewhere(ctx, DETAIL_METRICS),
        **{name: after.get(name) for name in GAUGES},
        lead_in_s=work["lead_in_s"], lead_in=ctx["lead_in"],
        # The engine thread's timeline over the WINDOW, as ``lead_in``
        # has it over the lead-in.
        window={name: after.get(name, 0.0)
                - ctx["registry_before"].get(name, 0.0)
                for name in LEAD_IN_SERIES},
        requests_whole=len(good), requests_sent=len(records),
        requests_whole_by_drain=len(whole),
        # The arrival rule's count, beside tokens_in_service's.
        prompt_tokens_in_window=prompt_tokens,
        generated_tokens_in_window=generated,
        tokens_per_s_by_arrival=(prompt_tokens + generated) / opts.seconds,
        generated_tokens_per_s=generated / opts.seconds,
        # Against the window's opening at 0: when the last primer ended
        # (negative: the window holds the mix's requests alone), how many
        # of the mix's requests held a slot as the window opened, and the
        # mix's last ending.
        primers_done_s=max((r["last"] for r in primed
                            if r["last"] is not None), default=None),
        in_service_at_open=sum(r["first"] <= 0 < r["last"] for r in whole),
        last_finished_s=max((r["last"] for r in whole), default=None),
        wait_for_slot_max_s=max((r["first"] - r["sent"] for r in whole),
                                default=None))
    return {
        "correct": bool(failed == 0 and checks_whole and ref_ok),
        "attempted": len(measured), "failed": failed,
        "end_to_end": {"setup_s": setup_s,
                       "tokens_per_s": tokens_in_service(
                           records, opts.seconds) / opts.seconds},
        "device": dict(info, memory_peak_bytes=peak),
        "trace": trace, "ctx": ctx, "detail": detail,
    }
