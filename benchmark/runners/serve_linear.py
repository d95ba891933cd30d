"""A serving cell of the Qwen3-Next family (``qwen3_next``: Gated DeltaNet
linear-attention layers that keep a recurrent state a slot, beside gated
full attention, a softmax-routed expert layer of which this chip holds a
share, and a gated shared expert; a config with
``linear_num_value_heads``).

The served path is ``runners/serve.py``'s to the letter (the same
deployment class on the same route, replica wait and load generator);
the trace reduction BY KERNEL is ``runners/serve_moe.py``'s; the
chunk-aware warm-up and the check requests that ask for their routes are
``runners/serve_window.py``'s; the window that opens on a RUNNING loop
behind one primer a slot, and both counts of the window's tokens, are
``runners/serve_mla.py``'s, imported and not repeated. This runner
replaces what those tie to their families: how the program's config
object is made from the published keys (FIRST, before the device is
opened: a program without the family fails there, at once) and the
reference the outputs are held to (``benchmark/reference_qwen3_next.py``,
given the same share). The check prompts end inside the first chunk, on
its last position, one past it (a second chunk of ONE token, which starts
from the carried state and conv tail), in the middle of the second chunk
and on its last two positions; chunked prefill and then decode through
the state cache are held to the reference's full forward (the recurrence
as the plain per-token loop) by the chosen tokens' logit gap AND the
routes.

``tokens_per_s`` counts GENERATED AND PROMPT tokens of requests that came
back whole, each request's over the time it held a slot
(``serve_mla.tokens_in_service``); ``detail`` carries the arrival rule's
count (``serve_mla.tokens_between``) beside it, so both spreads can be
read off the same runs (PERF.md section 6, PR 38).
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict

import numpy as np

from benchmark import harness, reference_qwen3_next, stats
from benchmark.harness import say
from benchmark.runners.serve import _prompts, _replica_up
from benchmark.runners.serve_mla import (_offer_after_lead_in,
                                         tokens_between, tokens_in_service)
from benchmark.runners.serve_moe import Trace
from benchmark.runners.serve_window import ask_with_routes, warm_up_chunked

GAUGES = ("ray_tpu_cb_state_cache_bytes",)


def layer_types(config: Dict[str, Any]):
    """Not a published key: as ``transformers`` derives it, layer ``i``
    is "linear_attention" unless ``(i + 1) % full_attention_interval ==
    0``."""
    every = config["full_attention_interval"]
    return tuple("full_attention" if (i + 1) % every == 0
                 else "linear_attention"
                 for i in range(config["num_hidden_layers"]))


def qwen3_next_config(config: Dict[str, Any], **extra):
    """The program's config object from the published ``config.json``
    keys and the share this chip holds (``experts_held`` of a router
    ``router_experts`` wide). What the program does not implement is
    refused here, not ignored."""
    from ray_tpu.models import llama

    unsupported = {
        "hidden_act": config["hidden_act"] != "silu",
        "tied head": config["tie_word_embeddings"],
        "rope_scaling": config["rope_scaling"] is not None,
        "use_sliding_window": config["use_sliding_window"],
        "decoder_sparse_step": config["decoder_sparse_step"] != 1,
        "mlp_only_layers": bool(config["mlp_only_layers"]),
        "experts_held != num_experts": (config["experts_held"][1]
                                        != config["num_experts"]),
    }
    if any(unsupported.values()):
        raise ValueError("qwen3_next config the program does not run: "
                         f"{[k for k, bad in unsupported.items() if bad]}")
    return llama.LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["moe_intermediate_size"],
        shared_intermediate_size=config["shared_expert_intermediate_size"],
        shared_expert_gate=True,
        num_layers=config["num_hidden_layers"],
        layer_types=layer_types(config),
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        partial_rotary_factor=float(config["partial_rotary_factor"]),
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        linear_num_key_heads=config["linear_num_key_heads"],
        linear_num_value_heads=config["linear_num_value_heads"],
        linear_key_head_dim=config["linear_key_head_dim"],
        linear_value_head_dim=config["linear_value_head_dim"],
        linear_conv_kernel_dim=config["linear_conv_kernel_dim"],
        num_experts=config["router_experts"],
        experts_held=tuple(config["experts_held"]),
        num_experts_per_tok=config["num_experts_per_tok"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        # The family's, not keys: the configuration file's ``assumed``.
        zero_centered_norms=True, qk_norm_per_head=True, attn_gate=True,
        **extra)


def hold_to_reference(params, config, checks, tolerance) -> Dict[str, Any]:
    """Each check request's chosen tokens and kept routes against the
    reference's teacher-forced pass over the same tokens
    (``reference_qwen3_next.gaps_and_routes``), by the TWO LIMITS of the
    configuration file's ``tolerance_why``: ``mean_gap_sd``, how far the
    chosen tokens lie under the reference's maximum, in standard
    deviations of a position's logits, on average over every checked
    position; ``route_disagreement_share``, the share of (decoded
    position, layer) pairs whose chosen experts, as a set over the
    router's whole width, are not the reference's. The worst gap is
    printed and not held."""
    longest = max(len(req["prompt"]) + len(rec["tokens"])
                  for req, rec in checks)
    gaps, differ = [], []
    for req, rec in checks:
        gap, routes = reference_qwen3_next.gaps_and_routes(
            params, req["prompt"], rec["tokens"], config, pad_to=longest)
        gaps.append(np.asarray(gap))
        got = np.sort(np.asarray(rec["routes"], np.int64), -1)
        want = np.sort(np.asarray(routes), -1)
        differ.append(np.any(got != want, axis=-1).ravel()
                      if got.shape == want.shape
                      else np.ones(want[..., 0].size, bool))
    gaps, differ = np.concatenate(gaps), np.concatenate(differ)
    out = {"worst_logit_gap_sd": float(gaps.max()),
           "mean_logit_gap_sd": float(gaps.mean()),
           "route_disagreement_share": float(differ.mean())}
    say(f"reference: over {gaps.size} chosen tokens, mean gap "
        f"{out['mean_logit_gap_sd']:.5f} (tolerance "
        f"{tolerance['serve_mean_logit_gap_sd']}) and worst gap "
        f"{out['worst_logit_gap_sd']:.4f} logit standard deviations under "
        f"the reference maximum, {int(np.count_nonzero(gaps))} tokens not "
        f"its argmax; of {differ.size} routings (position, layer) "
        f"{int(differ.sum())} are not its top k: "
        f"{out['route_disagreement_share']:.4f} (tolerance "
        f"{tolerance['serve_route_disagreement_share']})")
    out["ok"] = bool(
        out["mean_logit_gap_sd"] <= tolerance["serve_mean_logit_gap_sd"]
        and out["route_disagreement_share"]
        <= tolerance["serve_route_disagreement_share"])
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
    work, mix = cell["workload"], cell["traffic"]
    os.environ.update(work.get("env", {}))
    # First of all: a program without the family fails here, at once.
    config = qwen3_next_config(cell["config"],
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
        warm_up_chunked(port, cell, vocab, opts.seed)
        records, primed, setup_s, ctx = _offer_after_lead_in(
            port, cell, opts, vocab, trace)
        rng = np.random.default_rng([opts.seed, 0xc4ec])
        check_reqs = _prompts(rng, vocab, work["check"]["prompt_tokens"],
                              work["check"]["max_tokens"])
        check_recs = ask_with_routes(port, check_reqs, vocab)
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
    checks_whole = all(stats.whole(r) and r["routes"] is not None
                       for r in check_recs)
    held = (_check_against_reference(
        cell, config, list(zip(check_reqs, check_recs)))
        if checks_whole else {"ok": False})
    prompt_tokens, generated = tokens_between(records, opts.seconds)
    ctx.update(measured=good, engine=work["engine"])
    if opts.keep_records:
        os.makedirs(opts.keep_records, exist_ok=True)
        with open(os.path.join(opts.keep_records,
                               f"{cell['name']}.{opts.seed}.json"), "w") as f:
            json.dump({"records": records, "primers": primed,
                       "seconds": opts.seconds}, f)
    ref_ok = held.pop("ok")
    whole = [r for r in records if stats.whole(r)]
    detail.update(
        held, **{name: ctx["registry_after"].get(name) for name in GAUGES},
        lead_in_s=work["lead_in_s"], lead_in=ctx["lead_in"],
        requests_whole=len(good), requests_sent=len(records),
        requests_whole_by_drain=len(whole),
        # The arrival rule's count, beside tokens_in_service's.
        prompt_tokens_in_window=prompt_tokens,
        generated_tokens_in_window=generated,
        tokens_per_s_by_arrival=(prompt_tokens + generated) / opts.seconds,
        generated_tokens_per_s=generated / opts.seconds,
        first_tokens_in_window=sum(0 < r["first"] <= opts.seconds
                                   for r in whole),
        # Against the window's opening at 0: when every slot held a
        # primer, when the last primer ended (negative: the window holds
        # the mix's requests alone), how many of the mix's requests held
        # a slot as the window opened, and the mix's first and last
        # endings.
        primers_admitted_s=max((r["first"] for r in primed
                                if r["first"] is not None), default=None),
        primers_done_s=max((r["last"] for r in primed
                            if r["last"] is not None), default=None),
        in_service_at_open=sum(r["first"] <= 0 < r["last"] for r in whole),
        first_finished_s=min((r["last"] for r in whole), default=None),
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
