"""A serving cell of the DeepSeek-V3 family (``kimi_k2``: latent
attention over a compressed cache, a sigmoid-routed expert layer of which
this chip holds a share; a config with ``kv_lora_rank``).

The served path is ``runners/serve.py``'s to the letter (the same
deployment class on the same route, replica wait and load generator);
the trace reduction BY KERNEL is ``runners/serve_moe.py``'s; the
chunk-aware warm-up and the check requests that ask for their routes are
``runners/serve_window.py``'s. THE WINDOW OPENS ON A LOOP IN STEADY STATE
(``_offer_after_lead_in``): the closed loop starts ``lead_in_s`` before
the clock, as part of set-up, behind one PRIMER in every slot
(``primers``: short prompts whose answers end one after another, evenly,
over about a minute), so the mix's requests enter one at a time, as they
do for good once the loop turns over, and not as one wave of 96 prompts
whose streams begin together and end together by length. ``tokens_per_s``
is the tokens of requests that came back whole over the window, each
request's counted over the time it held a slot (``tokens_in_service``).
This runner also replaces what those runners tie to their families: how
the program's config object is made from the published keys (FIRST,
before the device is opened: a program without the family fails there,
at once) and the reference the outputs are held to
(``benchmark/reference_kimi_k2.py``, given the same share). The check
prompts end inside one chunk, on a chunk's last position, one past it,
and in 5 to 8 chunks; prefill (multi-chunk, expanded attention over
latents read back from the cache) and then decode through the cache
(absorbed attention) are held to the reference's full forward by the
chosen tokens' logit gap AND the routes, as the window cell's are.
"""

from __future__ import annotations

import asyncio
import json
import os
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List

import numpy as np

from benchmark import client, harness, manifest, reference_kimi_k2, stats
from benchmark.harness import say
from benchmark.runners.serve import _prompts, _replica_up
from benchmark.runners.serve_moe import Trace
from benchmark.runners.serve_window import ask_with_routes, warm_up_chunked

GAUGES = ("ray_tpu_cb_latent_kv_bytes",)
# The engine thread's timeline over the LEAD-IN, in ``detail`` (deltas
# of the registry between the loop's start and the window's opening):
# ticks, prefill batches, the three starved causes and the time with no
# work partition that thread's wall time (PERF.md section 3, PR 34), so
# a lead-in that held a stall says where the seconds went. No metric
# reads them: the window's readers bracket the window alone.
LEAD_IN_SERIES = (
    "ray_tpu_cb_tick_ms_count", "ray_tpu_cb_tick_ms_sum",
    "ray_tpu_cb_prefill_ms_count", "ray_tpu_cb_prefill_ms_sum",
    "ray_tpu_cb_starved_after_prefill_ms_sum",
    "ray_tpu_cb_starved_tick_late_ms_sum",
    "ray_tpu_cb_starved_before_prefill_ms_sum",
    "ray_tpu_cb_idle_no_work_ms_sum", "ray_tpu_cb_step_lock_wait_ms_sum",
    "ray_tpu_cb_decode_tokens_total")


def kimi_config(config: Dict[str, Any], **extra):
    """The program's config object from the published ``config.json``
    keys and the share this chip holds (``experts_held`` of a router
    ``router_experts`` wide). What the program does not implement is
    refused here, not ignored."""
    from ray_tpu.models import llama

    unsupported = {
        "scoring_func": config["scoring_func"] != "sigmoid",
        "topk_method": config["topk_method"] != "noaux_tc",
        "expert groups": (config["n_group"], config["topk_group"]) != (1, 1),
        "n_shared_experts": config["n_shared_experts"] != 1,
        "moe_layer_freq": config["moe_layer_freq"] != 1,
        "hidden_act": config["hidden_act"] != "silu",
        "attention_bias": config["attention_bias"],
        "tied head": config["tie_word_embeddings"],
        "num_nextn_predict_layers": config["num_nextn_predict_layers"] != 0,
        "experts_held != n_routed_experts": (config["experts_held"][1]
                                             != config["n_routed_experts"]),
    }
    if any(unsupported.values()):
        raise ValueError("kimi_k2 config the program does not run: "
                         f"{[k for k, bad in unsupported.items() if bad]}")
    layers = config["num_hidden_layers"]
    return llama.LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["moe_intermediate_size"],
        dense_intermediate_size=config["intermediate_size"],
        shared_intermediate_size=(config["n_shared_experts"]
                                  * config["moe_intermediate_size"]),
        num_layers=layers,
        num_dense_layers=config["first_k_dense_replace"],
        layer_types=("latent_attention",) * layers,
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        rope_theta=float(config["rope_theta"]),
        rope_scaling=llama.scaling_pairs(config["rope_scaling"]),
        rms_eps=float(config["rms_norm_eps"]),
        num_experts=config["router_experts"],
        experts_held=tuple(config["experts_held"]),
        num_experts_per_tok=config["num_experts_per_tok"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        router_score=config["scoring_func"],
        route_scale=float(config["routed_scaling_factor"]),
        **extra)


def hold_to_reference(params, config, checks, tolerance) -> Dict[str, Any]:
    """Each check request's chosen tokens and kept routes against the
    reference's teacher-forced pass over the same tokens
    (``reference_kimi_k2.gaps_and_routes``), by the TWO LIMITS of the
    configuration file's ``tolerance_why``: ``mean_gap_sd``, how far the
    chosen tokens lie under the reference's maximum, in standard
    deviations of a position's logits, on average over every checked
    position; ``route_disagreement_share``, the share of (decoded
    position, routed layer) pairs whose chosen experts, as a set over the
    router's whole width, are not the reference's. The worst gap is
    printed and not held."""
    longest = max(len(req["prompt"]) + len(rec["tokens"])
                  for req, rec in checks)
    gaps, differ = [], []
    for req, rec in checks:
        gap, routes = reference_kimi_k2.gaps_and_routes(
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


def primers(rng, vocab: int, spec: Dict[str, Any],
            slots: int) -> List[Dict[str, Any]]:
    """One set-up request for every slot, which bring the loop to its
    steady state and are no part of the mix: prompts of one chunk, so
    filling the slots takes seconds, and answers of ``longest_answer`` x
    (1 .. ``slots``) / ``slots`` tokens in seeded order, so they end one
    after another, evenly, and each ending admits ONE waiting caller, as
    every ending does once the loop turns over. Nothing counts them:
    they are gone before the window opens (``detail.primers_done_s``)."""
    answers = np.maximum(np.rint(
        np.arange(1, slots + 1) * spec["longest_answer"] / slots), 1)
    rng.shuffle(answers)
    return [{"prompt": rng.integers(1, vocab, spec["prompt_tokens"]).tolist(),
             "max_tokens": int(n)} for n in answers]


def _offer_after_lead_in(port: int, cell: Dict[str, Any], opts, vocab: int,
                         trace):
    """``runners/serve.py::_offer`` with the clock started ``lead_in_s``
    after the load: the generator runs ONE closed loop over lead-in and
    window (its own ``--seconds`` is their sum, so no caller starts a
    request after the window's end), behind the primers, which are sent
    ``head_start_s`` before it and so take the slots first; the registry
    and compile snapshots bracket the window alone, and instants come
    back relative to the window's opening, those of the lead-in
    negative. Returns (records, primers' records, setup_s, context for
    the readers)."""
    mix, work = cell["traffic"], cell["workload"]
    lead = float(work["lead_in_s"])
    primed: Dict[str, Any] = {}
    primer_reqs = primers(np.random.default_rng([opts.seed, 0x9e1]), vocab,
                          work["primers"], work["engine"]["num_slots"])
    priming = threading.Thread(daemon=True, target=lambda: primed.update(
        records=asyncio.run(client.wave(
            port, primer_reqs, vocab,
            timeout_s=lead + opts.seconds + mix["drain_s"]))))
    child = subprocess.Popen(
        [sys.executable, os.path.join(manifest.HERE, "client.py"),
         "--port", str(port), "--traffic", json.dumps(mix),
         "--seed", str(opts.seed), "--seconds", str(lead + opts.seconds),
         "--vocab", str(vocab)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    try:
        ready = child.stdout.readline().strip()
        if ready != "READY":
            raise RuntimeError(f"load generator said {ready!r}, not READY")
        started = (harness.registry_snapshot(), harness.compile_snapshot())
        priming.start()
        go = time.monotonic() + work["primers"]["head_start_s"]
        child.stdin.write(f"GO {go!r}\n")
        child.stdin.flush()
        t0 = go + lead
        say(f"closed loop started; the window opens in {lead:.0f}s")
        time.sleep(max(t0 - time.monotonic(), 0))
        ctx = {"registry_before": harness.registry_snapshot(),
               "compiles_before": harness.compile_snapshot()}
        ctx["lead_in"] = dict(
            {name: ctx["registry_before"].get(name, 0.0)
             - started[0].get(name, 0.0) for name in LEAD_IN_SERIES},
            compiles_and_cache_lookups=sum(
                ctx["compiles_before"].values()) - sum(started[1].values()))
        setup_s = t0 - opts.t_start
        say(f"window open: set-up took {setup_s:.2f}s")
        if opts.trace:
            time.sleep(max(t0 + work["trace_after_s"] - time.monotonic(), 0))
            trace.start()
            time.sleep(work["trace_seconds"])
            trace.stop()
        time.sleep(max(t0 + opts.seconds - time.monotonic(), 0))
        ctx["registry_after"] = harness.registry_snapshot()
        ctx["compiles_after"] = harness.compile_snapshot()
        say("window closed; draining")
        out, _ = child.communicate(timeout=mix["drain_s"] + 60)
        if child.returncode != 0:
            raise RuntimeError(f"load generator exited {child.returncode}")
        records = json.loads(out.strip().splitlines()[-1])["records"]
        priming.join(timeout=60)
        for rec in records + primed.get("records", []):
            for key in ("due", "sent", "first", "last"):
                if rec[key] is not None:
                    rec[key] -= t0
            rec["t"] = [t - t0 for t in rec["t"]]
        return records, primed.get("records", []), setup_s, ctx
    finally:
        if child.poll() is None:
            child.kill()
        child.wait()


def tokens_in_service(records: List[Dict[str, Any]], seconds: float) -> float:
    """The window's tokens: of every request that came back whole,
    whenever it began and ended, its prompt and generated tokens times
    the share of its time in a slot (first token to last) that lies
    inside the window. A request whole inside the window counts whole,
    as it does by ``tokens_between``, and a long window reads the same
    by both; THIS window is 40 s over requests that hold a slot for
    50-80 s, where that rule books a prompt as one 6k-token lump (1.2%
    of the window) on whichever side of an end its first token fell:
    six seeds of a steady loop spread 5.17% by it and 2.37% by this
    (PERF.md section 6). A failed or short request counts for nothing."""
    total = 0.0
    for rec in records:
        if stats.whole(rec):
            first, last = rec["first"], rec["last"]
            inside = max(min(last, seconds) - max(first, 0.0), 0.0)
            share = (inside / (last - first) if last > first
                     else float(0 < first <= seconds))
            total += (rec["prompt_tokens"] + rec["n"]) * share
    return total


def tokens_between(records: List[Dict[str, Any]], seconds: float):
    """(prompt tokens, generated tokens) that ARRIVED inside the window,
    for ``detail`` (``serve_moe.tokens_in_window`` with a lower end,
    since the loop runs before the window opens): of every request that
    came back whole, the generated tokens that arrived inside the
    window, and its prompt's if its first token did."""
    prompt = generated = 0
    for rec in records:
        if stats.whole(rec):
            generated += sum(1 for t in rec["t"] if 0 < t <= seconds)
            if 0 < rec["first"] <= seconds:
                prompt += rec["prompt_tokens"]
    return prompt, generated


def run(cell: Dict[str, Any], opts) -> Dict[str, Any]:
    work, mix = cell["workload"], cell["traffic"]
    os.environ.update(work.get("env", {}))
    # First of all: a program without the family fails here, at once.
    config = kimi_config(cell["config"],
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
        prompt_tokens_in_window=prompt_tokens,
        generated_tokens_in_window=generated,
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
