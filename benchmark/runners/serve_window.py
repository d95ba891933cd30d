"""A serving cell of the afmoe family (Trinity: sliding-window and full
attention layers in one stack, a sigmoid-routed expert layer of which
this chip holds a share; a config with ``sliding_window``).

The served path is ``runners/serve.py``'s to the letter (the same
deployment class on the same route, replica wait, load generator and
window), and the trace reduction BY KERNEL and the count of
``tokens_per_s`` for a closed loop of long answers are
``runners/serve_moe.py``'s, as ``runners/serve_hybrid.py`` takes them.
This runner replaces what those tie to their families: how the program's config object is made from the
published keys (FIRST, before the device is opened: a program without
the family fails there, at once), the reference the outputs are held to
(``benchmark/reference_afmoe.py``, given the same share), and the
warm-up: every prompt here is longer than the engine's prefill chunk,
so ``cb_prefill`` programs are one a (rows, earlier blocks) pair, not
one a (rows, padded length) pair (:func:`warm_up_chunked`). The check
prompts lie under the
window, cross it during decode (the ring's first wrap) and, most of
them, beyond it in several chunks; they ask the engine for the
experts each decoded position routed to, and ``correct`` holds those to
the reference's as well as the chosen tokens: a held share's output
leaves out seven eighths of what its router chose, so the router's
precision and its selection bias show in the routes and hardly in the
logits.
"""

from __future__ import annotations

import asyncio
import http.client
import json
import os
import re
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Set, Tuple

import numpy as np

from benchmark import client, harness, reference_afmoe, stats
from benchmark.harness import say
from benchmark.runners.serve import (_measured, _offer, _prompts,
                                     _replica_up)
from benchmark.runners.serve_moe import Trace, tokens_in_window

GAUGES = ("ray_tpu_cb_window_kv_bytes", "ray_tpu_cb_full_kv_bytes")


def afmoe_config(config: Dict[str, Any], **extra):
    """The program's config object from the published ``config.json``
    keys and the share this chip holds (``experts_held`` of a router
    ``router_experts`` wide). What the program does not implement is
    refused here, not ignored."""
    from ray_tpu.models import llama

    unsupported = {
        "score_func": config["score_func"] != "sigmoid",
        "expert groups": (config["n_group"], config["topk_group"]) != (1, 1),
        "num_shared_experts": config["num_shared_experts"] != 1,
        "rope_scaling": config["rope_scaling"] is not None,
        "hidden_act": config["hidden_act"] != "silu",
        "tied head": config["tie_word_embeddings"],
        "no mup_enabled": not config["mup_enabled"],
        "experts_held != num_experts": (config["experts_held"][1]
                                        != config["num_experts"]),
    }
    if any(unsupported.values()):
        raise ValueError("afmoe config the program does not run: "
                         f"{[k for k, bad in unsupported.items() if bad]}")
    return llama.LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["moe_intermediate_size"],
        dense_intermediate_size=config["intermediate_size"],
        shared_intermediate_size=(config["num_shared_experts"]
                                  * config["moe_intermediate_size"]),
        num_layers=config["num_hidden_layers"],
        num_dense_layers=config["num_dense_layers"],
        layer_types=tuple(config["layer_types"]),
        sliding_window=config["sliding_window"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        num_experts=config["router_experts"],
        experts_held=tuple(config["experts_held"]),
        num_experts_per_tok=config["num_experts_per_tok"],
        norm_topk_prob=bool(config["route_norm"]),
        router_score=config["score_func"],
        route_scale=float(config["route_scale"]),
        embedding_multiplier=float(config["hidden_size"]) ** 0.5,
        # The family's, not keys: the configuration file's ``assumed``.
        rope_full_attention=False, qk_norm_per_head=True, attn_gate=True,
        sandwich_norms=True,
        **extra)


def chunk_programs() -> Set[Tuple[int, int, int]]:
    """(rows, tokens a call, earlier blocks) of every ``cb_prefill``
    program compiled so far, read off the monitor's signature strings:
    the first three two-dimensional ``int32`` arguments are the token
    matrix, the earlier chunks' block table and the chunk's own."""
    from ray_tpu._private import xla_monitor

    rec = xla_monitor._programs.get("cb_prefill")
    out = set()
    for sig in (rec.signatures.values() if rec else ()):
        pairs = re.findall(r"int32\[(\d+),(\d+)\]", sig["signature"])
        if len(pairs) >= 2:
            out.add((int(pairs[0][0]), int(pairs[0][1]), int(pairs[1][1])))
    return out


def warm_up_chunked(port: int, cell: Dict[str, Any], vocab: int,
                    seed: int) -> None:
    """One wave for every (batch bucket, number of earlier chunks) the
    mix can produce, and nothing else. A prompt of c chunks runs the
    programs of 0, 1, ... c - 1 earlier chunks, so prompts of 1, 2, ...
    chunks (the last as long as the mix's longest) compile ONE new
    program a wave: the ingress gives a stream 60 s for its next item,
    and six cold compilations in one request pass that. The engine
    batches what is queued when it next admits: of a wave sent to an
    idle engine the first request is admitted alone and the rest, which
    arrive during its prefill, together; so a wave of n + 1 gives the
    n-row program, which program a wave really gave is read back, and a
    wave is sent again (n + 1 and n in turn) while it is missing."""
    engine, warm = cell["workload"]["engine"], cell["workload"]["warmup"]
    rng = np.random.default_rng([seed, 0x3a])
    longest = cell["traffic"]["prompt_tokens"]["max"]
    chunk, bs = engine["prefill_chunk"], engine["block_size"]
    from ray_tpu.models.continuous_batching import PREFILL_BATCH_TOKENS

    rows_cap = max(PREFILL_BATCH_TOKENS // chunk, 1)
    wanted = [(n, chunk, i * chunk // bs)
              for n in warm["batch_buckets"]
              if n <= min(engine["num_slots"], rows_cap)
              for i in range(-(-longest // chunk))]
    say(f"warm-up: {len(wanted)} prefill programs (rows, tokens, earlier "
        f"blocks) {wanted}")
    waves = 0
    for n, _, earlier in wanted:
        length = min(earlier * bs + chunk, longest)
        for attempt in range(warm["max_attempts"]):
            if (n, chunk, earlier) in chunk_programs():
                break
            size = 1 if n == 1 else n + 1 - attempt % 2
            recs = asyncio.run(client.wave(port, _prompts(
                rng, vocab, [length] * size, 2), vocab))
            waves += 1
            bad = [r for r in recs if not stats.whole(r)]
            if bad:
                raise RuntimeError(f"warm-up request failed: {bad[0]}")
        if (n, chunk, earlier) not in chunk_programs():
            raise RuntimeError(
                f"warm-up never got the engine to run {n} rows over "
                f"{earlier} earlier blocks; compiled so far: "
                f"{sorted(chunk_programs())}")
    say(f"warm after {waves} waves: cb_prefill programs "
        f"{sorted(chunk_programs())}")


def ask_with_routes(port: int, reqs: List[Dict[str, Any]],
                    vocab: int) -> List[Dict[str, Any]]:
    """Every request at once over the streamed route, each asking for
    its routes: ``client.wave``'s records (``tokens`` kept) with
    ``routes``, the stream's closing control object. (``client.py``
    drops control objects and sends no third key, and may not be
    edited.)"""
    def one(item):
        index, req = item
        rec = client._record(index, req)
        rec["routes"] = None
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
            conn.request("POST", client.ROUTE, json.dumps(
                {"prompt_token_ids": req["prompt"],
                 "max_tokens": req["max_tokens"], "return_routes": True}),
                {"Content-Type": "application/json"})
            resp = conn.getresponse()
            if resp.status != 200:
                rec["error"] = f"HTTP {resp.status}"
                return rec
            for line in resp:
                item = json.loads(line)
                if isinstance(item, dict):
                    rec["routes"] = item.get("routes", rec["routes"])
                    continue
                rec["tokens"].append(item)
                rec["bad"] += not 0 <= item < vocab
            rec["n"], rec["done"] = len(rec["tokens"]), True
        except (OSError, http.client.HTTPException, ValueError) as e:
            rec["error"] = f"{type(e).__name__}: {e}"[:200]
        return rec

    with ThreadPoolExecutor(len(reqs)) as pool:
        return list(pool.map(one, enumerate(reqs)))


def hold_to_reference(params, config, checks, tolerance) -> Dict[str, Any]:
    """Each check request's chosen tokens and kept routes against the
    reference's teacher-forced pass over the same tokens
    (``reference_afmoe.gaps_and_routes``). TWO LIMITS (the configuration
    file's ``tolerance_why``). ``mean_gap_sd``: how far the chosen
    tokens lie under the reference's maximum, in standard deviations of
    a position's logits, on average over every checked position: what
    tells the attention, the norms, the gate and the weights' precision.
    ``route_disagreement_share``: the share of (decoded position, routed
    layer) pairs whose chosen experts, as a set over the router's whole
    width, are not the reference's: what tells the router. The worst
    gap is printed and not held: one flipped near-tie moves one
    position's logits by up to a standard deviation in the sound
    program too."""
    # One padded length, so one compilation of each of the reference's
    # operations serves every check (a causal model: padding behind a
    # position cannot change it).
    longest = max(len(req["prompt"]) + len(rec["tokens"])
                  for req, rec in checks)
    gaps, differ = [], []
    for req, rec in checks:
        gap, routes = reference_afmoe.gaps_and_routes(
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
    config = afmoe_config(cell["config"],
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
        records, setup_s, ctx = _offer(port, cell, opts, vocab, trace)
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

    measured = _measured(records, mix, opts.seconds)
    good = [r for r in measured if stats.whole(r)]
    failed = len(measured) - len(good)
    checks_whole = all(stats.whole(r) and r["routes"] is not None
                       for r in check_recs)
    held = (_check_against_reference(
        cell, config, list(zip(check_reqs, check_recs)))
        if checks_whole else {"ok": False})
    tokens = tokens_in_window(records, opts.seconds)
    ctx.update(measured=good, engine=work["engine"])
    if opts.keep_records:
        os.makedirs(opts.keep_records, exist_ok=True)
        with open(os.path.join(opts.keep_records,
                               f"{cell['name']}.{opts.seed}.json"), "w") as f:
            json.dump({"records": measured, "seconds": opts.seconds}, f)
    ref_ok = held.pop("ok")
    detail.update(
        held, **{name: ctx["registry_after"].get(name) for name in GAUGES},
        requests_whole=len(good),
        tokens_per_s_whole_requests=sum(
            r["prompt_tokens"] + r["n"] for r in good) / opts.seconds,
        generated_tokens=sum(r["n"] for r in good),
        prompt_tokens=sum(r["prompt_tokens"] for r in good),
        last_finished_s=max((r["last"] for r in good), default=None))
    return {
        "correct": bool(failed == 0 and checks_whole and ref_ok),
        "attempted": len(measured), "failed": failed,
        "end_to_end": {"setup_s": setup_s,
                       "tokens_per_s": tokens / opts.seconds},
        "device": dict(info, memory_peak_bytes=peak),
        "trace": trace, "ctx": ctx, "detail": detail,
    }
