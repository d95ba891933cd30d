"""A serving cell of the ZAYA1 family (``model_type`` "zaya": every layer
Compressed Convolutional Attention, whose K/V live paged in the arena in
a compressed latent AND whose two convolutions and shifted value keep a
tail a slot beside it, then a top-1 routed expert layer whose router is
an MLP with a state carried from layer to layer; a config with
``cca_time0``).

The served path is ``runners/serve.py``'s to the letter (the same
deployment class on the same route, replica wait and load generator);
the trace reduction BY KERNEL is ``runners/serve_moe.py``'s; the
chunk-aware warm-up and the check requests that ask for their routes are
``runners/serve_window.py``'s; the window that opens on a RUNNING loop
behind one primer a slot, and both counts of the window's tokens, are
``runners/serve_mla.py``'s, imported and not repeated. This runner
replaces what those tie to their families: how the program's config
object is made from the published keys (FIRST, before the device is
opened: a program without the family fails there, at once); the
reference the outputs are held to (``benchmark/reference_zaya.py``); and,
in the trace reduction, the registry's deltas over the CAPTURE
(:class:`Trace`), which the two roofline shares divide by the capture's
kernel time. The check prompts end inside the first chunk, ON a chunk's
last position, one past it (a chunk of ONE real row behind a carried
tail), and in the third, third and fourth chunks; chunked prefill that
carries both convolutions' tails and the shifted value, then decode
through the arena and the tail cache, are held to the reference's full
forward by the chosen tokens' logit gap AND the routes: top 1 is the
hazard, a flipped route replaces a token's whole expert output.

``tokens_per_s`` counts GENERATED AND PROMPT tokens of requests that
came back whole, each request's over the time it held a slot
(``serve_mla.tokens_in_service``); ``detail`` carries the arrival rule's
count (``serve_mla.tokens_between``) and the generated tokens a second
beside it.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict

import numpy as np

from benchmark import harness, reference_zaya, stats
from benchmark.harness import say
from benchmark.readers import cca_roofline
from benchmark.runners import serve_moe
from benchmark.runners.serve import _prompts, _replica_up
from benchmark.runners.serve_mla import (LEAD_IN_SERIES, _offer_after_lead_in,
                                         tokens_between, tokens_in_service)
from benchmark.runners.serve_window import ask_with_routes, warm_up_chunked

GAUGES = ("ray_tpu_cb_cca_kv_bytes", "ray_tpu_cb_cca_tail_bytes",
          "ray_tpu_cb_kv_blocks_used", "ray_tpu_cb_kv_blocks_total")


def zaya_config(config: Dict[str, Any], **extra):
    """The program's config object from the published ``config.json``
    keys. What the program does not implement is refused here, not
    ignored."""
    from ray_tpu.models import llama

    rope = config["rope_parameters"]["hybrid"]
    unsupported = {
        "model_type": config["model_type"] != "zaya",
        "layer_types": set(config["layer_types"]) != {"hybrid"},
        "cca taps": (config["cca_time0"], config["cca_time1"]) != (2, 2),
        "attention_bias": config["attention_bias"],
        "lm_head_bias": config["lm_head_bias"],
        "hidden_act": config["hidden_act"] != "silu",
        "untied head": not config["tie_word_embeddings"],
        "sliding_window": config["sliding_window"] is not None,
        "rope_type": rope["rope_type"] != "default",
        "num_experts_per_tok": config["num_experts_per_tok"] != 1,
        "experts_held != every expert": (
            tuple(config["experts_held"]) != (0, config["num_experts"])
            or config["router_experts"] != config["num_experts"]),
    }
    if any(unsupported.values()):
        raise ValueError("zaya config the program does not run: "
                         f"{[k for k, bad in unsupported.items() if bad]}")
    layers = config["num_hidden_layers"]
    return llama.LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["moe_intermediate_size"],
        num_layers=layers,
        layer_types=("cca_attention",) * layers,
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        partial_rotary_factor=float(rope["partial_rotary_factor"]),
        rope_theta=float(rope["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        num_experts=config["num_experts"],
        # Every expert is held: the held share's tick rows carry each
        # slot's route, which the check requests ask for.
        experts_held=tuple(config["experts_held"]),
        num_experts_per_tok=config["num_experts_per_tok"],
        router_hidden_size=config["router_hidden_size"],
        tie_word_embeddings=True,
        # The family's, not keys: the configuration file's ``assumed``.
        residual_scaling=True,
        **extra)


class Trace(serve_moe.Trace):
    """``serve_moe.Trace`` whose reduction also keeps the registry's
    deltas between the profiler's start and stop (``cca_capture``), for
    ``readers/cca_roofline.py``: a tick's live tokens and touched experts
    over the same seconds as the kernels' time."""

    def __init__(self, enabled, keep_dir, detail):
        super().__init__(enabled, keep_dir, detail)
        self._registry = []

    def start(self) -> None:
        super().start()
        self._registry.append(harness.registry_snapshot())

    def stop(self) -> None:
        self._registry.append(harness.registry_snapshot())
        super().stop()

    def reduce(self):
        reduced = super().reduce()
        if not reduced or len(self._registry) < 2:
            return reduced
        first, last = self._registry[0], self._registry[-1]
        capture = {name: last.get(name, 0.0) - first.get(name, 0.0)
                   for name in cca_roofline.CAPTURED}
        self._detail["cca_capture"] = capture
        return dict(reduced, cca_capture=capture)


def hold_to_reference(params, config, checks, tolerance) -> Dict[str, Any]:
    """Each check request's chosen tokens and kept routes against the
    reference's teacher-forced pass over the same tokens
    (``reference_zaya.gaps_and_routes``), by the TWO LIMITS of the
    configuration file's ``tolerance_why``: ``mean_gap_sd``, how far the
    chosen tokens lie under the reference's maximum, in standard
    deviations of a position's logits, on average over every checked
    position; ``route_disagreement_share``, the share of (decoded
    position, layer) pairs whose ONE expert is not the reference's. The
    worst gap is printed and not held."""
    longest = max(len(req["prompt"]) + len(rec["tokens"])
                  for req, rec in checks)
    gaps, differ = [], []
    for req, rec in checks:
        gap, routes = reference_zaya.gaps_and_routes(
            params, req["prompt"], rec["tokens"], config, pad_to=longest)
        gaps.append(np.asarray(gap))
        got, want = np.asarray(rec["routes"], np.int64), np.asarray(routes)
        differ.append((got != want).ravel() if got.shape == want.shape
                      else np.ones(want.size, bool))
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
        f"{int(differ.sum())} are not its expert: "
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
    work = cell["workload"]
    os.environ.update(work.get("env", {}))
    # First of all: a program without the family fails here, at once.
    config = zaya_config(cell["config"],
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
    after = ctx["registry_after"]
    detail.update(
        held, **{name: after.get(name) for name in GAUGES},
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
