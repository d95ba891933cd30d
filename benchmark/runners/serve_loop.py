"""A serving cell of the Ouro family (``model_type`` "ouro": a LOOPED
stack, 48 LLaMA layers applied ``total_ut_steps`` times with the same
weights; a config with ``total_ut_steps``).

The served path is ``runners/serve.py``'s to the letter, as
``runners/serve_moe.py`` is (the same deployment class on the same
route, replica wait, warm-up, load generator and window; the trace
reduction BY KERNEL and by program is that runner's, imported and not
repeated).
This runner replaces what those tie to their families: how the
program's config object is made from the published keys (FIRST, before
the device is opened: a program without the family fails there, at
once); the reference the outputs are held to
(``benchmark/reference_ouro.py``: no routes here, so ONE limit, the
chosen tokens' mean logit gap, as EvaByte's); and, in the trace
reduction, the device time inside the programs' layer loops
(:func:`stack_seconds`) and the registry's deltas over the capture
(``loop_capture``), for ``readers/loop_roofline.py``.

THE WINDOW OPENS ON A LOOP IN STEADY STATE, as ``runners/serve_mla.py``'s
does and by its code (``_offer_after_lead_in``: the closed loop starts
``lead_in_s`` before the clock, as part of set-up, behind one primer a
slot whose answers end one after another), and ``tokens_per_s`` counts by
``serve_mla.tokens_in_service``: ISSUE 53's remedy for a count that
spreads over 2%. ``serve_moe.tokens_in_window`` on an unprimed window
books a prompt as one lump of 129-256 tokens on the side of the window's
edge its first token fell, 21 lumps a window here, and the window's first
quarter is one cohort of 8 cold starts that no steady pool produces: six
seeds spread 3.4% by it (PERF.md section 6, PR 53). The arrival rule's
count stays under ``detail`` (``tokens_per_s_by_arrival``), with the
readings of the entries the benchmark lists for other cells only
(:data:`DETAIL_METRICS`).
"""

from __future__ import annotations

import asyncio
import bisect
import importlib
import json
import os
from typing import Any, Dict

import numpy as np

from benchmark import (client, harness, manifest, reference_ouro, stats,
                       trace_reduce)
from benchmark.harness import say
from benchmark.readers import loop_roofline
from benchmark.runners import serve_moe
from benchmark.runners.serve import _prompts, _replica_up, _warm_up
from benchmark.runners.serve_mla import (LEAD_IN_SERIES, _offer_after_lead_in,
                                         tokens_between, tokens_in_service)

GAUGES = ("ray_tpu_cb_loop_kv_bytes", "ray_tpu_cb_kv_blocks_used",
          "ray_tpu_cb_kv_blocks_total")
# Metrics the benchmark lists for other cells only (a cell's PR adds
# entries and edits no list: PERF.md section 7 asks a ``benchmark`` PR to
# append this cell to them): read through their own files and printed
# under ``detail``; those of the last line need the trace.
DETAIL_METRICS = ("slot_occupancy", "engine_queue_ms", "prefill_ms",
                  "tick_thread_host_share", "paged_visit_fill_share",
                  "tick_wall_ms.closed_loop", "prefill_batch_ms",
                  "paged_live_block_share", "tick_overlap_share",
                  "decode_stall_share", "fetch_wait_share",
                  "stall_excess_share", "ready_at_fetch_share",
                  "device_starved_share")
DETAIL_TRACE_METRICS = ("paged_attn_time_share",)


def ouro_config(config: Dict[str, Any], **extra):
    """The program's config object from the published ``config.json``
    keys. What the program does not implement is refused here, not
    ignored. The four norms a layer are the family's (``model_type``),
    not a key: see the configuration file's ``assumed``."""
    from ray_tpu.models import llama

    unsupported = {
        "model_type": config["model_type"] != "ouro",
        "hidden_act": config["hidden_act"] != "silu",
        "layer_types": set(config["layer_types"]) != {"full_attention"}
        or len(config["layer_types"]) != config["num_hidden_layers"],
        "rope_scaling": config["rope_scaling"] is not None,
        "sliding_window": config["sliding_window"] is not None
        or config["use_sliding_window"],
        "tied head": config["tie_word_embeddings"],
        # Under 1 rows leave the loop at different depths: another
        # output, which the engine counts and does not act on.
        "early_exit_threshold": config["early_exit_threshold"] != 1,
        "total_ut_steps": config["total_ut_steps"] < 2,
    }
    if any(unsupported.values()):
        raise ValueError("ouro config the program does not run: "
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
        sandwich_norms=True,
        loop_steps=config["total_ut_steps"],
        **extra)


def stack_seconds(planes) -> float:
    """Device seconds inside the layer loops of ``jit_tick`` and
    ``jit_prefill`` within the traced seconds (the scopes
    ``loop/step<t>``), averaged over the chips that ran anything. A trace
    names instructions, not the program's scopes, so the loops are found
    by where they stand: the ``while`` instructions at the TOP level of
    those programs (one a step; a ``while`` inside another is the outer
    one's time already). The final norm a step and the gate stand outside
    them."""
    total, chips = 0.0, 0
    for lines in planes.values():
        ops = [e for e in lines.get(trace_reduce.OPS_LINE, []) if e[2] > 0]
        if not ops:
            continue
        chips += 1
        calls = sorted((start, start + dur) for name, start, dur
                       in lines.get(trace_reduce.MODULES_LINE, [])
                       if name.startswith(("jit_tick", "jit_prefill")))
        starts = [s for s, _ in calls]
        outer_end = 0
        for name, start, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
            if trace_reduce.opcode(name) != "while" or start < outer_end:
                continue
            i = bisect.bisect_right(starts, start) - 1
            if i < 0 or start >= calls[i][1]:
                continue
            outer_end = start + dur
            total += dur / 1e9
    return total / max(chips, 1)


class Trace(serve_moe.Trace):
    """``serve_moe.Trace`` whose reduction also keeps
    :func:`stack_seconds` (``loop_stack_s``) and the registry's deltas
    between the profiler's start and stop (``loop_capture``): a tick's
    live tokens and rows over the same seconds as the programs' time."""

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
        path = (self._state != "off"
                and trace_reduce.find(harness.TRACE_DIR))
        if not path:
            return super().reduce()     # nothing, or its own complaint
        # Read here first: the parent's reduction removes the file.
        first, last = self._registry[0], self._registry[-1]
        extra = {"loop_stack_s": stack_seconds(trace_reduce.load(path)),
                 "loop_capture": {name: last.get(name, 0.0)
                                  - first.get(name, 0.0)
                                  for name in loop_roofline.CAPTURED}}
        self._detail.update(extra)
        reduced = dict(super().reduce(), **extra)
        self._detail.update(_listed_elsewhere({"trace": reduced},
                                               DETAIL_TRACE_METRICS))
        return reduced


def hold_to_reference(params, config, checks, tolerance) -> Dict[str, Any]:
    """Each check request's chosen tokens against the reference's
    teacher-forced pass over the same tokens (``reference_ouro.gaps``),
    by the ONE LIMIT of the configuration file's ``tolerance_why``:
    ``serve_mean_logit_gap_sd``, how far the chosen tokens lie under the
    reference's maximum, in standard deviations of a position's logits,
    on average over every checked position. The worst gap is printed and
    not held."""
    longest = max(len(req["prompt"]) + len(rec["tokens"])
                  for req, rec in checks)
    gaps = np.concatenate([np.asarray(reference_ouro.gaps(
        params, req["prompt"], rec["tokens"], config, pad_to=longest)[0])
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


def _listed_elsewhere(ctx, names=DETAIL_METRICS) -> Dict[str, Any]:
    out = {}
    for name in names:
        spec = manifest.metric_file(name)
        reader = importlib.import_module("benchmark.readers." + spec["reader"])
        out[name] = reader.read(ctx, **spec.get("args", {}))
    return out


def run(cell: Dict[str, Any], opts) -> Dict[str, Any]:
    work, mix = cell["workload"], cell["traffic"]
    os.environ.update(work.get("env", {}))
    # First of all: a program without the family fails here, at once.
    config = ouro_config(cell["config"],
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
        records, primed, setup_s, ctx = _offer_after_lead_in(
            port, cell, opts, vocab, trace)
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
    ttft = stats.ttft_ms(good)
    after = ctx["registry_after"]
    detail.update(
        held, **_listed_elsewhere(ctx),
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
        ttft_p50_ms=stats.percentile(ttft, 50) if ttft else None,
        ttft_p90_ms=stats.percentile(ttft, 90) if ttft else None,
        itl_p50_ms=(stats.percentile(stats.itl_ms(good), 50)
                    if good else None))
    return {
        "correct": bool(failed == 0 and checks_whole and ref_ok),
        "attempted": len(measured), "failed": failed,
        "end_to_end": {"setup_s": setup_s,
                       "tokens_per_s": tokens_in_service(
                           records, opts.seconds) / opts.seconds},
        "device": dict(info, memory_peak_bytes=peak),
        "trace": trace, "ctx": ctx, "detail": detail,
    }
