"""A serving cell of the EvaByte family (``attention_class`` "eva": every
layer EVA attention, an exact window of the sequence beside one learned
summary for every chunk behind it, over a cache that compresses itself
in the prefill and in the tick; a config with ``window_size``).

The served path is ``runners/serve.py``'s to the letter (the same
deployment class on the same route, replica wait and load generator);
the trace reduction BY KERNEL is ``runners/serve_moe.py``'s; the window
that opens on a RUNNING loop behind one primer a slot, and both counts
of the window's tokens, are ``runners/serve_mla.py``'s, imported and not
repeated. This runner replaces what those tie to their families: how the
program's config object is made from the published keys (FIRST, before
the device is opened: a program without the family fails there, at
once); the warm-up (a chunk is a WINDOW, and a later chunk's table of
earlier blocks holds the summaries of the windows before it, two blocks
each, not their keys); the reference the outputs are held to
(``benchmark/reference_evabyte.py``: no routes here, so one limit, the
chosen bytes' mean logit gap over head 0); and, in the trace reduction,
the device time of the ticks' compression (:func:`compress_seconds`),
which is no Mosaic kernel and has no name of its own in the trace.

``tokens_per_s`` counts the GENERATED bytes that arrived inside the
window, of requests that came back whole: bytes out a second, what a
caller streaming a function back feels, and every prefill's stall is in
it. The prompts' bytes are NOT added, as the MLA and linear cells add
theirs: they are three quarters of a request's bytes here and arrive in
lumps of 8k, 26 to 32 of them a window, so either way of crediting them
(as they arrive: ``detail.tokens_per_s_by_arrival``; spread over the
request's time in its slot: ``detail.tokens_per_s_in_service``) reads
which requests the seed's pool put into the window and not the engine:
6.4-8.8% and 3.2-3.5% over six seeds against 1.7% for the generated
bytes (PERF.md section 6, PR 43; half the bound is 3%). ``detail`` also
carries the engine's own gauges of the cache (resident and uncompressed
bytes, the blocks held) and the four metrics whose lists of cells a
``benchmark`` issue has to widen.
"""

from __future__ import annotations

import asyncio
import bisect
import json
import os
from typing import Any, Dict

import numpy as np

from benchmark import (client, harness, manifest, reference_evabyte, stats,
                       trace_reduce)
from benchmark.harness import say
from benchmark.readers import registry_delta
from benchmark.runners import serve_moe
from benchmark.runners.serve import _prompts, _replica_up
from benchmark.runners.serve_mla import (LEAD_IN_SERIES, _offer_after_lead_in,
                                         tokens_between, tokens_in_service)
from benchmark.runners.serve_window import chunk_programs

GAUGES = ("ray_tpu_cb_eva_cache_bytes", "ray_tpu_cb_eva_uncompressed_bytes",
          "ray_tpu_cb_kv_blocks_used", "ray_tpu_cb_kv_blocks_total")
# Metrics whose ``workloads`` lists name every serve cell that existed
# and may not be edited here (PERF.md section 7): printed under
# ``detail`` by their own files' reader and arguments.
LISTED_ELSEWHERE = ("prefill_rows_per_batch", "prefill_request_ms",
                    "admit_hold_share", "stream_tokens_per_handoff")


def eva_config(config: Dict[str, Any], **extra):
    """The program's config object from the published ``config.json``
    keys. What the program does not implement is refused here, not
    ignored."""
    from ray_tpu.models import llama

    unsupported = {
        "attention_class": config["attention_class"] != "eva",
        "attention_bias": config["attention_bias"],
        "hidden_act": config["hidden_act"] != "silu",
        "tied head": config["tie_word_embeddings"],
        "rope_scaling": config["rope_scaling"] is not None,
        "num_chunks": config["num_chunks"] is not None,
        "norm_add_unit_offset": not config["norm_add_unit_offset"],
    }
    if any(unsupported.values()):
        raise ValueError("evabyte config the program does not run: "
                         f"{[k for k, bad in unsupported.items() if bad]}")
    layers = config["num_hidden_layers"]
    return llama.LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=layers,
        layer_types=("eva_attention",) * layers,
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        eva_window=config["window_size"],
        eva_chunk=config["chunk_size"],
        num_pred_heads=config["num_pred_heads"],
        zero_centered_norms=bool(config["norm_add_unit_offset"]),
        fp32_residual=bool(config["fp32_skip_add"]),
        **extra)


def warm_up_windows(port: int, cell: Dict[str, Any], vocab: int,
                    seed: int) -> None:
    """``serve_window.warm_up_chunked`` for a cache in which a chunk is
    a window and leaves ``window / chunk_size`` summaries behind it: one
    wave for every (batch bucket, number of earlier windows) the mix can
    produce, prompts of 1, 2, ... windows so that each wave compiles ONE
    new program; which program a wave really gave is read back, and a
    wave is sent again (n + 1 and n in turn) while it is missing."""
    from ray_tpu.models.continuous_batching import PREFILL_BATCH_TOKENS

    engine, warm = cell["workload"]["engine"], cell["workload"]["warmup"]
    rng = np.random.default_rng([seed, 0x3a])
    longest = cell["traffic"]["prompt_tokens"]["max"]
    window = cell["config"]["window_size"]
    per = window // cell["config"]["chunk_size"] // engine["block_size"]
    rows_cap = max(PREFILL_BATCH_TOKENS // window, 1)
    wanted = [(n, window, i * per) for n in warm["batch_buckets"]
              if n <= min(engine["num_slots"], rows_cap)
              for i in range(-(-longest // window))]
    say(f"warm-up: {len(wanted)} prefill programs (rows, tokens, earlier "
        f"blocks of summaries) {wanted}")
    waves = 0
    for n, _, earlier in wanted:
        length = min((earlier // per + 1) * window, longest)
        for attempt in range(warm["max_attempts"]):
            if (n, window, earlier) in chunk_programs():
                break
            size = 1 if n == 1 else n + 1 - attempt % 2
            recs = asyncio.run(client.wave(port, _prompts(
                rng, vocab, [length] * size, 2), vocab))
            waves += 1
            bad = [r for r in recs if not stats.whole(r)]
            if bad:
                raise RuntimeError(f"warm-up request failed: {bad[0]}")
        if (n, window, earlier) not in chunk_programs():
            raise RuntimeError(
                f"warm-up never got the engine to run {n} rows over "
                f"{earlier} earlier blocks; compiled so far: "
                f"{sorted(chunk_programs())}")
    say(f"warm after {waves} waves: cb_prefill programs "
        f"{sorted(chunk_programs())}")


def compress_seconds(planes) -> float:
    """Device seconds the decode ticks spent closing windows inside the
    traced seconds, averaged over the chips that ran anything. The
    compression is XLA's own code, not a Mosaic kernel, and a trace names
    instructions, not the program's scopes, so it is found by where it
    stands: an EVA model's tick is ONE layer loop (no second kind beside
    it), the first ``while`` of the program, and the loop over the rows
    that close a window is the ``while`` that follows it at the
    program's top level; its whole time counts, the gather of a window's
    blocks, the pooling and the write of the summaries (a tick that
    closes nothing runs it for no time at all). The pooling a PREFILL
    chunk does is fused with the chunk's other work under instruction
    names of its own and is not counted (PERF.md section 7, PR 43)."""
    total, chips = 0.0, 0
    for lines in planes.values():
        ops = [e for e in lines.get(trace_reduce.OPS_LINE, []) if e[2] > 0]
        if not ops:
            continue
        chips += 1
        ticks = sorted((start, start + dur) for name, start, dur
                       in lines.get(trace_reduce.MODULES_LINE, [])
                       if name.startswith("jit_tick"))
        starts = [s for s, _ in ticks]
        seen: Dict[int, int] = {}      # tick -> end of its last top-level loop
        for name, start, dur in sorted(ops, key=lambda e: e[1]):
            if trace_reduce.opcode(name) != "while":
                continue
            i = bisect.bisect_right(starts, start) - 1
            if i < 0 or start >= ticks[i][1]:
                continue                # a prefill's loop
            if i not in seen:
                seen[i] = start + dur   # the layer loop
            elif start >= seen[i]:      # at the top level, behind it
                seen[i] = start + dur
                total += dur / 1e9
    return total / max(chips, 1)


# What the roofline reads a tick's work from, taken over the CAPTURE:
# the open windows' fill swings by a tenth over half a minute, so the
# 40 s window's mean against 4 s of kernel time read 80.6% on one seed
# and 101.0% on the next (PERF.md section 6, PR 43).
CAPTURED = ("ray_tpu_cb_eva_summary_keys_sum", "ray_tpu_cb_eva_window_keys_sum",
            "ray_tpu_cb_eva_summary_keys_count",
            "ray_tpu_cb_decode_tokens_total")


class Trace(serve_moe.Trace):
    """``serve_moe.Trace`` whose reduction also keeps
    :func:`compress_seconds` and the registry's deltas between the
    profiler's start and stop (``eva_capture``), for
    ``readers/eva_roofline.py``."""

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
        extra = {"eva_compress_tick_s": compress_seconds(
                     trace_reduce.load(path)),
                 "eva_capture": {name: last.get(name, 0.0)
                                 - first.get(name, 0.0)
                                 for name in CAPTURED}}
        self._detail.update(extra)
        return dict(super().reduce(), **extra)


def longest_silence(records, seconds: float) -> float:
    """The longest time inside the window between two arrivals of a
    generated byte, over all requests, by the load generator's clock."""
    arrived = sorted(t for rec in records for t in rec["t"]
                     if 0 <= t <= seconds)
    edges = [0.0] + arrived + [seconds]
    return max(b - a for a, b in zip(edges, edges[1:]))


def hold_to_reference(params, config, checks, tolerance) -> Dict[str, Any]:
    """Each check request's chosen bytes against the reference's
    teacher-forced pass over the same bytes
    (``reference_evabyte.gaps``), by the ONE LIMIT of the configuration
    file's ``tolerance_why``: ``serve_mean_logit_gap_sd``, how far the
    chosen bytes lie under the reference's maximum, in standard
    deviations of a position's logits over head 0, on average over
    every checked position. The worst gap is printed and not held."""
    longest = max(len(req["prompt"]) + len(rec["tokens"])
                  for req, rec in checks)
    gaps = np.concatenate([np.asarray(reference_evabyte.gaps(
        params, req["prompt"], rec["tokens"], config, pad_to=longest))
        for req, rec in checks])
    out = {"worst_logit_gap_sd": float(gaps.max()),
           "mean_logit_gap_sd": float(gaps.mean()),
           "bytes_not_the_argmax": int(np.count_nonzero(gaps))}
    say(f"reference: over {gaps.size} chosen bytes, mean gap "
        f"{out['mean_logit_gap_sd']:.5f} (tolerance "
        f"{tolerance['serve_mean_logit_gap_sd']}) and worst gap "
        f"{out['worst_logit_gap_sd']:.4f} logit standard deviations under "
        f"the reference maximum, {out['bytes_not_the_argmax']} bytes not "
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
    config = eva_config(cell["config"],
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
        warm_up_windows(port, cell, vocab, opts.seed)
        records, primed, setup_s, ctx = _offer_after_lead_in(
            port, cell, opts, vocab, trace)
        rng = np.random.default_rng([opts.seed, 0xc4ec])
        check_reqs = _prompts(rng, vocab, work["check"]["prompt_tokens"],
                              work["check"]["max_tokens"])
        check_recs = asyncio.run(client.wave(port, check_reqs, vocab,
                                             timeout_s=300.0))
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
    block_bytes = (after.get(GAUGES[0], 0.0)
                   and after[GAUGES[0]] / max(after.get(GAUGES[2], 0.0), 1))
    detail.update(
        held, **{name: after.get(name) for name in GAUGES},
        # The cache by the engine's own gauges as the window closed: the
        # blocks the live slots held over the arena's, and a block.
        arena_blocks_held_share=(after.get(GAUGES[2], 0.0)
                                 / max(after.get(GAUGES[3], 0.0), 1)),
        arena_block_bytes=block_bytes,
        **{name: registry_delta.read(ctx, **manifest.metric_file(name)["args"])
           for name in LISTED_ELSEWHERE},
        lead_in_s=work["lead_in_s"], lead_in=ctx["lead_in"],
        # The engine thread's timeline over the WINDOW, as ``lead_in`` has
        # it over the lead-in, and the longest stretch of the window in
        # which no byte of any request arrived: one run in ten holds a
        # silence of seconds (PERF.md section 7), and this says whose.
        window={name: after.get(name, 0.0)
                - ctx["registry_before"].get(name, 0.0)
                for name in LEAD_IN_SERIES},
        longest_silence_s=longest_silence(records, opts.seconds),
        requests_whole=len(good), requests_sent=len(records),
        requests_whole_by_drain=len(whole),
        # With the prompts' bytes, by both rules (see the module's note).
        prompt_tokens_in_window=prompt_tokens,
        generated_tokens_in_window=generated,
        tokens_per_s_by_arrival=(prompt_tokens + generated) / opts.seconds,
        tokens_per_s_in_service=tokens_in_service(
            records, opts.seconds) / opts.seconds,
        first_tokens_in_window=sum(0 < r["first"] <= opts.seconds
                                   for r in whole),
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
                       "tokens_per_s": generated / opts.seconds},
        "device": dict(info, memory_peak_bytes=peak),
        "trace": trace, "ctx": ctx, "detail": detail,
    }
