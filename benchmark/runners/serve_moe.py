"""A serving cell of the OLMoE family (a config with routed experts).

The served path is ``runners/serve.py``'s to the letter: the same
deployment class on the same route, the same replica wait, warm-up,
load generator and window. This runner replaces only what that one
ties to the dense Llama block: how the program's config object is made
from the published keys, the reference the outputs are held to
(``benchmark/reference_olmoe.py``, and the router's choices beside the
chosen-token gap), the trace reduction, which here also keeps own
seconds and call counts BY KERNEL NAME and by program beside the
unchanged top-10 ``breakdown``, and HOW ``tokens_per_s`` counts a closed
loop of long answers (:func:`tokens_in_window`; ``attempted`` and
``failed`` are ``runners/serve.py``'s). What the benchmark lists for other
cells only (``tick_wall_ms``, ``prefill_batch_ms``, ``slot_occupancy``,
time to first token) is printed under ``detail``.
"""

from __future__ import annotations

import asyncio
import bisect
import importlib
import json
import os
import re
import shutil
from collections import defaultdict
from typing import Any, Dict, List, Tuple

import numpy as np

from benchmark import (client, harness, manifest, reference_olmoe, stats,
                       trace_reduce)
from benchmark.harness import say
from benchmark.runners.serve import (_measured, _offer, _prompts,
                                     _replica_up, _warm_up)

# Per-layer metrics the benchmark restricts to other cells: read here
# through their own metric files and printed under ``detail``.
DETAIL_METRICS = ("tick_wall_ms", "prefill_batch_ms", "slot_occupancy",
                  "engine_queue_ms", "tick_thread_host_share")


def olmoe_config(config: Dict[str, Any], **extra):
    """The program's config object from the published ``config.json``
    keys. QK-norm is the family's (``model_type``), not a key: see the
    configuration file's ``assumed``."""
    from ray_tpu.models import llama

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
        num_experts=config["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        qk_norm=config["model_type"] == "olmoe",
        **extra)


def kernel_name(event_name: str) -> str:
    """``%moe_gmm.12 = ...`` -> ``moe_gmm``: a Pallas ``name=`` heads
    the instruction's own name."""
    head = event_name.partition(" = ")[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def by_kernel(planes: Dict[str, trace_reduce.Plane]) -> Dict[str, Any]:
    """Own seconds and calls of every Mosaic kernel, by kernel name and
    by the program (``XLA Modules`` event) it ran inside, and each
    program's seconds and calls; averaged over the chips that ran
    anything."""
    kernels: Dict[str, Dict[str, List[float]]] = defaultdict(
        lambda: defaultdict(lambda: [0.0, 0]))
    programs: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    chips = 0
    for lines in planes.values():
        ops = [e for e in lines.get(trace_reduce.OPS_LINE, []) if e[2] > 0]
        if not ops:
            continue
        chips += 1
        modules = sorted(lines.get(trace_reduce.MODULES_LINE, []),
                         key=lambda e: e[1])
        starts = [s for _, s, _ in modules]
        for name, _, dur in modules:
            entry = programs[re.sub(r"\(\d+\)$", "", name)]
            entry[0] += dur / 1e9
            entry[1] += 1
        for (name, start, _), own, _ in trace_reduce.self_times(ops):
            if trace_reduce.MOSAIC_TARGET not in name:
                continue
            i = bisect.bisect_right(starts, start) - 1
            inside = (i >= 0 and start < modules[i][1] + modules[i][2])
            program = (re.sub(r"\(\d+\)$", "", modules[i][0])
                       if inside else "")
            entry = kernels[kernel_name(name)][program]
            entry[0] += own / 1e9
            entry[1] += 1
    n = max(chips, 1)
    return {
        "kernels": {k: {p: [s / n, c / n] for p, (s, c) in v.items()}
                    for k, v in kernels.items()},
        "programs": {p: [s / n, c / n] for p, (s, c) in programs.items()}}


class Trace(harness.Trace):
    """``harness.Trace`` whose reduction also keeps :func:`by_kernel`,
    for ``readers/kernel_time.py`` and, under ``detail``, for the line."""

    def __init__(self, enabled, keep_dir, detail: Dict[str, Any]):
        super().__init__(enabled, keep_dir)
        self._detail = detail

    def reduce(self):
        if self._state == "off":
            return None
        path = trace_reduce.find(harness.TRACE_DIR)
        if path is None:
            raise RuntimeError(
                f"the profiler wrote no trace under {harness.TRACE_DIR}")
        planes = trace_reduce.load(path)
        extra = by_kernel(planes)
        self._detail.update(extra)
        say(f"trace {path} ({os.path.getsize(path) / 1e6:.1f} MB) reduced")
        if self.keep_dir:
            os.makedirs(self.keep_dir, exist_ok=True)
            shutil.copy(path, self.keep_dir)
        shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
        return dict(trace_reduce.reduce(planes), **extra)


def _check_against_reference(cell, config, checks) -> Tuple[bool, float, float]:
    """After the replica is gone: rebuild the engine's weights (the
    served path always seeds them with 0), hold each token the engine
    chose to the reference's logits, and the experts the program's own
    bf16 forward routes each position to, to the reference's."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama

    # The replica's tick thread never exits, so its engine is never
    # collected: free the chip by deleting every buffer the process has.
    for array in jax.live_arrays():
        array.delete()
    params = jax.jit(lambda key: llama.init_params(config, key))(
        jax.random.PRNGKey(0))
    longest = max(len(req["prompt"]) + len(rec["tokens"])
                  for req, rec in checks)
    routes_of = jax.jit(lambda p, t: llama.forward(
        p, t, config, return_routes=True)[1])
    worst, same, seen = 0.0, 0, 0
    for req, rec in checks:
        gaps, want = reference_olmoe.gaps_and_choices(
            params, req["prompt"], rec["tokens"], config, pad_to=longest)
        worst = max(worst, float(np.max(np.asarray(gaps))))
        seq = (req["prompt"] + rec["tokens"])[:-1]
        padded = seq + [0] * (longest - len(seq))
        want = np.sort(np.asarray(want), -1)
        got = np.sort(np.asarray(routes_of(
            params, jnp.asarray(padded)[None])), -1)[:, :len(seq)]
        same += int(np.sum(np.all(got == want, axis=-1)))
        seen += want.shape[0] * want.shape[1]
    agreement = same / seen
    tol = cell["config"]["tolerance"]
    say(f"reference: worst chosen-token gap {worst:.4f} logit standard "
        f"deviations under the reference maximum (tolerance "
        f"{tol['serve_logit_gap_sd']}); {100 * agreement:.2f}% of "
        f"{seen} (layer, position) expert sets equal the reference's "
        f"(at least {100 * tol['router_agreement_min']:.0f}%)")
    ok = (worst <= tol["serve_logit_gap_sd"]
          and agreement >= tol["router_agreement_min"])
    return ok, worst, agreement


def tokens_in_window(records: List[Dict[str, Any]], seconds: float) -> int:
    """Tokens the window itself produced: of every request that came
    back whole (inside the window, or in the drain after it), the
    generated tokens that ARRIVED inside the window, and its prompt's if
    its first token did. ``runners/serve.py`` counts the tokens of
    requests that ENDED inside the window; with answers of a quarter of
    the window that estimate loses the progress of the 48 requests in
    flight at its close, a random 12% whose run-to-run spread alone (3.1%
    over six runs, PERF.md PR 25) is over half the metric's bound. A
    failed or short request still counts for nothing."""
    total = 0
    for rec in records:
        if stats.whole(rec):
            total += sum(1 for t in rec["t"] if t <= seconds)
            if rec["first"] <= seconds:
                total += rec["prompt_tokens"]
    return total


def _listed_elsewhere(ctx) -> Dict[str, Any]:
    out = {}
    for name in DETAIL_METRICS:
        spec = manifest.metric_file(name)
        reader = importlib.import_module("benchmark.readers." + spec["reader"])
        out[name] = reader.read(ctx, **spec.get("args", {}))
    return out


def run(cell: Dict[str, Any], opts) -> Dict[str, Any]:
    work, mix = cell["workload"], cell["traffic"]
    os.environ.update(work.get("env", {}))
    # First of all: a program without the family fails here, at once.
    config = olmoe_config(cell["config"],
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
    ref_ok, worst_gap, agreement = (_check_against_reference(
        cell, config, list(zip(check_reqs, check_recs)))
        if checks_whole else (False, float("nan"), float("nan")))
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
        router_agreement=agreement, requests_whole=len(good),
        # runners/serve.py's estimate, for comparison with other cells.
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
