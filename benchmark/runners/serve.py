"""A serving cell: the served path as a user meets it.

``ray_tpu.init`` -> ``serve.run(build_continuous_llama_app(...))`` ->
``serve.start_http`` (the set-up ``chip_smoke.py`` proved on the chip),
one replica on one chip, loaded over HTTP on the streamed route by a
child process that never imports JAX. The engine runs every switch at
its default; the cell file gives sizes only.
"""

from __future__ import annotations

import asyncio
import json
import os
import re
import subprocess
import sys
import time
from typing import Any, Dict, List, Set, Tuple

import numpy as np

from benchmark import client, harness, manifest, reference, stats, traffic
from benchmark.harness import say

DEPLOYMENT = "ContinuousLlamaDeployment"


def _replica_up(timeout_s: float = 240.0) -> None:
    """Wait until the controller routes one replica that answers a
    health call (``chip_smoke.py::_replicas``)."""
    import ray_tpu

    controller = ray_tpu.get_actor("__serve_controller__")
    deadline = time.monotonic() + timeout_s
    last = None
    while time.monotonic() < deadline:
        reps = ray_tpu.get(controller.get_replicas.remote(DEPLOYMENT),
                           timeout=30)
        if len(reps) == 1:
            try:
                ray_tpu.get(reps[0].health.remote(), timeout=5)
                return
            except Exception as e:  # noqa: BLE001 — constructing, or dead
                last = e
        time.sleep(0.2)
    raise RuntimeError(f"no healthy replica of {DEPLOYMENT} in "
                       f"{timeout_s:.0f}s; last health call: {last!r}")


def _prefill_shapes() -> Set[Tuple[int, int]]:
    """(rows, padded length) of every ``cb_prefill`` program compiled so
    far, read off the monitor's signature strings (the token matrix is
    the widest ``int32[n,s]`` argument). The monitor has no public view
    of signatures yet: PERF.md lists it."""
    from ray_tpu._private import xla_monitor

    rec = xla_monitor._programs.get("cb_prefill")
    shapes = set()
    for sig in (rec.signatures.values() if rec else ()):
        pairs = re.findall(r"int32\[(\d+),(\d+)\]", sig["signature"])
        if pairs:
            shapes.add(max(((int(n), int(s)) for n, s in pairs),
                           key=lambda p: p[1]))
    return shapes


def _prompts(rng, vocab: int, lengths: List[int], max_tokens: int):
    return [{"prompt": rng.integers(1, vocab, n).tolist(),
             "max_tokens": max_tokens} for n in lengths]


def _warm_up(port: int, cell: Dict[str, Any], vocab: int, seed: int) -> None:
    """One wave for every (batch bucket, length bucket) the mix can
    produce, and nothing else. The engine batches what is queued when it
    next admits: of a wave sent to an idle engine the first request is
    admitted alone and the rest, which arrive during its prefill,
    together. So a wave of n + 1 gives the n-row program; which program
    a wave really gave is read back, and a wave is sent again (n + 1
    and n in turn) while its program is missing."""
    from ray_tpu.models.continuous_batching import _bucket

    engine, warm = cell["workload"]["engine"], cell["workload"]["warmup"]
    rng = np.random.default_rng([seed, 0x3a])
    spec = cell["traffic"]["prompt_tokens"]
    longest = spec["value"] if spec["dist"] == "constant" else spec["max"]
    wanted = [(n, s)
              for s in traffic.prompt_buckets(cell["traffic"], _bucket,
                                              engine["block_size"])
              for n in warm["batch_buckets"]
              if n <= engine["num_slots"] and n * s <= warm["max_batch_tokens"]]
    say(f"warm-up: {len(wanted)} prefill programs {wanted}")
    waves = 0
    for n, s in wanted:
        for attempt in range(warm["max_attempts"]):
            if (n, s) in _prefill_shapes():
                break
            size = 1 if n == 1 else n + 1 - attempt % 2
            recs = asyncio.run(client.wave(port, _prompts(
                rng, vocab, [min(s, longest)] * size, 2), vocab))
            waves += 1
            bad = [r for r in recs if not stats.whole(r)]
            if bad:
                raise RuntimeError(f"warm-up request failed: {bad[0]}")
        if (n, s) not in _prefill_shapes():
            raise RuntimeError(
                f"warm-up never got the engine to batch {n} x {s}; "
                f"compiled so far: {sorted(_prefill_shapes())}")
    say(f"warm after {waves} waves: cb_prefill programs "
        f"{sorted(_prefill_shapes())}")


def _offer(port: int, cell: Dict[str, Any], opts, vocab: int, trace):
    """Start the load generator, open the window, bracket it with
    snapshots, trace a few seconds in its middle; returns (records,
    setup_s, context for the readers)."""
    mix = cell["traffic"]
    child = subprocess.Popen(
        [sys.executable, os.path.join(manifest.HERE, "client.py"),
         "--port", str(port), "--traffic", json.dumps(mix),
         "--seed", str(opts.seed), "--seconds", str(opts.seconds),
         "--vocab", str(vocab)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    try:
        ready = child.stdout.readline().strip()
        if ready != "READY":
            raise RuntimeError(f"load generator said {ready!r}, not READY")
        ctx = {"registry_before": harness.registry_snapshot(),
               "compiles_before": harness.compile_snapshot()}
        t0 = time.monotonic() + 0.25
        child.stdin.write(f"GO {t0!r}\n")
        child.stdin.flush()
        setup_s = t0 - opts.t_start
        say(f"window open: set-up took {setup_s:.2f}s")
        work = cell["workload"]
        if opts.trace:
            time.sleep(max(t0 + work["trace_after_s"] - time.monotonic(), 0))
            trace.start()
            time.sleep(work["trace_seconds"])
            trace.stop()
        time.sleep(max(t0 + opts.seconds - time.monotonic(), 0))
        ctx["registry_after"] = harness.registry_snapshot()
        ctx["compiles_after"] = harness.compile_snapshot()
        say("window closed; draining")
        out, _ = child.communicate(
            timeout=mix.get("drain_s", 0) + 60)
        if child.returncode != 0:
            raise RuntimeError(f"load generator exited {child.returncode}")
        records = json.loads(out.strip().splitlines()[-1])["records"]
        for rec in records:
            for key in ("due", "sent", "first", "last"):
                if rec[key] is not None:
                    rec[key] -= t0
            rec["t"] = [t - t0 for t in rec["t"]]
        return records, setup_s, ctx
    finally:
        if child.poll() is None:
            child.kill()
        child.wait()


def _measured(records, mix, seconds: float):
    """Open loop: every request due inside the window (all of them).
    Closed loop: those that ended inside it, whole or failed; what was
    in flight when it closed was dropped by the generator."""
    if mix["loop"] == "open":
        return records
    return [r for r in records
            if r["error"] is not None or (r["done"] and r["last"] <= seconds)]


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
    longest = max(len(req["prompt"]) + len(rec["tokens"])
                  for req, rec in checks)
    for req, rec in checks:
        gaps = reference.chosen_gaps(params, req["prompt"], rec["tokens"],
                                     config, pad_to=longest)
        worst = max(worst, float(np.max(np.asarray(gaps))))
    tol = cell["config"]["tolerance"]["serve_logit_gap_sd"]
    say(f"reference: worst chosen-token gap {worst:.4f} logit standard "
        f"deviations under the reference maximum (tolerance {tol})")
    return worst <= tol, worst


def run(cell: Dict[str, Any], opts) -> Dict[str, Any]:
    work, mix = cell["workload"], cell["traffic"]
    os.environ.update(work.get("env", {}))
    info = harness.open_device(cell["chips"], opts.rehearse)
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import build_continuous_llama_app

    config = manifest.llama_config(cell["config"],
                                   max_seq_len=work["engine"]["max_len"])
    vocab = config.vocab_size
    trace = harness.Trace(opts.trace, opts.keep_trace)
    # No chips to detect on the CPU: name one, so placement runs its
    # real path (as chip_smoke.py --rehearse does).
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
    tokens = sum(r["prompt_tokens"] + r["n"] for r in good)
    end_to_end = {"setup_s": setup_s, "tokens_per_s": tokens / opts.seconds}
    # ``ttft_p<q>_ms``, ``tpot_p<q>_ms`` and ``itl_p<q>_ms``: whichever
    # percentiles the cell's entries in BENCHMARK.json name.
    samples = {"ttft": ttft, "tpot": stats.tpot_ms(good),
               "itl": stats.itl_ms(good)}
    for name in manifest.names(cell["end_to_end"]):
        match = re.fullmatch(r"(ttft|tpot|itl)_p(\d+)_ms", name)
        if match and samples[match.group(1)]:
            end_to_end[name] = stats.percentile(samples[match.group(1)],
                                                int(match.group(2)))
    ctx.update(measured=good, engine=work["engine"])
    if opts.keep_records:
        os.makedirs(opts.keep_records, exist_ok=True)
        with open(os.path.join(opts.keep_records,
                               f"{cell['name']}.{opts.seed}.json"), "w") as f:
            json.dump({"records": measured, "seconds": opts.seconds}, f)
    return {
        "correct": bool(failed == 0 and checks_whole and ref_ok),
        "attempted": len(measured), "failed": failed,
        "end_to_end": end_to_end,
        "device": dict(info, memory_peak_bytes=peak),
        "trace": trace, "ctx": ctx,
        "detail": {"worst_logit_gap_sd": worst_gap,
                   "requests_whole": len(good),
                   "generated_tokens": sum(r["n"] for r in good),
                   "prompt_tokens": sum(r["prompt_tokens"] for r in good),
                   "ttft_max_ms": max(ttft) if ttft else None,
                   # A queue that grows shows as a later half slower than
                   # the earlier: how the knee was judged in the sweep.
                   "ttft_p50_ms_by_half": [
                       stats.percentile(half, 50) if half else None
                       for half in (
                           stats.ttft_ms([r for r in good if (
                               r["due"] < opts.seconds / 2) == first])
                           for first in (True, False))],
                   "last_finished_s": max((r["last"] for r in good),
                                          default=None)},
    }
