"""ray_tpu headline benchmark: Llama train-step throughput on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...diag}.

The north-star target (BASELINE.md) is >=90% of an H100+NCCL stack's
tokens/sec/chip on Llama-2-7B. A single v5e chip cannot hold 7B + optimizer,
so the bench runs a ~1B-param Llama (same architecture, same kernels, bf16,
flash attention, remat scan) and reports **model FLOPs utilization** — the
chip-count- and chip-generation-independent measure of the training stack.
``vs_baseline`` = achieved MFU / 0.45 (0.45 ~= strong H100+NCCL LLM-training
MFU, the normalized form of BASELINE.json's tokens/sec/chip criterion).

The bench needs a TPU: without one, or on a device whose peak is not in
``PEAK_FLOPS``, it raises instead of timing something else. It times
several independent rounds and emits every round's time beside the best,
so one slow round is visible in the artifact.
"""

from __future__ import annotations

import json
import sys
import time

import os

import jax
import jax.numpy as jnp

PEAK_FLOPS = {
    # bf16 peak per chip
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5": 459e12,        # v5p
    "TPU v4": 275e12,
    "TPU v6 lite": 918e12,   # v6e
}
BASELINE_MFU = 0.45


def _peak_flops(device) -> float:
    kind = device.device_kind
    for name, peak in PEAK_FLOPS.items():
        if kind.startswith(name):
            return peak
    raise ValueError(f"no bf16 peak on record for device_kind {kind!r}; "
                     "add it to PEAK_FLOPS with its source")


def _train_flops(config, n_params: int, n_batch: int, seq_len: int) -> int:
    """fwd+bwd FLOPs for one step: 6*P per token, plus causal attention
    12 * L * H * D * S^2 / 2 per batch element. Single source of truth —
    the headline MFU and the microbatch sweep must stay comparable."""
    model = 6 * n_params * n_batch * seq_len
    attn = (12 * config.num_layers * config.num_heads * config.head_dim
            * seq_len * seq_len * n_batch // 2)
    return model + attn


def main() -> None:
    from ray_tpu.models import llama
    from ray_tpu.models.training import (
        ShardedTrainer, default_optimizer, synthetic_batch,
    )
    from ray_tpu.parallel import MeshConfig, make_mesh

    if jax.default_backend() != "tpu":
        raise RuntimeError(
            f"bench.py measures the chip; JAX reports backend "
            f"{jax.default_backend()!r}. A CPU timing is not a result.")
    peak = _peak_flops(jax.devices()[0])   # unknown device: fail first
    config = llama.LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_layers=16, num_heads=16, num_kv_heads=16, head_dim=128,
        max_seq_len=2048, remat=True,
        remat_policy=os.environ.get("RAY_TPU_BENCH_REMAT", "full"),
    )
    # Batch 6 does not fit one v5e (optimizer moments already bf16); the
    # remat policies' relative cost is not measured on the current code.
    batch_size = int(os.environ.get("RAY_TPU_BENCH_BATCH", 5))
    seq_len = 2048
    rounds, steps_per_round = 3, 5

    # Is the pallas flash kernel engaged for this shape (vs XLA fallback)?
    from ray_tpu.ops.attention import flash_applicable
    flash_engaged = flash_applicable(seq_len, seq_len, config.head_dim)

    mesh = make_mesh(MeshConfig(fsdp=-1), devices=jax.devices()[:1])
    trainer = ShardedTrainer(
        config, mesh,
        optimizer=default_optimizer(warmup_steps=10, total_steps=1000),
    )
    state = trainer.init_state(0)
    batch = trainer.shard_batch(
        synthetic_batch(batch_size, seq_len, config.vocab_size)
    )

    # Warmup (compile) then timed rounds, each closed by a host fetch of
    # the loss (which waits for the device).
    t0 = time.perf_counter()
    state, metrics = trainer.train_step(state, batch)
    float(metrics["loss"])
    compile_s = time.perf_counter() - t0

    # One extra synced step: measures dispatch+execute+fetch latency, and
    # absorbs any first-execution overhead that follows compilation.
    t0 = time.perf_counter()
    state, metrics = trainer.train_step(state, batch)
    float(metrics["loss"])
    synced_step_s = time.perf_counter() - t0

    round_times = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(steps_per_round):
            state, metrics = trainer.train_step(state, batch)
        float(metrics["loss"])
        round_times.append((time.perf_counter() - t0) / steps_per_round)
    step_time = min(round_times)

    cache_misses = None
    try:  # detect silent recompiles during the timed loop
        cache_misses = trainer._step._cache_size()
    except Exception:
        pass

    # ---- input pipeline: prefetch off vs on ------------------------------
    # "Off" reproduces the r05 real-loop shape: host batch assembly +
    # synchronous shard_batch + a per-step loss fetch, all inside the
    # step loop. "On" stages batches through the DevicePrefetcher's
    # background double/triple buffer and drives the AsyncStepLoop with
    # windowed metric fetches — the configuration the gap acceptance
    # (synced_step_s - step_time_s cut >=2x, stall fraction <5%) grades.
    from ray_tpu.train.ingest import DevicePrefetcher, synthetic_host_batches
    from ray_tpu.train.loop import AsyncStepLoop

    pipe_steps = rounds * steps_per_round
    t0 = time.perf_counter()
    for hb in synthetic_host_batches(batch_size, seq_len,
                                     config.vocab_size, pipe_steps):
        state, metrics = trainer.train_step(state, trainer.shard_batch(hb))
        float(metrics["loss"])
    host_loop_step_s = (time.perf_counter() - t0) / pipe_steps

    pf = DevicePrefetcher(
        synthetic_host_batches(batch_size, seq_len, config.vocab_size,
                               pipe_steps + 1),
        trainer, depth=3, name="bench")
    loop = AsyncStepLoop(trainer, state, sync_every=4, name="bench")
    loop.step(next(pf))   # warm the window + fill the buffer...
    loop.sync()
    pf.reset_stats()      # ...then measure steady state only
    t0 = time.perf_counter()
    state, _ = loop.run(pf)
    pipe_wall = time.perf_counter() - t0
    pipelined_step_s = pipe_wall / pipe_steps
    stall = pf.stats()
    pf.close()
    n_params = llama.num_params(config)

    # ---- gradient-accumulation microbatch sweep (M in {1, 2, 4}) ---------
    # Global batch fixed (largest multiple of 4 <= batch_size) so the
    # three points compare step time at IDENTICAL tokens/step; the carry
    # accumulates in the params' dtype to keep HBM flat. OOM at a sweep
    # point is reported, not fatal — the headline metric stands alone.
    # Free the headline trainer first: on TPU the 1B headline sits within
    # ~400MB of OOM, so a sweep point's second params+optimizer copy only
    # fits once state/loop/batch drop their references.
    state = batch = loop = trainer = None
    sweep_global = max(4, batch_size - batch_size % 4)
    microbatch_sweep = []
    for m_count in (1, 2, 4):
        entry = {"microbatches": m_count,
                 "global_batch": sweep_global,
                 "micro_batch": sweep_global // m_count}
        try:
            tr_m = ShardedTrainer(
                config, mesh,
                optimizer=default_optimizer(warmup_steps=10,
                                            total_steps=1000),
                microbatches=m_count, grad_accum_dtype=config.dtype)
            st_m = tr_m.init_state(0)
            b_m = tr_m.shard_batch(
                synthetic_batch(sweep_global, seq_len, config.vocab_size))
            st_m, mm = tr_m.train_step(st_m, b_m)   # compile
            float(mm["loss"])
            t0 = time.perf_counter()
            for _ in range(steps_per_round):
                st_m, mm = tr_m.train_step(st_m, b_m)
            float(mm["loss"])
            m_step = (time.perf_counter() - t0) / steps_per_round
            m_tokens_s = sweep_global * seq_len / m_step
            entry["step_time_s"] = round(m_step, 4)
            entry["tokens_per_sec_per_chip"] = round(m_tokens_s, 1)
            m_flops = _train_flops(config, n_params, sweep_global, seq_len)
            entry["mfu"] = round(m_flops / m_step / peak, 4)
        except Exception as e:  # noqa: BLE001 — typically OOM at 1B
            entry["error"] = f"{type(e).__name__}: {str(e)[:200]}"
        finally:
            # Drop the point's state either way: an OOM'd point must not
            # keep its params+optimizer moments alive into the next M.
            st_m = tr_m = b_m = mm = None
        microbatch_sweep.append(entry)

    # ---- RL post-training loop: weight-sync + rollout phase --------------
    # Generate → publish → subscribe → tick-boundary swap on a tiny llama
    # through the REAL engine and sync plane (ray_tpu/rl): per-sync
    # latency p50/p95 (publish through swapped-live), sync bytes/s,
    # rollout staleness, and tokens generated between syncs. Gated: an
    # rl_loop failure reports in the artifact, never sinks the headline.
    rl_loop = {}
    try:
        import numpy as np

        from ray_tpu.models.continuous_batching import ContinuousBatcher
        from ray_tpu.rl import (RolloutScheduler, WeightPublisher,
                                WeightSubscriber)

        tiny = llama.LlamaConfig.tiny()
        rl_tokens: dict = {}
        eng = ContinuousBatcher(
            tiny, num_slots=4, max_len=64,
            token_callback=lambda rid, t:
                rl_tokens.setdefault(rid, []).append(t))
        pub = WeightPublisher(run="bench_rl", n_subscribers=1)
        sub = WeightSubscriber(pub.subscriber_spec(0), run="bench_rl")

        def rl_generate(prompt, max_new):
            rid = eng.submit(list(prompt), max_new_tokens=max_new)
            while True:
                if rid in eng.step():
                    break
            out = rl_tokens.pop(rid, [])
            lps = (np.asarray(eng.score_logprobs(prompt, out), np.float32)
                   if out else np.zeros(0, np.float32))
            return out, lps, eng.weight_version

        sched = RolloutScheduler(rl_generate, lambda: pub.version,
                                 run="bench_rl")
        sync_times, tokens_between, total_bytes = [], [], 0
        rl_rounds, rl_prompts, rl_new = 4, 2, 8
        for r in range(rl_rounds):
            n = sched.collect([[1 + r, 2, 3]] * rl_prompts, rl_new,
                              lambda p, t: float(len(t)))
            tokens_between.append(n * rl_new)
            # The publisher ships the canonical tree; the engine's own
            # holds the q/k/v projections heads-major.
            faked = jax.tree.map(lambda a: (a * 0.999).astype(a.dtype),
                                 llama.canonical_layout(eng.params))
            t0 = time.perf_counter()
            manifest = pub.publish(faked, step=r)
            got = sub.poll(timeout=5.0)
            if got is not None:
                m, params = got
                eng.swap_params(params, version=int(m["version"]))
            sync_times.append(time.perf_counter() - t0)
            total_bytes += manifest["bytes"]
        sync_times.sort()
        staleness = sched.buffer.staleness()
        rl_loop = {
            "sync_p50_s": round(sync_times[len(sync_times) // 2], 5),
            "sync_p95_s": round(sync_times[-1], 5),
            "sync_bytes_per_s": round(
                total_bytes / max(sum(sync_times), 1e-9), 1),
            "rollout_staleness_max": max(staleness) if staleness else 0,
            "tokens_between_syncs": (
                sum(tokens_between) / len(tokens_between)),
            "generator_version": eng.weight_version,
            "trainer_version": pub.version,
        }
        pub.destroy()
    except Exception as e:  # noqa: BLE001 — report, don't sink the bench
        rl_loop = {"error": f"{type(e).__name__}: {str(e)[:200]}"}

    tokens_per_step = batch_size * seq_len
    tokens_per_sec = tokens_per_step / step_time
    flops_per_sec = (
        _train_flops(config, n_params, batch_size, seq_len) / step_time)
    mfu = flops_per_sec / peak

    result = {
        "metric": "llama1b_train_mfu",
        "value": round(mfu, 4),
        "unit": "mfu",
        "vs_baseline": round(mfu / BASELINE_MFU, 4),
        "tokens_per_sec_per_chip": round(tokens_per_sec, 1),
        "step_time_s": round(step_time, 4),
        "n_params": n_params,
        # diagnostics: every round, so a single slow one shows.
        "round_step_times_s": [round(t, 4) for t in round_times],
        "synced_step_s": round(synced_step_s, 4),
        # Input pipeline: the host-in-loop gap vs the prefetch+async gap
        # (per-step overhead above the pure device step time). Acceptance:
        # pipelined_gap_s <= synced_gap_s / 2 and input_stall_frac < 0.05.
        "host_loop_step_s": round(host_loop_step_s, 4),
        "pipelined_step_s": round(pipelined_step_s, 4),
        "synced_gap_s": round(synced_step_s - step_time, 4),
        "host_loop_gap_s": round(host_loop_step_s - step_time, 4),
        "pipelined_gap_s": round(pipelined_step_s - step_time, 4),
        "input_stall_frac": round(stall["input_stall_frac"], 4),
        "ingest_bytes_per_s": round(stall["bytes_per_s"], 1),
        "prefetch_avg_occupancy": round(stall["avg_occupancy"], 3),
        "tokens_per_sec_per_chip_pipelined": round(
            tokens_per_step / pipelined_step_s, 1),
        "microbatch_sweep": microbatch_sweep,
        "rl_loop": rl_loop,
        "compile_s": round(compile_s, 2),
        "flash_kernel": flash_engaged,
        "jit_cache_entries": cache_misses,
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
    }
    if max(round_times) > 3 * min(round_times):
        print(f"WARNING: unstable round times {round_times}, rerun advised",
              file=sys.stderr)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
