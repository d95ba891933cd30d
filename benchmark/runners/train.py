"""A training cell: ``ShardedTrainer`` on a mesh of the cell's chips, a
fresh seeded batch every step through ``DevicePrefetcher`` and
``AsyncStepLoop`` (the loop ``bench.py`` drives), timed on the host
clock at the fetches that wait for the device."""

from __future__ import annotations

import math
import time
from typing import Any, Dict

from benchmark import harness, manifest, reference, traffic
from benchmark.harness import say


def run(cell: Dict[str, Any], opts) -> Dict[str, Any]:
    info = harness.open_device(cell["chips"], opts.rehearse)
    import jax
    import numpy as np

    from ray_tpu.models.training import ShardedTrainer, default_optimizer
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.train.ingest import DevicePrefetcher
    from ray_tpu.train.loop import AsyncStepLoop

    job, work = cell["traffic"], cell["workload"]
    config = manifest.llama_config(
        cell["config"], max_seq_len=job["sequence_tokens"], remat=True,
        remat_policy=work["remat_policy"])
    mesh = make_mesh(MeshConfig(**work["mesh"]),
                     devices=jax.devices()[:cell["chips"]])
    trainer = ShardedTrainer(config, mesh, optimizer=default_optimizer())
    vocab = config.vocab_size
    tokens_per_step = job["batch_sequences"] * job["sequence_tokens"]

    state = trainer.init_state(opts.seed)
    batches = traffic.train_batches(job, opts.seed, vocab)
    first_batch = next(traffic.train_batches(job, opts.seed, vocab))
    prefetch = DevicePrefetcher(batches, trainer, depth=3, name="benchmark")
    loop = AsyncStepLoop(trainer, state, sync_every=work["sync_every"],
                         name="benchmark")
    del state
    try:
        # Warm-up: the step that compiles, and one more. The first runs
        # on the seed's own parameters and the seed's first batch, so
        # its loss is the one the reference must reproduce.
        for _ in range(work["warmup_steps"]):
            loop.step(next(prefetch))
            loop.sync()
        first_loss = loop.history[0]["loss"]
        say(f"warm: first-step loss {first_loss:.5f}")
        prefetch.reset_stats()
        before = harness.compile_snapshot()
        trace = harness.Trace(opts.trace, opts.keep_trace)
        trace_from = work["trace_after_steps"]
        trace_to = trace_from + work["trace_steps"]

        synced = len(loop.history)
        windows = []                      # (seconds, steps) per fetch
        done = 0                          # steps closed by a fetch
        t0 = last = time.monotonic()
        setup_s = t0 - opts.t_start
        while True:
            if done >= trace_from:
                trace.start()             # once; a no-op afterwards
            loop.step(next(prefetch))
            closed = len(loop.history) - synced
            if closed > done:             # a fetch waited for the device
                now = time.monotonic()
                windows.append((now - last, closed - done))
                last, done = now, closed
                if done >= trace_to:
                    trace.stop()
                if now - t0 >= opts.seconds:
                    break
        trace.stop()
        window_s = last - t0
        after = harness.compile_snapshot()
        peak = harness.memory_peak_bytes(cell["chips"])
        losses = [h["loss"] for h in loop.history]
        stall = prefetch.stats()
    finally:
        prefetch.close()
    say(f"{done} steps in {window_s:.2f}s; input stall {stall}")

    tokens_per_s = done * tokens_per_step / window_s
    finite = all(math.isfinite(x) for x in losses)
    # Outside the window: the trained state goes, the seed's parameters
    # come back, and the plain reference computes the first batch's loss.
    for leaf in jax.tree.leaves(loop.state):
        leaf.delete()
    params = trainer.init_state(opts.seed).params
    nll = sum(float(reference.loss(params, np.asarray(seq), config))
              for seq in first_batch["tokens"])
    ref_loss = nll / (job["batch_sequences"] * (job["sequence_tokens"] - 1))
    rel = abs(first_loss - ref_loss) / abs(ref_loss)
    tol = cell["config"]["tolerance"]["train_loss_rel"]
    say(f"first-step loss {first_loss:.6f} vs reference {ref_loss:.6f}: "
        f"relative difference {rel:.2e} (tolerance {tol:.0e}); "
        f"all {len(losses)} losses finite: {finite}")
    return {
        "correct": bool(finite and rel <= tol),
        "attempted": done, "failed": 0,
        "end_to_end": {"tokens_per_s": tokens_per_s, "setup_s": setup_s},
        "device": dict(info, memory_peak_bytes=peak),
        "trace": trace,
        "ctx": {"step_windows": windows,
                "compiles_before": before, "compiles_after": after},
        "detail": {"first_loss": first_loss, "reference_loss": ref_loss,
                   "loss_rel_diff": rel, "steps": done,
                   "window_s": window_s, "input_stall": stall},
    }
