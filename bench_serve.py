"""Serving benchmark: continuous-batching decode throughput on one chip.

Prints ONE JSON line and writes ``BENCH_SERVE_r{N}.json``.

Metric: steady-state decode tokens/sec/chip of the ContinuousBatcher
(``models/continuous_batching.py``) running the same ~1B-param Llama the
training bench uses, all KV slots saturated — PAGED KV arena by default
(block tables + optional int8 storage), which is the ISSUE-6 roofline
lever. Also reported: time-to-first-token (submit -> first streamed
token, p50/p95 over every request admitted during the run), prefill
tokens/s, and TWO per-tick bytes-read figures so regressions are
attributable:

* ``bytes_read_per_tick_cost`` — the compiled tick's ``cost_analysis()``
  harvested by the XLA monitor (static: prices the paged program at its
  worst case, every table entry live);
* ``bytes_read_per_tick_live`` — the engine's live-token accounting
  (params + live KV blocks actually streamed), which is what the
  achieved-bandwidth gauges use and what must SCALE WITH LIVE TOKENS
  rather than ``S_max``.

A ``sweep`` section measures decode tokens/s and both byte figures
across ``kv_dtype x block_size`` so the r06 entry captures the roofline
climb curve, not one point. A ``spec_phase`` section (r06+) runs the
speculative-decoding ladder — committed decode tokens/s at ``spec_k``
in {0, 2, 4} with accept rates — since a spec tick commits a variable
number of tokens, all throughput figures here are COMMITTED tokens
over wall time, never ticks times slots. A ``disagg_phase`` section
(r07+) A/Bs colocated against split prefill/decode engines on a mixed
long-prefill/long-decode backlog — TTFT/TPOT each way, KV-transfer
bytes/s over the real shm-channel path, and the export/channel/import
handoff breakdown, which must sum to the measured handoff wall.

Criterion (v5e HBM roofline): every decode tick must read the full
parameter set plus the active KV prefixes from HBM, so
``roofline_tokens_per_s = num_slots * HBM_BW / (param_bytes + kv_bytes)``
with ``kv_bytes`` priced at the ENGINE'S OWN storage (bf16 dense, or the
paged arena's bf16/int8 bytes-per-token). The criterion is 10% of the
bf16-dense roofline; none of the engine's data planes has been measured
against it on the current code. ``vs_baseline`` = achieved /
(0.10 * roofline), and ``hbm_efficiency`` reports the raw fraction.

The bench needs a TPU: without one, or on a device whose bandwidth is not
in ``HBM_GBPS``, it raises instead of timing something else.
"""

from __future__ import annotations

import json
import sys
import time

import jax

HBM_GBPS = {
    "TPU v5 lite": 819e9,   # v5e
    "TPU v5": 2765e9,       # v5p
    "TPU v4": 1228e9,
    "TPU v6 lite": 1640e9,  # v6e
}


def _hbm_bw(device) -> float:
    kind = device.device_kind
    for name, bw in HBM_GBPS.items():
        if kind.startswith(name):
            return bw
    raise ValueError(f"no HBM bandwidth on record for device_kind "
                     f"{kind!r}; add it to HBM_GBPS with its source")


def _pct(sorted_vals, q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


def _tick_cost_stats() -> tuple:
    """The compiled cb_tick's cost-analysis (bytes, flops) for the
    latest compile — zeros when the backend offers no cost analysis."""
    from ray_tpu._private import xla_monitor

    stats = xla_monitor.program_stats("cb_tick") or {}
    return (int(stats.get("bytes_accessed") or 0),
            int(stats.get("flops") or 0))


def _prefill_seconds(eng) -> float:
    """Seconds of ``eng``'s prefill batches so far (``CB_PREFILL_MS``
    books each batch's device time)."""
    from ray_tpu._private import metrics_defs as mdefs

    return mdefs.CB_PREFILL_MS.totals(eng._mtags)[0] / 1e3


def _breakdown_pcts(breakdowns) -> dict:
    """p50/p95 of the TTFT decomposition from engine request records."""
    churn = [b for b in breakdowns
             if b["outcome"] == "finished" and b["ttft_s"] is not None]
    out = {}
    for comp in ("queue", "arena_wait", "prefill", "ttft", "tpot"):
        vals = sorted(b[f"{comp}_s"] for b in churn
                      if b.get(f"{comp}_s") is not None)
        out[f"{comp}_p50_ms"] = round(_pct(vals, 0.50) * 1e3, 2)
        out[f"{comp}_p95_ms"] = round(_pct(vals, 0.95) * 1e3, 2)
    out["samples"] = len(churn)
    return out


def _prefix_phase(config, params, num_slots, max_len,
                  block_size, shared_blocks, tail_len, rounds,
                  shared_frac=0.75) -> dict:
    """Prefix-reuse churn: ``shared_frac`` of requests share one system
    prompt (``shared_blocks`` full KV blocks) ahead of a unique tail —
    the chat-fleet traffic shape prefix caching exists for. Runs the
    same schedule with the prefix cache ON and OFF and reports
    ``prefix_hit_rate``, ``prefill_tokens_saved``, effective prefill
    throughput (tokens the clients asked prefilled over the engine's own
    prefill wall time — cached tokens cost ~0), and the
    ``ttft_breakdown`` each way. The routing analog (affinity keeps a
    prefix's requests on the replica holding it) rides the same engine
    counters per replica."""
    import numpy as _np

    from ray_tpu.models.continuous_batching import ContinuousBatcher

    rng = _np.random.default_rng(17)
    shared = list(map(int, rng.integers(1, config.vocab_size,
                                        size=shared_blocks * block_size)))
    sched = []
    for i in range(rounds * num_slots):
        if (i % 4) < int(round(shared_frac * 4)):
            prompt = shared + list(map(int, rng.integers(
                1, config.vocab_size, size=tail_len)))
        else:
            prompt = list(map(int, rng.integers(
                1, config.vocab_size,
                size=shared_blocks * block_size + tail_len)))
        sched.append(prompt)
    out = {"shared_frac": shared_frac,
           "shared_prefix_tokens": len(shared)}
    for on in (True, False):
        eng = ContinuousBatcher(config, params=params,
                                num_slots=num_slots, max_len=max_len,
                                block_size=block_size, prefix_cache=on)
        # Warm-up = the steady state of a serving replica: the system
        # prompt is resident AND both prefill program shapes (cold full
        # prompt, warm suffix-after-match) are compiled before timing.
        for _ in range(2):
            eng.submit(list(sched[0]), max_new_tokens=2)
            while eng.has_work():
                eng.step()
        eng.request_breakdowns.clear()
        hit0, miss0 = eng.prefix_hit_tokens, eng.prefix_miss_tokens
        prefill0, pwall0 = eng.prefill_tokens, _prefill_seconds(eng)
        t0 = time.perf_counter()
        for prompt in sched:
            eng.submit(list(prompt), max_new_tokens=4)
            eng.step()
        while eng.has_work():
            eng.step()
        wall = time.perf_counter() - t0
        hits = eng.prefix_hit_tokens - hit0
        misses = eng.prefix_miss_tokens - miss0
        prefilled = eng.prefill_tokens - prefill0
        asked = (hits + misses) if on else prefilled
        prefill_wall = max(_prefill_seconds(eng) - pwall0, 1e-9)
        key = "cache_on" if on else "cache_off"
        out[key] = {
            "prefix_hit_rate": round(hits / max(hits + misses, 1), 4),
            "prefill_tokens": prefilled,
            "prefill_tokens_saved": hits,
            "effective_prefill_tokens_per_s": round(
                asked / prefill_wall, 1),
            "wall_s": round(wall, 3),
            "ttft_breakdown": _breakdown_pcts(eng.request_breakdowns),
        }
    on_d, off_d = out["cache_on"], out["cache_off"]
    out["prefill_tokens_saved_frac"] = round(
        on_d["prefill_tokens_saved"]
        / max(on_d["prefill_tokens_saved"] + on_d["prefill_tokens"], 1),
        4)
    out["effective_prefill_speedup"] = round(
        on_d["effective_prefill_tokens_per_s"]
        / max(off_d["effective_prefill_tokens_per_s"], 1e-9), 3)
    return out


def _measure_decode(eng, num_slots, max_len, prompt_len, ticks):
    """Steady-state decode tokens/s at full occupancy (compile warm-up
    included). Returns (tokens_per_s, mean_tick_s, live_bytes).

    Throughput is COMMITTED tokens over wall time — not slots/tick —
    because a speculative tick commits a variable number of tokens per
    slot. Buffered engines apply tokens at fetch boundaries, so the
    window is flushed (inside the timed interval) before counting."""
    def top_up():
        while len(eng._slots) + len(eng._waiting) < num_slots:
            eng.submit(list(range(1, prompt_len + 1)),
                       max_new_tokens=max_len - prompt_len - 1)
    top_up()
    for _ in range(5):
        eng.step()
        top_up()
    while eng._buf or eng._pending:  # start the window with clean books
        eng.step()
    live_before = eng.tick_bytes_estimate()
    decoded0 = eng.decoded_tokens
    nticks = ticks
    t0 = time.perf_counter()
    for _ in range(ticks):
        top_up()
        eng.step()
    while eng._buf or eng._pending:  # drain the speculative buffer
        eng.step()
        nticks += 1
    jax.block_until_ready(eng.cache.k)
    wall = time.perf_counter() - t0
    med = wall / nticks
    committed = eng.decoded_tokens - decoded0
    # Live positions grow linearly across the window, so the mean of the
    # endpoint estimates IS the window's average per-tick traffic — a
    # single start-of-window snapshot would understate it severalfold.
    live_bytes = (live_before + eng.tick_bytes_estimate()) / 2
    return committed / wall, med, live_bytes


def _spec_phase(config, params, num_slots, max_len, prompt_len, ticks,
                draft_layers_full, draft_layers_cheap) -> dict:
    """Speculative-decoding ladder (ISSUE-17 tentpole): steady-state
    decode at ``spec_k`` in {0, 2, 4}, fresh engine per point, per-tick
    sync so the spec lever is isolated from fetch buffering. Three
    drafter settings per k: ``full_draft`` (target drafts for itself —
    accept 1.0 but full-priced draft passes, isolating the VERIFY
    path's k+1-tokens-per-param-stream win), ``cheap_draft`` (the
    honest truncated-layer default — random init gives it a near-zero
    accept rate, so this is the WORST case), and ``primed_draft`` (the
    truncated drafter against a target whose post-draft layers are
    residual identities — a high-accept workload with cheap drafts,
    standing in for a trained drafter on natural text). Reported per
    point: committed decode tokens/s, accept rate, committed tokens per
    tick, and the per-slot inter-token latency (TPOT) from committed
    counts. ``speedup_at_k4`` is primed_draft k=4 over the k=0 point —
    the >=1.5x acceptance criterion at >=0.5 accept."""
    from ray_tpu.models.continuous_batching import ContinuousBatcher

    # "primed" target: output projections of every layer past the cheap
    # draft depth zeroed, so those layers are exact residual identities
    # and the truncated drafter PREDICTS THE TARGET PERFECTLY. Random
    # init can't give a shallow drafter a real accept rate, so this
    # stands in for a trained drafter on natural text: a high-accept
    # workload with honestly-priced cheap draft passes — the regime the
    # >=1.5x acceptance bound is judged on. Same architecture, same
    # per-tick FLOPs and bytes as the random target.
    primed_layers = dict(params["layers"])
    for name in ("wo", "w_down"):
        primed_layers[name] = (
            primed_layers[name].at[draft_layers_cheap:].set(0))
    primed = dict(params, layers=primed_layers)

    grid = [(0, None, "base", params)]
    for k in (2, 4):
        grid.append((k, draft_layers_full, "full_draft", params))
        if draft_layers_cheap != draft_layers_full:
            grid.append((k, draft_layers_cheap, "cheap_draft", params))
        grid.append((k, draft_layers_cheap, "primed_draft", primed))
    points = []
    eng = None
    for k, dl, label, pp in grid:
        del eng  # release the previous point's arena first
        eng = ContinuousBatcher(config, params=pp,
                                num_slots=num_slots, max_len=max_len,
                                spec_k=k, spec_draft_layers=dl,
                                spec_adaptive=False)
        tps, med, _ = _measure_decode(eng, num_slots, max_len,
                                      prompt_len, ticks)
        committed_per_tick = tps * med
        points.append({
            "label": label, "spec_k": k,
            "draft_layers": dl if k else None,
            "decode_tokens_per_s": round(tps, 1),
            "accept_rate": round(eng.spec_accept_rate, 4) if k else None,
            "committed_tokens_per_tick": round(committed_per_tick, 2),
            "tpot_ms": round(num_slots / tps * 1e3, 3),
            "mean_tick_ms": round(med * 1e3, 2),
            "tick_bytes_live": eng.tick_bytes_estimate(spec_k=k),
        })
    base_tps = points[0]["decode_tokens_per_s"]
    out = {"points": points}
    for p in points[1:]:
        if p["spec_k"] != 4:
            continue
        if p["label"] == "primed_draft":
            # The acceptance-criterion figure: cheap drafter, >=0.5
            # accept by construction.
            out["speedup_at_k4"] = round(
                p["decode_tokens_per_s"] / max(base_tps, 1e-9), 3)
            out["speedup_at_k4_accept_rate"] = p["accept_rate"]
        elif p["label"] == "full_draft":
            out["full_draft_speedup_at_k4"] = round(
                p["decode_tokens_per_s"] / max(base_tps, 1e-9), 3)
    return out


def _disagg_phase(config, params, num_slots, max_len, block_size,
                  long_prompt, short_prompt, long_new, short_new,
                  rounds) -> dict:
    """Disaggregated prefill/decode A/B (ISSUE-20 tentpole): the same
    mixed workload — alternating long-prefill requests (``long_prompt``
    tokens, ``short_new`` generated) and long-decode requests
    (``short_prompt`` tokens, ``long_new`` generated) — run colocated
    (one ``role="both"`` engine) and split (a ``role="prefill"`` engine
    handing finished KV blocks to a ``role="decode"`` engine over the
    REAL shm-channel path, ``kv_transfer.send_handoff`` →
    ``receive_handoff``). Client-visible TTFT for the split leg closes
    when ``receive_handoff`` returns: that is the moment the prefill's
    first token lands in a live decode slot and streams out. Reported:
    TTFT p50/p95 and TPOT each way, transfer bytes/s over the handoff
    wall, and the handoff latency breakdown (export/channel/import)
    from the decode engine's ``request_breakdowns`` — whose components
    must sum to the measured handoff wall
    (``breakdown_cover_frac`` ~ 1.0). Acceptance: split TTFT p95 <=
    colocated TTFT p95 on this mixed shape (long decodes hold
    colocated slots hostage; the dedicated prefill engine never
    waits on them)."""
    import numpy as _np

    from ray_tpu.models.continuous_batching import ContinuousBatcher
    from ray_tpu.serve import kv_transfer

    rng = _np.random.default_rng(23)

    # 1-in-4 requests is prefill-heavy, the rest decode-heavy: the
    # chat-fleet shape disaggregation exists for — long generations
    # hold colocated slots hostage while fresh prompts queue behind
    # them, which is exactly the contention the split topology removes.
    def _mixed(n):
        reqs = []
        for i in range(n):
            if i % 4 == 0:
                size, new = long_prompt, short_new   # prefill-heavy
            else:
                size, new = short_prompt, long_new   # decode-heavy
            reqs.append((list(map(int, rng.integers(
                1, config.vocab_size, size=size))), new))
        return reqs

    # Warm-up replays the exact workload shape with its own prompts:
    # same backlog size, same max_new mix — so every admission-batch
    # and prefill bucket the timed run hits is compiled, and the radix
    # cache cannot splice the timed prefills on either leg.
    warm = _mixed(rounds * num_slots)
    sched = _mixed(rounds * num_slots)

    def _pair(vals):
        v = sorted(vals)
        return (round(_pct(v, 0.50) * 1e3, 2),
                round(_pct(v, 0.95) * 1e3, 2))

    out = {"requests": len(sched),
           "long_prompt": long_prompt, "short_prompt": short_prompt,
           "long_new": long_new, "short_new": short_new}

    # ---- colocated leg: one engine does both phases; long decodes and
    # incoming prefills contend for the same slots and ticks.
    submit_ts = {}
    ttft = []

    def on_token(rid, _tok):
        t0 = submit_ts.pop(rid, None)
        if t0 is not None:
            ttft.append(time.perf_counter() - t0)

    colo = ContinuousBatcher(config, params=params, role="both",
                             num_slots=num_slots, max_len=max_len,
                             block_size=block_size,
                             token_callback=on_token)
    def _run_colo(reqs):
        t0 = time.perf_counter()
        for prompt, n in reqs:  # full backlog up front, same both legs
            rid = colo.submit(list(prompt), max_new_tokens=n)
            submit_ts[rid] = time.perf_counter()
        while colo.has_work():
            colo.step()
        return time.perf_counter() - t0

    _run_colo(warm)
    ttft.clear()
    submit_ts.clear()
    colo.request_breakdowns.clear()
    colo_wall = _run_colo(sched)
    colo_p50, colo_p95 = _pair(ttft)
    colo_tpot = sorted(b["tpot_s"] for b in colo.request_breakdowns
                       if b.get("tpot_s") is not None)
    out["colocated"] = {
        "ttft_p50_ms": colo_p50, "ttft_p95_ms": colo_p95,
        "tpot_p50_ms": round(_pct(colo_tpot, 0.50) * 1e3, 3),
        "wall_s": round(colo_wall, 3)}
    del colo

    # ---- split leg: dedicated prefill engine exports each parked
    # request through a real shm channel into the decode engine, gated
    # on a free decode slot (production pre-reserves; the bench polls).
    pre = ContinuousBatcher(config, params=params, role="prefill",
                            num_slots=num_slots, max_len=max_len,
                            block_size=block_size)
    # Role-specific sizing is one of disaggregation's levers: a decode
    # slot costs arena blocks, not prefill compute, so a decode-role
    # engine runs more concurrent generations than a colocated engine
    # (which must bound admission by prefill interference).
    decode_slots = 2 * num_slots
    dec = ContinuousBatcher(config, params=params, role="decode",
                            num_slots=decode_slots, max_len=max_len,
                            block_size=block_size)
    submit_ts.clear()
    split_ttft = []
    handoff_walls = []
    xfer_bytes = 0

    def _run_split(reqs):
        nonlocal xfer_bytes
        inflight = []  # sent manifests waiting on a free decode slot
        t0 = time.perf_counter()
        for prompt, n in reqs:
            rid = pre.submit(list(prompt), max_new_tokens=n)
            submit_ts[rid] = time.perf_counter()
        while (pre.has_work() or pre.handoff_ready() or inflight
               or dec.has_work()):
            if pre.has_work():
                pre.step()
            for rid in list(pre.handoff_ready()):
                # Send frees the prefill slot/blocks immediately: the
                # bytes wait in the shm channel, never on the prefill
                # engine, so the next admission wave starts now.
                ts0 = time.perf_counter()
                m = kv_transfer.send_handoff(pre, rid,
                                             deployment="bench")
                m["journaled"] = True  # bench drives the transfer
                inflight.append(
                    (m, rid, time.perf_counter() - ts0))
            while inflight and dec._free:
                m, rid, send_s = inflight.pop(0)
                tr0 = time.perf_counter()
                kv_transfer.receive_handoff(dec, m, deployment="bench")
                now = time.perf_counter()
                # Transfer wall = send + receive durations; channel
                # queue time (waiting on a decode slot) is admission
                # pressure, not transfer cost.
                handoff_walls.append(send_s + (now - tr0))
                split_ttft.append(now - submit_ts.pop(rid))
                xfer_bytes += m["nbytes"]
            if dec.has_work():
                dec.step()
        return time.perf_counter() - t0

    _run_split(warm)
    submit_ts.clear()
    split_ttft.clear()
    handoff_walls.clear()
    xfer_bytes = 0
    pre.request_breakdowns.clear()
    dec.request_breakdowns.clear()
    split_wall = _run_split(sched)
    split_p50, split_p95 = _pair(split_ttft)
    split_tpot = sorted(b["tpot_s"] for b in dec.request_breakdowns
                        if b.get("tpot_s") is not None)
    comps = [b["handoff"] for b in dec.request_breakdowns
             if b.get("handoff")]
    breakdown = {}
    comp_total = 0.0
    for leg in ("export_s", "channel_s", "import_s"):
        vals = [c.get(leg, 0.0) for c in comps]
        comp_total += sum(vals)
        p50, p95 = _pair(vals)
        breakdown[leg.replace("_s", "_p50_ms")] = p50
        breakdown[leg.replace("_s", "_p95_ms")] = p95
    wall_total = sum(handoff_walls)
    out["split"] = {
        "decode_slots": decode_slots,
        "ttft_p50_ms": split_p50, "ttft_p95_ms": split_p95,
        "tpot_p50_ms": round(_pct(split_tpot, 0.50) * 1e3, 3),
        "wall_s": round(split_wall, 3),
        "transfer": {
            "handoffs": len(handoff_walls),
            "bytes_total": xfer_bytes,
            "bytes_per_s": round(xfer_bytes / max(wall_total, 1e-9), 1),
            "handoff_wall_p50_ms": _pair(handoff_walls)[0],
            "handoff_wall_p95_ms": _pair(handoff_walls)[1],
            "breakdown": breakdown,
            # export_s + channel_s + import_s over the measured wall —
            # the acceptance check that the breakdown accounts for the
            # handoff, not a fraction of it.
            "breakdown_cover_frac": round(
                comp_total / max(wall_total, 1e-9), 3),
        }}
    out["split_vs_colocated_ttft_p95"] = round(
        split_p95 / max(colo_p95, 1e-9), 3)
    kv_transfer.reap_channels(force=True)
    return out


def main() -> None:
    from ray_tpu.models import llama
    from ray_tpu.models.continuous_batching import ContinuousBatcher

    if jax.default_backend() != "tpu":
        raise RuntimeError(
            f"bench_serve.py measures the chip; JAX reports backend "
            f"{jax.default_backend()!r}. A CPU timing is not a result.")
    bw = _hbm_bw(jax.devices()[0])   # unknown device: fail before timing
    config = llama.LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_layers=16, num_heads=16, num_kv_heads=16, head_dim=128,
        max_seq_len=2048)
    num_slots, max_len, prompt_len, ticks = 32, 512, 32, 120
    sweep_grid = [(kv, bs) for kv in ("bf16", "int8")
                  for bs in (32, 64, 128)]
    sweep_ticks = 40

    # TTFT: submit timestamp per rid; first token closes the interval.
    submit_ts = {}
    ttft_s = []

    def on_token(rid, _tok):
        t0 = submit_ts.pop(rid, None)
        if t0 is not None:
            ttft_s.append(time.perf_counter() - t0)

    eng = ContinuousBatcher(config, num_slots=num_slots, max_len=max_len,
                            token_callback=on_token)
    param_bytes = eng.param_bytes

    def top_up(max_new=None, stamp=False):
        max_new = max_new if max_new is not None \
            else max_len - prompt_len - 1
        while len(eng._slots) + len(eng._waiting) < num_slots:
            rid = eng.submit(list(range(1, prompt_len + 1)),
                             max_new_tokens=max_new)
            if stamp:
                submit_ts[rid] = time.perf_counter()

    # Phase 1 — compile warm-up: a full admission burst + tick shapes.
    top_up(max_new=2)
    while eng.has_work():
        eng.step()

    # Phase 2 — churn (timed): short generations at full admission
    # pressure. The steady-state window below never frees a slot, so
    # TTFT (queueing included) and prefill throughput are measured here.
    ttft_s.clear()
    submit_ts.clear()
    eng.request_breakdowns.clear()
    prefill_tokens0 = eng.prefill_tokens
    prefill_seconds0 = _prefill_seconds(eng)
    for _ in range(2 * num_slots):
        rid = eng.submit(list(range(1, prompt_len + 1)), max_new_tokens=4)
        submit_ts[rid] = time.perf_counter()
    while eng.has_work():
        eng.step()
    prefill_tokens = eng.prefill_tokens - prefill_tokens0
    # Denominator is the engine's own dispatch->sync prefill interval, so
    # a decode-tick regression cannot masquerade as a prefill one.
    prefill_wall = max(_prefill_seconds(eng) - prefill_seconds0, 1e-9)
    # TTFT decomposition from the engine's request-path telemetry
    # (queue -> arena-wait -> prefill; the same records the
    # ray_tpu_serve_request_* histograms observe): the regression
    # baseline routing/admission changes are judged against — a router
    # change should move queue_ms, not prefill_ms. This churn phase has
    # NO shared prefixes, so it also guards the affinity-routing
    # acceptance bound (queue/prefill p95 must not regress when traffic
    # has nothing to share).
    ttft_breakdown = _breakdown_pcts(eng.request_breakdowns)

    # Phase 2c — prefix-reuse churn (ISSUE-8 tentpole): 75% of requests
    # share a block-aligned system prompt; the radix cache must turn
    # their prefills into table splices. Acceptance: >=2x effective
    # prefill tokens/s (or >=50% prefill_tokens_saved) at 75% shared
    # traffic.
    prefix_phase = _prefix_phase(config, eng.params, num_slots,
                                 max_len, block_size=64,
                                 shared_blocks=4, tail_len=16, rounds=4)

    # Phase 2d — speculative-decoding ladder (ISSUE-17 tentpole):
    # committed decode tokens/s at spec_k in {0, 2, 4}; full-depth
    # self-draft isolates the batched-verify win at accept-rate 1.0,
    # the truncated default shows the honest operating point.
    spec_phase = _spec_phase(config, eng.params, num_slots, max_len,
                             prompt_len, ticks=60,
                             draft_layers_full=config.num_layers,
                             draft_layers_cheap=max(
                                 1, config.num_layers // 4))

    # Phase 2e — disaggregated prefill/decode A/B (ISSUE-20 tentpole):
    # the same mixed long-prefill/long-decode backlog colocated vs
    # split over the KV-block channel plane. Acceptance: split TTFT
    # p95 <= colocated TTFT p95, breakdown components sum to the
    # handoff wall.
    disagg_phase = _disagg_phase(config, eng.params, num_slots,
                                 max_len=512, block_size=64,
                                 long_prompt=256, short_prompt=32,
                                 long_new=128, short_new=8, rounds=2)

    # Phase 3 — steady-state decode at full occupancy. No per-tick
    # device sync: the buffered engine's whole point is overlapping
    # fetches with compute, so the wall clock over the window is the
    # honest measure.
    tokens_per_s, med, live_bytes = _measure_decode(
        eng, num_slots, max_len, prompt_len, ticks)
    # Capture the MAIN engine's compiled-tick cost now: the sweep below
    # recompiles cb_tick per config and would otherwise overwrite it.
    cost_bytes, tick_flops = _tick_cost_stats()

    # Roofline: params + average live KV prefix, read once per tick,
    # priced at the arena's OWN storage bytes-per-token. The
    # 10%-of-bf16 criterion stays fixed across configs so vs_baseline
    # remains comparable round over round.
    avg_pos = (prompt_len + max_len) / 2
    per_token = eng.cache.token_bytes()
    kv_bytes = num_slots * avg_pos * per_token
    bf16_per_token = (2 * config.num_layers * config.num_kv_heads
                      * config.head_dim * 2)
    roofline = num_slots * bw / (param_bytes + kv_bytes)
    criterion = 0.10 * (num_slots * bw / (param_bytes + num_slots
                                          * avg_pos * bf16_per_token))

    # kv_dtype x block_size sweep: short steady-state windows, each on a
    # fresh engine (fresh compile), reporting tokens/s + both byte
    # figures. The live figure must track live tokens; the cost figure
    # shows what the compiler statically prices.
    sweep = []
    s_eng = None
    for kv_dtype, bs in sweep_grid:
        del s_eng  # release the previous config's arena before allocating
        s_eng = ContinuousBatcher(config, num_slots=num_slots,
                                  max_len=max_len,
                                  block_size=bs,
                                  kv_dtype=kv_dtype, params=eng.params)
        tps, _, lb = _measure_decode(s_eng, num_slots, max_len,
                                     prompt_len, sweep_ticks)
        sweep.append({
            "kv_dtype": kv_dtype, "block_size": bs,
            "tokens_per_s": round(tps, 1),
            "bytes_read_per_tick_cost": _tick_cost_stats()[0],
            "bytes_read_per_tick_live": int(lb),
        })

    ttft_sorted = sorted(ttft_s)
    out = {
        "metric": "decode_tokens_per_s_per_chip",
        "value": round(tokens_per_s, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tokens_per_s / criterion, 3),
        "roofline_tokens_per_s": round(roofline, 1),
        "hbm_efficiency": round(tokens_per_s / roofline, 3),
        "mean_tick_ms": round(med * 1e3, 2),
        "ttft_p50_ms": round(_pct(ttft_sorted, 0.50) * 1e3, 2),
        "ttft_p95_ms": round(_pct(ttft_sorted, 0.95) * 1e3, 2),
        "ttft_samples": len(ttft_sorted),
        "ttft_breakdown": ttft_breakdown,
        "prefix_phase": prefix_phase,
        "spec_phase": spec_phase,
        "disagg_phase": disagg_phase,
        "prefill_tokens_per_s": round(prefill_tokens / prefill_wall, 1),
        # Live-token accounting is the headline figure (it is what the
        # achieved-BW gauges use); the static cost-analysis figure rides
        # along for the worst-case comparison. (The r05-era
        # bytes_read_per_tick_est key is dropped rather than silently
        # repointed at a different quantity.)
        "bytes_read_source": "live_estimate",
        "bytes_read_per_tick_cost": cost_bytes,
        "bytes_read_per_tick_live": int(live_bytes),
        "tick_flops": tick_flops,
        "decode_kernel": eng.use_decode_kernel,
        "block_size": eng.block_size,
        "kv_dtype": eng.kv_dtype,
        "sweep": sweep,
        "num_slots": num_slots,
        "param_bytes": param_bytes,
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "device_count": len(jax.devices()),
    }
    print(json.dumps(out))
    rnd = int(sys.argv[sys.argv.index("--round") + 1]) \
        if "--round" in sys.argv else 5
    with open(f"BENCH_SERVE_r{rnd:02d}.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
