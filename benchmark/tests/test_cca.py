"""The CCA cell's benchmark code on the CPU: the manifest finds the
configuration, traffic, cell and metrics (the new entries in their ORDER
among themselves, wherever a later PR appends after them); the program's
config from the published keys (and what is refused); the configuration
file against the catalog's numbers; ``flops_cca.py`` by hand count; each
new metric file through its reader on hand-made registries and a
hand-made trace; the traffic and workload files' numbers; ``correct``
under both limits; and the rehearsal of the cell end to end, which has
to come out ``correct``."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import flops_cca, manifest, traffic
from benchmark.runners import serve_cca
from benchmark.tests.test_benchmark_entries import (entry_for,
                                                    listed_as_it_was)
from benchmark.tests.test_window import _metric, _registry

NAME = "zaya1-8b-l10"
ZAYA = manifest.load_json(manifest.HERE + f"/configs/{NAME}.json")
CELL = "serve_cca_decode"
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# The catalog row's numbers (model-configs guide, architectures.jsonl).
PUBLISHED = {
    "cca_time0": 2, "cca_time1": 2, "head_dim": 128, "hidden_size": 2048,
    "max_position_embeddings": 131072, "moe_intermediate_size": 2048,
    "num_attention_heads": 8, "num_experts": 16, "num_experts_per_tok": 1,
    "num_hidden_layers": 40, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
    "router_hidden_size": 256, "vocab_size": 262272}
# ISSUE 46's sixteen: four entries of the family's own (three of them PR
# 46's, ``cca_kv_resident_share`` waited for room until PR 51) and the
# twelve accepted measurements the cell joined when PR 51 made the room
# (``tick_wall_ms.cca`` is ``tick_wall_ms.closed_loop`` since).
OWN = ("paged_attn_roofline_share.cca", "moe_gmm_roofline_share.cca",
       "cca_kv_resident_share")
AGAIN = ("tick_wall_ms.closed_loop", "paged_attn_time_share",
         "moe_gmm_time_share", "moe_experts_touched_share",
         "moe_load_imbalance", "prefill_batch_ms", "prefill_chunk_ms",
         "slot_occupancy", "decode_stall_share", "device_starved_share",
         "tick_overlap_share", "ttft_p50_ms", "engine_queue_ms")


def test_manifest_finds_the_cell_and_its_files():
    cell = manifest.cell(CELL)
    assert (cell["config_name"], cell["traffic_name"], cell["chips"]) == (
        NAME, "reasoning_context_decode", 1)
    assert cell["workload"]["runner"] == "serve_cca"
    listed = manifest.names(cell["per_layer"])
    assert set(OWN + AGAIN) <= set(listed) and len(OWN + AGAIN) == 16
    assert {"mosaic_time_share", "compiles_in_window"} <= set(listed)
    assert manifest.names(cell["end_to_end"]) == ["tokens_per_s", "setup_s"]
    bench = manifest.benchmark()
    for name in OWN:
        entry, spec = entry_for(name, CELL)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "tokens_per_s"
        assert spec["reader"] == "cca_roofline"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["reduced"] == ["num_hidden_layers", "layer_types"]
    assert entry["source"] == ZAYA["source"]


@pytest.mark.parametrize("name", AGAIN)
def test_an_accepted_measurement_is_listed_for_the_cell(name):
    listed_as_it_was(name, CELL)


def test_file_keeps_every_published_number_but_the_reduced_ones():
    for key, value in PUBLISHED.items():
        if key == "num_hidden_layers":
            assert ZAYA[key] == 10 and ZAYA["published"][key] == value
        else:
            assert ZAYA[key] == value, key
    assert ZAYA["layer_types"] == ["hybrid"] * 10
    assert set(ZAYA["reduced"]) == {"num_hidden_layers", "layer_types"}
    rope = ZAYA["rope_parameters"]
    assert rope["hybrid"] == {"partial_rotary_factor": 0.5,
                              "rope_theta": 5000000, "rope_type": "default"}
    assert rope["hybrid_sliding"]["rope_theta"] == 10000
    assert ZAYA["tie_word_embeddings"] and ZAYA["sliding_window"] is None
    assert not ZAYA["attention_bias"] and not ZAYA["lm_head_bias"]
    assert (ZAYA["hidden_act"], ZAYA["model_type"]) == ("silu", "zaya")
    for said in ("value_shift", "convolutions", "qk_mean", "router",
                 "qk_norm_and_temperature", "residual_scaling",
                 "no_skip_expert", "rope", "weights", "precisions"):
        assert said in ZAYA["assumed"]
    assert 0 < ZAYA["tolerance"]["serve_mean_logit_gap_sd"] < 1
    assert 0 < ZAYA["tolerance"]["serve_route_disagreement_share"] < 1


def test_program_config_carries_the_published_keys():
    c = serve_cca.zaya_config(ZAYA, max_seq_len=7168)
    assert c.layer_types == ("cca_attention",) * 10 and c.cca_layers == 10
    assert (c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim) == (
        2048, 8, 2, 128)
    assert (c.num_experts, c.num_experts_per_tok, c.intermediate_size,
            c.router_hidden_size) == (16, 1, 2048, 256)
    assert c.experts_held == (0, 16) and c.rotary_dim == 64
    assert c.rope_theta == 5e6 and c.rms_eps == 1e-5
    assert c.tie_word_embeddings and c.residual_scaling
    assert c.vocab_size == 262272 and c.max_seq_len == 7168


@pytest.mark.parametrize("key,value", [
    ("cca_time0", 4), ("attention_bias", True), ("hidden_act", "gelu"),
    ("tie_word_embeddings", False), ("sliding_window", 4096),
    ("num_experts_per_tok", 2), ("experts_held", [0, 8]),
    ("layer_types", ["hybrid"] * 9 + ["hybrid_sliding"])])
def test_what_the_program_does_not_run_is_refused(key, value):
    with pytest.raises(ValueError, match="does not run"):
        serve_cca.zaya_config(dict(ZAYA, **{key: value}))


def test_flops_cca_by_hand():
    assert flops_cca.kv_token_bytes(ZAYA) == 2 * 2 * 128 * 2 == 1024
    assert flops_cca.expert_params(ZAYA) == 3 * 2048 * 2048
    # 96 rows over 4,100 tokens each: 4.03 GB of K/V, HBM-bound.
    tokens = 96 * 4100.0
    moved = (tokens * 1024 + 2 * 96 * 1024 * 2) * 10
    assert moved == pytest.approx(4.03e9, rel=2e-3)
    flops = 4.0 * 1024 * tokens * 10
    assert flops / moved == pytest.approx(4.0, rel=1e-2)
    assert flops_cca.tick_attn_seconds(ZAYA, tokens, 96, V5E) == \
        pytest.approx(moved / 819e9)
    # 96 assignments a layer over 15 touched experts: 25.2 MB each.
    one = 15 * 3 * 2048 * 2048 * 2 + 2 * 96 * 2048 * 2
    assert flops_cca.tick_gmm_seconds(ZAYA, 96, 15, V5E) == pytest.approx(
        10 * one / 819e9)
    # A thousand rows an expert are bound by the multiplications.
    assert flops_cca.tick_gmm_seconds(ZAYA, 16000, 16, V5E) == \
        pytest.approx(10 * 2.0 * 3 * 2048 * 2048 * 16000 / 197e12)


def test_metrics_read_through_their_files_on_a_synthetic_ctx():
    engine = manifest.cell(CELL)["workload"]["engine"]
    before, after = _registry(**{
        # 96 slots x 112 entries; 64 live blocks a slot over the window.
        "ray_tpu_cb_paged_live_block_share_sum": 100 * 64 / 112,
        "ray_tpu_cb_paged_live_block_share_count": 100.0,
        "ray_tpu_cb_decode_tokens_total": 9600.0,
        "ray_tpu_cb_moe_local_assignments_total": 100 * 960.0,
        "ray_tpu_cb_moe_experts_touched_share_sum": 100 * 0.9,
        "ray_tpu_cb_moe_experts_touched_share_count": 100.0,
        "ray_tpu_cb_tick_ms_sum": 1400.0, "ray_tpu_cb_tick_ms_count": 100.0})
    # The capture's own ticks: 66 live blocks a slot, 92% touched.
    capture = {
        "ray_tpu_cb_paged_live_block_share_sum": 20 * 66 / 112,
        "ray_tpu_cb_paged_live_block_share_count": 20.0,
        "ray_tpu_cb_decode_tokens_total": 20 * 95.0,
        "ray_tpu_cb_moe_local_assignments_total": 20 * 960.0,
        "ray_tpu_cb_moe_experts_touched_share_sum": 20 * 0.92,
        "ray_tpu_cb_moe_experts_touched_share_count": 20.0}
    trace = {"busy_s": 3.6, "cca_capture": capture,
             "kernels": {"paged_decode_attn": {"jit_tick": [1.6, 200]},
                         "moe_gmm": {"jit_tick": [1.5, 400],
                                     "jit_prefill": [0.1, 40]}},
             "programs": {"jit_tick": [3.3, 20], "jit_prefill": [0.3, 2]}}
    ctx = {"registry_before": before, "registry_after": after,
           "config": ZAYA, "trace": trace, "device": {"kind": "TPU v5 lite"},
           "engine": engine}
    # Live tokens: 96 slots x 66 blocks x 64, less half a block a row.
    tokens = 96 * 66 * 64 - 95 * 32
    assert _metric("paged_attn_roofline_share.cca", ctx) == pytest.approx(
        100 * flops_cca.tick_attn_seconds(ZAYA, tokens, 95, V5E)
        / (1.6 / 20))
    assert _metric("moe_gmm_roofline_share.cca", ctx) == pytest.approx(
        100 * flops_cca.tick_gmm_seconds(ZAYA, 96, 0.92 * 16, V5E)
        / (1.5 / 20))
    # Without a capture the window's own deltas serve.
    windowed = dict(ctx, trace=dict(trace, cca_capture=None))
    assert _metric("moe_gmm_roofline_share.cca", windowed) == pytest.approx(
        100 * flops_cca.tick_gmm_seconds(ZAYA, 96, 0.9 * 16, V5E)
        / (1.5 / 20))
    assert _metric("tick_wall_ms.closed_loop", ctx) == pytest.approx(14.0)
    # The two stores over per-head K and V at the hidden width (8 x the
    # latent's bytes): gauges as the window closed, no trace needed.
    gauged = dict(ctx, trace={}, registry_after=dict(
        after, ray_tpu_cb_cca_kv_bytes=7_047_086_080.0,
        ray_tpu_cb_cca_tail_bytes=5_160_960.0))
    assert _metric("cca_kv_resident_share", gauged) == pytest.approx(
        100 * (7_047_086_080 + 5_160_960) / (8 * 7_047_086_080))
    assert _metric("cca_kv_resident_share", ctx) is None     # no gauge
    # The parent commit books none of it and traces none of it.
    bare = dict(ctx, registry_before={}, registry_after={}, trace={})
    for name in OWN:
        assert _metric(name, bare) is None
    # ... and another family's cell reads nothing here.
    other = dict(gauged, trace=trace, config={"sliding_window": 4096})
    for name in OWN:
        assert _metric(name, other) is None
    # No share of a roofline over 100%: at the kernel's own time equal to
    # the least time it reads 100, whatever the blocks' overhang.
    least = flops_cca.tick_attn_seconds(ZAYA, tokens, 95, V5E)
    exact = dict(ctx, trace=dict(trace, kernels={
        "paged_decode_attn": {"jit_tick": [least * 20, 200]}}))
    assert _metric("paged_attn_roofline_share.cca", exact) == \
        pytest.approx(100.0)


def test_traffic_and_workload_hold_the_cells_numbers():
    cell = manifest.cell(CELL)
    mix, work = cell["traffic"], cell["workload"]
    assert (mix["loop"], mix["clients"], mix["sharing"]) == (
        "closed", 192, "none")
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 3072,
                                    "sigma": 0.2, "min": 2049, "max": 4096}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 2048,
                                    "sigma": 0.25, "min": 1024, "max": 3072}
    engine = work["engine"]
    assert engine == {"num_slots": 96, "max_len": 7168, "block_size": 64,
                      "num_blocks": 1 + 96 * 112, "prefill_chunk": 1024}
    assert engine["max_len"] == (mix["prompt_tokens"]["max"]
                                 + mix["output_tokens"]["max"])
    assert mix["clients"] == 2 * engine["num_slots"]
    # Every prompt is 3 or 4 chunks; the check prompts stand inside one
    # chunk, ON a boundary, one past it, and in later chunks.
    chunk = engine["prefill_chunk"]
    assert {-(-n // chunk) for n in (mix["prompt_tokens"]["min"],
                                     mix["prompt_tokens"]["max"])} == {3, 4}
    assert work["check"]["prompt_tokens"] == [300, 1023, 1024, 1025, 2049,
                                              3000, 4096]
    assert max(work["check"]["prompt_tokens"]) + work["check"][
        "max_tokens"] <= engine["max_len"]
    assert work["primers"]["prompt_tokens"] <= chunk
    # The pool outlasts lead-in and window at ten admissions a second.
    reqs = traffic.requests(dict(mix, pool_per_s=0.1), 5, 1000, 130.0)
    assert all(2049 <= len(r["prompt"]) <= 4096
               and 1024 <= r["max_tokens"] <= 3072 for r in reqs)
    assert mix["pool_per_s"] * (work["lead_in_s"] + 40) > 192 + 10 * 130 / 2


@pytest.mark.parametrize("gap,routes,ok", [
    (0.2, 0.5, True), (0.00001, 0.5, False), (0.2, 0.00001, False)])
def test_correct_holds_both_limits(monkeypatch, gap, routes, ok):
    """One position's gap and one flipped route of four: each limit
    alone refuses."""
    import numpy as np

    def fake(params, prompt, chosen, config, pad_to=0):
        return (np.asarray([0.0, 0.1]),
                np.asarray([[[1], [2]]]))
    monkeypatch.setattr(serve_cca.reference_zaya, "gaps_and_routes", fake)
    checks = [({"prompt": [1, 2]}, {"tokens": [3, 4],
                                    "routes": [[[1], [3]]]})]
    held = serve_cca.hold_to_reference(None, None, checks, {
        "serve_mean_logit_gap_sd": gap,
        "serve_route_disagreement_share": routes})
    assert held["ok"] is ok
    assert held["mean_logit_gap_sd"] == pytest.approx(0.05)
    assert held["route_disagreement_share"] == pytest.approx(0.5)


def test_the_rehearsal_runs_end_to_end_and_is_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(manifest.HERE, "run.py"), "--workload",
         CELL, "--seed", "3000000003", "--seconds", "5", "--trace", "1",
         "--rehearse"], env=env, cwd=manifest.ROOT, capture_output=True,
        text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["detail"]["rehearsal"] and line["device"]["platform"] == "cpu"
    # What needs no device trace is read on the CPU too.
    for name in ("tick_wall_ms.closed_loop", "cca_kv_resident_share",
                 "slot_occupancy", "prefill_chunk_ms", "compiles_in_window"):
        assert name in line["metrics"], name
    # 2 x 2 x 16 of K/V against 2 x 64 at the hidden width, and the tail.
    assert 50 < line["metrics"]["cca_kv_resident_share"]["value"] < 100
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert line["detail"]["ray_tpu_cb_cca_tail_bytes"] == 4 * 3 * 208 * 2
    assert line["detail"]["cca_capture"][
        "ray_tpu_cb_moe_experts_touched_share_count"] > 0
