"""The linear-attention cell's benchmark code on the CPU: the manifest
finds the configuration, traffic, cell and metrics; the program's config
from the published keys and the share (and what is refused); the
configuration file against the catalog's numbers; ``flops_gdn.py`` by
hand count; each new metric file through its reader on hand-made
registries and a hand-made by-kernel trace. The traced CPU rehearsal of
the cell that ends ``correct`` is ``test_rehearsal.py``'s, which runs
every cell the manifest lists."""

import pytest

from benchmark import flops_gdn, flops_window, manifest
from benchmark.readers import gdn_roofline, kernel_time
from benchmark.runners import serve_linear, serve_moe
from benchmark.tests.test_benchmark_entries import (entry_for,
                                                    listed_as_it_was)
from benchmark.tests.test_window import _custom_call, _metric, _registry
from ray_tpu.models import llama

NAME = "qwen3-next-80b-a3b-l8-e64"
QWEN = manifest.load_json(manifest.HERE + f"/configs/{NAME}.json")
CELL = "serve_linear_decode"
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# The catalog row's numbers (model-configs guide, architectures.jsonl).
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "moe_intermediate_size": 512, "num_attention_heads": 16,
    "num_experts": 512, "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "vocab_size": 151936}
REDUCED = {"num_hidden_layers", "num_experts", "vocab_size"}
# The accepted measurements the cell is listed for besides its own
# (entries of their own, ``<name>.linear``, until PR 51 merged each into
# the one entry of its reader and arguments).
AGAIN = ("moe_gmm_time_share", "moe_local_assignment_share",
         "moe_experts_touched_share", "paged_attn_time_share",
         "tick_wall_ms.closed_loop", "prefill_batch_ms", "prefill_chunk_ms",
         "slot_occupancy", "decode_stall_share", "device_starved_share",
         "tick_overlap_share", "ttft_p50_ms", "stream_loop_ms",
         "stream_items_per_pull")
OWN = ("gdn_step_time_share", "gdn_step_roofline_share",
       "state_cache_resident_share", "prefill_state_carry_share",
       "moe_gmm_roofline_share.linear")


def test_manifest_finds_the_cell_and_its_files():
    cell = manifest.cell(CELL)
    assert (cell["config_name"], cell["traffic_name"], cell["chips"]) == (
        NAME, "context_decode", 1)
    assert cell["workload"]["runner"] == "serve_linear"
    listed = manifest.names(cell["per_layer"])
    assert set(OWN + AGAIN) <= set(listed) and len(OWN + AGAIN) == 19
    # ... and the two every cell reports.
    assert {"mosaic_time_share", "compiles_in_window"} <= set(listed)
    assert manifest.names(cell["end_to_end"]) == ["tokens_per_s", "setup_s"]
    for name in OWN:
        entry, _ = entry_for(name, CELL)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "tokens_per_s"
    from benchmark.tests.test_rehearsal import CELLS
    assert CELL in CELLS


@pytest.mark.parametrize("name", AGAIN)
def test_an_accepted_measurement_is_listed_for_the_cell(name):
    listed_as_it_was(name, CELL)


def test_file_keeps_every_published_number_but_the_reduced_ones():
    entry = [c for c in manifest.benchmark()["configs"]
             if c["name"] == NAME][0]
    assert set(entry["reduced"]) == REDUCED == set(QWEN["reduced"])
    assert entry["source"] == QWEN["source"]
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert QWEN[key] != value
            assert QWEN["published"][key] == value
        else:
            assert QWEN[key] == value, key
    assert (QWEN["hidden_act"], QWEN["model_type"], QWEN["rope_scaling"],
            QWEN["mlp_only_layers"]) == ("silu", "qwen3_next", None, [])
    assert (QWEN["norm_topk_prob"], QWEN["tie_word_embeddings"],
            QWEN["use_sliding_window"]) == (True, False, False)
    assert (QWEN["num_experts"], QWEN["router_experts"],
            QWEN["experts_held"]) == (64, 512, [0, 64])
    assert QWEN["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert QWEN["num_hidden_layers"] == 2 * QWEN["full_attention_interval"]
    assert {"state_dtype", "conv_tail_dtype", "projection_layout", "chunk",
            "norms", "rope", "layer_types"} <= set(QWEN["assumed"])
    assert set(QWEN["tolerance"]) == {"serve_mean_logit_gap_sd",
                                      "serve_route_disagreement_share"}


def test_program_config_carries_the_published_keys_and_the_share():
    config = serve_linear.qwen3_next_config(QWEN, max_seq_len=3072)
    period = ("linear_attention",) * 3 + ("full_attention",)
    assert config.layer_types == period * 2 == serve_linear.layer_types(QWEN)
    assert (config.state_layers, config.attn_layers, config.moe_layers,
            config.num_dense_layers) == (6, 2, 8, 0)
    assert (config.num_experts, config.experts_held, config.experts_here,
            config.num_experts_per_tok) == (512, (0, 64), 64, 10)
    assert (config.hidden_size, config.intermediate_size,
            config.shared_intermediate_size,
            config.shared_expert_gate) == (2048, 512, 512, True)
    assert (config.num_heads, config.num_kv_heads, config.head_dim,
            config.rotary_dim, config.rope_theta) == (16, 2, 256, 64, 1e7)
    assert (config.linear_num_key_heads, config.linear_num_value_heads,
            config.linear_key_head_dim, config.linear_value_head_dim,
            config.linear_conv_kernel_dim) == (16, 32, 128, 128, 4)
    assert (config.router_score, config.norm_topk_prob,
            config.zero_centered_norms, config.qk_norm_per_head,
            config.attn_gate) == ("softmax", True, True, True, True)
    assert config == llama.LlamaConfig.qwen3_next_80b_a3b(
        num_layers=8, layer_types=period * 2, vocab_size=18992,
        experts_held=(0, 64), max_seq_len=3072)
    # The configuration file's arithmetic: 1,978.8M held.
    assert abs(llama.num_params(config) / 1e6 - 1978.8) < 0.1


@pytest.mark.parametrize("key,value", [
    ("hidden_act", "gelu"), ("tie_word_embeddings", True),
    ("rope_scaling", {"type": "yarn", "factor": 4}),
    ("use_sliding_window", True), ("decoder_sparse_step", 2),
    ("mlp_only_layers", [0]), ("experts_held", [0, 32])])
def test_what_the_program_does_not_run_is_refused(key, value):
    with pytest.raises(ValueError, match="does not run"):
        serve_linear.qwen3_next_config(dict(QWEN, **{key: value}))


def test_flops_gdn_by_hand():
    assert flops_gdn.state_elements(QWEN) == 32 * 128 * 128 == 524_288
    assert flops_gdn.linear_layers(QWEN) == 6
    assert flops_gdn.linear_layers(dict(QWEN, num_hidden_layers=48)) == 36
    # One sequence, one layer: the state in and out, q and k of 16 x 128
    # and v and o of 32 x 128 in bf16, g and beta of 32 in float32.
    rows = (2 * 2048 + 2 * 4096) * 2 + 2 * 32 * 4
    assert flops_gdn.step_bytes(QWEN, 1) == 2 * 524_288 * 4 + rows
    assert flops_gdn.step_flops(QWEN, 1) == 7 * 524_288
    # ISSUE 38's tick: 256 slots x 6 layers read and write 6.44 GB.
    assert 6 * 256 * 2 * 524_288 * 4 == pytest.approx(6.44e9, rel=0.002)
    least = flops_gdn.tick_step_seconds(QWEN, 256, V5E)
    assert least == pytest.approx(
        6 * 256 * (2 * 524_288 * 4 + rows) / 819e9)
    assert least == pytest.approx(7.89e-3, rel=0.005)
    # 1.75 operations a byte: the bytes win on any chip ...
    assert 6 * flops_gdn.step_flops(QWEN, 256) / 197e12 < least / 100
    # ... but one with a thousandth of the FLOP/s.
    slow = dict(V5E, bf16_flops_per_s=197e9)
    assert flops_gdn.tick_step_seconds(QWEN, 256, slow) == pytest.approx(
        6 * 256 * 7 * 524_288 / 197e9)
    # What a request keeps: 2.10 MB of state and 49 kB of tail a layer.
    assert flops_gdn.state_bytes(QWEN, 1) == 6 * (524_288 * 4
                                                  + 3 * 8192 * 2)
    assert flops_gdn.state_bytes(QWEN, 256) == pytest.approx(3.30e9,
                                                             rel=0.005)
    # ... and 4,096 B a token in the two full-attention layers.
    assert flops_gdn.kv_token_bytes(QWEN) == 4096


HAND_MADE = {"/device:TPU:0": {
    "XLA Modules": [("jit_tick(7)", 0, 20000), ("jit_tick(7)", 22000, 20000)],
    "XLA Ops": [("%while.1 = while(...)", 0, 20000),
                (_custom_call("gdn_step.3"), 1000, 9000),
                (_custom_call("moe_gmm.4"), 11000, 4000),
                (_custom_call("paged_decode_attn.9"), 16000, 2000),
                ("%while.1 = while(...)", 22000, 20000),
                (_custom_call("gdn_step.3"), 23000, 11000),
                (_custom_call("moe_gmm.4"), 35000, 5000),
                (_custom_call("paged_decode_attn.9"), 40500, 1000)]}}


def test_roofline_and_resident_share_on_a_synthetic_ctx():
    trace = dict(serve_moe.by_kernel(HAND_MADE), busy_s=40000e-9)
    before, after = _registry(**{
        "ray_tpu_cb_state_live_slots_sum": 200 * 240.0,
        "ray_tpu_cb_state_live_slots_count": 200.0,
        "ray_tpu_cb_paged_live_block_share_sum": 200 * 0.5,
        "ray_tpu_cb_paged_live_block_share_count": 200.0,
        "ray_tpu_cb_moe_local_assignments_total": 200 * 8 * 300.0,
        "ray_tpu_cb_moe_experts_touched_share_sum": 200 * 0.99,
        "ray_tpu_cb_moe_experts_touched_share_count": 200.0,
        "ray_tpu_cb_prefill_state_carries_total": 30.0,
        "ray_tpu_cb_state_installs_total": 30.0})
    engine = {"num_slots": 256, "max_len": 3072, "block_size": 64,
              "num_blocks": None}
    ctx = {"trace": trace, "config": QWEN, "registry_before": before,
           "registry_after": after, "device": {"kind": "TPU v5 lite"},
           "engine": engine}
    assert _metric("gdn_step_time_share", ctx) == pytest.approx(
        100 * 20000 / 40000)
    assert _metric("moe_gmm_time_share", ctx) == pytest.approx(
        100 * 9000 / 40000)
    assert _metric("paged_attn_time_share", ctx) == pytest.approx(
        100 * 3000 / 40000)
    # Counted over the 240 LIVE slots, though the kernel advances 256.
    least = flops_gdn.tick_step_seconds(QWEN, 240, V5E)
    assert _metric("gdn_step_roofline_share", ctx) == pytest.approx(
        100 * least / (20000e-9 / 2))
    gmm = flops_window.tick_gmm_seconds(
        dict(QWEN, num_dense_layers=0), 300, 0.99 * 64, V5E)
    assert gmm == pytest.approx(
        8 * (0.99 * 64 * 3 * 2048 * 512 * 2 + 2 * 300 * 2048 * 2) / 819e9)
    assert _metric("moe_gmm_roofline_share.linear", ctx) == pytest.approx(
        100 * gmm / (9000e-9 / 2))
    # 240 requests' state against the K/V of half the table's entries.
    state = 240 * 6 * (524_288 * 4 + 3 * 8192 * 2)
    kv = 0.5 * 256 * 48 * 64 * 4096
    assert _metric("state_cache_resident_share", ctx) == pytest.approx(
        100 * state / (state + kv))
    assert _metric("prefill_state_carry_share", ctx) == pytest.approx(50.0)
    # The parent commit (none of the series), a trace reduced without the
    # by-kernel part, a program that never ran the kernel, or another
    # family's configuration: nothing, and no error.
    parent = dict(ctx, registry_before={}, registry_after={"x": 1.0})
    for other in (parent, dict(ctx, trace={"busy_s": 1.0}),
                  dict(ctx, trace=dict(trace, programs={})),
                  dict(ctx, config={"linear_num_value_heads": None}),
                  dict(ctx, config={})):
        for name in ("gdn_step_roofline_share",
                     "moe_gmm_roofline_share.linear"):
            assert _metric(name, other) is None
    for other in (parent, dict(ctx, config={})):
        assert _metric("state_cache_resident_share", other) is None
    assert _metric("prefill_state_carry_share", parent) is None
    assert kernel_time.read(dict(ctx, trace={}), kernel="gdn_step",
                            stat="time_share") is None
    with pytest.raises(ValueError):
        gdn_roofline.read(ctx, stat="other", kernel="moe_gmm",
                          program="jit_tick")


def test_traffic_and_workload_hold_the_cells_numbers():
    mix = manifest.load_json(manifest.HERE + "/traffic/context_decode.json")
    # ISSUE 38's mix: 512 callers over 256 slots, so a request always waits.
    assert (mix["loop"], mix["clients"], mix["sharing"]) == (
        "closed", 512, "none")
    assert mix["prompt_tokens"] == {
        "dist": "lognormal", "median": 1280, "sigma": 0.25, "min": 1025,
        "max": 2048}
    assert mix["output_tokens"] == {
        "dist": "lognormal", "median": 512, "sigma": 0.4, "min": 192,
        "max": 1024}
    work = manifest.cell(CELL)["workload"]
    assert work["env"] == {"RAY_TPU_SHED_QUEUE_DEPTH": "0"}
    assert work["engine"] == {"num_slots": 256, "max_len": 3072,
                              "block_size": 64, "num_blocks": None,
                              "prefill_chunk": 1024}
    assert work["check"] == {"prompt_tokens": [300, 1024, 1025, 1500, 2047,
                                               2048], "max_tokens": 32}
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] <= 3072
    # Every prompt is exactly two chunks: 8 prefill programs to warm.
    chunk = work["engine"]["prefill_chunk"]
    assert {-(-n // chunk) for n in (mix["prompt_tokens"]["min"],
                                     mix["prompt_tokens"]["max"])} == {2}
    assert work["warmup"]["batch_buckets"] == [1, 2, 4, 8]
    # 2 layers x (1 + 256 x 48) blocks x 64 x 2 heads x 256 x 2 B x K, V.
    assert abs(2 * (1 + 256 * 48) * 64 * 2 * 256 * 2 * 2 / 1e9 - 3.22) < 0.01
    # The loop runs before the clock; the pool outlasts lead-in and window.
    lead = work["lead_in_s"]
    assert lead == 70 and mix["drain_s"] >= 90
    assert mix["pool_per_s"] * (lead + 40) >= 4 * mix["clients"]
    # One primer a slot, inside one chunk and the cache's length.
    primers = work["primers"]
    assert primers == {"prompt_tokens": 1000, "longest_answer": 384,
                       "head_start_s": 1.0}
    assert primers["prompt_tokens"] <= chunk
    assert primers["prompt_tokens"] + primers["longest_answer"] <= 3072
    rehearse = manifest.rehearsal(manifest.cell(CELL))
    assert rehearse["workload"]["engine"] == {
        "num_slots": 4, "max_len": 64, "block_size": 8, "num_blocks": None,
        "prefill_chunk": 16}
    assert (rehearse["traffic"]["prompt_tokens"]["min"],
            rehearse["traffic"]["prompt_tokens"]["max"]) == (17, 32)


class _Reference:
    @staticmethod
    def gaps_and_routes(params, prompt, chosen, config, pad_to=0):
        import numpy as np
        return np.asarray([0.0, 0.5, 0.0]), np.asarray(
            [[[3, 9], [8, 3]], [[3, 9], [9, 3]]])


@pytest.mark.parametrize("routes,share,ok", [
    ([[[9, 3], [3, 8]], [[3, 9], [9, 3]]], 0.0, True),
    ([[[9, 4], [3, 8]], [[3, 9], [9, 3]]], 0.25, True),
    ([[[9, 4], [3, 7]], [[3, 9], [9, 3]]], 0.5, False),
    ([[[9, 3], [3, 9]]], 1.0, False),                     # a position short
])
def test_correct_holds_the_routes_and_the_mean_gap(monkeypatch, routes,
                                                   share, ok):
    monkeypatch.setattr(serve_linear, "reference_qwen3_next", _Reference)
    tol = {"serve_mean_logit_gap_sd": 0.2,
           "serve_route_disagreement_share": 0.3}
    checks = [({"prompt": [1, 2]}, {"tokens": [5, 6, 7], "routes": routes})]
    out = serve_linear.hold_to_reference(None, None, checks, tol)
    assert out["route_disagreement_share"] == pytest.approx(share)
    assert out["mean_logit_gap_sd"] == pytest.approx(0.5 / 3)
    assert out["ok"] is ok
