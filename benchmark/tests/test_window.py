"""The window cells' benchmark code on the CPU: the program's config
from the published keys and the share (and what is refused), the
configuration file against the catalog's numbers, ``flops_window.py`` by
hand count, each new metric file through its reader on hand-made
registries and a hand-made by-kernel trace, and the chunk-aware
warm-up's list of programs."""


import pytest

from benchmark import flops_window, manifest
from benchmark.readers import (kernel_time, kv_resident, registry_delta,
                               window_roofline)
from benchmark.runners import serve_moe, serve_window
from benchmark.tests.test_benchmark_entries import (entry_for,
                                                    listed_as_it_was)
from ray_tpu.models import llama

TRINITY = manifest.load_json(
    manifest.HERE + "/configs/trinity-large-preview-l5-e32.json")
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# The catalog row's numbers (model-configs guide, architectures.jsonl).
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_size": 3072,
    "intermediate_size": 12288, "load_balance_coeff": 5e-05,
    "max_position_embeddings": 262144, "moe_intermediate_size": 3072,
    "n_group": 1, "num_attention_heads": 48, "num_dense_layers": 6,
    "num_expert_groups": 1, "num_experts": 256, "num_experts_per_tok": 4,
    "num_hidden_layers": 60, "num_key_value_heads": 8,
    "num_limited_groups": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_theta": 10000, "route_scale": 2.448, "sliding_window": 4096,
    "topk_group": 1, "vocab_size": 200192}
REDUCED = {"num_hidden_layers", "num_dense_layers", "layer_types",
           "num_experts", "vocab_size"}


def test_file_keeps_every_published_number_but_the_reduced_ones():
    entry = [c for c in manifest.benchmark()["configs"]
             if c["name"] == "trinity-large-preview-l5-e32"][0]
    assert set(entry["reduced"]) == REDUCED == set(TRINITY["reduced"])
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert TRINITY[key] != value
            assert TRINITY["published"][key] == value
        else:
            assert TRINITY[key] == value, key
    assert TRINITY["layer_types"] == ["sliding_attention"] * 3 + [
        "full_attention", "sliding_attention"]
    assert (TRINITY["num_experts"], TRINITY["router_experts"],
            TRINITY["experts_held"]) == (32, 256, [0, 32])
    assert TRINITY["vocab_size"] * 8 == PUBLISHED["vocab_size"]


def test_program_config_carries_the_published_keys_and_the_share():
    config = serve_window.afmoe_config(TRINITY, max_seq_len=7168)
    assert config.layer_types == ("sliding_attention",) * 3 + (
        "full_attention", "sliding_attention")
    assert (config.window_layers, config.attn_layers, config.moe_layers,
            config.sliding_window) == (4, 1, 4, 4096)
    assert (config.num_experts, config.experts_held, config.experts_here,
            config.num_experts_per_tok) == (256, (0, 32), 32, 4)
    assert (config.hidden_size, config.intermediate_size,
            config.dense_intermediate_size,
            config.shared_intermediate_size) == (3072, 3072, 12288, 3072)
    assert (config.num_heads, config.num_kv_heads, config.head_dim) == (
        48, 8, 128)
    assert (config.router_score, config.route_scale, config.norm_topk_prob
            ) == ("sigmoid", 2.448, True)
    assert config.embedding_multiplier == 3072 ** 0.5
    assert not config.rope_full_attention and config.rope
    assert (config.qk_norm_per_head and config.attn_gate
            and config.sandwich_norms and not config.tie_word_embeddings)
    # The configuration file's arithmetic: 4.32B held, 8.64 GB in bf16.
    assert abs(llama.num_params(config) * 2 / 1e9 - 8.64) < 0.01


@pytest.mark.parametrize("key,value", [
    ("score_func", "softmax"), ("n_group", 4), ("num_shared_experts", 2),
    ("rope_scaling", {"type": "yarn"}), ("hidden_act", "gelu"),
    ("tie_word_embeddings", True), ("mup_enabled", False),
    ("experts_held", [0, 16])])
def test_what_the_program_does_not_run_is_refused(key, value):
    with pytest.raises(ValueError, match="does not run"):
        serve_window.afmoe_config(dict(TRINITY, **{key: value}))


def test_flops_window_by_hand():
    # One token's K and V in one layer: 2 x 8 heads x 128 x 2 B.
    assert flops_window.kv_token_bytes(TRINITY) == 4096
    assert flops_window.layer_kinds(TRINITY) == (4, 1)
    # 48 sequences at 5,900 tokens: 4096 in each window layer, all in
    # the full one.
    window, full = 48 * 4096, 48 * 5900
    tokens = 4 * window + full
    least = flops_window.tick_attn_seconds(TRINITY, window, full, V5E)
    assert least == pytest.approx(tokens * 4096 / 819e9)      # HBM-bound
    assert 4 * 48 * 128 * tokens / 197e12 < least
    assert tokens * 4096 == pytest.approx(4.38e9, rel=0.01)   # ISSUE 32's 4.4 GB
    # Experts: 3 x 3072 x 3072 each; 24 local assignments a layer on 17
    # touched experts, 4 routed layers.
    assert flops_window.expert_params(TRINITY) == 28_311_552
    assert flops_window.routed_layers(TRINITY) == 4
    gmm = flops_window.tick_gmm_seconds(TRINITY, 24, 17, V5E)
    assert gmm == pytest.approx(
        4 * (17 * 28_311_552 * 2 + 2 * 24 * 3072 * 2) / 819e9)


def _metric(name, ctx):
    spec = manifest.metric_file(name)
    reader = __import__("benchmark.readers." + spec["reader"],
                        fromlist=["read"])
    return reader.read(ctx, **spec.get("args", {}))


def _registry(**deltas):
    before = {k: 1.0 for k in deltas}
    return before, {k: 1.0 + v for k, v in deltas.items()}


def test_counter_metrics_read_through_their_files():
    before, after = _registry(**{
        "ray_tpu_cb_window_live_block_share_sum": 75.0,
        "ray_tpu_cb_window_live_block_share_count": 100.0,
        "ray_tpu_cb_moe_local_assignments_total": 9_600.0,
        "ray_tpu_cb_moe_assignments_total": 76_800.0,
        "ray_tpu_cb_prefill_chunk_ms_sum": 600.0,
        "ray_tpu_cb_prefill_chunk_ms_count": 20.0})
    after.update({"ray_tpu_cb_window_kv_bytes": 3.32e9,
                  "ray_tpu_cb_full_kv_bytes": 1.41e9})
    ctx = {"registry_before": before, "registry_after": after,
           "config": TRINITY, "engine": {"num_slots": 48}}
    assert _metric("window_live_block_share", ctx) == pytest.approx(75.0)
    assert _metric("moe_local_assignment_share", ctx) == pytest.approx(12.5)
    assert _metric("prefill_chunk_ms", ctx) == pytest.approx(30.0)
    # 4.73 GB resident of the 7.05 GB one table for five layers holds.
    assert _metric("window_kv_resident_share", ctx) == pytest.approx(
        100 * 4.73 / 7.05)
    # A program that books none of these (the parent commit): nothing.
    empty = dict(ctx, registry_before={}, registry_after={})
    for name in ("window_live_block_share", "moe_local_assignment_share",
                 "prefill_chunk_ms", "window_kv_resident_share"):
        assert _metric(name, empty) is None
    assert kv_resident.read({"registry_after": after, "config": {}}) is None
    assert registry_delta.read({}, num=["a"], den=["b"]) is None


def _custom_call(name):
    return (f"%{name} = bf16[48,8,6,128]{{3,2,1,0}} custom-call(...), "
            'custom_call_target="tpu_custom_call"')


HAND_MADE = {"/device:TPU:0": {
    trace_name: events for trace_name, events in (
        ("XLA Modules", [("jit_tick(7)", 0, 12000),
                         ("jit_prefill(9)", 13000, 500),
                         ("jit_tick(7)", 14000, 12000)]),
        ("XLA Ops", [("%while.1 = while(...)", 0, 12000),
                     (_custom_call("paged_decode_attn.9"), 100, 5000),
                     (_custom_call("moe_gmm.4"), 6000, 1500),
                     ("%while.1 = while(...)", 14000, 12000),
                     (_custom_call("paged_decode_attn.9"), 14100, 6000),
                     (_custom_call("moe_gmm.4"), 21000, 1700)]))}}


def test_kernel_metrics_read_through_their_files():
    trace = dict(serve_moe.by_kernel(HAND_MADE), busy_s=24500e-9)
    before, after = _registry(**{
        # 48 slots x 112 entries; 86 live blocks a slot.
        "ray_tpu_cb_paged_live_block_share_sum": 200 * 86 / 112,
        "ray_tpu_cb_paged_live_block_share_count": 200.0,
        "ray_tpu_cb_window_live_block_share_sum": 200 * 65 / 86,
        "ray_tpu_cb_window_live_block_share_count": 200.0,
        "ray_tpu_cb_moe_local_assignments_total": 200 * 4 * 24.0,
        "ray_tpu_cb_moe_experts_touched_share_sum": 200 * 17 / 32,
        "ray_tpu_cb_moe_experts_touched_share_count": 200.0})
    ctx = {"trace": trace, "config": TRINITY, "registry_before": before,
           "registry_after": after, "device": {"kind": "TPU v5 lite"},
           "engine": {"num_slots": 48, "max_len": 7168, "block_size": 64}}
    assert _metric("paged_attn_time_share", ctx) == pytest.approx(
        100 * 11000 / 24500)
    assert _metric("moe_gmm_time_share", ctx) == pytest.approx(
        100 * 3200 / 24500)
    full = 48 * 86 * 64
    window = min(48 * 65 * 64, 48 * 4096)
    least = flops_window.tick_attn_seconds(TRINITY, window, full, V5E)
    assert _metric("paged_attn_roofline_share.window", ctx) == pytest.approx(
        100 * least / (11000e-9 / 2))
    gmm = flops_window.tick_gmm_seconds(TRINITY, 24, 17, V5E)
    assert _metric("moe_gmm_roofline_share.window", ctx) == pytest.approx(
        100 * gmm / (3200e-9 / 2))
    # The parent commit (no window counters), a trace reduced without
    # the by-kernel part, or a configuration without a window: nothing,
    # and no error.
    parent = dict(ctx, registry_before={}, registry_after={"x": 1.0})
    for other in (parent, dict(ctx, trace={"busy_s": 1.0}),
                  dict(ctx, trace=dict(trace, programs={})),
                  dict(ctx, config={"sliding_window": None}),
                  dict(ctx, config={})):
        for stat in ("paged_attn", "moe_gmm"):
            kernel = "moe_gmm" if stat == "moe_gmm" else "paged_decode_attn"
            assert window_roofline.read(other, kernel=kernel,
                                        program="jit_tick",
                                        stat=stat) is None
    assert kernel_time.read(dict(ctx, trace={}), kernel="moe_gmm",
                            stat="time_share") is None


# The accepted measurements the cell is listed for besides its own
# (entries of their own, ``<name>.window``, until PR 51 merged each into
# the one entry of its reader and arguments). ``itl_p99_ms.window`` is
# still its own entry: ``itl_p99_ms`` moves ``itl_p50_ms``, which
# ``serve_chat`` alone reports.
LISTED_AGAIN = ("moe_experts_touched_share", "moe_load_imbalance",
                "paged_live_block_share", "tick_overlap_share",
                "prefill_batch_ms", "slot_occupancy", "engine_queue_ms",
                "tick_wall_ms.closed_loop", "tick_thread_host_share",
                "ttft_p50_ms", "paged_attn_time_share",
                "moe_gmm_time_share")


@pytest.mark.parametrize("name", LISTED_AGAIN)
def test_an_accepted_measurement_is_listed_for_the_cell(name):
    listed_as_it_was(name, "serve_window_decode")


def test_itl_p99_of_the_window_cell_is_the_chat_cells_reading():
    mine, spec = entry_for("itl_p99_ms.window", "serve_window_decode")
    theirs, base = entry_for("itl_p99_ms", "serve_chat")
    assert (spec["reader"], spec["args"]) == (base["reader"], base["args"])
    assert (mine["moves"], theirs["moves"]) == ("tokens_per_s", "itl_p50_ms")


def test_itl_p50_of_the_window_cell_reads_the_clients_gaps():
    spec = manifest.metric_file("itl_p50_ms.window")
    assert (spec["reader"], spec["args"]) == ("client_clock",
                                              {"stat": "itl", "q": 50})
    ctx = {"measured": [{"t": [0.0, 0.019, 0.038, 0.157]},
                        {"t": [1.0, 1.02]}]}
    assert _metric("itl_p50_ms.window", ctx) == pytest.approx(19.5)


class _Reference:
    """``reference_afmoe.gaps_and_routes`` for two decoded positions of
    two routed layers: gaps 0 and 0.5, routes (3, 9) everywhere."""

    @staticmethod
    def gaps_and_routes(params, prompt, chosen, config, pad_to=0):
        import numpy as np
        return (np.asarray([0.0, 0.5, 0.0]),
                np.asarray([[[3, 9], [3, 9]], [[3, 9], [3, 9]]]))


@pytest.mark.parametrize("routes,share,ok", [
    ([[[9, 3], [3, 9]], [[3, 9], [9, 3]]], 0.0, True),    # sets, not order
    ([[[9, 4], [3, 9]], [[3, 9], [9, 3]]], 0.25, True),
    ([[[9, 4], [3, 8]], [[3, 9], [9, 3]]], 0.5, False),
    ([[[9, 3], [3, 9]]], 1.0, False),                     # a position short
])
def test_correct_holds_the_routes_and_the_mean_gap(monkeypatch, routes,
                                                   share, ok):
    monkeypatch.setattr(serve_window, "reference_afmoe", _Reference)
    tol = {"serve_mean_logit_gap_sd": 0.2,
           "serve_route_disagreement_share": 0.3}
    checks = [({"prompt": [1, 2]}, {"tokens": [5, 6, 7], "routes": routes})]
    out = serve_window.hold_to_reference(None, None, checks, tol)
    assert out["route_disagreement_share"] == pytest.approx(share)
    assert out["mean_logit_gap_sd"] == pytest.approx(0.5 / 3)
    assert out["worst_logit_gap_sd"] == 0.5 and out["ok"] is ok
    tight = dict(tol, serve_mean_logit_gap_sd=0.1)
    assert not serve_window.hold_to_reference(None, None, checks,
                                              tight)["ok"]


def test_the_configuration_states_its_two_limits():
    assert set(TRINITY["tolerance"]) == {"serve_mean_logit_gap_sd",
                                         "serve_route_disagreement_share"}
    assert "0.01" in TRINITY["assumed"]["weights"]
    check = manifest.cell("serve_window_decode")["workload"]["check"]
    assert sum(n > TRINITY["sliding_window"]
               for n in check["prompt_tokens"]) >= 3
    assert {600, 4080, 5200, 6100} <= set(check["prompt_tokens"])


def test_new_per_layer_entries_name_their_cells():
    for name in ("window_live_block_share", "window_kv_resident_share",
                 "paged_attn_roofline_share.window",
                 "moe_gmm_roofline_share.window"):
        entry, _ = entry_for(name, "serve_window_decode")
        assert entry["workloads"] == ["serve_window_decode"]
        assert entry["moves"] == "tokens_per_s"
    for name in ("moe_local_assignment_share", "prefill_chunk_ms"):
        entry, _ = entry_for(name, "serve_window_decode")
        assert entry["moves"] == "tokens_per_s"
    cells = {w["name"]: w for w in manifest.benchmark()["workloads"]}
    assert cells["serve_window_decode"]["chips"] == 1


def test_traffic_files_hold_the_issues_numbers():
    long_ctx = manifest.load_json(
        manifest.HERE + "/traffic/long_context_decode.json")
    assert (long_ctx["loop"], long_ctx["clients"]) == ("closed", 96)
    assert long_ctx["prompt_tokens"] == {
        "dist": "lognormal", "median": 5120, "sigma": 0.15, "min": 4097,
        "max": 6144}
    assert long_ctx["output_tokens"] == {
        "dist": "lognormal", "median": 768, "sigma": 0.3, "min": 384,
        "max": 1024}
    window = manifest.cell("serve_window_decode")["workload"]["engine"]
    assert (window["num_slots"], window["max_len"], window["block_size"]
            ) == (48, 7168, 64)
    # 48 x (4 x 66 + 112) blocks of 256 KB, and the garbage blocks.
    blocks = 4 * (1 + 48 * 66) + (1 + 48 * 112)
    assert abs(blocks * 262_144 / 1e9 - 4.73) < 0.01
