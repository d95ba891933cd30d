"""The MLA cell's benchmark code on the CPU: the manifest finds the
configuration, traffic, cell and metrics; the program's config from the
published keys and the share (and what is refused); the configuration
file against the catalog's numbers; ``flops_mla.py`` by hand count; each
new metric file through its reader on hand-made registries and a
hand-made by-kernel trace. The traced CPU rehearsal of the cell that
ends ``correct`` is ``test_rehearsal.py``'s, which runs every cell the
manifest lists."""

import pytest

from benchmark import flops_mla, manifest, stats
from benchmark.readers import kernel_time, mla_roofline
from benchmark.runners import serve_mla, serve_moe
from benchmark.tests.test_benchmark_entries import (entry_for,
                                                    listed_as_it_was)
from benchmark.tests.test_window import _custom_call, _metric, _registry
from ray_tpu.models import llama

KIMI = manifest.load_json(
    manifest.HERE + "/configs/kimi-k2.7-code-l5-e12.json")
CELL = "serve_mla_decode"
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# The catalog row's numbers (model-configs guide, architectures.jsonl).
PUBLISHED = {
    "ep_size": 1, "first_k_dense_replace": 1, "hidden_size": 7168,
    "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 262144, "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 384,
    "n_shared_experts": 1, "num_attention_heads": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 61,
    "num_key_value_heads": 64, "num_nextn_predict_layers": 0,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_theta": 50000,
    "routed_scaling_factor": 2.827, "topk_group": 1, "v_head_dim": 128,
    "vocab_size": 163840}
REDUCED = {"num_hidden_layers", "n_routed_experts", "vocab_size"}
OWN = ("mla_attn_time_share", "mla_attn_roofline_share",
       "latent_kv_resident_share", "moe_gmm_roofline_share.mla")
# The accepted measurements the cell is listed for besides its own
# (entries of their own, ``<name>.mla``, until PR 51 merged each into
# the one entry of its reader and arguments).
AGAIN = ("moe_gmm_time_share", "moe_local_assignment_share",
         "moe_experts_touched_share", "tick_wall_ms.closed_loop",
         "prefill_batch_ms", "prefill_chunk_ms", "slot_occupancy",
         "ttft_p50_ms", "decode_stall_share", "device_starved_share",
         "prefill_row_fill_share", "tick_overlap_share",
         "paged_visit_fill_share", "engine_queue_ms")


def test_manifest_finds_the_cell_and_its_files():
    cell = manifest.cell(CELL)
    assert (cell["config_name"], cell["traffic_name"], cell["chips"]) == (
        "kimi-k2.7-code-l5-e12", "code_context_decode", 1)
    assert cell["workload"]["runner"] == "serve_mla"
    listed = manifest.names(cell["per_layer"])
    assert set(OWN + AGAIN) <= set(listed) and len(OWN + AGAIN) == 18
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
             if c["name"] == "kimi-k2.7-code-l5-e12"][0]
    assert set(entry["reduced"]) == REDUCED == set(KIMI["reduced"])
    assert entry["source"] == KIMI["source"]
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert KIMI[key] != value
            assert KIMI["published"][key] == value
        else:
            assert KIMI[key] == value, key
    assert KIMI["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert (KIMI["n_routed_experts"], KIMI["router_experts"],
            KIMI["experts_held"]) == (12, 384, [0, 12])
    assert KIMI["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert {"deployment_width", "rope_pairing", "cache_row"} <= set(
        KIMI["assumed"])
    assert set(KIMI["tolerance"]) == {"serve_mean_logit_gap_sd",
                                      "serve_route_disagreement_share"}


def test_program_config_carries_the_published_keys_and_the_share():
    config = serve_mla.kimi_config(KIMI, max_seq_len=10240)
    assert config.layer_types == ("latent_attention",) * 5
    assert (config.latent_layers, config.attn_layers, config.moe_layers,
            config.num_dense_layers) == (5, 0, 4, 1)
    assert (config.num_experts, config.experts_held, config.experts_here,
            config.num_experts_per_tok) == (384, (0, 12), 12, 8)
    assert (config.hidden_size, config.intermediate_size,
            config.dense_intermediate_size,
            config.shared_intermediate_size) == (7168, 2048, 18432, 2048)
    assert (config.num_heads, config.q_lora_rank, config.kv_lora_rank,
            config.qk_nope_head_dim, config.qk_rope_head_dim,
            config.v_head_dim) == (64, 1536, 512, 128, 64, 128)
    assert (config.router_score, config.route_scale, config.norm_topk_prob
            ) == ("sigmoid", 2.827, True)
    assert dict(config.rope_scaling)["factor"] == 64
    assert config.attn_scale == pytest.approx(0.14468, abs=1e-5)
    assert config == llama.LlamaConfig.kimi_k2_7_code(
        num_layers=5, layer_types=("latent_attention",) * 5,
        vocab_size=20480, experts_held=(0, 12), max_seq_len=10240)
    # The configuration file's arithmetic: 3.497B held.
    assert abs(llama.num_params(config) / 1e9 - 3.497) < 0.001


@pytest.mark.parametrize("key,value", [
    ("scoring_func", "softmax"), ("n_group", 8), ("n_shared_experts", 2),
    ("moe_layer_freq", 2), ("hidden_act", "gelu"), ("attention_bias", True),
    ("tie_word_embeddings", True), ("num_nextn_predict_layers", 1),
    ("topk_method", "greedy"), ("experts_held", [0, 16])])
def test_what_the_program_does_not_run_is_refused(key, value):
    with pytest.raises(ValueError, match="does not run"):
        serve_mla.kimi_config(dict(KIMI, **{key: value}))


def test_flops_mla_by_hand():
    assert flops_mla.latent_token_bytes(KIMI) == 1152
    assert flops_mla.per_head_token_bytes(KIMI) == 40960
    # ISSUE 36's tick: 670k live positions in each of 5 layers.
    live = 670_000
    rows = live * 1152 * 5
    assert rows == pytest.approx(3.86e9, rel=0.002)
    flops = flops_mla.tick_attn_flops(KIMI, live)
    assert flops == pytest.approx(2 * 64 * live * (576 + 512) * 5)
    assert flops == pytest.approx(0.47e12, rel=0.01)
    ends = 5 * 96 * 64 * (576 + 512) * 2          # q in and o out
    assert flops_mla.tick_attn_bytes(KIMI, live, 96) == rows + ends
    # The larger of the two times wins: bytes here (121 FLOP/B is under
    # the v5e's ridge of 240) ...
    least = flops_mla.tick_attn_seconds(KIMI, live, 96, V5E)
    assert least == pytest.approx((rows + ends) / 819e9)
    assert flops / 197e12 < least
    # ... and the operations on a chip with a tenth of the FLOP/s.
    slow = dict(V5E, bf16_flops_per_s=19.7e12)
    assert flops_mla.tick_attn_seconds(KIMI, live, 96, slow) == (
        pytest.approx(flops / 19.7e12))
    # Experts: 3 x 7168 x 2048 each; 24 local assignments a layer on
    # 10.5 touched experts, 4 routed layers.
    assert flops_mla.expert_params(KIMI) == 44_040_192
    assert flops_mla.routed_layers(KIMI) == 4
    gmm = flops_mla.tick_gmm_seconds(KIMI, 24, 10.5, V5E)
    assert gmm == pytest.approx(
        4 * (10.5 * 44_040_192 * 2 + 2 * 24 * 7168 * 2) / 819e9)


HAND_MADE = {"/device:TPU:0": {
    "XLA Modules": [("jit_tick(7)", 0, 12000), ("jit_tick(7)", 14000, 12000)],
    "XLA Ops": [("%while.1 = while(...)", 0, 12000),
                (_custom_call("latent_decode_attn.3"), 1000, 6000),
                (_custom_call("moe_gmm.4"), 8000, 1500),
                ("%while.1 = while(...)", 14000, 12000),
                (_custom_call("latent_decode_attn.3"), 15000, 7000),
                (_custom_call("moe_gmm.4"), 23000, 1700)]}}


def test_roofline_and_resident_share_on_a_synthetic_ctx():
    trace = dict(serve_moe.by_kernel(HAND_MADE), busy_s=24500e-9)
    before, after = _registry(**{
        "ray_tpu_cb_mla_live_tokens_sum": 200 * 670_000.0,
        "ray_tpu_cb_mla_live_tokens_count": 200.0,
        "ray_tpu_cb_moe_local_assignments_total": 200 * 4 * 24.0,
        "ray_tpu_cb_moe_experts_touched_share_sum": 200 * 10.5 / 12,
        "ray_tpu_cb_moe_experts_touched_share_count": 200.0})
    engine = {"num_slots": 96, "max_len": 10240, "block_size": 64,
              "num_blocks": None}
    blocks = 1 + 96 * 160
    after["ray_tpu_cb_latent_kv_bytes"] = 5 * blocks * 64 * 640 * 2.0
    ctx = {"trace": trace, "config": KIMI, "registry_before": before,
           "registry_after": after, "device": {"kind": "TPU v5 lite"},
           "engine": engine}
    assert _metric("mla_attn_time_share", ctx) == pytest.approx(
        100 * 13000 / 24500)
    assert _metric("moe_gmm_time_share", ctx) == pytest.approx(
        100 * 3200 / 24500)
    least = flops_mla.tick_attn_seconds(KIMI, 670_000, 96, V5E)
    assert _metric("mla_attn_roofline_share", ctx) == pytest.approx(
        100 * least / (13000e-9 / 2))
    gmm = flops_mla.tick_gmm_seconds(KIMI, 24, 10.5, V5E)
    assert _metric("moe_gmm_roofline_share.mla", ctx) == pytest.approx(
        100 * gmm / (3200e-9 / 2))
    # 640 stored values a row against 64 x 320 per-head: 3.125%.
    assert _metric("latent_kv_resident_share", ctx) == pytest.approx(3.125)
    assert 100 * 1152 / 40960 == pytest.approx(2.8125)
    # The parent commit (none of the series), a trace reduced without the
    # by-kernel part, a program that never ran the kernel, or another
    # family's configuration: nothing, and no error.
    parent = dict(ctx, registry_before={}, registry_after={"x": 1.0})
    for other in (parent, dict(ctx, trace={"busy_s": 1.0}),
                  dict(ctx, trace=dict(trace, programs={})),
                  dict(ctx, config={"kv_lora_rank": None}),
                  dict(ctx, config={})):
        for name in ("mla_attn_roofline_share", "moe_gmm_roofline_share.mla"):
            assert _metric(name, other) is None
    for other in (parent, dict(ctx, config={})):
        assert _metric("latent_kv_resident_share", other) is None
    assert kernel_time.read(dict(ctx, trace={}), kernel="latent_decode_attn",
                            stat="time_share") is None
    with pytest.raises(ValueError):
        mla_roofline.read(ctx, stat="other", kernel="moe_gmm",
                          program="jit_tick")


def test_traffic_and_workload_hold_the_cells_numbers():
    mix = manifest.load_json(
        manifest.HERE + "/traffic/code_context_decode.json")
    # ISSUE 36's mix: 192 callers over 96 slots, so a request always waits.
    assert (mix["loop"], mix["clients"]) == ("closed", 192)
    assert mix["prompt_tokens"] == {
        "dist": "lognormal", "median": 6144, "sigma": 0.2, "min": 4097,
        "max": 8192}
    assert mix["output_tokens"] == {
        "dist": "lognormal", "median": 1536, "sigma": 0.3, "min": 768,
        "max": 2048}
    work = manifest.cell(CELL)["workload"]
    assert work["env"] == {"RAY_TPU_SHED_QUEUE_DEPTH": "0"}
    assert work["engine"] == {"num_slots": 96, "max_len": 10240,
                              "block_size": 64, "num_blocks": None,
                              "prefill_chunk": 1024}
    assert work["check"] == {"prompt_tokens": [600, 1023, 1025, 4100, 6000,
                                               7000, 8100], "max_tokens": 32}
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] <= 10240
    # 5 layers x (1 + 96 x 160) blocks x 64 rows x 640 lanes x 2 B.
    assert abs(5 * (1 + 96 * 160) * 64 * 640 * 2 / 1e9 - 6.29) < 0.01
    # The loop runs before the clock, and the drain lets every request
    # that waited for a slot at the window's end come back whole; the
    # pool outlasts lead-in and window.
    assert work["lead_in_s"] == 80 and mix["drain_s"] >= 180
    assert mix["pool_per_s"] * (80 + 40) >= 2 * mix["clients"]
    # One primer a slot, inside one chunk and the cache's length.
    assert work["primers"] == {"prompt_tokens": 1000, "longest_answer": 1600,
                               "head_start_s": 1.0}
    rehearse = manifest.rehearsal(manifest.cell(CELL))
    assert rehearse["workload"]["engine"] == {
        "num_slots": 4, "max_len": 160, "block_size": 8, "num_blocks": None,
        "prefill_chunk": 16}
    assert (rehearse["traffic"]["prompt_tokens"]["min"],
            rehearse["traffic"]["prompt_tokens"]["max"]) == (34, 64)


def _rec(first, n, gap, prompt=100, done=True):
    t = [first + gap * i for i in range(n)]
    return {"prompt_tokens": prompt, "max_tokens": n, "n": n, "bad": 0,
            "done": done, "error": None, "first": t[0], "last": t[-1],
            "t": t}


def test_primers_end_one_after_another_and_are_seeded():
    import numpy as np
    spec = {"prompt_tokens": 1000, "longest_answer": 1600}
    reqs = serve_mla.primers(np.random.default_rng([7, 0x9e1]), 20480, spec,
                             96)
    again = serve_mla.primers(np.random.default_rng([7, 0x9e1]), 20480, spec,
                              96)
    assert reqs == again and len(reqs) == 96
    assert {len(r["prompt"]) for r in reqs} == {1000}
    answers = sorted(r["max_tokens"] for r in reqs)
    assert (answers[0], answers[-1]) == (17, 1600)
    assert set(np.diff(answers)) <= {16, 17}
    assert [r["max_tokens"] for r in reqs] != answers      # shuffled
    assert all(0 < t < 20480 for r in reqs for t in r["prompt"])


@pytest.mark.parametrize("record,tokens", [
    (_rec(5.0, 11, 1.0), 111.0),                  # whole inside: all of it
    (_rec(-30.0, 41, 1.0), 141 * 10 / 40),        # 10 s of its 40 inside
    (_rec(30.0, 41, 1.0), 141 * 10 / 40),         # ends in the drain
    (_rec(-20.0, 81, 1.0), 181 * 40 / 80),        # spans the whole window
    (_rec(41.0, 5, 1.0), 0.0),                    # after the close
    (_rec(-9.0, 5, 1.0), 0.0),                    # before the opening
    (_rec(3.0, 1, 1.0), 101.0),                   # one token, inside
    (_rec(1.0, 10, 1.0, done=False), 0.0),        # short: nothing
])
def test_tokens_in_service_counts_a_request_over_its_time_in_a_slot(record,
                                                                    tokens):
    assert serve_mla.tokens_in_service([record], 40.0) == pytest.approx(tokens)


def test_tokens_between_counts_what_arrived_inside_the_window():
    rec = _rec
    records = [rec(-30.0, 40, 1.0),           # began in the lead-in: 9 tokens
               rec(5.0, 10, 1.0),             # whole inside: prompt + 10
               rec(38.5, 10, 1.0),            # ends in the drain: prompt + 2
               rec(41.0, 5, 1.0),             # first token after the close
               rec(1.0, 10, 1.0, done=False)]     # short: nothing
    assert all(stats.whole(r) for r in records[:4])
    assert serve_mla.tokens_between(records, 40.0) == (200, 9 + 10 + 2)


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
    monkeypatch.setattr(serve_mla, "reference_kimi_k2", _Reference)
    tol = {"serve_mean_logit_gap_sd": 0.2,
           "serve_route_disagreement_share": 0.3}
    checks = [({"prompt": [1, 2]}, {"tokens": [5, 6, 7], "routes": routes})]
    out = serve_mla.hold_to_reference(None, None, checks, tol)
    assert out["route_disagreement_share"] == pytest.approx(share)
    assert out["mean_logit_gap_sd"] == pytest.approx(0.5 / 3)
    assert out["ok"] is ok
