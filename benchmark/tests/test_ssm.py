"""The Jamba cell's benchmark code on the CPU: the manifest finds the
configuration, traffic, cell and metrics; the program's config from the
published keys (and what is refused); the configuration file against the
catalog's numbers, nothing reduced; ``flops_mamba1.py`` by hand count;
each new metric file through its reader on hand-made registries and a
hand-made trace, and nothing from a program without the series; the
traffic and workload files' numbers; and the rehearsal of the cell end
to end, which has to come out ``correct``."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import flops_mamba1, manifest, traffic
from benchmark.runners import serve_ssm
from benchmark.tests.test_benchmark_entries import entry_for
from benchmark.tests.test_window import _metric, _registry

NAME = "jamba2-3b"
JAMBA = manifest.load_json(manifest.HERE + f"/configs/{NAME}.json")
CELL = "serve_ssm_decode"
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
       "hbm_bytes": 16e9}
# The catalog row's ``config`` (model-configs guide, architectures.jsonl).
PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1,
    "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "model_type": "jamba", "num_attention_heads": 20, "num_experts": 1,
    "num_experts_per_tok": 1, "num_hidden_layers": 28,
    "num_key_value_heads": 1, "num_logits_to_keep": 1, "rms_norm_eps": 1e-06,
    "sliding_window": None, "tie_word_embeddings": True,
    "use_mamba_kernels": True, "vocab_size": 65536}
OWN = ("mamba1_step_time_share", "mamba1_step_roofline_share",
       "mamba1_scan_time_share", "mamba1_scan_roofline_share",
       "ssm_tick_roofline_share", "state_cache_resident_share.ssm",
       "paged_attn_roofline_share.ssm")
# Accepted measurements whose lists gained the cell (one entry a
# measurement: a second entry under a suffix would be the same reader,
# arguments and ``moves`` twice).
JOINED = ("tick_wall_ms.closed_loop", "slot_occupancy",
          "device_starved_share")


def test_manifest_finds_the_cell_and_its_files():
    cell = manifest.cell(CELL)
    assert (cell["config_name"], cell["traffic_name"], cell["chips"]) == (
        NAME, "reasoning_wide_decode", 1)
    assert cell["workload"]["runner"] == "serve_ssm"
    assert set(manifest.names(cell["per_layer"])) == (
        set(OWN) | set(JOINED) | {"mosaic_time_share", "compiles_in_window"})
    assert manifest.names(cell["end_to_end"]) == ["tokens_per_s", "setup_s"]
    bench = manifest.benchmark()
    for name in OWN:
        entry, _ = entry_for(name, CELL)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "tokens_per_s"
    for name in JOINED:
        entry, _ = entry_for(name, CELL)
        assert entry["workloads"][-1] == CELL
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["reduced"] == [] and entry["source"] == JAMBA["source"]
    assert bench["configs"][-1]["name"] == NAME
    assert bench["workloads"][-1]["name"] == CELL


def test_file_keeps_every_published_number_and_reduces_none():
    for key, value in PUBLISHED.items():
        assert JAMBA[key] == value, key
    assert JAMBA["reduced"] == {} and JAMBA["head_dim"] == 128
    for key in ("assumed", "unused_keys", "deployment", "tolerance_why"):
        assert JAMBA[key], key
    assert set(JAMBA["tolerance"]) == {"serve_mean_logit_gap_sd"}
    rehearse = manifest.rehearsal(manifest.cell(CELL))["config"]
    assert (rehearse["hidden_size"], rehearse["num_hidden_layers"],
            rehearse["mamba_dt_rank"]) == (64, 4, 8)


def test_program_config_carries_the_published_keys():
    from ray_tpu.models import llama

    config = serve_ssm.jamba_config(JAMBA, max_seq_len=2560)
    assert dict(vars(config), max_seq_len=0) == dict(
        vars(llama.LlamaConfig.jamba2_3b()), max_seq_len=0)
    assert [i for i, t in enumerate(config.layer_types)
            if t == "attention"] == [7, 21]
    assert config.mamba_dims[0] == 5120 and not config.rope
    assert llama.num_params(config) == 3_029_337_472


@pytest.mark.parametrize("key,value", [
    ("model_type", "mamba"), ("hidden_act", "gelu"), ("num_experts", 16),
    ("mamba_conv_bias", False), ("mamba_proj_bias", True),
    ("tie_word_embeddings", False), ("sliding_window", 4096),
    ("head_dim", 64)])
def test_what_the_program_does_not_run_is_refused(key, value):
    word = {"tie_word_embeddings": "untied head"}.get(key, key)
    with pytest.raises(ValueError, match=word):
        serve_ssm.jamba_config(dict(JAMBA, **{key: value}))


def test_flops_mamba1_by_hand():
    c = JAMBA
    assert (flops_mamba1.mamba_layers(c), flops_mamba1.attn_layers(c)) == (
        26, 2)
    assert flops_mamba1.state_elements(c) == 5120 * 16 == 81_920
    # THE STEP, one layer, 256 sequences: 6 operations and one exp a
    # state element; the state in and out, u (2 B), dt and y (4 B) a
    # channel, B and C, and A once.
    assert flops_mamba1.step_flops(c, 256) == 6 * 81_920 * 256
    per_token = 5120 * (2 + 4 + 4) + 2 * 16 * 4
    assert flops_mamba1.token_bytes(c) == per_token == 51_328
    assert flops_mamba1.step_bytes(c, 256) == (
        256 * (2 * 327_680 + per_token) + 327_680)
    # HBM-bound: 0.221 ms a layer, 5.75 ms the 26 layers of a tick.
    assert flops_mamba1.tick_step_seconds(c, 256, V5E) == pytest.approx(
        26 * flops_mamba1.step_bytes(c, 256) / 819e9)
    assert abs(flops_mamba1.tick_step_seconds(c, 256, V5E) * 1e3
               - 5.75) < 0.01
    # THE SCAN, one layer, a call of 16 rows with 6,144 real tokens, none
    # carried: the tokens' operands and a state out a row.
    assert flops_mamba1.scan_bytes(c, 6144, 16, 0) == (
        6144 * per_token + 16 * 327_680 + 327_680)
    assert flops_mamba1.scan_seconds(c, 6144, 16, 0, V5E) == pytest.approx(
        26 * flops_mamba1.scan_bytes(c, 6144, 16, 0) / 819e9)
    # A chunk that carried its state reads it as well.
    assert (flops_mamba1.scan_bytes(c, 1, 1, 1)
            - flops_mamba1.scan_bytes(c, 1, 1, 0)) == 327_680
    # What a request keeps, and a token's K/V.
    assert flops_mamba1.state_bytes(c, 1) == 26 * 358_400
    assert flops_mamba1.kv_token_bytes(c) == 1024
    # Every matmul weight: the model less its norms, conv, A, D, biases
    # and with the tied embedding counted once, as the head.
    mamba = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    attention = 2560 * 128 * 42
    assert flops_mamba1.matmul_params(c) == (
        26 * mamba + 2 * attention + 28 * 3 * 2560 * 8192 + 2560 * 65536)
    # THE TICK at 256 rows of 1,200 live tokens: operations 7.9 ms,
    # bytes 13.2 ms: the larger.
    live, rows = 256 * 1200, 256
    flops = flops_mamba1.tick_flops(c, live, rows)
    bytes_ = flops_mamba1.tick_bytes(c, live, rows)
    assert flops == (2 * flops_mamba1.matmul_params(c) * 256
                     + 26 * 6 * 81_920 * 256 + 2 * 4 * 20 * 128 * live)
    assert bytes_ == (
        flops_mamba1.matmul_params(c) * 2 + 26 * 327_680
        + 2 * 256 * 26 * 358_400 + live * 1024 + 2 * 256 * 2 * 20 * 128 * 2)
    assert abs(flops / 197e12 * 1e3 - 7.87) < 0.05
    assert abs(bytes_ / 819e9 * 1e3 - 13.6) < 0.1
    assert flops_mamba1.tick_seconds(c, live, rows, V5E) == pytest.approx(
        bytes_ / 819e9)


def test_metrics_read_through_their_files_on_a_synthetic_ctx():
    engine = manifest.cell(CELL)["workload"]["engine"]
    width = -(-engine["max_len"] // 64)               # 40 table entries
    # 100 ticks of 250 live slots; 5,000 of the 10,240 entries live.
    share = 5000 / (256 * width)
    before, after = _registry(**{
        "ray_tpu_cb_state_live_slots_sum": 25_000.0,
        "ray_tpu_cb_state_live_slots_count": 100.0,
        "ray_tpu_cb_paged_live_block_share_sum": 100 * share,
        "ray_tpu_cb_paged_live_block_share_count": 100.0,
        "ray_tpu_cb_prefill_tokens_total": 61_440.0,
        "ray_tpu_cb_prefill_chunk_ms_count": 10.0,
        "ray_tpu_cb_state_installs_total": 160.0,
        "ray_tpu_cb_prefill_state_carries_total": 0.0})
    live = 5000 * 64 - 250 * 32
    trace = {"busy_s": 3.9,
             "kernels": {
                 "mamba1_step": {"jit_tick": [1.4, 5200]},
                 "mamba1_scan": {"jit_prefill": [0.12, 52]},
                 "paged_decode_attn": {"jit_tick": [0.1, 400]}},
             "programs": {"jit_tick": [3.4, 200], "jit_prefill": [0.5, 2]}}
    ctx = {"registry_before": before, "registry_after": after,
           "config": JAMBA, "trace": trace, "engine": engine,
           "device": {"kind": "TPU v5 lite", "platform": "tpu"}}
    assert _metric("mamba1_step_time_share", ctx) == pytest.approx(
        100 * 1.4 / 3.9)
    assert _metric("mamba1_scan_time_share", ctx) == pytest.approx(
        100 * 0.12 / 3.9)
    assert _metric("mamba1_step_roofline_share", ctx) == pytest.approx(
        100 * flops_mamba1.tick_step_seconds(JAMBA, 250, V5E) / (1.4 / 200))
    assert _metric("mamba1_scan_roofline_share", ctx) == pytest.approx(
        100 * flops_mamba1.scan_seconds(JAMBA, 6144, 16, 0, V5E)
        / (0.12 / 2))
    assert _metric("ssm_tick_roofline_share", ctx) == pytest.approx(
        100 * flops_mamba1.tick_seconds(JAMBA, live, 250, V5E) / (3.4 / 200))
    assert _metric("paged_attn_roofline_share.ssm", ctx) == pytest.approx(
        100 * flops_mamba1.tick_attn_seconds(JAMBA, live, 250, V5E)
        / (0.1 / 200))
    state = 250 * 26 * 358_400
    assert _metric("state_cache_resident_share.ssm", ctx) == pytest.approx(
        100 * state / (state + live * 1024))
    for name in OWN:
        assert 0 < _metric(name, ctx) < 100, name
    # The parent commit books none of it and traces none of it.
    bare = dict(ctx, registry_before={}, registry_after={}, trace={})
    for name in OWN:
        assert _metric(name, bare) is None
    # ... and another family's cell reads nothing here.
    other = dict(ctx, config={"linear_num_value_heads": 32})
    for name in OWN[1::2] + OWN[4:]:
        assert _metric(name, other) is None


def test_traffic_and_workload_hold_the_cells_numbers():
    cell = manifest.cell(CELL)
    mix, work = cell["traffic"], cell["workload"]
    assert (mix["loop"], mix["clients"], mix["sharing"]) == (
        "closed", 512, "none")
    assert (mix["pool_per_s"], mix["stratify_block"], mix["drain_s"]) == (
        40, 32, 120)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 384,
                                    "sigma": 0.25, "min": 257, "max": 512}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 1536,
                                    "sigma": 0.25, "min": 1024, "max": 2048}
    engine = work["engine"]
    assert engine == {"num_slots": 256, "max_len": 2560, "block_size": 64,
                      "num_blocks": None, "prefill_chunk": 1024}
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] == 2560
    assert work["warmup"]["batch_buckets"] == [1, 2, 4, 8, 16]
    assert work["check"] == {
        "prompt_tokens": [300, 511, 512, 1024, 1025, 1600], "max_tokens": 32}
    assert work["env"] == {"RAY_TPU_SHED_QUEUE_DEPTH": "0"}
    from ray_tpu.models.continuous_batching import _bucket
    assert traffic.prompt_buckets(mix, _bucket, 64) == [512]     # ONE bucket
    reqs = traffic.requests(mix, 2**31 + 55, JAMBA["vocab_size"], 2.0)
    assert len(reqs) == 80
    assert all(257 <= len(r["prompt"]) <= 512
               and 1024 <= r["max_tokens"] <= 2048 for r in reqs)
    # What the chip holds beside the weights: 2.39 GB of state, 0.67 GB
    # of arena.
    assert abs(flops_mamba1.state_bytes(JAMBA, 256) / 1e9 - 2.386) < 0.001
    assert abs((1 + 256 * 40) * 64 * 1024 / 1e9 - 0.671) < 0.001


class _Reference:
    @staticmethod
    def gaps(params, prompt, chosen, config, pad_to=0):
        import numpy as np
        return np.asarray([0.0, 0.3, 0.0])


@pytest.mark.parametrize("limit,ok", [(0.2, True), (0.05, False)])
def test_correct_holds_the_mean_gap(monkeypatch, limit, ok):
    monkeypatch.setattr(serve_ssm, "reference_jamba", _Reference)
    checks = [({"prompt": [1, 2]}, {"tokens": [5, 6, 7]})]
    out = serve_ssm.hold_to_reference(
        None, None, checks, {"serve_mean_logit_gap_sd": limit})
    assert out["mean_logit_gap_sd"] == pytest.approx(0.1)
    assert out["worst_logit_gap_sd"] == pytest.approx(0.3)
    assert out["tokens_not_the_argmax"] == 1 and out["ok"] is ok


def test_the_rehearsal_runs_end_to_end_and_is_correct():
    proc = subprocess.run(
        [sys.executable, os.path.join(manifest.HERE, "run.py"), "--workload",
         CELL, "--seed", "5500000003", "--seconds", "4", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=600,
        cwd=manifest.ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["detail"]["rehearsal"] and line["attempted"] > 0
    got = set(line["metrics"])
    # What needs no chip is there; the device's shares need one.
    assert {"state_cache_resident_share.ssm", "slot_occupancy",
            "tick_wall_ms.closed_loop", "device_starved_share",
            "compiles_in_window"} <= got
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    for name in serve_ssm.DETAIL_METRICS:
        assert name in line["detail"]
    assert line["detail"]["lead_in_s"] == 3
    assert line["detail"]["primers_done_s"] is not None
