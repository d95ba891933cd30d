"""The EVA-attention cell's benchmark code on the CPU: the manifest finds
the configuration, traffic, cell and metrics (the new entries in their
ORDER among themselves, wherever a later PR appends after them); the
program's config from the published keys (and what is refused); the
configuration file against the catalog's numbers; ``flops_eva.py`` by
hand count; each new metric file through its reader on hand-made
registries and a hand-made trace; the traffic file's clips; the
compression's seconds from a hand-made trace; and the rehearsal of the
cell end to end, which has to come out ``correct``."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import flops_eva, manifest, traffic
from benchmark.runners import serve_eva
from benchmark.tests.test_benchmark_entries import (entry_for,
                                                    listed_as_it_was)
from benchmark.tests.test_window import _custom_call, _metric, _registry

NAME = "evabyte-6.5b-l8"
EVA = manifest.load_json(manifest.HERE + f"/configs/{NAME}.json")
CELL = "serve_eva_decode"
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# The catalog row's numbers (model-configs guide, architectures.jsonl).
PUBLISHED = {
    "chunk_size": 16, "hidden_size": 4096, "init_std": 0.01275,
    "intermediate_size": 11008, "max_position_embeddings": 32768,
    "max_seq_length": 32768, "num_attention_heads": 32,
    "num_hidden_layers": 32, "num_key_value_heads": 32, "num_pred_heads": 8,
    "rms_norm_eps": 1e-05, "rope_theta": 100000, "vocab_size": 320,
    "window_size": 2048}
# The accepted measurements the cell is listed for besides its own
# (entries of their own, ``<name>.eva``, until PR 51 merged each into the
# one entry of its reader and arguments; ``eva_attn_time_share`` was
# ``paged_attn_time_share``'s reader and arguments under another name:
# EvaByte's tick runs ``paged_decode_attn``).
AGAIN = ("paged_attn_time_share", "tick_wall_ms.closed_loop",
         "prefill_batch_ms", "prefill_chunk_ms", "slot_occupancy",
         "decode_stall_share", "device_starved_share", "tick_overlap_share",
         "ttft_p50_ms", "engine_queue_ms")
OWN = ("eva_attn_roofline_share", "eva_compress_time_share",
       "eva_summary_key_share", "eva_cache_resident_share",
       "eva_windows_closed_in_tick_share")


def test_manifest_finds_the_cell_and_its_files():
    cell = manifest.cell(CELL)
    assert (cell["config_name"], cell["traffic_name"], cell["chips"]) == (
        NAME, "byte_context_decode", 1)
    assert cell["workload"]["runner"] == "serve_eva"
    listed = manifest.names(cell["per_layer"])
    assert set(OWN + AGAIN) <= set(listed) and len(OWN + AGAIN) == 15
    assert {"mosaic_time_share", "compiles_in_window"} <= set(listed)
    assert manifest.names(cell["end_to_end"]) == ["tokens_per_s", "setup_s"]
    bench = manifest.benchmark()
    for name in OWN:
        entry, _ = entry_for(name, CELL)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "tokens_per_s"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == EVA["source"]


@pytest.mark.parametrize("name", AGAIN)
def test_an_accepted_measurement_is_listed_for_the_cell(name):
    listed_as_it_was(name, CELL)


def test_file_keeps_every_published_number_but_the_reduced_one():
    for key, value in PUBLISHED.items():
        if key == "num_hidden_layers":
            assert EVA[key] == 8 and EVA["published"][key] == value
        else:
            assert EVA[key] == value, key
    assert set(EVA["reduced"]) == {"num_hidden_layers"}
    assert EVA["attention_class"] == "eva" and EVA["num_chunks"] is None
    assert EVA["norm_add_unit_offset"] and EVA["fp32_skip_add"]
    for named in ("pooling_logit_scale", "mu_on_pooled_key", "head_layout",
                  "sampled_head", "precisions", "max_len"):
        assert named in EVA["assumed"]
    assert "eva.py" in EVA["assumed"]["pooling_logit_scale"]
    assert "4 PIPELINE STAGES of 8 layers" in EVA["deployment"]
    assert set(EVA["tolerance"]) == {"serve_mean_logit_gap_sd"}
    # 8 x 202.4M + embedding + head, in bf16.
    layer = 4 * 4096 * 4096 + 3 * 4096 * 11008
    total = 8 * layer + 320 * 4096 + 4096 * 8 * 320
    assert round(layer / 1e6, 1) == 202.4 and round(total / 1e6, 1) == 1630.8


def test_program_config_carries_the_published_keys():
    config = serve_eva.eva_config(EVA, max_seq_len=16384)
    assert config.layer_types == ("eva_attention",) * 8
    assert (config.num_heads, config.num_kv_heads, config.head_dim) == (
        32, 32, 128)
    assert (config.eva_window, config.eva_chunk, config.num_pred_heads) == (
        2048, 16, 8)
    assert (config.vocab_size, config.intermediate_size) == (320, 11008)
    assert config.zero_centered_norms and config.fp32_residual
    assert config.rope_theta == 1e5 and config.rms_eps == 1e-5
    tiny = serve_eva.eva_config(manifest.overlay(EVA, EVA["rehearse"]))
    assert (tiny.eva_window, tiny.eva_chunk, tiny.head_dim) == (32, 4, 16)


@pytest.mark.parametrize("key,value", [
    ("attention_class", "softmax"), ("attention_bias", True),
    ("hidden_act", "gelu"), ("tie_word_embeddings", True),
    ("rope_scaling", {"type": "linear"}), ("num_chunks", 4),
    ("norm_add_unit_offset", False)])
def test_what_the_program_does_not_run_is_refused(key, value):
    with pytest.raises(ValueError, match="does not run"):
        serve_eva.eva_config(dict(EVA, **{key: value}))


def test_flops_eva_by_hand():
    # A key and its value, one layer: 2 x 32 heads x 128 x 2 B.
    assert flops_eva.key_bytes(EVA) == 16384
    assert flops_eva.summaries_per_window(EVA) == 128
    # 32 slots, each 4 closed windows (512 summaries) and 1,000 raw keys.
    keys, slots = 32 * 1512, 32
    want_bytes = 8 * (keys * 16384 + 2 * slots * 32 * 128 * 2)
    assert flops_eva.tick_attn_bytes(EVA, keys, slots) == want_bytes
    assert flops_eva.tick_attn_flops(EVA, keys) == 4 * 32 * 128 * keys * 8
    # HBM-bound: about an operation a byte.
    assert flops_eva.tick_attn_seconds(EVA, keys, slots, V5E) == \
        pytest.approx(want_bytes / 819e9)
    assert abs(want_bytes / 1e9 - 6.35) < 0.01
    # Pooling one window: 2048 keys and values in, 128 pairs out, a layer.
    assert flops_eva.summarise_bytes(EVA, 1) == 8 * (2048 + 128) * 16384
    assert flops_eva.summarise_flops(EVA, 1) == 8 * 2048 * 6 * 32 * 128
    assert flops_eva.summarise_seconds(EVA, 1, V5E) == pytest.approx(
        8 * 2176 * 16384 / 819e9)


def test_metrics_read_through_their_files_on_a_synthetic_ctx():
    before, after = _registry(**{
        "ray_tpu_cb_eva_summary_keys_sum": 100 * 16384.0,
        "ray_tpu_cb_eva_summary_keys_count": 100.0,
        "ray_tpu_cb_eva_window_keys_sum": 100 * 32000.0,
        "ray_tpu_cb_eva_window_keys_count": 100.0,
        "ray_tpu_cb_decode_tokens_total": 3200.0,
        "ray_tpu_cb_eva_windows_closed_total": 40.0,
        "ray_tpu_cb_eva_blocks_retired_total": 300.0})
    after.update({"ray_tpu_cb_eva_cache_bytes": 6.6e9,
                  "ray_tpu_cb_eva_uncompressed_bytes": 42e9})
    # The capture's own ticks held fewer keys than the window's mean.
    least = flops_eva.tick_attn_seconds(EVA, 40000, 30, V5E)
    trace = {"busy_s": 3.8, "eva_compress_tick_s": 0.019,
             "eva_capture": {
                 "ray_tpu_cb_eva_summary_keys_sum": 200 * 15000.0,
                 "ray_tpu_cb_eva_window_keys_sum": 200 * 25000.0,
                 "ray_tpu_cb_eva_summary_keys_count": 200.0,
                 "ray_tpu_cb_decode_tokens_total": 6000.0},
             "kernels": {"paged_decode_attn": {"jit_tick": [2.0, 1600]}},
             "programs": {"jit_tick": [3.2, 200]}}
    ctx = {"registry_before": before, "registry_after": after,
           "config": EVA, "trace": trace, "device": {"kind": "TPU v5 lite"},
           "engine": manifest.cell(CELL)["workload"]["engine"]}
    assert _metric("eva_summary_key_share", ctx) == pytest.approx(
        100 * 16384 / (16384 + 32000))
    assert _metric("eva_cache_resident_share", ctx) == pytest.approx(
        100 * 6.6 / 42)
    # 300 blocks retired = 10 windows closed by ticks, of 40.
    assert _metric("eva_windows_closed_in_tick_share", ctx) == \
        pytest.approx(25.0)
    assert _metric("paged_attn_time_share", ctx) == pytest.approx(
        100 * 2.0 / 3.8)
    assert _metric("eva_attn_roofline_share", ctx) == pytest.approx(
        100 * least / (2.0 / 200))
    assert _metric("eva_compress_time_share", ctx) == pytest.approx(
        100 * 0.019 / 3.8)
    # The parent commit books none of it and traces none of it.
    bare = dict(ctx, registry_before={}, registry_after={}, trace={})
    for name in OWN + ("paged_attn_time_share",):
        assert _metric(name, bare) is None
    # ... and another family's cell reads nothing here.
    other = dict(ctx, config={"sliding_window": 4096})
    assert _metric("eva_summary_key_share", other) is None


def test_compress_seconds_from_a_hand_made_trace():
    layers = ("%while.32 = (s32[], f32[32,1,4096], bf16[8,1345,32,64,128]) "
              "while(%tuple.8), condition=%c, body=%b")
    closing = ("%while.31 = (s32[], bf16[8,1345,32,64,128], s32[32,46]) "
               "while(%tuple.9), condition=%c, body=%b")
    gather = ("%while.33 = (s32[], bf16[8,1345,32,64,128], s32[32,1]) "
              "while(%tuple.7), condition=%c, body=%b")
    chunk = ("%while.40 = (s32[], f32[1,2048,4096], pred[1]) "
             "while(%tuple.6), condition=%c, body=%b")
    planes = {"/device:TPU:0": {
        "XLA Modules": [("jit_tick(7)", 0, 20000),
                        ("jit_prefill(9)", 30000, 9000),
                        ("jit_tick(7)", 40000, 15000)],
        "XLA Ops": [(layers, 10, 12000),
                    (_custom_call("paged_decode_attn.9"), 100, 5000),
                    (closing, 13000, 3800), (gather, 13100, 560),
                    ("%fusion.9 = f32[8,128,32,128] fusion(...)", 15000, 300),
                    (chunk, 30010, 8000),
                    (layers, 40010, 12000),
                    (_custom_call("paged_decode_attn.9"), 40100, 5000)]}}
    # The first tick's closing loop whole, its inner gather not counted
    # twice; no layer loop, no prefill's loop; the second tick closed none.
    assert serve_eva.compress_seconds(planes) == pytest.approx(3.8e-6)
    assert serve_eva.compress_seconds({}) == 0.0


def test_traffic_and_workload_hold_the_cells_numbers():
    mix = manifest.load_json(
        manifest.HERE + "/traffic/byte_context_decode.json")
    # ISSUE 43's mix: 64 callers over 32 slots, so a request always waits.
    assert (mix["loop"], mix["clients"], mix["sharing"]) == (
        "closed", 64, "none")
    assert mix["prompt_tokens"] == {
        "dist": "lognormal", "median": 8192, "sigma": 0.2, "min": 6145,
        "max": 12287}
    assert mix["output_tokens"] == {
        "dist": "lognormal", "median": 2560, "sigma": 0.25, "min": 2049,
        "max": 4096}
    work = manifest.cell(CELL)["workload"]
    engine = work["engine"]
    assert (engine["num_slots"], engine["max_len"], engine["block_size"],
            engine["prefill_chunk"]) == (32, 16384, 64, 2048)
    assert mix["clients"] == 2 * engine["num_slots"]
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] \
        <= engine["max_len"]
    # The clips: 3 to 5 windows closed by the prompt, the last one open;
    # every answer longer than a window.
    window = EVA["window_size"]
    assert {mix["prompt_tokens"]["min"] // window,
            mix["prompt_tokens"]["max"] // window} == {3, 5}
    assert mix["prompt_tokens"]["max"] % window and \
        mix["prompt_tokens"]["min"] % window
    assert mix["output_tokens"]["min"] > window
    reqs = traffic.requests(mix, 4300000001, 320, 30)
    assert all(6145 <= len(r["prompt"]) <= 12287
               and 2049 <= r["max_tokens"] <= 4096
               and max(r["prompt"]) < 320 for r in reqs)
    # The arena: every slot's compressed worst case (2 x closed + 32).
    assert engine["num_blocks"] == 32 * 42 + 1
    assert work["check"] == {"prompt_tokens": [300, 2047, 2048, 2049, 4090,
                                               6200], "max_tokens": 32}
    assert work["warmup"]["batch_buckets"] == [1, 2, 4]
    lead = work["lead_in_s"]
    assert mix["pool_per_s"] * (lead + 40) >= 3 * mix["clients"]
    primers = work["primers"]
    assert primers["prompt_tokens"] <= window
    assert primers["prompt_tokens"] + primers["longest_answer"] \
        <= engine["max_len"]
    rehearse = manifest.rehearsal(manifest.cell(CELL))
    assert rehearse["config"]["window_size"] == rehearse["workload"][
        "engine"]["prefill_chunk"] == 32


class _Reference:
    @staticmethod
    def gaps(params, prompt, chosen, config, pad_to=0):
        import numpy as np
        return np.asarray([0.0, 0.3, 0.0])


@pytest.mark.parametrize("limit,ok", [(0.2, True), (0.05, False)])
def test_correct_holds_the_mean_gap(monkeypatch, limit, ok):
    monkeypatch.setattr(serve_eva, "reference_evabyte", _Reference)
    checks = [({"prompt": [1, 2]}, {"tokens": [5, 6, 7]})]
    out = serve_eva.hold_to_reference(
        None, None, checks, {"serve_mean_logit_gap_sd": limit})
    assert out["mean_logit_gap_sd"] == pytest.approx(0.1)
    assert out["worst_logit_gap_sd"] == pytest.approx(0.3)
    assert out["bytes_not_the_argmax"] == 1 and out["ok"] is ok


def test_the_rehearsal_runs_end_to_end_and_is_correct():
    proc = subprocess.run(
        [sys.executable, os.path.join(manifest.HERE, "run.py"), "--workload",
         CELL, "--seed", "4300000003", "--seconds", "4", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=600,
        cwd=manifest.ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["detail"]["rehearsal"] and line["attempted"] > 0
    got = set(line["metrics"])
    # What needs no chip is there; the device's shares need one.
    assert {"eva_summary_key_share", "eva_cache_resident_share",
            "eva_windows_closed_in_tick_share", "tick_wall_ms.closed_loop",
            "slot_occupancy", "compiles_in_window"} <= got
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    for name in serve_eva.LISTED_ELSEWHERE:
        assert line["detail"][name] is not None
