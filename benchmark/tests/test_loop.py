"""The looped-stack cell's benchmark code on the CPU: the manifest finds
the configuration, traffic, cell and metrics; the program's config from
the published keys (and what is refused); the configuration file against
the catalog's numbers, nothing reduced; ``flops_loop.py`` by hand count;
each new metric file through its reader on hand-made registries and a
hand-made trace, and nothing from a program without the series; the
layer loops' seconds from a hand-made trace; the traffic and workload
files' numbers; and the rehearsal of the cell end to end, which has to
come out ``correct``."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import flops_loop, manifest, traffic
from benchmark.runners import serve_loop
from benchmark.tests.test_benchmark_entries import entry_for
from benchmark.tests.test_window import _custom_call, _metric, _registry

NAME = "ouro-2.6b"
OURO = manifest.load_json(manifest.HERE + f"/configs/{NAME}.json")
CELL = "serve_loop_decode"
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
       "hbm_bytes": 16e9}
# The catalog row's numbers (model-configs guide, architectures.jsonl).
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2048, "intermediate_size": 5632,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "num_attention_heads": 16, "num_hidden_layers": 48,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-06, "rope_theta": 1000000,
    "total_ut_steps": 4, "early_exit_threshold": 1, "vocab_size": 49152}
# Accepted measurements the cell shares with other cells. PR 51's rule is
# an entry a measurement, its ``workloads`` the cells it is taken in, and
# a cell's PR edits no list: the runner prints them under ``detail`` until
# a ``benchmark`` PR appends the cell (PERF.md section 7, PR 53).
AGAIN = ("tick_wall_ms.closed_loop", "prefill_batch_ms",
         "paged_attn_time_share", "paged_live_block_share",
         "tick_overlap_share", "decode_stall_share", "fetch_wait_share",
         "stall_excess_share", "ready_at_fetch_share",
         "device_starved_share")
OWN = ("loop_tick_roofline_share", "paged_attn_roofline_share.loop",
       "loop_stack_time_share", "loop_steps_per_token",
       "loop_kv_resident_share")


def test_manifest_finds_the_cell_and_its_files():
    cell = manifest.cell(CELL)
    assert (cell["config_name"], cell["traffic_name"], cell["chips"]) == (
        NAME, "reasoning_short_decode", 1)
    assert cell["workload"]["runner"] == "serve_loop"
    listed = manifest.names(cell["per_layer"])
    assert set(listed) == set(OWN) | {"mosaic_time_share",
                                      "compiles_in_window"}
    assert manifest.names(cell["end_to_end"]) == ["tokens_per_s", "setup_s"]
    bench = manifest.benchmark()
    for name in OWN:
        entry, _ = entry_for(name, CELL)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "tokens_per_s"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["reduced"] == [] and entry["source"] == OURO["source"]
    assert (len(bench["configs"]), len(bench["workloads"])) == (10, 12)


@pytest.mark.parametrize("name", AGAIN)
def test_an_accepted_measurement_is_printed_under_detail(name):
    """Its list is as the parent left it, and the runner reads it through
    its own file."""
    (entry,) = [m for m in manifest.benchmark()["per_layer"]
                if m["name"] == name]
    assert CELL not in entry["workloads"]
    assert name in (serve_loop.DETAIL_METRICS
                    + serve_loop.DETAIL_TRACE_METRICS)
    assert manifest.metric_file(name)["reader"]


def test_file_keeps_every_published_number_and_reduces_none():
    for key, value in PUBLISHED.items():
        assert OURO[key] == value, key
    assert OURO["reduced"] == {} and OURO["layer_types"] == [
        "full_attention"] * 48
    assert OURO["published"]["num_hidden_layers"] == 48
    for key in ("assumed", "unused_keys", "deployment", "tolerance_why"):
        assert OURO[key], key
    assert set(OURO["tolerance"]) == {"serve_mean_logit_gap_sd"}
    rehearse = manifest.rehearsal(manifest.cell(CELL))["config"]
    assert (rehearse["hidden_size"], rehearse["num_hidden_layers"],
            rehearse["total_ut_steps"]) == (64, 3, 4)


def test_program_config_carries_the_published_keys():
    config = serve_loop.ouro_config(OURO, max_seq_len=704)
    assert (config.num_layers, config.loop_steps, config.vocab_size) == (
        48, 4, 49152)
    assert (config.num_heads, config.num_kv_heads, config.head_dim) == (
        16, 16, 128)
    assert config.sandwich_norms and not config.layer_types
    assert (config.rope_theta, config.rms_eps) == (1e6, 1e-6)
    from ray_tpu.models import llama
    assert dict(vars(config), max_seq_len=0) == dict(
        vars(llama.LlamaConfig.ouro_2_6b()), max_seq_len=0)


@pytest.mark.parametrize("key,value", [
    ("model_type", "llama"), ("hidden_act", "gelu"),
    ("early_exit_threshold", 0.9), ("total_ut_steps", 1),
    ("use_sliding_window", True), ("tied_head", True),
    ("rope_scaling", {"type": "yarn"}),
    ("layer_types", ["sliding_attention"] * 48)])
def test_what_the_program_does_not_run_is_refused(key, value):
    changed = {"tied_head": "tie_word_embeddings"}.get(key, key)
    with pytest.raises(ValueError, match=key.split("_")[-1]):
        serve_loop.ouro_config(dict(OURO, **{changed: value}))


def test_flops_loop_by_hand():
    # One token's K and V in every (step, layer) pair.
    assert flops_loop.kv_token_bytes(OURO) == 1_572_864
    # One pass of the stack: 48 x (4 x 2048^2 + 3 x 2048 x 5632) x 2 B.
    assert flops_loop.stack_weight_bytes(OURO) == 48 * 51_380_224 * 2
    assert flops_loop.head_bytes(OURO) == 2048 * 49152 * 2
    # 8 rows of 400 live tokens each.
    live, rows = 3200, 8
    ends = 2 * rows * 16 * 128 * 2 * 192
    assert flops_loop.tick_attn_bytes(OURO, live, rows) == (
        live * 1_572_864 + ends)
    want = 4 * 48 * 51_380_224 * 2 + 2048 * 49152 * 2 + live * 1_572_864 + ends
    assert flops_loop.tick_bytes(OURO, live, rows) == want
    assert abs(want / 1e9 - 24.97) < 0.01
    # HBM-bound at 8 rows: 30.5 ms at the v5e's 819 GB/s.
    assert flops_loop.tick_seconds(OURO, live, rows, V5E) == pytest.approx(
        want / 819e9)
    assert abs(want / 819e9 * 1e3 - 30.5) < 0.1
    assert flops_loop.tick_attn_seconds(OURO, live, rows, V5E) == \
        pytest.approx((live * 1_572_864 + ends) / 819e9)


def test_metrics_read_through_their_files_on_a_synthetic_ctx():
    before, after = _registry(**{
        "ray_tpu_cb_loop_rows_total": 8000.0,
        "ray_tpu_cb_loop_steps_total": 32000.0})
    after["ray_tpu_cb_loop_kv_bytes"] = 8.96e9
    engine = manifest.cell(CELL)["workload"]["engine"]
    # The capture: 100 ticks of 8 rows, 50 of the table's 88 entries live.
    share = 50 / (8 * 11)
    live = 50 * 64 - 8 * 32
    trace = {"busy_s": 3.9, "loop_stack_s": 3.6,
             "loop_capture": {
                 "ray_tpu_cb_paged_live_block_share_sum": 100 * share,
                 "ray_tpu_cb_paged_live_block_share_count": 100.0,
                 "ray_tpu_cb_loop_rows_total": 800.0},
             "kernels": {"paged_decode_attn": {"jit_tick": [0.8, 19200]}},
             "programs": {"jit_tick": [3.5, 100]}}
    ctx = {"registry_before": before, "registry_after": after,
           "config": OURO, "trace": trace, "engine": engine,
           "device": {"kind": "TPU v5 lite", "platform": "tpu"}}
    assert _metric("loop_steps_per_token", ctx) == pytest.approx(4.0)
    assert _metric("loop_kv_resident_share", ctx) == pytest.approx(56.0)
    assert _metric("loop_stack_time_share", ctx) == pytest.approx(
        100 * 3.6 / 3.9)
    assert _metric("loop_tick_roofline_share", ctx) == pytest.approx(
        100 * flops_loop.tick_seconds(OURO, live, 8, V5E) / 0.035)
    assert _metric("paged_attn_roofline_share.loop", ctx) == pytest.approx(
        100 * flops_loop.tick_attn_seconds(OURO, live, 8, V5E) / 0.008)
    assert _metric("loop_tick_roofline_share", ctx) < 100
    # The parent commit books none of it and traces none of it.
    bare = dict(ctx, registry_before={}, registry_after={}, trace={})
    for name in OWN:
        assert _metric(name, bare) is None
    # ... another family's cell reads nothing here, nor does a rehearsal.
    for other in (dict(ctx, config={"sliding_window": 4096}),
                  dict(ctx, device={"kind": "cpu", "platform": "cpu"})):
        assert _metric("loop_kv_resident_share", other) is None
        assert _metric("loop_tick_roofline_share", other) is None


def test_stack_seconds_from_a_hand_made_trace():
    """Four top-level loops a tick and a prefill; a loop inside a loop
    is the outer one's time; another program's loop is not counted."""
    planes = {"/device:TPU:0": {
        "XLA Modules": [("jit_tick(7)", 0, 40000),
                        ("jit_prefill(9)", 50000, 30000),
                        ("jit_cb_merge_tokens(3)", 90000, 1000)],
        "XLA Ops": [
            ("%fusion.1 = bf16[8,2048]{1,0} fusion(...)", 0, 500),
            *[(f"%while.{i} = while(...)", 1000 + 9000 * i, 8000)
              for i in range(4)],
            ("%while.9 = while(...)", 2000, 1000),      # inside while.0
            (_custom_call("paged_decode_attn.3"), 2100, 300),
            *[(f"%while.{10 + i} = while(...)", 51000 + 7000 * i, 6000)
              for i in range(4)],
            ("%while.20 = while(...)", 90100, 500)]}}
    assert serve_loop.stack_seconds(planes) == pytest.approx(
        (4 * 8000 + 4 * 6000) / 1e9)
    assert serve_loop.stack_seconds({}) == 0.0


def test_traffic_and_workload_hold_the_cells_numbers():
    cell = manifest.cell(CELL)
    mix, work = cell["traffic"], cell["workload"]
    assert (mix["loop"], mix["clients"], mix["sharing"]) == (
        "closed", 16, "none")
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 192,
                                    "sigma": 0.2, "min": 129, "max": 256}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 320,
                                    "sigma": 0.25, "min": 192, "max": 448}
    engine = work["engine"]
    assert engine == {"num_slots": 8, "max_len": 704, "block_size": 64,
                      "num_blocks": 89}
    # No admission ever waits for blocks, and a request always fits.
    assert engine["num_blocks"] == 1 + 8 * -(-704 // 64)
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] == 704
    assert work["warmup"]["batch_buckets"] == [1, 2, 4, 8]
    assert work["check"] == {"prompt_tokens": [130, 192, 250, 255, 256],
                             "max_tokens": 32}
    assert work["env"] == {"RAY_TPU_SHED_QUEUE_DEPTH": "0"}
    from ray_tpu.models.continuous_batching import _bucket
    assert traffic.prompt_buckets(mix, _bucket, 64) == [256]     # ONE bucket
    reqs = traffic.requests(mix, 2**31 + 53, OURO["vocab_size"], 40.0)
    assert len(reqs) == 80
    assert all(129 <= len(r["prompt"]) <= 256 and 192 <= r["max_tokens"] <= 448
               for r in reqs)
    # The arena: 89 blocks x 64 tokens x 1,572,864 B = 8.96 GB.
    assert abs(89 * 64 * flops_loop.kv_token_bytes(OURO) / 1e9 - 8.96) < 0.01


class _Reference:
    @staticmethod
    def gaps(params, prompt, chosen, config, pad_to=0):
        import numpy as np
        return np.asarray([0.0, 0.3, 0.0]), None


@pytest.mark.parametrize("limit,ok", [(0.2, True), (0.05, False)])
def test_correct_holds_the_mean_gap(monkeypatch, limit, ok):
    monkeypatch.setattr(serve_loop, "reference_ouro", _Reference)
    checks = [({"prompt": [1, 2]}, {"tokens": [5, 6, 7]})]
    out = serve_loop.hold_to_reference(
        None, None, checks, {"serve_mean_logit_gap_sd": limit})
    assert out["mean_logit_gap_sd"] == pytest.approx(0.1)
    assert out["worst_logit_gap_sd"] == pytest.approx(0.3)
    assert out["tokens_not_the_argmax"] == 1 and out["ok"] is ok


def test_the_rehearsal_runs_end_to_end_and_is_correct():
    proc = subprocess.run(
        [sys.executable, os.path.join(manifest.HERE, "run.py"), "--workload",
         CELL, "--seed", "5300000003", "--seconds", "4", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=600,
        cwd=manifest.ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["detail"]["rehearsal"] and line["attempted"] > 0
    got = set(line["metrics"])
    # What needs no chip is there; the device's shares need one.
    assert {"loop_steps_per_token", "compiles_in_window"} <= got
    assert line["metrics"]["loop_steps_per_token"]["value"] == 4.0
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    for name in (serve_loop.DETAIL_METRICS
                 + serve_loop.DETAIL_TRACE_METRICS):
        assert name in line["detail"]
    assert line["detail"]["lead_in_s"] == 3
    assert line["detail"]["primers_done_s"] is not None
