"""The two per-layer metrics of the prefill scan kernel (PR 44): each is
a file that loads, names a reader that exists, and has an entry in
``BENCHMARK.json`` that lists the linear cell: asserted by PRESENCE,
wherever later PRs append theirs. Both through their readers on a
hand-made by-kernel trace and registry; against a program without the
kernel (the parent) both read nothing."""

import importlib

import pytest

from benchmark import flops_gdn, manifest
from benchmark.readers import gdn_scan_roofline
from benchmark.runners import serve_moe
from benchmark.tests.test_benchmark_entries import entry_for
from benchmark.tests.test_window import _custom_call, _metric, _registry

CELL = "serve_linear_decode"
NEW = {"gdn_scan_time_share": ("kernel_time", "lower"),
       "gdn_scan_roofline_share": ("gdn_scan_roofline", "higher")}
QWEN = manifest.load_json(
    manifest.HERE + "/configs/qwen3-next-80b-a3b-l8-e64.json")
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.mark.parametrize("name", sorted(NEW))
def test_metric_file_loads_and_is_listed_for_the_cell(name):
    entry, spec = entry_for(name, CELL)
    assert spec["reader"] == NEW[name][0] and spec["doc"].strip()
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    assert callable(reader.read)
    assert spec["args"]["kernel"] == "gdn_chunk_scan"
    assert entry == {"name": name, "unit": "%", "better": NEW[name][1],
                     "source": "device_trace",
                     "layer": "linear-attention mixer",
                     "moves": "tokens_per_s", "workloads": [CELL]}
    assert name in manifest.names(manifest.cell(CELL)["per_layer"])


def test_least_seconds_by_hand():
    """Two rows of 1024 and 512 real tokens, the second carried: a token
    moves (2 x 2048 + 2 x 4096) bf16 + 2 x 32 float32 = 24,832 bytes; a
    state is 32 x 128 x 128 float32 = 2,097,152 bytes, written twice and
    read once; 7 operations x 524,288 elements a token. HBM-bound, six
    layers."""
    bytes_ = 1536 * 24_832 + 3 * 2_097_152
    flops = 7 * 524_288 * 1536
    assert flops / V5E["bf16_flops_per_s"] < bytes_ / V5E["hbm_bytes_per_s"]
    assert flops_gdn.linear_layers(QWEN) == 6
    assert gdn_scan_roofline.least_seconds(
        QWEN, 1536, 2, 1, V5E) == pytest.approx(6 * bytes_ / 819e9)


def _ctx(kernel):
    """A 1 s capture of two prefill calls, 3 ms of ``kernel`` in each,
    and a window of 10 calls, 15,360 real tokens, 20 rows of which 10
    carried a state."""
    planes = {"/device:TPU:0": {
        "XLA Modules": [("jit_prefill(9)", 0, 40_000_000),
                        ("jit_prefill(9)", 500_000_000, 40_000_000),
                        ("jit_tick(7)", 900_000_000, 100_000_000)],
        "XLA Ops": [(_custom_call(kernel + ".3"), 1_000_000, 3_000_000),
                    ("%fusion.1 = fusion(...)", 5_000_000, 35_000_000),
                    (_custom_call(kernel + ".3"), 501_000_000, 3_000_000),
                    (_custom_call("gdn_step.1"), 900_000_000, 94_000_000)]}}
    before, after = _registry(**{
        "ray_tpu_cb_prefill_tokens_total": 15_360.0,
        "ray_tpu_cb_prefill_chunk_ms_count": 10.0,
        "ray_tpu_cb_state_installs_total": 10.0,
        "ray_tpu_cb_prefill_state_carries_total": 10.0})
    return {"config": QWEN, "registry_before": before,
            "registry_after": after, "device": {"kind": "TPU v5 lite"},
            "trace": dict(serve_moe.by_kernel(planes), busy_s=0.135)}


def test_both_read_the_kernel_and_nothing_without_it():
    ctx = _ctx("gdn_chunk_scan")
    assert _metric("gdn_scan_time_share", ctx) == pytest.approx(
        100 * 0.006 / 0.135)
    least = gdn_scan_roofline.least_seconds(QWEN, 1536, 2, 1, V5E)
    assert _metric("gdn_scan_roofline_share", ctx) == pytest.approx(
        100 * least / 0.003)
    parent = _ctx("some_other_kernel")
    assert _metric("gdn_scan_time_share", parent) is None
    assert _metric("gdn_scan_roofline_share", parent) is None
    no_window = dict(ctx, registry_after=ctx["registry_before"])
    assert _metric("gdn_scan_roofline_share", no_window) is None
    assert _metric("gdn_scan_roofline_share",
                   dict(ctx, config={"hidden_size": 8})) is None
