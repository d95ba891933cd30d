"""The three per-layer metrics of a gathered admission (PR 40): each is
a file over the accepted reader ``registry_delta``, names samples the
program's registry defines, is listed for the serve cells (the seven of
PR 40 and whichever later PRs append), and reads a number in their
rehearsals; against a program without the hold
counter ``admit_hold_share`` reads 0, never nothing."""

import json

import pytest

from benchmark import manifest
from benchmark.readers import registry_delta
from benchmark.tests.test_benchmark_entries import entry_for
from benchmark.tests.test_rehearsal import _run
from benchmark.tests.test_timeline_metrics import _registry_samples

NEW = {"prefill_rows_per_batch": ("ratio", "higher"),
       "prefill_request_ms": ("ms", "lower"),
       "admit_hold_share": ("%", "lower")}
SERVE_CELLS = ["serve_chat", "serve_prefill_heavy", "serve_moe_decode",
               "serve_hybrid_decode", "serve_window_decode",
               "serve_mla_decode", "serve_linear_decode"]


@pytest.mark.parametrize("name", sorted(NEW))
def test_metric_file_and_entry(name):
    entry, spec = entry_for(name, *SERVE_CELLS)
    assert spec["reader"] == "registry_delta" and spec["doc"].strip()
    args = spec["args"]
    assert set(args) <= {"num", "den", "scale"}
    assert set(args["num"]) | set(args["den"]) <= _registry_samples()
    assert dict(entry, workloads=None) == {
        "name": name, "unit": NEW[name][0], "better": NEW[name][1],
        "source": "program_counter", "layer": "engine scheduler",
        "moves": "tokens_per_s", "workloads": None}


def test_the_three_read_a_batched_window_and_the_parent_reads_no_hold():
    """Four batches admit ten requests in 120 ms; of 1000 slot-ms 50
    were held. The parent has no held counter: 0, and the other two as
    the change reads them."""
    before = {}
    after = {"ray_tpu_cb_prefill_requests_total": 10.0,
             "ray_tpu_cb_prefill_ms_count": 4.0,
             "ray_tpu_cb_prefill_ms_sum": 120.0,
             "ray_tpu_cb_admit_held_slot_ms_total": 50.0,
             "ray_tpu_cb_slot_stalled_ms_total": 350.0,
             "ray_tpu_cb_slot_advancing_ms_total": 600.0}
    parent = {k: v for k, v in after.items() if "held" not in k}
    want = {"prefill_rows_per_batch": (2.5, 2.5),
            "prefill_request_ms": (12.0, 12.0),
            "admit_hold_share": (5.0, 0.0)}
    for name, (change, old) in want.items():
        args = manifest.metric_file(name)["args"]
        for registry, value in ((after, change), (parent, old)):
            ctx = {"registry_before": before, "registry_after": registry}
            assert registry_delta.read(ctx, **args) == pytest.approx(value)
        idle = {"registry_before": after, "registry_after": after}
        assert registry_delta.read(idle, **args) is None


@pytest.mark.parametrize("cell", ["serve_chat", "serve_linear_decode"])
def test_rehearsal_reads_the_three(cell):
    """The bypass cell (more slots than requests, ever) and the cell the
    mechanism is for: both report all three, as shares and ratios of
    what their engines did."""
    proc = _run("--workload", cell, "--seed", "5", "--seconds", "4",
                "--trace", "1", "--rehearse")
    assert proc.returncode == 0, proc.stderr[-3000:]
    metrics = json.loads(proc.stdout.strip().splitlines()[-1])["metrics"]
    assert set(NEW) <= set(metrics)
    assert metrics["prefill_rows_per_batch"]["value"] >= 1
    assert metrics["prefill_request_ms"]["value"] > 0
    assert 0 <= metrics["admit_hold_share"]["value"] < 100
    if cell == "serve_chat":
        assert metrics["admit_hold_share"]["value"] == 0
