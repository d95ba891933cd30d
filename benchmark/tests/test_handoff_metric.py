"""``stream_tokens_per_handoff`` (PR 41): a file over the accepted reader
``registry_delta``, names samples the program's registry defines, is
listed for the serve cells (PR 41's seven and whichever later PRs
append), reads the
tokens one tick-thread -> loop call carried, reads nothing against a
program without the counter, and is reported by a serve cell's
rehearsal."""

import json

import pytest

from benchmark import manifest
from benchmark.readers import registry_delta
from benchmark.tests.test_benchmark_entries import entry_for
from benchmark.tests.test_rehearsal import _run
from benchmark.tests.test_timeline_metrics import _registry_samples

NAME = "stream_tokens_per_handoff"
SERVE_CELLS = ["serve_chat", "serve_prefill_heavy", "serve_moe_decode",
               "serve_hybrid_decode", "serve_window_decode",
               "serve_mla_decode", "serve_linear_decode"]


def test_metric_file_and_entry():
    entry, spec = entry_for(NAME, *SERVE_CELLS)
    assert spec["reader"] == "registry_delta" and spec["doc"].strip()
    assert spec["args"] == {
        "num": ["ray_tpu_serve_stream_replica_items_total"],
        "den": ["ray_tpu_serve_stream_handoffs_total"]}
    assert set(spec["args"]["num"] + spec["args"]["den"]) \
        <= _registry_samples()
    assert dict(entry, workloads=None) == {
        "name": NAME, "unit": "ratio", "better": "higher",
        "source": "program_counter", "layer": "ingress and router",
        "moves": "tokens_per_s", "workloads": None}


def test_reads_the_tokens_a_call_carried_and_the_parent_reads_nothing():
    """36 landings handed 8,640 tokens over: 240 a call. The parent has
    the items and no hand-over counter: nothing, not a number."""
    args = manifest.metric_file(NAME)["args"]
    after = {"ray_tpu_serve_stream_replica_items_total": 8640.0,
             "ray_tpu_serve_stream_handoffs_total": 36.0}
    parent = {"ray_tpu_serve_stream_replica_items_total": 8640.0}
    ctx = {"registry_before": {}, "registry_after": after}
    assert registry_delta.read(ctx, **args) == pytest.approx(240.0)
    ctx = {"registry_before": {}, "registry_after": parent}
    assert registry_delta.read(ctx, **args) is None
    idle = {"registry_before": after, "registry_after": after}
    assert registry_delta.read(idle, **args) is None


def test_rehearsal_of_a_serve_cell_reports_it():
    """The cell the mechanism is for, at its rehearsal's size: a
    hand-over carries at least a token and at most a tick's live rows,
    and the readers of the hops around it still read a number."""
    proc = _run("--workload", "serve_linear_decode", "--seed", "5",
                "--seconds", "4", "--trace", "1", "--rehearse")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    metrics = line["metrics"]
    assert NAME in metrics
    slots = manifest.cell("serve_linear_decode")["workload"][
        "rehearse"]["engine"]["num_slots"]
    assert 1 <= metrics[NAME]["value"] <= slots
    for hop in ("stream_loop_ms", "stream_items_per_pull"):
        assert metrics[hop]["value"] > 0
