"""The per-layer metrics that read the engine's timeline and the
streams' hops (PR 34): each is a file over the accepted reader
``registry_delta``, names samples the program's registry defines, is
listed for the five serve cells of PR 34 (and whichever later PRs
append), and reads a number in their rehearsals."""

import json

import pytest

from benchmark import manifest
from benchmark.tests.test_benchmark_entries import entry_for
from benchmark.tests.test_rehearsal import _run

NEW = ["device_starved_share", "starved_after_prefill_ms",
       "starved_tick_late_ms", "starved_before_prefill_ms",
       "decode_stall_share", "prefill_row_fill_share",
       "prefill_token_fill_share", "stream_handoff_ms", "stream_store_ms",
       "stream_loop_ms", "stream_items_per_pull"]
SERVE_CELLS = ["serve_chat", "serve_prefill_heavy", "serve_moe_decode",
               "serve_hybrid_decode", "serve_window_decode"]


def _registry_samples():
    from ray_tpu._private import metrics_defs
    from ray_tpu.util.metrics import Counter, Histogram

    names = set()
    for metric in vars(metrics_defs).values():
        if isinstance(metric, Histogram):
            names |= {metric.name + "_sum", metric.name + "_count"}
        elif isinstance(metric, Counter):
            names.add(metric.name)
    return names


@pytest.mark.parametrize("name", NEW)
def test_metric_file_loads_and_names_samples_the_registry_defines(name):
    entry, spec = entry_for(name, *SERVE_CELLS)
    assert spec["reader"] == "registry_delta" and spec["doc"].strip()
    args = spec["args"]
    assert set(args) <= {"num", "den", "scale"}
    assert args["num"] and args["den"]
    assert set(args["num"]) | set(args["den"]) <= _registry_samples()
    assert entry["source"] == "program_counter"
    assert entry["moves"] == "tokens_per_s"


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_serve_rehearsal_reads_every_new_metric(cell):
    proc = _run("--workload", cell, "--seed", "5", "--seconds", "4",
                "--trace", "1", "--rehearse")
    assert proc.returncode == 0, proc.stderr[-3000:]
    metrics = json.loads(proc.stdout.strip().splitlines()[-1])["metrics"]
    # An engine that never ran out of work dispatched no prefill into an
    # empty device: that one metric may be left out.
    missing = set(NEW) - set(metrics) - {"starved_before_prefill_ms"}
    assert not missing, missing
    for name in set(NEW) & set(metrics):
        assert metrics[name]["value"] >= 0, name
    for share in ("device_starved_share", "decode_stall_share",
                  "prefill_row_fill_share", "prefill_token_fill_share"):
        assert 0 <= metrics[share]["value"] <= 100, share
    assert metrics["stream_items_per_pull"]["value"] >= 1
