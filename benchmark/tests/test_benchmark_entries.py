"""``BENCHMARK.json``'s ``per_layer`` by the rule "one entry a (reader,
args, ``moves``)" (PR 51): an entry names a measurement and its
``workloads`` list says in which cells it is taken, so a reader listed
again under a cell's suffix is a second entry of the same measurement
and takes room from a metric that has none (the driver's form holds
128). One case an entry. Pure JSON: no JAX, no program.

:func:`entry_for` is what the other test files use to ask "is this
metric listed for this cell, and read as it was": by presence, wherever
the entry stands and whichever other cells share it.
"""

import json
import os

import pytest

from benchmark import manifest

BENCH = manifest.benchmark()
PER_LAYER = BENCH["per_layer"]
CELLS = {w["name"]: w for w in BENCH["workloads"]}


def _judged(cell):
    return {m["name"] for m in BENCH["end_to_end"]
            if cell in m.get("workloads", CELLS)}


def _measurement(entry):
    spec = manifest.metric_file(entry["name"])
    return (spec["reader"], json.dumps(spec.get("args", {}), sort_keys=True),
            entry["moves"])


def entry_for(name, *cells):
    """The one entry called ``name`` and its metric file, after checking
    that every cell of ``cells`` is on its list."""
    (entry,) = [m for m in PER_LAYER if m["name"] == name]
    missing = set(cells) - set(entry.get("workloads", CELLS))
    assert not missing, (name, missing)
    return entry, manifest.metric_file(name)


@pytest.mark.parametrize("entry", PER_LAYER, ids=manifest.names(PER_LAYER))
def test_entry_has_its_file_its_reader_and_cells_that_report_what_it_moves(
        entry):
    spec = manifest.metric_file(entry["name"])
    assert set(spec) <= {"reader", "args", "doc", "cells"}, entry["name"]
    assert os.path.exists(os.path.join(
        manifest.HERE, "readers", spec["reader"] + ".py"))
    assert spec["doc"].strip()
    cells = entry.get("workloads", list(CELLS))
    assert cells and len(set(cells)) == len(cells)
    for cell in cells:
        assert cell in CELLS, (entry["name"], cell)
        assert entry["moves"] in _judged(cell), (entry["name"], cell)
    # What a merged file keeps of a cell (row counts, bytes a tick): no
    # reader reads it, and it speaks of listed cells only.
    assert set(spec.get("cells", {})) <= set(cells), entry["name"]


def test_no_two_entries_are_one_measurement():
    seen = {}
    for entry in PER_LAYER:
        twin = seen.setdefault(_measurement(entry), entry["name"])
        assert twin == entry["name"], (twin, entry["name"])


def test_the_list_fits_the_drivers_form():
    # PR 51 left 48 free; a cell's PR writes suffixed entries again (it
    # may not append to a list) and a ``benchmark`` PR folds them.
    assert len(PER_LAYER) <= 128


def test_every_metric_file_is_named_by_exactly_one_entry():
    files = sorted(f[:-len(".json")] for f in os.listdir(
        os.path.join(manifest.HERE, "metrics")))
    assert files == sorted(manifest.names(PER_LAYER))


def test_a_listless_entry_is_one_every_cell_can_print():
    """A list-less entry that some cell does not print reads to the
    driver as a metric that died: only the two that every runner's
    trace and compile snapshots feed may go without a list."""
    assert [m["name"] for m in PER_LAYER if "workloads" not in m] == [
        "mosaic_time_share", "compiles_in_window"]


def _mean(histogram, **more):
    return ("registry_delta", dict(num=[histogram + "_sum"],
                                   den=[histogram + "_count"], **more))


def _ratio(num, den, **more):
    return ("registry_delta", dict(num=num, den=den, **more))


_HOST = ["ray_tpu_cb_step_%s_ms_sum" % phase for phase in (
    "lock_wait", "admit", "upload", "apply", "account")]
_STARVED = ["ray_tpu_cb_starved_%s_ms_sum" % cause for cause in (
    "after_prefill", "tick_late", "before_prefill")]
_CALLS = ["ray_tpu_cb_tick_ms_sum", "ray_tpu_cb_prefill_ms_sum"]
# The twenty measurements that were listed again under a cell's suffix
# until PR 51: the reader and arguments each had then, on every cell.
MERGED = {
    "ttft_p50_ms": ("client_clock", {"stat": "ttft", "q": 50}),
    "engine_queue_ms": _ratio(
        ["ray_tpu_serve_request_queue_seconds_sum",
         "ray_tpu_serve_request_arena_wait_seconds_sum"],
        ["ray_tpu_serve_request_queue_seconds_count"], scale=1000.0),
    "slot_occupancy": _ratio(
        ["ray_tpu_cb_decode_tokens_total"], ["ray_tpu_cb_tick_ms_count"],
        den_times="num_slots", scale=100.0),
    "prefill_batch_ms": _mean("ray_tpu_cb_prefill_ms"),
    "tick_thread_host_share": _ratio(_HOST, _HOST + _CALLS, scale=100.0),
    "moe_experts_touched_share": _mean(
        "ray_tpu_cb_moe_experts_touched_share", scale=100.0),
    "moe_load_imbalance": _mean("ray_tpu_cb_moe_load_imbalance"),
    "moe_gmm_time_share": ("kernel_time", {"kernel": "moe_gmm",
                                           "stat": "time_share"}),
    "paged_live_block_share": _mean(
        "ray_tpu_cb_paged_live_block_share", scale=100.0),
    "paged_attn_time_share": ("kernel_time", {"kernel": "paged_decode_attn",
                                              "stat": "time_share"}),
    "tick_overlap_share": _ratio(
        ["ray_tpu_cb_tick_overlapped_total"], ["ray_tpu_cb_tick_ms_count"],
        scale=100.0),
    "moe_local_assignment_share": _ratio(
        ["ray_tpu_cb_moe_local_assignments_total"],
        ["ray_tpu_cb_moe_assignments_total"], scale=100.0),
    "prefill_chunk_ms": _mean("ray_tpu_cb_prefill_chunk_ms"),
    "tick_wall_ms.closed_loop": _mean("ray_tpu_cb_tick_ms"),
    "paged_visit_fill_share": _mean(
        "ray_tpu_cb_paged_visit_fill_share", scale=100.0),
    "device_starved_share": _ratio(
        _STARVED, _STARVED + _CALLS + ["ray_tpu_cb_idle_no_work_ms_sum"],
        scale=100.0),
    "decode_stall_share": _ratio(
        ["ray_tpu_cb_slot_stalled_ms_total"],
        ["ray_tpu_cb_slot_stalled_ms_total",
         "ray_tpu_cb_slot_advancing_ms_total"], scale=100.0),
    "prefill_row_fill_share": _ratio(
        ["ray_tpu_cb_prefill_requests_total"],
        ["ray_tpu_cb_prefill_padded_rows_total"], scale=100.0),
    "stream_loop_ms": _ratio(
        ["ray_tpu_serve_stream_loop_seconds_total"],
        ["ray_tpu_serve_stream_pulls_total"], scale=1000.0),
    "stream_items_per_pull": _ratio(
        ["ray_tpu_serve_stream_items_total"],
        ["ray_tpu_serve_stream_pulls_total"]),
}


def listed_as_it_was(name, cell):
    """A merged measurement is on ``cell``'s line and reads there through
    the reader and arguments it had under the cell's own suffix."""
    entry, spec = entry_for(name, cell)
    assert (spec["reader"], spec["args"]) == MERGED[name]
    assert entry["moves"] == "tokens_per_s"
    assert name in manifest.names(manifest.cell(cell)["per_layer"])


@pytest.mark.parametrize("name", sorted(MERGED))
def test_a_merged_entry_reads_through_the_reader_and_arguments_it_had(name):
    spec = manifest.metric_file(name)
    assert (spec["reader"], spec["args"]) == MERGED[name]


def test_retired_names_continue_under_a_listed_entry():
    """``renamed.json``: a per-layer name that ended with PR 51 -> the
    entry and cell its ledger series continues under."""
    renamed = manifest.load_json(
        os.path.join(manifest.HERE, "renamed.json"))["renamed"]
    assert len(renamed) == 53
    assert {name for name, _ in renamed.values()} == set(MERGED)
    for old, (name, cell) in renamed.items():
        assert old not in manifest.names(PER_LAYER), old
        entry_for(name, cell)


def test_pairs_against_finds_nothing_lost_against_this_tree(capsys):
    """``pairs_against.py`` is how a PR that folds entries shows that no
    (metric, cell) pair was lost: against the tree itself it finds every
    pair again and none added."""
    from benchmark.tests import pairs_against

    assert pairs_against.main(manifest.ROOT) == 0
    assert "0 pairs lost or changed, 0 pairs added" in capsys.readouterr().out
