"""``ray_tpu.util.profile_gaps``: a trace's idle gaps split among the
engine thread's annotations, on hand-made planes and on a trace recorded
on the v5e from the benchmark's ``serve_prefill_heavy`` cell."""

import bisect
import os

import pytest

from ray_tpu.util import profile_gaps

MS = 1_000_000   # ns


def _ops(*busy):
    return [(f"op{i}", s * MS, (e - s) * MS) for i, (s, e) in enumerate(busy)]


def _ev(name, start, end):
    return (name, start * MS, (end - start) * MS)


# The chip is busy 0-10 and 20-30 ms: one idle gap of 10 ms.
GAP = _ops((0, 10), (20, 30))


@pytest.mark.parametrize("case,host,want", [
    ("one annotation over the whole gap",
     [_ev("engine.admit", 8, 22)],
     {"engine.admit": 10.0}),
    ("nested: the innermost takes its part, the outer the rest",
     [_ev("engine.tick", 5, 29), _ev("engine.tick.dispatch", 12, 16),
      _ev("engine.tick.fetch", 16, 29)],
     {"engine.tick": 2.0, "engine.tick.dispatch": 4.0,
      "engine.tick.fetch": 4.0}),
    ("two in turn, and a hole between them",
     [_ev("engine.apply", 9, 13), _ev("engine.lock_wait", 15, 21)],
     {"engine.apply": 3.0, "engine.lock_wait": 5.0, "unattributed": 2.0}),
    ("none: other threads' events and names outside the prefix do not count",
     [_ev("PjitFunction(tick)", 0, 30), _ev("serve.route", 10, 20)],
     {"unattributed": 10.0}),
])
def test_a_gap_is_split_among_the_annotations_over_it(case, host, want):
    got = profile_gaps.split(GAP, [host])
    assert got["window_s"] == pytest.approx(0.030)
    assert got["busy_s"] == pytest.approx(0.020)
    assert got["idle_s"] == pytest.approx(0.010)
    by_phase = {name: s * 1e3 for name, s, _ in got["by_phase"]}
    assert by_phase == pytest.approx(want), case
    assert sum(share for _, _, share in got["by_phase"]) == pytest.approx(1.0)
    seconds = [s for _, s, _ in got["by_phase"]]
    assert seconds == sorted(seconds, reverse=True)


def test_gaps_add_up_over_threads_and_nested_device_events():
    """Device events nest (a ``while`` spans its body): busy time is
    their union. Two threads that annotate in turn (the replica's tick
    thread and a caller of ``run_to_completion``) both count."""
    ops = [("while", 0, 10 * MS), ("fusion", 2 * MS, 3 * MS),
           ("fusion", 20 * MS, 5 * MS), ("copy", 40 * MS, 5 * MS)]
    got = profile_gaps.split(ops, [
        [_ev("engine.upload", 9, 21)],
        [_ev("engine.admit", 24, 50), _ev("engine.prefill", 30, 41)],
    ])
    assert got["idle_s"] == pytest.approx(0.025)
    assert {n: round(s * 1e3, 6) for n, s, _ in got["by_phase"]} == {
        "engine.upload": 10.0, "engine.admit": 5.0, "engine.prefill": 10.0}


def test_innermost_cuts_a_child_that_outlasts_its_parent():
    flat = profile_gaps.innermost([_ev("engine.a", 0, 10),
                                   _ev("engine.b", 4, 14),
                                   _ev("engine.c", 20, 22)])
    assert flat == [(0, 4 * MS, "engine.a"), (4 * MS, 10 * MS, "engine.b"),
                    (20 * MS, 22 * MS, "engine.c")]


def test_a_trace_with_no_device_event_has_nothing_to_split():
    got = profile_gaps.split([], [[_ev("engine.admit", 0, 5)]])
    assert got == {"window_s": 0.0, "busy_s": 0.0, "idle_s": 0.0,
                   "by_phase": []}


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "serve_prefill_heavy.v5e.host.xplane.pb")


def test_recorded_v5e_trace_names_its_idle_time():
    """One second of a ``serve_prefill_heavy`` run on the v5e (PR 23,
    ``benchmark/run.py --trace 1 --keep-trace``; the chip's two lines
    and the host threads' ``engine.*`` events kept, stats dropped): the
    annotations are on the device events' clock, one host thread
    carries them, and nearly all of the idle time falls under one."""
    chips, host_lines = profile_gaps.load(RECORDED)
    assert list(chips) == ["/device:TPU:0"] and len(host_lines) == 1
    names = {name for name, _, _ in host_lines[0]}
    assert {"engine.lock_wait", "engine.admit", "engine.prefill",
            "engine.prefill.dispatch", "engine.prefill.fetch",
            "engine.upload", "engine.tick", "engine.tick.dispatch",
            "engine.tick.fetch", "engine.account", "engine.apply"} <= names
    got = profile_gaps.split(chips["/device:TPU:0"], host_lines)
    assert 0.9 < got["window_s"] <= 1.0
    assert 0.05 < got["idle_s"] / got["window_s"] < 0.25
    by_phase = {name: share for name, _, share in got["by_phase"]}
    assert by_phase.get("unattributed", 0.0) < 0.10
    assert all(name == "unattributed" or name.startswith("engine.")
               for name in by_phase)
    # Same clock: each tick's annotation has the chip at work inside it.
    starts = sorted(s for _, s, _ in chips["/device:TPU:0"])
    ticks = [(s, s + d) for n, s, d in host_lines[0] if n == "engine.tick"]
    assert len(ticks) > 20
    for begin, end in ticks:
        i = bisect.bisect_left(starts, begin)
        assert i < len(starts) and starts[i] < end, (begin, end)


def test_cli_prints_seconds_and_share_by_name(capsys):
    assert profile_gaps.main([RECORDED]) == 0
    out = capsys.readouterr().out
    assert out.startswith("/device:TPU:0: window ")
    assert "engine.tick.fetch" in out and "% of idle" in out
    assert profile_gaps.main([]) == 2


def test_longest_gaps_lists_each_with_its_neighbours_and_annotations():
    """``--gaps N``: the N longest gaps one by one, longest first, each
    with the programs on either side and the annotations over it."""
    ops = _ops((0, 10), (20, 30), (31, 40), (65, 70))
    programs = [_ev("jit_tick(1)", 0, 10), _ev("jit_prefill(2)", 20, 30),
                _ev("jit_merge_tokens(3)", 31, 40), _ev("jit_tick(4)", 65, 70)]
    host = [[_ev("engine.admit", 8, 22), _ev("engine.upload", 40, 60)]]
    got = profile_gaps.longest_gaps(ops, host, 2, programs)
    assert [(g["at_s"], g["ms"]) for g in got] == [
        (pytest.approx(0.040), pytest.approx(25.0)),
        (pytest.approx(0.010), pytest.approx(10.0))]
    assert (got[0]["after"], got[0]["before"]) == (
        "jit_merge_tokens(3)", "jit_tick(4)")
    assert got[0]["host"] == [["engine.upload", pytest.approx(20.0)],
                              ["unattributed", pytest.approx(5.0)]]
    assert (got[1]["after"], got[1]["before"]) == (
        "jit_tick(1)", "jit_prefill(2)")
    assert got[1]["host"] == [["engine.admit", pytest.approx(10.0)]]
    # Without the programs' line the instructions on either side do.
    (only,) = profile_gaps.longest_gaps(ops, host, 1)
    assert (only["after"], only["before"]) == ("op2", "op3")
    assert len(profile_gaps.longest_gaps(ops, host, 9)) == 3
    assert profile_gaps.longest_gaps(ops, host, 0) == []


def test_cli_lists_the_recorded_traces_longest_gaps(capsys):
    """On the v5e recording every listed gap lies between two programs
    and under the engine's annotations, longest first; the listing adds
    up to no more than the idle time above it."""
    assert profile_gaps.main([RECORDED, "--gaps", "5"]) == 0
    out = capsys.readouterr().out
    rows = [line.split() for line in out.splitlines()
            if line.startswith("  gap at ")]
    assert len(rows) == 5
    lengths = [float(row[4]) for row in rows]
    assert lengths == sorted(lengths, reverse=True) and lengths[-1] > 0
    idle_s = float(out.split("idle ")[1].split(" s")[0])
    assert sum(lengths) / 1e3 <= idle_s + 1e-6
    for row in rows:
        assert "->" in row and "jit_" in " ".join(row), row
    assert "engine." in out.split("  gap at ", 1)[1]
    assert profile_gaps.main([RECORDED, "--gaps"]) == 2
