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


# ------------------------------------------------------- --calls (PR 48)


def _six_calls(kind="engine.tick", program="jit_tick(1)"):
    """Six calls 50 ms apart, each dispatch 0-1, program 2-12 on the
    chip, fetch 1-12.5: launch 1, device 10, return 0.5 ms. Call 2's
    program starts 9 ms late (launch), call 4's runs three times as long
    with one instruction grown (device), call 5's fetch returns 8 ms
    after its program ended (return). The host comes for each result
    before it is there: none is late."""
    annotations, modules, ops = [], [], []
    for i in range(6):
        t = 50 * i
        start = t + 2 + (9 if i == 2 else 0)
        length = 30 if i == 4 else 10
        end = start + length
        annotations += [(kind + ".dispatch", t * MS, 1 * MS, 100 + i),
                        (kind + ".fetch", (t + 1) * MS,
                         (end - t - 1) * MS + (8 * MS if i == 5 else 0)
                         + MS // 2, 100 + i)]
        modules.append((program, start * MS, length * MS))
        ops += [("%fusion.1 = f32[8] fusion()", start * MS, 4 * MS),
                ("%while.2 = () while()", (start + 4) * MS,
                 (length - 4) * MS),
                ("%copy.3 = f32[8] copy()", (start + 5) * MS, 2 * MS)]
    return annotations, modules, ops


def test_calls_names_the_slow_part_of_each_slow_call():
    annotations, modules, ops = _six_calls()
    # Events --calls must step over: an annotation with no seq (a trace
    # of an older program), one whose program the trace cut off.
    annotations += [("engine.tick.dispatch", 500 * MS, MS, None),
                    ("engine.tick.dispatch", 600 * MS, MS, 999),
                    ("engine.tick.fetch", 601 * MS, MS, 999)]
    got = profile_gaps.calls(annotations, modules, ops)
    assert [c["seq"] for c in got["calls"]] == [100 + i for i in range(6)]
    parts = {c["seq"]: (c["launch_ms"], c["device_ms"], c["return_ms"])
             for c in got["calls"]}
    assert parts[100] == pytest.approx((1.0, 10.0, 0.5))
    assert parts[102] == pytest.approx((10.0, 10.0, 0.5))
    assert parts[104] == pytest.approx((1.0, 30.0, 0.5))
    assert parts[105] == pytest.approx((1.0, 10.0, 8.5))
    (group,) = got["by_program"]
    assert (group["kind"], group["program"], group["n"]) == (
        "engine.tick", "jit_tick(1)", 6)
    assert group["median_ms"] == pytest.approx(
        {"launch": 1.0, "device": 10.0, "return": 0.5, "late": 0.0})
    assert all(c["late_ms"] == 0.0 for c in got["calls"])
    far = group["furthest"]
    assert far["launch"] == [[102, pytest.approx(9.0)]]
    assert far["device"] == [[104, pytest.approx(20.0)]]
    assert far["return"] == [[105, pytest.approx(8.0)]]
    # Only call 104 is over two medians of the whole; the instruction
    # that grew in it is the while's own time (its copy is a child).
    assert group["slow"] == [104]
    assert [c["slow"] for c in got["calls"]] == [False] * 4 + [True, False]
    name, slow_ms, others_ms = group["grew"][0]
    assert name == "while.2"
    assert (slow_ms, others_ms) == pytest.approx((24.0, 4.0))
    assert {g[0] for g in group["grew"]} == {"while.2", "fusion.1", "copy.3"}


def test_calls_keeps_programs_and_kinds_apart_and_launch_where_the_chip_was_free():
    ticks = _six_calls()
    # A prefill batch of two chunk programs dispatched at 300, behind a
    # tick that holds the chip until 310: the wait behind it is not
    # launch, and both programs are the call's device time.
    annotations = ticks[0] + [
        ("engine.prefill.dispatch", 300 * MS, 2 * MS, 7),
        ("engine.prefill.fetch", 305 * MS, 50 * MS, 7)]
    modules = ticks[1] + [("jit_tick(1)", 290 * MS, 20 * MS),
                          ("jit_prefill(2)", 311 * MS, 15 * MS),
                          ("jit_prefill(2)", 326 * MS, 15 * MS)]
    got = profile_gaps.calls(annotations, modules)
    (prefill,) = [c for c in got["calls"] if c["kind"] == "engine.prefill"]
    assert prefill["program"] == "jit_prefill(2)"
    assert (prefill["launch_ms"], prefill["device_ms"],
            prefill["return_ms"]) == pytest.approx((1.0, 30.0, 14.0))
    # A host that came 200 ms after the tick's program had ended, and
    # then had its row in 0.3 ms: late, not return.
    late = profile_gaps.calls(
        [("engine.tick.dispatch", 0, MS, 1),
         ("engine.tick.fetch", 212 * MS, 3 * MS // 10, 1)],
        [("jit_tick(1)", 2 * MS, 10 * MS)])["calls"][0]
    assert (late["launch_ms"], late["device_ms"], late["return_ms"],
            late["late_ms"]) == pytest.approx((1.0, 10.0, 0.3, 200.0))
    assert [(g["kind"], g["n"]) for g in got["by_program"]] == [
        ("engine.prefill", 1), ("engine.tick", 6)]
    assert all(g["grew"] == [] for g in got["by_program"])     # no ops given
    # The tick at 290 started after every dispatched call had landed:
    # nobody's, and call 105 keeps its own 10 ms.
    (last,) = [c for c in got["calls"] if c["seq"] == 105]
    assert last["device_ms"] == pytest.approx(10.0)


def test_cli_calls_prints_each_call_and_what_grew(capsys, monkeypatch):
    annotations, modules, ops = _six_calls()
    monkeypatch.setattr(
        profile_gaps, "load_calls",
        lambda path: ({"/device:TPU:0": (modules, ops)}, annotations))
    assert profile_gaps.main(["trace.xplane.pb", "--calls"]) == 0
    out = capsys.readouterr().out.splitlines()
    assert out[0].startswith("/device:TPU:0: 6 engine calls")
    calls = [line for line in out if " seq " in line and " at " in line]
    assert len(calls) == 6 and calls[4].lstrip().startswith("* seq")
    assert any("furthest over in launch: seq 102 +9.000" in l for l in out)
    assert any("furthest over in return: seq 105 +8.000" in l for l in out)
    assert any("grew in them: while.2" in l for l in out)
    # The recorded v5e trace predates the seq: no call to list, no error.
    monkeypatch.undo()
    assert profile_gaps.main([RECORDED, "--calls"]) == 0
    assert "0 engine calls" in capsys.readouterr().out
