"""``xla_monitor``'s record of measured executions and its stalled stretch
(ISSUE 48): the rule on synthetic timings, a tiny engine whose HOST is
the slow side (the chaos site ``delay_tick``) and one whose fetch blocks,
the capture a stretch takes of itself where ``stall_capture_s`` asks for
one, and the generation-2 clock. CPU, tiny sizes: whose a stretch was and
what it booked are the test, no time here is a speed."""

import gc
import glob
import json
import os
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu._private import chaos
from ray_tpu._private import metrics_defs as mdefs
from ray_tpu._private import xla_monitor as xm
from ray_tpu.models import llama
from ray_tpu.models.continuous_batching import ContinuousBatcher

BASE = 0.010       # a synthetic shape's normal call, seconds


def _total(counter, **tags):
    want = set(tags.items())
    return sum(v for _, key, v in counter.samples() if want <= set(key))


def _warm(rec, program="p", shape=4, seconds=BASE, n=xm.BASELINE_MIN + 1):
    """A first reading (dropped) and enough more for a median."""
    for _ in range(n):
        rec.note(program, shape, seconds, 1, None)


def _feed(rec, seconds, n, program="p", shape=4):
    return [rec.note(program, shape, seconds, 1, None) for _ in range(n)]


def _dumps(tmp_path, program="p"):
    """The dumps of ``program``'s stretches. The process's own record may
    close a stretch an earlier test's engine left open (a slow CPU tick
    on a loaded machine) into this test's folder: not the test's."""
    out = []
    for path in sorted(glob.glob(str(tmp_path / "stalls" / "*.json"))):
        with open(path) as f:
            doc = json.load(f)
        if doc.get("program", program) == program:
            out.append(doc)
    return out


@pytest.fixture
def session(tmp_path, monkeypatch):
    monkeypatch.setenv("RAY_TPU_SESSION_DIR", str(tmp_path))
    monkeypatch.delenv("RAY_TPU_stall_capture_s", raising=False)
    yield tmp_path
    xm.stop_all()


# ---------------------------------------------------------------- the rule


def test_a_stretch_opens_at_the_first_slow_call_and_books_its_excess(session):
    rec = xm._CallRecord()
    _warm(rec)
    before = (_total(mdefs.XLA_STALL_STRETCHES),
              _total(mdefs.XLA_STALL_EXCESS_SECONDS))
    assert not rec.stretch_open
    slow = [0.050, 0.120, 0.031]
    for i, seconds in enumerate(slow):
        assert rec.note("p", 4, seconds, 1, None) and rec.stretch_open, i
    # Back under the rule: open until CLOSE_AFTER calls in a row are.
    for i in range(xm.CLOSE_AFTER):
        assert rec.stretch_open, i
        assert not rec.note("p", 4, BASE, 1, None)
    assert not rec.stretch_open
    assert _total(mdefs.XLA_STALL_STRETCHES) - before[0] == 1
    assert _total(mdefs.XLA_STALL_EXCESS_SECONDS) - before[1] == \
        pytest.approx(sum(slow) - 3 * BASE, abs=1e-12)
    rec.maintain()
    (dump,) = _dumps(session)
    assert dump["slow_calls"] == 3 and dump["closed_by"] == "calls"
    assert dump["excess_s"] == pytest.approx(sum(slow) - 3 * BASE)
    assert dump["side"] == "mixed" and dump["capture"] is None


def test_a_slow_call_between_normal_ones_keeps_the_stretch_open(session):
    rec = xm._CallRecord()
    _warm(rec)
    _feed(rec, 0.2, 1)
    _feed(rec, BASE, xm.CLOSE_AFTER - 1)
    _feed(rec, 0.2, 1)                       # the run of normal calls restarts
    _feed(rec, BASE, xm.CLOSE_AFTER - 1)
    assert rec.stretch_open
    _feed(rec, BASE, 1)
    assert not rec.stretch_open
    rec.maintain()
    (dump,) = _dumps(session)
    assert dump["slow_calls"] == 2


def test_a_500_call_stretch_leaves_the_median_where_it_was(session):
    rec = xm._CallRecord()
    _warm(rec, n=xm.BASELINE_CALLS + 1)
    base = rec._baselines["p", 4]
    assert base.median == pytest.approx(BASE)
    assert all(_feed(rec, 10 * BASE + 0.05, 500))
    assert base.median == pytest.approx(BASE)
    assert max(base.recent) == pytest.approx(BASE)
    # The ring is bounded and keeps the newest.
    _feed(rec, BASE, xm.RING_CALLS)
    assert len(rec.records()) == xm.RING_CALLS
    assert not any(r.slow for r in rec.records())


@pytest.mark.parametrize("case,readings,slow", [
    ("a shape's first reading, its compilation, is never a baseline",
     [5.0] + [BASE] * xm.BASELINE_MIN + [0.2], True),
    ("no median before BASELINE_MIN readings: nothing is slow yet",
     [BASE] * xm.BASELINE_MIN + [0.2], False),
    ("over the factor but under the floor is not slow",
     [0.001] * (xm.BASELINE_MIN + 1) + [xm.SLOW_FLOOR_MS / 1e3 * 0.9], False),
    ("over the floor but under the factor is not slow",
     [0.1] * (xm.BASELINE_MIN + 1) + [xm.SLOW_FACTOR * 0.1 * 0.9], False),
])
def test_what_is_a_baseline_and_what_is_slow(session, case, readings, slow):
    rec = xm._CallRecord()
    got = [rec.note("p", 4, seconds, 1, None) for seconds in readings]
    assert not any(got[:-1]), case
    assert got[-1] is slow, case
    if slow:
        assert rec._baselines["p", 4].median == pytest.approx(BASE), case


def test_each_shape_and_program_has_its_own_median(session):
    rec = xm._CallRecord()
    _warm(rec, "tick", 4, 0.010)
    _warm(rec, "tick", 96, 0.200)
    _warm(rec, "prefill", (4, 128, 0, 1), 0.300)
    assert not rec.note("tick", 96, 0.210, 1, None)
    assert not rec.note("prefill", (4, 128, 0, 1), 0.310, 1, None)
    assert rec.note("tick", 4, 0.210, 1, None)
    # Several back-to-back calls in one reading: the rule is a call's.
    _warm(rec, "chunks", 1, 0.400, n=xm.BASELINE_MIN + 1)
    assert not rec.note("chunks", 1, 0.400, 1, None)
    for _ in range(xm.BASELINE_MIN + 1):
        rec.note("chunked", 1, 0.400, 4, None)
    assert rec._baselines["chunked", 1].median == pytest.approx(0.100)


def _call(seq, wall, ready, waited):
    """A stand-in for ``xm.Dispatched`` as ``note`` reads one."""
    now = time.perf_counter()
    return types.SimpleNamespace(
        seq=seq, ts=time.time() - wall, pc=now - wall, ready=ready,
        fetch_pc=now - waited, landed_ts=time.time(), landed_pc=now)


@pytest.mark.parametrize("side,slow_calls", [
    ("host", [(True, 0.001)] * 3 + [(False, 0.2)]),
    ("device", [(False, 0.19)] * 3 + [(True, 0.0)]),
    # Ready but the fetch still blocked (the transfer back), and not
    # ready but hardly waited for: neither side's.
    ("mixed", [(True, 0.19), (False, 0.01), (True, 0.0), (False, 0.2)]),
])
def test_side_is_read_off_the_slow_calls(session, side, slow_calls):
    rec = xm._CallRecord()
    _warm(rec)
    ready0 = _total(mdefs.XLA_RESULTS_READY, program="p")
    wait0 = _total(mdefs.XLA_FETCH_WAIT_SECONDS, program="p")
    for i, (ready, waited) in enumerate(slow_calls):
        assert rec.note("p", 4, 0.2, 1, _call(1000 + i, 0.2, ready, waited))
        (got,) = rec.records(last=1)
        assert got.slow and got.ready is ready
        assert got.waited_s == pytest.approx(waited, abs=1e-6)
    # Both counters are booked with the call, nothing trails the ring.
    assert _total(mdefs.XLA_RESULTS_READY, program="p") - ready0 == \
        sum(1 for ready, _ in slow_calls if ready)
    _feed(rec, BASE, xm.CLOSE_AFTER)
    rec.maintain()
    (dump,) = _dumps(session)
    assert dump["side"] == side
    assert _total(mdefs.XLA_RESULTS_READY, program="p") - ready0 == \
        sum(1 for ready, _ in slow_calls if ready)
    assert _total(mdefs.XLA_FETCH_WAIT_SECONDS, program="p") - wait0 == \
        pytest.approx(sum(w for _, w in slow_calls), abs=1e-4)
    # The records carry the dispatch's seq and both clocks.
    kept = [r for r in dump["records"] if r["slow"]]
    assert [r["seq"] for r in kept] == [1000 + i
                                        for i in range(len(slow_calls))]
    assert all(r["dispatch_ts"] < r["landed_ts"]
               and r["dispatch_pc"] < r["landed_pc"] for r in kept)


def test_one_slow_call_alone_is_a_stretch_of_one(session):
    rec = xm._CallRecord()
    _warm(rec)
    before = (_total(mdefs.XLA_STALL_STRETCHES),
              _total(mdefs.XLA_STALL_EXCESS_SECONDS))
    _feed(rec, 0.110, 1)
    _feed(rec, BASE, xm.CLOSE_AFTER)
    assert not rec.stretch_open
    assert _total(mdefs.XLA_STALL_STRETCHES) - before[0] == 1
    assert _total(mdefs.XLA_STALL_EXCESS_SECONDS) - before[1] == \
        pytest.approx(0.100)
    rec.maintain()
    (dump,) = _dumps(session)
    assert dump["slow_calls"] == 1 and dump["calls"] == 1 + xm.CLOSE_AFTER
    assert dump["excess_s"] == pytest.approx(0.100)
    # It ended with its slow call, not with the calls that showed it had.
    assert dump["seconds"] == pytest.approx(0.110, abs=0.05)


def test_the_two_counters_move_with_every_call(session):
    rec = xm._CallRecord()
    ready0 = _total(mdefs.XLA_RESULTS_READY, program="q")
    wait0 = _total(mdefs.XLA_FETCH_WAIT_SECONDS, program="q")
    for i, ready in enumerate([True, False, True]):
        rec.note("q", 1, BASE, 1, _call(i, BASE, ready, 0.001))
        assert _total(mdefs.XLA_RESULTS_READY, program="q") - ready0 == \
            [1, 1, 2][i]
        assert _total(mdefs.XLA_FETCH_WAIT_SECONDS, program="q") - wait0 == \
            pytest.approx((i + 1) * 0.001, abs=1e-4)
    # A reading with no ``Dispatched`` says nothing of the fetch.
    rec.note("q", 1, BASE, 1, None)
    assert _total(mdefs.XLA_RESULTS_READY, program="q") - ready0 == 2


def test_a_process_prunes_its_own_dumps_and_nobody_elses(session,
                                                         monkeypatch):
    monkeypatch.setattr(xm, "DUMPS_KEPT", 3)
    (session / "stalls").mkdir()
    foreign = session / "stalls" / "00000000T000000.000-1.json"
    foreign.write_text("{}")
    rec = xm._CallRecord()
    _warm(rec)
    for _ in range(6):
        _open_and_close(rec)
        rec.maintain()
    assert foreign.read_text() == "{}"
    assert [d.get("slow_calls") for d in _dumps(session)] == [None, 2, 2, 2]


def test_the_default_session_dir_is_under_the_process_own_tmpdir(
        tmp_path, monkeypatch):
    monkeypatch.delenv("RAY_TPU_SESSION_DIR", raising=False)
    monkeypatch.setattr(xm.tempfile, "tempdir", str(tmp_path))
    assert xm.session_dir() == str(tmp_path / "ray_tpu_state")
    rec = xm._CallRecord()
    _warm(rec)
    _open_and_close(rec)
    rec.maintain()
    (dump,) = _dumps(tmp_path / "ray_tpu_state")
    assert dump["pid"] == os.getpid() and dump["slow_calls"] == 2


def test_a_stretch_left_open_when_the_calls_stop_closes_itself(
        session, monkeypatch):
    monkeypatch.setattr(xm, "IDLE_CLOSE_S", 0.2)
    rec = xm._CallRecord()
    _warm(rec)
    _feed(rec, 0.2, 2)
    rec.maintain()
    assert rec.stretch_open          # a call landed a moment ago
    time.sleep(0.12)
    rec.mint(time.perf_counter())    # a dispatch: the engine is at work
    time.sleep(0.12)
    rec.maintain()
    assert rec.stretch_open
    # That call never lands (its program raised): it holds nothing open.
    time.sleep(0.12)
    rec.maintain()
    assert not rec.stretch_open
    (dump,) = _dumps(session)
    assert dump["closed_by"] == "idle" and dump["slow_calls"] == 2


def test_the_dump_holds_the_calls_before_the_stretch_and_both_snapshots(session):
    rec = xm._CallRecord()
    _warm(rec, n=xm.LEAD_IN_CALLS + 100)
    _feed(rec, 0.1, 5)
    _feed(rec, BASE, xm.CLOSE_AFTER)
    rec.maintain()
    (dump,) = _dumps(session)
    records = dump["records"]
    assert len(records) == xm.LEAD_IN_CALLS + 5 + xm.CLOSE_AFTER
    assert not any(r["slow"] for r in records[:xm.LEAD_IN_CALLS])
    assert all(r["slow"] for r in records[xm.LEAD_IN_CALLS:][:5])
    assert dump["calls"] == 5 + xm.CLOSE_AFTER
    for key in ("cpu_user_s", "cpu_system_s", "ctx_voluntary",
                "ctx_involuntary", "major_faults", "threads",
                "gc2_collections", "gc2_ms", "load_1m"):
        assert key in dump["before"] and key in dump["after"], key
        assert dump["delta"][key] == pytest.approx(
            dump["after"][key] - dump["before"][key])
    assert dump["before"]["ts"] <= dump["after"]["ts"]
    assert dump["rule"]["slow_factor"] == xm.SLOW_FACTOR
    assert dump["opened_ts"] <= dump["closed_ts"]


@pytest.mark.parametrize("generation,counted", [(2, 1), (0, 0), (1, 0)])
def test_the_gc_hook_counts_generation_2_alone(generation, counted):
    xm._CallRecord().note("p", 1, BASE, 1, None)     # installs the hook
    assert gc.callbacks.count(xm._gen2) == 1
    gc.collect(2)                                    # settle
    count, ms = xm._gen2.count, xm._gen2.ms
    gc.collect(generation)
    assert xm._gen2.count - count == counted
    assert (xm._gen2.ms > ms) is bool(counted)
    assert xm._snapshot()["gc2_collections"] == xm._gen2.count


# ------------------------------------------------------- a tiny engine


@pytest.fixture(scope="module")
def config():
    return llama.LlamaConfig.tiny(dtype=jnp.float32)


@pytest.fixture
def steady_engine(config):
    """Two long requests, stepped past the warm-up of their shape; reset
    after the test whatever it did, so no tick of it stays in flight in
    the process's record."""
    eng = ContinuousBatcher(config, num_slots=2, max_len=256)
    for prompt in ([1, 2, 3], [4, 5, 6]):
        eng.submit(prompt, max_new_tokens=200)
    for _ in range(xm.BASELINE_MIN + 12):
        eng.step()
    assert len(eng._slots) == 2
    yield eng
    eng.reset()


def _slow_in(dump):
    """The stretch's own slow calls (its lead-in may hold an earlier
    stretch's)."""
    return [r for r in dump["records"]
            if r["slow"] and r["landed_ts"] >= dump["opened_ts"]]


def _stretch_of(tmp_path, slow_at_least):
    """The dump of the test's own stretch (a loaded machine may add one
    of a single slow CPU tick)."""
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        xm._calls.maintain()
        got = [d for d in _dumps(tmp_path, "cb_tick")
               if d["slow_calls"] >= slow_at_least]
        if got:
            return got[-1]
        time.sleep(0.05)
    raise AssertionError(
        f"no dump under {tmp_path}: {_dumps(tmp_path, 'cb_tick')}")


def test_a_slow_host_reads_host_with_every_slow_call_ready(
        session, steady_engine):
    """``delay_tick`` sleeps in ``step()`` on the host's side while two
    ticks are in flight: their rows are there long before the thread
    comes for them, and ``CB_TICK_MS`` reads the host's period."""
    eng = steady_engine
    ready0 = _total(mdefs.XLA_RESULTS_READY, program="cb_tick")
    host0 = _total(mdefs.XLA_STALL_STRETCHES, side="host")
    excess0 = _total(mdefs.XLA_STALL_EXCESS_SECONDS, side="host")
    chaos.configure("delay_tick:secs=0.08,times=6", seed=1)
    try:
        for _ in range(6 + xm.CLOSE_AFTER + 4):
            eng.step()
    finally:
        chaos.configure(None)
    dump = _stretch_of(session, 5)
    assert dump["program"] == "cb_tick" and dump["side"] == "host"
    slow = _slow_in(dump)
    assert len(slow) >= 5 and all(r["ready"] is True for r in slow)
    assert all(r["waited_s"] < r["wall_s"] / 2 and r["wall_s"] > 0.07
               and r["shape"] == 2 for r in slow)
    assert _total(mdefs.XLA_RESULTS_READY, program="cb_tick") - ready0 >= 5
    assert _total(mdefs.XLA_STALL_STRETCHES, side="host") - host0 >= 1
    assert _total(mdefs.XLA_STALL_EXCESS_SECONDS, side="host") - excess0 \
        >= 5 * 0.06
    # Calls from before the stretch and from inside it, in seq order,
    # and the snapshots of its opening and its close.
    first = dump["records"].index(slow[0])
    assert first >= xm.BASELINE_MIN
    seqs = [r["seq"] for r in dump["records"] if r["program"] == "cb_tick"]
    assert seqs == sorted(seqs)
    assert dump["delta"]["cpu_user_s"] >= 0 and dump["capture"] is None


class _LateRow:
    """A tick's row that is not there when the host comes for it and
    takes ``seconds`` to fetch."""

    def __init__(self, row, seconds):
        self._row, self._seconds = row, seconds

    def copy_to_host_async(self):
        self._row.copy_to_host_async()

    def is_ready(self):
        return False

    def __array__(self, dtype=None, copy=None):
        time.sleep(self._seconds)
        return np.asarray(self._row)


def test_a_fetch_that_blocks_reads_device(session, steady_engine,
                                          monkeypatch):
    eng = steady_engine
    device0 = _total(mdefs.XLA_STALL_STRETCHES, side="device")
    wait0 = _total(mdefs.XLA_FETCH_WAIT_SECONDS, program="cb_tick")
    run_tick, late = eng._run_tick, [6]

    def run_late():
        row = run_tick()
        if late[0] <= 0:
            return row
        late[0] -= 1
        if isinstance(row, tuple):
            return tuple(_LateRow(part, 0.08 / len(row)) for part in row)
        return _LateRow(row, 0.08)

    monkeypatch.setattr(eng, "_run_tick", run_late)
    for _ in range(6 + xm.CLOSE_AFTER + 4):
        eng.step()
    dump = _stretch_of(session, 5)
    assert dump["program"] == "cb_tick" and dump["side"] == "device"
    slow = _slow_in(dump)
    assert len(slow) >= 5 and all(r["ready"] is False for r in slow)
    # The wait is about the wall time: the thread waited on the result.
    assert all(r["waited_s"] > 0.07 and r["waited_s"] > 0.8 * r["wall_s"]
               for r in slow)
    assert _total(mdefs.XLA_STALL_STRETCHES, side="device") - device0 >= 1
    assert _total(mdefs.XLA_FETCH_WAIT_SECONDS, program="cb_tick") - wait0 \
        >= 5 * 0.07


def test_prefill_batches_and_ticks_are_one_record_each_in_seq_order(
        session, config):
    eng = ContinuousBatcher(config, num_slots=4, max_len=64)
    seen = len(xm._calls.records())
    ticks0 = mdefs.CB_TICK_MS.totals(eng._mtags)[1]
    prefills0 = mdefs.CB_PREFILL_MS.totals(eng._mtags)[1]
    for prompt in ([1, 2, 3], [4, 5, 6, 7, 8], [9]):
        eng.submit(prompt, max_new_tokens=5)
    eng.run_to_completion()
    mine = xm._calls.records()[seen:]
    ticks = [r for r in mine if r.program == "cb_tick"]
    prefills = [r for r in mine if r.program == "cb_prefill"]
    assert len(ticks) == mdefs.CB_TICK_MS.totals(eng._mtags)[1] - ticks0
    assert len(prefills) == \
        mdefs.CB_PREFILL_MS.totals(eng._mtags)[1] - prefills0
    assert ticks and prefills
    assert all(isinstance(r.shape, int) and 1 <= r.shape <= 4 for r in ticks)
    assert all(len(r.shape) == 4 for r in prefills)      # _batch_ms's key
    for r in mine:
        assert r.ready in (True, False) and r.waited_s >= 0
        assert r.dispatch_pc < r.landed_pc and r.wall_s > 0
    assert len({r.seq for r in mine}) == len(mine)


# ------------------------------------------------------------ the capture


def _open_and_close(rec, slow=2):
    time.sleep(0.002)       # a dump is named by the millisecond it opened in
    _feed(rec, 0.2, slow)
    stretch = rec._stretch
    _feed(rec, BASE, xm.CLOSE_AFTER)
    return stretch


def test_with_capture_off_no_profiler_call_is_reachable(session, monkeypatch):
    def never(*args, **kwargs):
        raise AssertionError("the profiler was reached with the capture off")

    monkeypatch.setattr(jax.profiler, "start_trace", never)
    monkeypatch.setattr(xm, "_capture_trace", never)
    rec = xm._CallRecord()
    _warm(rec)
    threads = {t.name for t in threading.enumerate()}
    stretch = _open_and_close(rec)
    assert stretch.capture is None and stretch.capture_thread is None
    started = {t.name for t in threading.enumerate()} - threads
    assert "xla-stall-capture" not in started
    rec.maintain()
    (dump,) = _dumps(session)
    assert dump["capture"] is None


def test_a_stretch_takes_one_capture_and_a_busy_profiler_raises_nothing(
        session, monkeypatch):
    """One stretch, one capture through the function the listener's
    command uses; a second stretch while it runs, and one under a
    foreign ``jax.profiler`` session, say ``busy``."""
    monkeypatch.setenv("RAY_TPU_stall_capture_s", "0.3")
    through, capture_trace = [], xm._capture_trace

    def spy(capture_id, duration_s, address, **kwargs):
        through.append((capture_id, duration_s, kwargs))
        return capture_trace(capture_id, duration_s, address, **kwargs)

    monkeypatch.setattr(xm, "_capture_trace", spy)
    done0 = _total(mdefs.PROFILE_CAPTURES, status="done")
    jnp.dot(jnp.ones((32, 32)), jnp.ones((32, 32))).block_until_ready()
    rec = xm._CallRecord()
    _warm(rec)
    lone = _open_and_close(rec, slow=1)      # one slow call alone takes none
    assert lone.capture is None and not through
    first = _open_and_close(rec)
    assert first.capture_thread is not None
    deadline = time.monotonic() + 60
    while not xm._capture_lock.locked() and first.capture_thread.is_alive():
        assert time.monotonic() < deadline
        time.sleep(0.01)
    second = _open_and_close(rec)            # while the first one's runs
    assert second.capture == "busy" and second.capture_thread is None
    first.capture_thread.join(timeout=120)
    assert not first.capture_thread.is_alive()
    assert len(through) == 1
    assert through[0][1] == 0.3 and through[0][2]["reason"] == "stall"
    assert first.capture["status"] == "done", first.capture
    assert first.capture["files"] > 0 and first.capture["reason"] == "stall"
    assert str(session / "profiles") in first.capture["trace_dir"]
    assert _total(mdefs.PROFILE_CAPTURES, status="done") - done0 == 1
    # Within a minute of a capture no second one is taken.
    third = _open_and_close(rec)
    assert third.capture == "busy" and third.capture_thread is None
    assert len(through) == 1
    # A foreign session holds the profiler: busy, and nothing raised.
    rec._last_capture = None
    jax.profiler.start_trace(str(session / "foreign"))
    try:
        fourth = _open_and_close(rec)
        fourth.capture_thread.join(timeout=120)
        assert fourth.capture == "busy"
        assert rec._last_capture is None     # it took none: no minute's wait
    finally:
        jax.profiler.stop_trace()
    rec.maintain()
    captures = [d["capture"] for d in _dumps(session)]
    assert captures.count("busy") == 3 and captures.count(None) == 1
    assert len(captures) == 5
    (kept,) = [c for c in captures if isinstance(c, dict)]
    assert kept["status"] == "done" and os.path.isdir(kept["trace_dir"])


@pytest.mark.parametrize("error,status", [
    ("Profile has already been started. Only one profile may be run at a "
     "time.", "busy"),
    ("the profiler's backend is gone", "failed"),
])
def test_only_a_profiler_that_is_held_reads_busy(session, monkeypatch,
                                                 error, status):
    """``ray-tpu profile capture`` and a stretch come through one
    function: another session's hold is ``busy``, any other
    ``RuntimeError`` of ``start_trace`` is a capture that failed, and
    is counted as one."""
    def refuse(*args, **kwargs):
        raise RuntimeError(error)

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    failed0 = _total(mdefs.PROFILE_CAPTURES, status="failed")
    got = xm._capture_trace("cap-test", 0.1, None, reason="command")
    assert got["status"] == status and error in got["error"]
    assert _total(mdefs.PROFILE_CAPTURES, status="failed") - failed0 == \
        (status == "failed")
    assert not xm._capture_lock.locked()
