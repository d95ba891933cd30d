"""The tick thread's timeline, seen from the device, from a live slot and
from the prefill programs (ISSUE 34): ``CB_TICK_MS`` + ``CB_PREFILL_MS``
+ the three starved causes + idle partition the thread's wall time, a
live slot is advancing or standing still through every millisecond of
it, and a prefill batch's padded rows and tokens are counted beside its
real ones. CPU, tiny sizes: the COUNTS and the identity are the test,
no time here is a speed."""

import time

import jax.numpy as jnp
import pytest

from ray_tpu._private import metrics_defs as mdefs
from ray_tpu.models import continuous_batching as cb
from ray_tpu.models import llama
from ray_tpu.models.continuous_batching import ContinuousBatcher

TIMELINE = (mdefs.CB_TICK_MS, mdefs.CB_PREFILL_MS,
            mdefs.CB_STARVED_AFTER_PREFILL_MS, mdefs.CB_STARVED_TICK_LATE_MS,
            mdefs.CB_STARVED_BEFORE_PREFILL_MS, mdefs.CB_IDLE_NO_WORK_MS)
COUNTERS = (mdefs.CB_SLOT_ADVANCING_MS, mdefs.CB_SLOT_STALLED_MS,
            mdefs.CB_PREFILL_REQUESTS, mdefs.CB_PREFILL_TOKENS,
            mdefs.CB_PREFILL_PADDED_ROWS, mdefs.CB_PREFILL_PADDED_TOKENS)


@pytest.fixture(scope="module")
def config():
    return llama.LlamaConfig.tiny(dtype=jnp.float32)


def _read(eng):
    """``{name: (sum, count)}`` of the timeline's histograms and
    ``{name: value}`` of the counters, this engine's label set alone."""
    key = tuple(sorted(eng._mtags.items()))
    out = {h.name: h.totals(eng._mtags) for h in TIMELINE}
    for c in COUNTERS:
        out[c.name] = sum(v for _, k, v in c.samples() if k == key)
    return out


def _gained(before, after):
    return {k: (tuple(a - b for a, b in zip(after[k], before[k]))
                if isinstance(after[k], tuple) else after[k] - before[k])
            for k in after}


def _slow(monkeypatch, eng, name, seconds):
    """Make ``eng.<name>`` take ``seconds`` longer: a stubbed slow phase."""
    orig = getattr(eng, name)

    def slowed(*args, **kwargs):
        time.sleep(seconds)
        return orig(*args, **kwargs)

    monkeypatch.setattr(eng, name, slowed)


def _warm(eng, prompts=((1, 2, 3),)):
    for p in prompts:
        eng.submit(list(p), max_new_tokens=4)
    eng.run_to_completion()
    assert eng._no_work and eng._empty_since is not None


def _timeline_ms(gained):
    return sum(gained[h.name][0] for h in TIMELINE)


def test_timeline_partitions_the_wall_time_and_names_each_cause(
        config, monkeypatch):
    """An idle stretch, an admission into an empty engine, ticks, an
    admission behind a queued tick, ticks to the end: the six sums add
    up to the thread's wall time between the first and the last landing,
    and each cause holds the interval a slow phase was put in."""
    eng = ContinuousBatcher(config, num_slots=4, max_len=64, block_size=16)
    _warm(eng)
    begin, before = eng._empty_since, _read(eng)
    time.sleep(0.05)                                    # nothing to do
    with monkeypatch.context() as m:
        _slow(m, eng, "_alloc_blocks", 0.03)    # admission, device empty
        _slow(m, eng, "_upload_state", 0.02)    # the restart after a prefill
        eng.submit([1, 2, 3, 4], max_new_tokens=12)
        for _ in range(4):
            eng.step()
    eng.submit([5, 6, 7], max_new_tokens=6)     # its prefill queues behind a tick
    while eng.has_work():
        eng.step()
    got = _gained(before, _read(eng))
    wall_ms = (eng._empty_since - begin) * 1e3
    assert _timeline_ms(got) == pytest.approx(wall_ms, rel=0.05)
    ms = {h: got[h.name] for h in TIMELINE}
    assert ms[mdefs.CB_IDLE_NO_WORK_MS][1] == 1
    assert 50 <= ms[mdefs.CB_IDLE_NO_WORK_MS][0] < 50 + 25
    # The first prefill found the device empty, the second a tick queued.
    assert ms[mdefs.CB_PREFILL_MS][1] == 2
    assert ms[mdefs.CB_STARVED_BEFORE_PREFILL_MS][1] == 1
    assert 30 <= ms[mdefs.CB_STARVED_BEFORE_PREFILL_MS][0] < 30 + 25
    # Each prefill is followed by a restart; the first one's was slow.
    assert ms[mdefs.CB_STARVED_AFTER_PREFILL_MS][1] == 2
    assert 20 <= ms[mdefs.CB_STARVED_AFTER_PREFILL_MS][0] < 20 + 25
    # A plain engine keeps a tick queued behind the one that runs.
    assert ms[mdefs.CB_STARVED_TICK_LATE_MS][1] == 0
    assert ms[mdefs.CB_TICK_MS][1] > 0


def test_a_tick_with_nothing_queued_behind_it_books_the_hosts_time(
        config, monkeypatch):
    """A speculative engine fetches each tick before it dispatches the
    next (a depth of 1), so the device waits through the host's whole
    turn: each tick after the first follows a ``tick_late`` interval,
    which holds the slow phase, and the identity still holds."""
    eng = ContinuousBatcher(config, num_slots=2, max_len=64, block_size=16,
                            spec_k=2, spec_adaptive=False,
                            prefix_cache=False)
    _warm(eng)
    begin, before = eng._empty_since, _read(eng)
    _slow(monkeypatch, eng, "_emit_gauges", 0.01)
    eng.submit([1, 2, 3, 4], max_new_tokens=12)
    while eng.has_work():
        eng.step()
    got = _gained(before, _read(eng))
    wall_ms = (eng._empty_since - begin) * 1e3
    assert _timeline_ms(got) == pytest.approx(wall_ms, rel=0.05)
    late_ms, late_n = got[mdefs.CB_STARVED_TICK_LATE_MS.name]
    ticks = got[mdefs.CB_TICK_MS.name][1]
    assert ticks >= 3 and late_n == ticks - 1   # the first follows the prefill
    assert got[mdefs.CB_STARVED_AFTER_PREFILL_MS.name][1] == 1
    assert late_ms >= 10 * late_n
    # One slot was live through every one of those intervals.
    stalled = got[mdefs.CB_SLOT_STALLED_MS.name]
    assert stalled == pytest.approx(
        late_ms + got[mdefs.CB_STARVED_AFTER_PREFILL_MS.name][0], rel=1e-6)


def _breakdown(eng, rid):
    (rec,) = [b for b in eng.request_breakdowns if b["rid"] == rid]
    return rec


def test_stalled_s_is_the_other_requests_prefill_and_zero_alone(config):
    """A request that decodes through another's admission stood still
    for that prefill batch's booked time, once; a request alone never.
    The slot counters hold the same story in slot-milliseconds: every
    tick's wall time for each member, the second prefill and both
    restarts for each slot live through them."""
    eng = ContinuousBatcher(config, num_slots=4, max_len=64, block_size=16)
    _warm(eng)
    before = _read(eng)
    alone = eng.submit([1, 2, 3, 4], max_new_tokens=5)
    eng.run_to_completion()
    rec = _breakdown(eng, alone)
    assert (rec["stalled_s"], rec["stall_count"]) == (0.0, 0)
    got = _gained(before, _read(eng))
    # Alone: four ticks of one member; stalled through its own restart.
    assert got[mdefs.CB_TICK_MS.name][1] == 4
    assert got[mdefs.CB_SLOT_ADVANCING_MS.name] == pytest.approx(
        got[mdefs.CB_TICK_MS.name][0], rel=1e-6)
    assert got[mdefs.CB_SLOT_STALLED_MS.name] == pytest.approx(
        got[mdefs.CB_STARVED_AFTER_PREFILL_MS.name][0], rel=1e-6)

    before = _read(eng)
    landed, land = [], eng._land

    def logged_land(tick, **kwargs):
        fresh = tick["wall"] is None
        land(tick, **kwargs)
        if fresh:
            landed.append((tick["wall"] * 1e3, len(tick["members"])))

    eng._land = logged_land
    first = eng.submit([1, 2, 3, 4], max_new_tokens=10)
    for _ in range(3):
        eng.step()
    mid = _read(eng)
    second = eng.submit([5, 6, 7], max_new_tokens=3)
    eng.run_to_completion()
    got, tail = _gained(before, _read(eng)), _gained(mid, _read(eng))
    assert tail[mdefs.CB_PREFILL_MS.name][1] == 1
    others_prefill_ms = tail[mdefs.CB_PREFILL_MS.name][0]
    rec1, rec2 = _breakdown(eng, first), _breakdown(eng, second)
    assert rec1["stall_count"] == 1
    assert rec1["stalled_s"] * 1e3 == pytest.approx(others_prefill_ms,
                                                    rel=1e-6)
    assert (rec2["stalled_s"], rec2["stall_count"]) == (0.0, 0)
    stall_sum, stall_n = mdefs.SERVE_REQ_DECODE_STALL.totals(
        {"engine": eng._mtags["engine"]})
    assert stall_n >= 3 and stall_sum >= rec1["stalled_s"]
    # By hand: ``first`` decodes nine ticks; ``second`` joins the two
    # dispatched after its prefill (the tick in flight then lands
    # without it).
    assert [members for _, members in landed] == [1, 1, 1, 1, 2, 2, 1, 1, 1]
    assert got[mdefs.CB_SLOT_ADVANCING_MS.name] == pytest.approx(
        sum(ms * members for ms, members in landed), rel=1e-6)
    # Stood still: ``first`` through its own restart and through the
    # second prefill, both through the restart after that.
    restart_2 = tail[mdefs.CB_STARVED_AFTER_PREFILL_MS.name][0]
    restart_1 = got[mdefs.CB_STARVED_AFTER_PREFILL_MS.name][0] - restart_2
    assert got[mdefs.CB_SLOT_STALLED_MS.name] == pytest.approx(
        restart_1 + others_prefill_ms + 2 * restart_2, rel=1e-6)


def test_padded_rows_and_tokens_beside_the_real_ones(config, monkeypatch):
    """Five waiting prompts of one bucket run as eight rows; a prompt of
    three chunks runs three calls of the chunk's length, the last one
    padded."""
    eng = ContinuousBatcher(config, num_slots=8, max_len=64, block_size=16)
    before = _read(eng)
    prompts = [list(range(1, n + 1)) for n in (17, 20, 25, 30, 31)]
    for p in prompts:
        eng.submit(p, max_new_tokens=2)
    eng.run_to_completion()
    got = _gained(before, _read(eng))
    assert got[mdefs.CB_PREFILL_MS.name][1] == 1
    assert got[mdefs.CB_PREFILL_REQUESTS.name] == 5
    assert got[mdefs.CB_PREFILL_PADDED_ROWS.name] == 8
    assert got[mdefs.CB_PREFILL_TOKENS.name] == sum(map(len, prompts))
    assert got[mdefs.CB_PREFILL_PADDED_TOKENS.name] == 8 * 32

    chunked = ContinuousBatcher(config, num_slots=2, max_len=64,
                                block_size=8, prefill_chunk=16,
                                prefix_cache=False)
    before = _read(chunked)
    chunked.submit(list(range(1, 41)), max_new_tokens=2)    # 16 + 16 + 8
    chunked.run_to_completion()
    got = _gained(before, _read(chunked))
    assert got[mdefs.CB_PREFILL_MS.name][1] == 1
    assert got[mdefs.CB_PREFILL_PADDED_ROWS.name] == 1
    assert got[mdefs.CB_PREFILL_TOKENS.name] == 40
    assert got[mdefs.CB_PREFILL_PADDED_TOKENS.name] == 1 * 16 * 3
    assert (1, 16) in chunked._prefill_shapes
    assert cb.PREFILL_BATCH_TOKENS >= 16


HELD = (mdefs.CB_ADMIT_HELD_SLOT_MS, mdefs.CB_ADMIT_HELD_TICKS)


@pytest.mark.parametrize("holds", [True, False])
def test_a_held_slot_is_the_third_part_of_the_slots_time(config, holds):
    """ISSUE 40: a saturated engine keeps free slots empty for a few
    ticks so that the next to free join their prefill call. Through each
    such tick a slot is advancing, held, or ending in the tick ahead;
    the held slot-milliseconds are each tick's wall time times the slots
    its hold kept empty, beside the live slots' advancing and stalled
    ones; and the thread's own identity is untouched, since the thread
    ticks through a hold. With no hold the third part reads 0."""
    eng = ContinuousBatcher(config, num_slots=4, max_len=64, block_size=16,
                            prefix_cache=False)
    _warm(eng)
    # The readings a fixed-cost prefill call would leave: a second row
    # is free, an empty slot forgoes an eighth of a millisecond a tick.
    for rows in (1, 2, 4):
        eng._batch_ms[(rows, 16, 0, 1)] = [10.0]
    eng._note_reading = lambda table, shape, ms: None
    eng._tick_ms, eng._note_tick_ms = 0.5, lambda ms: None
    if not holds:
        eng._holds_admission = lambda: False

    def read():
        key = tuple(sorted(eng._mtags.items()))
        return dict(_read(eng), **{
            c.name: sum(v for _, k, v in c.samples() if k == key)
            for c in HELD})

    landed, land, dispatch = [], eng._land, eng._dispatch_tick

    def logged_dispatch(members):
        dispatch(members)
        eng._inflight[-1]["ending"] = len(eng._slots) - len(members)

    def logged_land(tick, **kwargs):
        fresh = tick["wall"] is None
        land(tick, **kwargs)
        if fresh:
            landed.append((tick["wall"] * 1e3, len(tick["members"]),
                           tick["held"] if tick["hold"] else 0,
                           tick["ending"]))

    eng._dispatch_tick, eng._land = logged_dispatch, logged_land
    begin, before = eng._empty_since, read()
    for i in range(10):
        eng.submit([1 + i, 2, 3, 4], max_new_tokens=4 + 3 * (i % 4))
    eng.run_to_completion()
    got = _gained(before, read())
    wall_ms = (eng._empty_since - begin) * 1e3
    assert _timeline_ms(got) == pytest.approx(wall_ms, rel=0.05)
    assert got[mdefs.CB_SLOT_ADVANCING_MS.name] == pytest.approx(
        sum(ms * members for ms, members, _, _ in landed), rel=1e-6)
    assert got[mdefs.CB_ADMIT_HELD_SLOT_MS.name] == pytest.approx(
        sum(ms * held for ms, _, held, _ in landed), rel=1e-6)
    held_ticks = [tick for tick in landed if tick[2]]
    assert got[mdefs.CB_ADMIT_HELD_TICKS.name] == len(held_ticks)
    assert bool(held_ticks) == holds
    for _, members, held, ending in held_ticks:
        assert members + held + ending == eng.num_slots
    # Every slot's millisecond has at most one name.
    assert (got[mdefs.CB_SLOT_ADVANCING_MS.name]
            + got[mdefs.CB_SLOT_STALLED_MS.name]
            + got[mdefs.CB_ADMIT_HELD_SLOT_MS.name]
            <= eng.num_slots * _timeline_ms(got))
    assert got[mdefs.CB_PREFILL_REQUESTS.name] == 10
    assert (got[mdefs.CB_PREFILL_MS.name][1] < 6) == holds
