"""A streamed token's way back, hop by hop (ISSUE 34): the replica's
generator books ``handoff`` and ``store``, the ingress ``loop`` and the
burst, each on locals that reach the metrics registry at the stream's
end and every 64 items; a traced request's chain ends in the two
summary spans ``engine.stream`` and ``serve.stream``; and a replica
returns its newest per-request records."""

import http.client
import json
import time

import jax.numpy as jnp
import pytest

from ray_tpu._private import metrics_defs as mdefs
from ray_tpu.models import llama
from ray_tpu.util import metrics as metrics_mod
from ray_tpu.util import tracing

STREAM_COUNTERS = (mdefs.SERVE_STREAM_HANDOFF_SECONDS,
                   mdefs.SERVE_STREAM_STORE_SECONDS,
                   mdefs.SERVE_STREAM_REPLICA_ITEMS,
                   mdefs.SERVE_STREAM_LOOP_SECONDS,
                   mdefs.SERVE_STREAM_PULLS, mdefs.SERVE_STREAM_ITEMS)


@pytest.fixture(scope="module", autouse=True)
def _leave_no_runtime():
    """A deployment built outside a runtime fixture (``_engine``) starts
    an in-process runtime as it asks for its chip: shut it down with the
    module, or the next file of this worker finds it initialised."""
    import ray_tpu

    yield
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()


class _Records:
    def __init__(self):
        self.records = []

    def add(self, record):
        self.records.append(record)


def _totals():
    return {c.name: sum(v for _, _, v in c.samples())
            for c in STREAM_COUNTERS}


@pytest.fixture()
def served(ray_start_regular):
    """One in-process replica of the engine behind the HTTP ingress."""
    from ray_tpu import serve
    from ray_tpu.llm import ContinuousLlamaDeployment

    serve.run(ContinuousLlamaDeployment.options(name="LagLlama").bind(
        config=llama.LlamaConfig.tiny(dtype=jnp.float32), num_slots=2,
        max_len=256), name="lag")
    port = serve.start_http(port=0)

    def stream(max_tokens, request_id=""):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        body = json.dumps({"prompt_token_ids": [1, 2, 3],
                           "max_tokens": max_tokens})
        headers = {"Content-Type": "application/json"}
        if request_id:
            headers["x-request-id"] = request_id
        conn.request("POST", "/LagLlama/stream/generate", body=body,
                     headers=headers)
        resp = conn.getresponse()
        assert resp.status == 200, resp.read()
        tokens = [json.loads(x) for x in resp.read().splitlines()
                  if x.strip()]
        conn.close()
        assert len(tokens) == max_tokens, tokens
        return tokens

    stream(4)                                       # compiles
    try:
        yield stream
    finally:
        serve.stop_http()
        serve.shutdown()


def test_traced_stream_ends_in_engine_stream_and_serve_stream(
        monkeypatch, ray_start_regular):
    """``... > engine.prefill > engine.decode_window > engine.stream >
    serve.stream``: the two summary spans carry one ``request_id``,
    the replica's starts at the first landing (the end of the prefill
    span), and joined they give the whole-path lag of the first and the
    last token, neither negative."""
    monkeypatch.setenv("RAY_TPU_TRACING", "1")
    sink = _Records()
    monkeypatch.setattr(tracing, "_reporter", sink)
    from ray_tpu import serve
    from ray_tpu.llm import ContinuousLlamaDeployment

    serve.run(ContinuousLlamaDeployment.options(name="LagLlama").bind(
        config=llama.LlamaConfig.tiny(dtype=jnp.float32), num_slots=2,
        max_len=64), name="lag")
    port = serve.start_http(port=0)
    req_id = "req-stream-0123456789abcdef"
    try:
        for rid in ("req-stream-warmup", req_id):
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
            conn.request("POST", "/LagLlama/stream/generate",
                         body=json.dumps({"prompt_token_ids": [1, 2, 3],
                                          "max_tokens": 6}),
                         headers={"Content-Type": "application/json",
                                  "x-request-id": rid})
            resp = conn.getresponse()
            assert resp.status == 200
            assert len(resp.read().splitlines()) == 6
            conn.close()
        deadline = time.monotonic() + 10     # the ingress span closes last
        while time.monotonic() < deadline:
            trace = [e for e in sink.records
                     if e.get("request_id") == req_id]
            if any(e["name"] == "serve.ingress" for e in trace):
                break
            time.sleep(0.05)
    finally:
        serve.stop_http()
        serve.shutdown()
    assert len({e["trace_id"] for e in trace}) == 1
    by_name = {e["name"]: e for e in trace}
    assert {"serve.ingress", "serve.route", "engine.prefill",
            "engine.decode_window", "engine.stream", "serve.stream",
            "engine.finished"} <= set(by_name), sorted(by_name)
    replica, ingress = by_name["engine.stream"], by_name["serve.stream"]
    assert ingress["parent_span_id"] == by_name["serve.ingress"]["span_id"]
    assert replica["parent_span_id"] == by_name["serve.route"]["span_id"]
    assert replica["tokens"] == ingress["items"] == 6
    assert 1 <= ingress["pulls"] <= 6
    assert ingress["items"] / ingress["pulls"] >= 1
    assert 1 <= ingress["max_items_per_pull"] <= 6
    prefill = by_name["engine.prefill"]
    assert replica["ts"] == replica["landed_first_ts"] == pytest.approx(
        prefill["ts"] + prefill["dur"], abs=5e-3)
    assert replica["landed_first_ts"] <= replica["landed_last_ts"]
    # The two whole-path lags a request: first token, last token.
    first_lag = ingress["first_write_ts"] - replica["landed_first_ts"]
    last_lag = ingress["last_write_ts"] - replica["landed_last_ts"]
    assert 0 <= first_lag < 5 and 0 <= last_lag < 5
    assert 0 <= replica["handoff_mean_s"] <= replica["handoff_max_s"] < 5
    assert replica["store_mean_s"] >= 0
    assert ingress["ts"] == ingress["first_write_ts"]
    assert ingress["ts"] + ingress["dur"] == pytest.approx(
        ingress["last_write_ts"])
    # The terminal span says how long the stream stood still (alone: 0).
    assert by_name["engine.finished"]["stall_count"] == 0
    assert by_name["engine.finished"]["stalled_s"] == 0.0


def test_stream_counters_move_and_no_span_with_tracing_off(
        monkeypatch, served):
    monkeypatch.delenv("RAY_TPU_TRACING", raising=False)
    sink = _Records()
    monkeypatch.setattr(tracing, "_reporter", sink)
    before = _totals()
    served(12)
    got = {k: v - before[k] for k, v in _totals().items()}
    assert got[mdefs.SERVE_STREAM_REPLICA_ITEMS.name] == 12
    assert got[mdefs.SERVE_STREAM_ITEMS.name] == 12
    assert 1 <= got[mdefs.SERVE_STREAM_PULLS.name] <= 12
    assert got[mdefs.SERVE_STREAM_HANDOFF_SECONDS.name] > 0
    assert got[mdefs.SERVE_STREAM_STORE_SECONDS.name] > 0
    assert got[mdefs.SERVE_STREAM_LOOP_SECONDS.name] > 0
    assert not [r for r in sink.records if r.get("state") == "SPAN"]


def test_per_item_loops_make_no_registry_call(monkeypatch, served):
    """The token stream's turn on the replica's loop (``__anext__``)
    and ``_route``'s loop sum on locals: over a 200-token stream each
    stream counter is touched at most 200 / 64 + 2 times, and the
    registry calls made from inside those two functions (whatever they
    call) are those of a 20-token stream plus the flushes: bounded by
    the flushes, not by the tokens."""
    import sys

    loops = {"__anext__", "_route"}
    calls = []

    def counting(method):
        def wrapped(self, *args, **kwargs):
            frame, inside = sys._getframe(1), False
            while frame is not None and not inside:
                inside = frame.f_code.co_name in loops
                frame = frame.f_back
            if inside:
                calls.append(self.name)
            return method(self, *args, **kwargs)
        return wrapped

    monkeypatch.setattr(metrics_mod.Counter, "inc",
                        counting(metrics_mod.Counter.inc))
    monkeypatch.setattr(metrics_mod.Histogram, "observe",
                        counting(metrics_mod.Histogram.observe))
    monkeypatch.setattr(metrics_mod.Gauge, "set",
                        counting(metrics_mod.Gauge.set))

    def from_the_loops(n):
        del calls[:]
        served(n)
        time.sleep(0.2)                     # the replica's end-of-stream
        return list(calls)

    short = from_the_loops(20)
    long = from_the_loops(200)
    for counter in STREAM_COUNTERS:
        assert short.count(counter.name) == 1, counter.name
        assert 1 <= long.count(counter.name) <= 200 // 64 + 2, counter.name
    flushes = len(STREAM_COUNTERS) * (200 // 64)
    assert len(long) <= len(short) + flushes < 200, (short, long)


def test_stream_books_flush_every_64_items_and_at_the_end():
    from ray_tpu.llm import _StreamLag
    from ray_tpu.serve.proxy import _StreamPulls

    before = _totals()
    lag = _StreamLag({"engine": "lag-unit"})
    for i in range(130):
        lag.note(100.0 + i, 100.25 + i, 100.75 + i)
    mid = {k: v - before[k] for k, v in _totals().items()}
    assert mid[mdefs.SERVE_STREAM_REPLICA_ITEMS.name] == 128
    assert mid[mdefs.SERVE_STREAM_HANDOFF_SECONDS.name] == 0.25 * 128
    lag.close(None)
    lag.close(None)                                 # nothing twice
    pulls = _StreamPulls("lag-unit")
    for i in range(40):                             # two items a pull
        pulls.started, pulls.returned = 10.0 + i + 0.125, 10.0 + i + 0.5
        pulls.resumed(10.0 + i, 10.0 + i + 0.75)
        pulls.wrote(2, 10.0 + i + 0.875)
    pulls.close(None)
    got = {k: v - before[k] for k, v in _totals().items()}
    assert got[mdefs.SERVE_STREAM_REPLICA_ITEMS.name] == 130
    assert got[mdefs.SERVE_STREAM_HANDOFF_SECONDS.name] == 0.25 * 130
    assert got[mdefs.SERVE_STREAM_STORE_SECONDS.name] == 0.5 * 130
    assert (lag.handoff_mean_s, lag.handoff_max_s) == (0.25, 0.25)
    assert got[mdefs.SERVE_STREAM_PULLS.name] == 40
    assert got[mdefs.SERVE_STREAM_ITEMS.name] == 80
    assert got[mdefs.SERVE_STREAM_LOOP_SECONDS.name] == (0.125 + 0.25) * 40
    assert (pulls.max_items, pulls.first_write, pulls.last_write) == (
        2, 10.875, 49.875)


def test_replica_returns_its_newest_request_breakdowns():
    """``request_breakdowns(n)``: an operator asks a live replica why a
    request was slow, and gets the record with both halves of it."""
    from ray_tpu.llm import ContinuousLlamaDeployment

    dep = ContinuousLlamaDeployment._cls_or_fn(
        config=llama.LlamaConfig.tiny(dtype=jnp.float32), num_slots=2,
        max_len=64)
    for n in (3, 4, 5):
        assert len(list(dep.generate([1, 2, 3], n))) == n
    recs = dep.request_breakdowns()
    assert [r["tokens"] for r in recs] == [3, 4, 5]
    assert [r["tokens"] for r in dep.request_breakdowns(n=2)] == [4, 5]
    assert dep.request_breakdowns(n=0) == []
    for rec in recs:
        assert {"lock_wait_s", "queue_s", "prefill_s", "ttft_s", "tpot_s",
                "stalled_s", "stall_count", "handoff_mean_s"} <= set(rec)
        assert rec["stall_count"] == 0 and rec["stalled_s"] == 0.0
        assert rec["handoff_mean_s"] is not None
        assert 0 <= rec["handoff_mean_s"] < 5
    recs[0]["tokens"] = -1                          # a copy, not the book
    assert dep.request_breakdowns()[0]["tokens"] == 3


def test_a_stream_that_falls_behind_ships_what_piled_up(ray_start_regular):
    """A sync generator's items reach the consumer one by one, in order
    and once each, whatever the replica shipped them as: a generator
    that outruns its consumer (200 items made at once, the consumer
    starting late) crosses the object store in fewer objects than
    items (``serve.api.StreamBatch``), one that is slower than its
    consumer in one object an item; an error raised after items were
    made arrives after them."""
    from ray_tpu import serve
    from ray_tpu.serve import api

    @serve.deployment
    class Source:
        def fast(self, n):
            yield from range(n)

        def slow(self, n):
            for i in range(n):
                time.sleep(0.05)
                yield i

        def broken(self, n):
            yield from range(n)
            raise RuntimeError("after the items")

    handle = serve.run(Source.bind(), name="batches")
    shipped = []
    real_get = api.ray_tpu.get

    def counting_get(ref, **kw):
        item = real_get(ref, **kw)
        shipped.append(item)
        return item

    stream = handle.options(stream=True)
    gen = stream.fast.remote(200)
    time.sleep(0.5)                     # every item is made by now
    api.ray_tpu.get, before = counting_get, api.ray_tpu.get
    try:
        assert list(gen) == list(range(200))
        batches = [s for s in shipped if isinstance(s, api.StreamBatch)]
        assert sum(len(b) for b in batches) == 200
        assert len(batches) < 200 and max(len(b) for b in batches) > 1
        del shipped[:]
        assert list(stream.slow.remote(5)) == list(range(5))
        assert [list(s) for s in shipped] == [[i] for i in range(5)]
        gen = stream.broken.remote(3)
        got = []
        with pytest.raises(Exception, match="after the items"):
            for item in gen:
                got.append(item)
        assert got == [0, 1, 2]
    finally:
        api.ray_tpu.get = before


# ------------------------------------------------------------------------
# The hand-over (ISSUE 41): the tick thread hands a landing's tokens to
# the replica's loop in one call, the loop spreads them over the streams,
# and no thread exists per open stream.

PROMPTS = [[1, 2, 3], [4, 5, 6, 7], [9, 8], [3, 1, 4, 1, 5], [2, 7, 1, 8]]


def _engine(**kw):
    from ray_tpu.llm import ContinuousLlamaDeployment

    kw.setdefault("num_slots", 4)
    kw.setdefault("max_len", 256)
    return ContinuousLlamaDeployment._cls_or_fn(
        config=llama.LlamaConfig.tiny(dtype=jnp.float32), **kw)


def _flat(items):
    from ray_tpu.serve.api import StreamBatch

    out = []
    for item in items:
        out.extend(item if isinstance(item, StreamBatch) else [item])
    return out


async def _drive(stream, pause=0.0):
    """What the replica's loop does with a stream: the shipped objects."""
    import asyncio

    shipped = []
    async for item in stream:
        shipped.append(item)
        if pause:
            await asyncio.sleep(pause)
    return shipped


def _wait_for(cond, timeout_s=10.0):
    deadline = time.monotonic() + timeout_s
    while not cond():
        assert time.monotonic() < deadline, "timed out"
        time.sleep(0.01)


@pytest.fixture()
def slow_ticks():
    """Every engine step sleeps 20 ms first: streams stay open, and a
    consumer on the loop keeps up with them."""
    from ray_tpu._private import chaos

    chaos.configure("delay_tick:secs=0.02,times=-1", seed=1)
    yield
    chaos.configure(None)


def test_generate_iterates_synchronously_and_from_a_loop():
    """One object for both consumers: ``__call__``, ``list`` and a
    direct ``for`` on the caller's thread, ``async for`` on a loop, the
    same tokens each way; an abandoned resume is an empty stream."""
    import asyncio

    dep = _engine()
    want = list(dep.generate(PROMPTS[0], 9))
    assert len(want) == 9 and all(isinstance(t, int) for t in want)
    assert dep({"prompt_token_ids": PROMPTS[0],
                "max_tokens": 9}) == {"token_ids": want}
    stream = dep.generate({"prompt_token_ids": PROMPTS[0], "max_tokens": 9})
    assert iter(stream) is stream and stream.__aiter__() is stream
    got = []
    for token in stream:
        got.append(token)
    assert got == want
    assert _flat(asyncio.run(_drive(dep.generate(PROMPTS[0], 9)))) == want
    assert not dep._streams


def test_concurrent_streams_whole_in_order_and_control_last(monkeypatch):
    """Five streams over four slots driven from one loop: each comes
    back whole and in order whatever it was shipped as, with its control
    object after its last token."""
    import asyncio

    dep = _engine()
    want = [list(dep.generate(p, 12 + i)) for i, p in enumerate(PROMPTS)]
    monkeypatch.setattr(dep.batcher, "take_routes",
                        lambda rid: [[rid]])

    async def main():
        return await asyncio.gather(*[
            _drive(dep.generate(p, 12 + i))
            for i, p in enumerate(PROMPTS)])

    for shipped, tokens in zip(asyncio.run(main()), want):
        items = _flat(shipped)
        assert items[:-1] == tokens
        assert set(items[-1]) == {"routes"}
    assert not dep._streams


def test_streams_through_a_served_replica_whole_and_in_order(
        ray_start_regular):
    from ray_tpu import serve
    from ray_tpu.llm import ContinuousLlamaDeployment

    handle = serve.run(ContinuousLlamaDeployment.options(name="Many").bind(
        config=llama.LlamaConfig.tiny(dtype=jnp.float32), num_slots=4,
        max_len=256), name="many")
    try:
        stream = handle.options(stream=True)
        want = [list(stream.generate.remote(p, 10 + i))
                for i, p in enumerate(PROMPTS)]
        assert [len(w) for w in want] == [10, 11, 12, 13, 14]
        gens = [stream.generate.remote(
            {"prompt_token_ids": p, "max_tokens": 10 + i})
            for i, p in enumerate(PROMPTS) for _ in range(3)]
        assert [list(g) for g in gens] == [w for w in want
                                           for _ in range(3)]
    finally:
        serve.shutdown()


def test_open_streams_cost_the_replica_no_thread(ray_start_regular,
                                                 slow_ticks):
    """32 streams open at once: the process holds the threads of the
    idle replica and the deployment's one hop thread, not one a
    stream."""
    import threading

    from ray_tpu import serve
    from ray_tpu.llm import ContinuousLlamaDeployment

    handle = serve.run(ContinuousLlamaDeployment.options(
        name="Wide", max_ongoing_requests=64).bind(
        config=llama.LlamaConfig.tiny(dtype=jnp.float32), num_slots=32,
        max_len=128), name="wide")
    try:
        stream = handle.options(stream=True)
        assert len(list(stream.generate.remote([1, 2, 3], 4))) == 4

        def threads():
            """(the request pool's, every other thread's) names."""
            names = sorted(t.name for t in threading.enumerate())
            pool = [n for n in names if n.startswith("llm-req")]
            return pool, [n for n in names if n not in pool]

        idle = threads()
        gens = [stream.generate.remote([1, 2, 3 + i], 60)
                for i in range(32)]
        firsts = [next(g) for g in gens]            # all 32 are open
        assert len(firsts) == 32
        pool, others = threads()
        # The deployment's one hop thread (engines of earlier tests in
        # this process keep theirs); the runtime's own threads (the
        # controller's probes) may come and go by a few.
        assert len(pool) - len(idle[0]) <= 1, (idle[0], pool)
        assert len(others) <= len(idle[1]) + 4, (idle, others)
        assert not [n for n in others if n.startswith("replica-sync")]
        assert all(len(list(g)) == 59 for g in gens)
    finally:
        serve.shutdown()


def test_one_handoff_a_landing_and_first_tokens_ahead_of_the_next_row(
        slow_ticks):
    """Each landing is ONE call to the streams' side: a tick over k live
    rows carries k tokens under one stamp and moves the hand-off counter
    by one and the replica's items by k; a prefill batch's first tokens
    are handed over before the next tick's row is waited for."""
    import asyncio

    dep = _engine()
    list(dep.generate(PROMPTS[0], 3))                # compiles
    events = []
    ship, land = dep._ship, dep.batcher._land
    device_empty = dep.batcher._device_empty

    def shipping(entries):
        events.append(("ship", entries))
        return ship(entries)

    def landing(tick, **kw):
        if tick["wall"] is None:
            events.append(("land", None))
        return land(tick, **kw)

    def emptied(now, after):
        if after == "prefill":
            events.append(("prefilled", None))
        return device_empty(now, after)

    dep._ship, dep.batcher._land = shipping, landing
    dep.batcher._device_empty = emptied
    before = _totals()
    handoffs = sum(v for _, _, v in mdefs.SERVE_STREAM_HANDOFFS.samples())

    async def main():
        return await asyncio.gather(*[
            _drive(dep.generate(p, 30)) for p in PROMPTS[:3]])

    assert [len(_flat(s)) for s in asyncio.run(main())] == [30, 30, 30]
    landings = [e for kind, e in events
                if kind == "ship" and isinstance(e[0][1], tuple)]
    assert sum(len(e) for e in landings) == 90
    for entries in landings:
        streams = [stream for stream, _ in entries]
        assert len(set(map(id, streams))) == len(streams)   # one a row
        assert len({landed for _, (_, landed) in entries}) == 1
    assert max(len(e) for e in landings) == 3       # a tick over 3 rows
    assert sum(v for _, _, v in mdefs.SERVE_STREAM_HANDOFFS.samples()) \
        - handoffs == len(landings)
    got = {k: v - before[k] for k, v in _totals().items()}
    assert got[mdefs.SERVE_STREAM_REPLICA_ITEMS.name] == 90
    kinds = [kind for kind, _ in events]
    prefills = [i for i, kind in enumerate(kinds) if kind == "prefilled"]
    assert prefills
    for i in prefills:      # first tokens leave before any row lands
        assert kinds[i + 1] == "ship", kinds[i:i + 3]


def test_a_stream_the_loop_reaches_late_ships_one_batch(slow_ticks):
    """A consumer that keeps up gets bare tokens, one a turn; one that
    comes back late gets everything its stream holds as one
    ``StreamBatch``."""
    import asyncio

    from ray_tpu.serve.api import StreamBatch

    dep = _engine()
    want = list(dep.generate(PROMPTS[1], 16))
    prompt = asyncio.run(_drive(dep.generate(PROMPTS[1], 16)))
    assert prompt == want                            # token by token
    late = asyncio.run(_drive(dep.generate(PROMPTS[1], 16), pause=0.15))
    assert _flat(late) == want
    batches = [item for item in late if isinstance(item, StreamBatch)]
    assert batches and max(len(b) for b in batches) > 2
    assert len(late) < 16


def test_simulated_death_in_front_of_token_n_delivers_exactly_n():
    """``phase=decode,token=5`` with everything piled up: the batch is
    cut in front of token 5 and shipped, then the death is raised, and
    the dead stream's request is dropped."""
    import asyncio

    from ray_tpu._private import chaos

    dep = _engine()
    want = list(dep.generate(PROMPTS[2], 12))

    async def main():
        stream = dep.generate(PROMPTS[2], 12)
        shipped = [await stream.__anext__()]
        await asyncio.sleep(0.5)                    # the rest piles up
        chaos.configure("kill_replica:phase=decode,token=5", seed=7)
        with pytest.raises(chaos.SimulatedProcessDeath):
            while True:
                shipped.append(await stream.__anext__())
        return shipped

    try:
        shipped = asyncio.run(main())
    finally:
        chaos.configure(None)
        chaos._clear_dying()
    assert _flat(shipped) == want[:5]
    # What piled up in front of token 5 ships as ONE batch (the first
    # pull may itself have found two tokens on a loaded machine).
    assert len(shipped) == 2 and len(shipped[1]) >= 3
    assert not dep._streams


@pytest.mark.parametrize("consumer", ["loop", "thread", "break", "raise"])
def test_an_abandoned_stream_frees_its_slot(slow_ticks, consumer):
    """A consumer that leaves before the end (``aclose`` from the
    replica's loop, ``close`` from a thread, a ``break`` or an exception
    out of a ``for`` with no ``close`` at all: the deployment holds its
    streams weakly, and the dropped one settles from ``__del__``): the
    request is cancelled under the engine lock and stops burning
    ticks."""
    import asyncio

    dep = _engine()

    async def leave_early():
        stream = dep.generate(PROMPTS[3], 200)
        first = [await stream.__anext__(), await stream.__anext__()]
        await stream.aclose()
        await stream.aclose()                       # nothing twice
        return first

    if consumer == "loop":
        assert len(_flat(asyncio.run(leave_early()))) >= 2
    elif consumer == "thread":
        stream = dep.generate(PROMPTS[3], 200)
        assert isinstance(next(stream), int)
        stream.close()
        with pytest.raises(StopIteration):
            next(stream)
    elif consumer == "break":
        for token in dep.generate(PROMPTS[3], 200):
            assert isinstance(token, int)
            break
    else:
        with pytest.raises(ZeroDivisionError):
            for token in dep.generate(PROMPTS[3], 200):
                token / 0
    _wait_for(lambda: not dep.batcher._slots and not dep._streams, 3.0)
    assert dep.batcher.decoded_tokens < 150
    (rec,) = [r for r in dep.request_breakdowns() if r["tokens"] < 150]
    assert rec["outcome"] == "evicted"


def test_an_engine_error_reaches_every_open_stream(slow_ticks):
    import asyncio

    dep = _engine()
    list(dep.generate(PROMPTS[0], 3))
    step, calls = dep.batcher.step, []

    def failing():
        calls.append(1)
        if len(calls) > 4:
            dep.batcher.step = step
            raise RuntimeError("the device fell over")
        return step()

    dep.batcher.step = failing

    async def main():
        return await asyncio.gather(*[
            _drive(dep.generate(p, 100)) for p in PROMPTS[:3]],
            return_exceptions=True)

    errors = asyncio.run(main())
    assert [str(e) for e in errors] == ["the device fell over"] * 3
    assert not dep._streams and not dep.batcher._slots
    assert len(list(dep.generate(PROMPTS[0], 5))) == 5     # it goes on


@pytest.mark.parametrize("consumer", ["loop", "thread"])
def test_stream_item_timeout_is_honoured(monkeypatch, consumer):
    """An engine that never gets to the request: the wait for an item
    ends after ``STREAM_ITEM_TIMEOUT_S``, and the request is dropped."""
    import asyncio

    import ray_tpu.llm as llm_mod

    dep = _engine()
    list(dep.generate(PROMPTS[0], 3))
    monkeypatch.setattr(llm_mod, "STREAM_ITEM_TIMEOUT_S", 0.3)
    monkeypatch.setattr(dep.batcher, "has_work", lambda: False)
    t0 = time.monotonic()
    with pytest.raises(TimeoutError):
        if consumer == "loop":
            asyncio.run(_drive(dep.generate(PROMPTS[0], 5)))
        else:
            list(dep.generate(PROMPTS[0], 5))
    assert 0.25 <= time.monotonic() - t0 < 5
    _wait_for(lambda: not dep.batcher._waiting and not dep._streams, 3.0)


def test_each_stream_submits_under_its_own_request_context():
    """Streams driven by one loop, each opened under its own request
    context: the engine's records carry each request's own id (the hop
    that submits runs in the request's copied context)."""
    import asyncio

    from ray_tpu.serve import context as serve_context

    dep = _engine()

    async def one(i):
        token = serve_context._set_request_context(
            {"request_id": f"req-ctx-{i}", "trace_id": f"{i:016x}",
             "parent_span_id": "", "deployment": "ctx"})
        try:
            return _flat(await _drive(dep.generate(PROMPTS[i], 4 + i)))
        finally:
            serve_context._reset_request_context(token)

    async def main():
        return await asyncio.gather(*[one(i) for i in range(4)])

    assert [len(t) for t in asyncio.run(main())] == [4, 5, 6, 7]
    by_id = {r["request_id"]: r["tokens"]
             for r in dep.request_breakdowns()}
    assert by_id == {f"req-ctx-{i}": 4 + i for i in range(4)}


def test_replica_closes_an_async_iterator_its_consumer_left():
    """``handle_request_streaming`` drives what has ``__aiter__`` on its
    own loop and, when the consumer goes first, closes it."""
    import asyncio

    from ray_tpu.serve.api import Replica

    class Numbers:
        def __init__(self):
            self.n, self.closed = 0, 0

        def __aiter__(self):
            return self

        async def __anext__(self):
            self.n += 1
            return self.n

        async def aclose(self):
            self.closed += 1

    class Source:
        def __init__(self):
            self.made = []

        def numbers(self):
            self.made.append(Numbers())
            return self.made[-1]

    replica = Replica(Source, (), {}, False, sync_workers=1)

    async def main():
        agen = replica.handle_request_streaming("numbers", (), {})
        got = [await agen.__anext__(), await agen.__anext__()]
        await agen.aclose()
        return got

    assert asyncio.run(main()) == [1, 2]
    assert [n.closed for n in replica.instance.made] == [1]
    assert replica.metrics()["ongoing"] == 0


def test_hand_over_under_a_short_switch_interval():
    """More streams than cores, consumers on a loop AND on threads of
    their own, and the interpreter switching threads every 10 us: the
    tick thread's appends, the loop's spread and the consumers' pops
    lose no token and reorder none."""
    import asyncio
    import sys
    import threading

    dep = _engine(num_slots=8)
    want = {tuple(p): list(dep.generate(p, 40)) for p in PROMPTS}
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        by_thread = {}

        def on_a_thread(i, p):
            by_thread[i] = (p, list(dep.generate(p, 40)))

        threads = [threading.Thread(target=on_a_thread, args=(i, p))
                   for i, p in enumerate(PROMPTS * 2)]

        async def main():
            for t in threads:
                t.start()
            return await asyncio.gather(*[
                _drive(dep.generate(p, 40)) for p in PROMPTS * 4])

        shipped = asyncio.run(asyncio.wait_for(main(), 120))
        for t in threads:
            t.join(60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    for p, items in zip(PROMPTS * 4, shipped):
        assert _flat(items) == want[tuple(p)]
    assert len(by_thread) == 10
    for p, tokens in by_thread.values():
        assert tokens == want[tuple(p)]
    assert not dep._streams


def test_a_wave_that_waits_for_the_lock_is_admitted_together():
    """Twenty requests arrive while the engine lock is held (a prefill
    in progress): the hop thread's next hold of the lock starts every
    stream that waits, so the engine admits the wave as ONE prefill
    batch (the benchmark's warm-up counts on it)."""
    import asyncio

    dep = _engine(num_slots=32, max_len=64)
    list(dep.generate(PROMPTS[0], 3))
    tags = dep.batcher._mtags
    batches = mdefs.CB_PREFILL_MS.totals(tags)[1]
    requests = dep.batcher.prefill_requests

    async def main():
        dep._lock.acquire()
        try:
            tasks = [asyncio.ensure_future(
                _drive(dep.generate([1, 2, 3 + i], 3))) for i in range(20)]
            await asyncio.sleep(0.3)
            assert len(dep._starting) == 20
        finally:
            dep._lock.release()
        return await asyncio.gather(*tasks)

    assert [len(_flat(s)) for s in asyncio.run(main())] == [3] * 20
    assert dep.batcher.prefill_requests - requests == 20
    assert mdefs.CB_PREFILL_MS.totals(tags)[1] - batches == 1
    assert not dep._starting and not dep._streams


class _CountedLock:
    """The engine lock, and which thread took it each time."""

    def __init__(self):
        import threading

        self._lock = threading.Lock()
        self.holders = []

    def acquire(self, *args, **kw):
        import threading

        got = self._lock.acquire(*args, **kw)
        if got:
            self.holders.append(threading.current_thread().name)
        return got

    def release(self):
        self._lock.release()

    def locked(self):
        return self._lock.locked()

    def __enter__(self):
        self.acquire()

    def __exit__(self, *exc):
        self.release()


def test_one_hold_of_the_lock_settles_and_starts_all_that_wait(slow_ticks):
    """Twelve streams end and five begin while a step holds the engine
    lock: the hop thread's NEXT hold cancels the twelve and submits the
    five (a burst of endings keeps no start from the lock while slots
    stand empty), and the tasks queued behind it find nothing to do and
    take no hold."""
    import asyncio

    dep = _engine(num_slots=16, max_len=128)
    list(dep.generate(PROMPTS[0], 3))
    lock = dep._lock = _CountedLock()

    def hops():
        return [n for n in lock.holders if n.startswith("llm-req")]

    async def main():
        streams = [dep.generate([1, 2, 3 + i], 100) for i in range(12)]
        for stream in streams:
            await stream.__anext__()                # all twelve are open
        assert len(dep.batcher._slots) == 12
        lock.acquire()
        try:
            before = len(hops())
            for stream in streams:
                await stream.aclose()
            late = [asyncio.ensure_future(_drive(dep.generate([7, i], 3)))
                    for i in range(5)]
            await asyncio.sleep(0.2)
            assert len(dep._settling) == 12 and len(dep._starting) == 5
            assert len(hops()) == before            # all wait for one hold
        finally:
            lock.release()
        while dep._starting or dep._settling or dep._hops._work_queue.qsize():
            await asyncio.sleep(0.01)
        assert len(hops()) == before + 1
        return await asyncio.gather(*late)

    assert [len(_flat(s)) for s in asyncio.run(main())] == [3] * 5
    _wait_for(lambda: not dep.batcher._slots and not dep._streams, 3.0)
    outcomes = [r["outcome"] for r in dep.request_breakdowns()]
    assert outcomes.count("evicted") == 12


@pytest.mark.parametrize("leaves", ["before", "during"])
def test_a_stream_closed_while_it_starts_is_not_left_registered(leaves):
    """A consumer that leaves before its stream's turn at the lock is
    never submitted; one that leaves while the submit runs is cancelled
    by the thread that started it, and neither stays among the
    deployment's streams."""
    dep = _engine()
    list(dep.generate(PROMPTS[0], 3))
    requests = dep.batcher.prefill_requests
    stream = dep.generate(PROMPTS[1], 50)
    stream._event = __import__("threading").Event()
    stream._begin()
    if leaves == "before":
        stream.close()
    else:
        submit = stream._open

        def leave_during_submit():
            rid = submit()
            stream.close()              # finds no request id yet
            return rid

        stream._open = leave_during_submit
    dep._turn()
    assert stream.rid is None if leaves == "before" \
        else stream.rid is not None
    assert not dep._streams and not dep._starting
    _wait_for(lambda: not dep.batcher.has_work(), 3.0)
    assert not dep.batcher._slots and not dep.batcher._waiting
    assert dep.batcher.prefill_requests == requests
    with pytest.raises(StopIteration):
        next(stream)


def _at_the_door(dep, ages, now):
    """Streams that wait to start, the oldest first, each arrived
    ``age`` seconds before ``now``."""
    for i, age in enumerate(ages):
        stream = dep.generate([5, 6, 7 + i], 2)
        stream._entered = now - age
        dep._starting.append(stream)


@pytest.fixture(scope="module")
def door():
    """One idle engine for every case below (an engine a case would
    crowd the process's metrics registry)."""
    dep = _engine(num_slots=8, max_len=128)
    list(dep.generate(PROMPTS[0], 3))
    _wait_for(lambda: not dep.batcher.has_work(), 3.0)
    return dep


@pytest.mark.parametrize("ages,engine,room", [
    ((0.050, 0.030, 0.002), "idle", 0.048),     # still running
    ((0.050, 0.002), "idle", 0.048),
    ((0.002,), "idle", 0.0),                    # one stream waits for nothing
    ((0.900, 0.880, 0.860), "idle", 0.0),       # it has paused
    ((0.050, 0.025), "idle", 0.0),              # paused as long as it ran
    ((0.050, 0.030, 0.002), "live", 0.0),
    ((0.050, 0.030, 0.002), "queued", 0.0),
    ((), "idle", 0.0),
])
def test_a_burst_still_running_at_an_idle_door_is_given_room(door, ages,
                                                             engine, room):
    """Several streams at an idle engine's door whose newest came more
    recently than the burst has lasted get as long again as it has
    lasted; one stream, a burst that has paused, an engine with a
    request live or queued get none."""
    dep = door
    now = time.time()
    with dep._lock:
        _at_the_door(dep, ages, now)
        if engine == "live":
            dep.batcher._slots[0] = {"rid": -1}
        elif engine == "queued":
            dep.batcher._waiting.append({"rid": -1})
        try:
            assert dep._burst_room(now) == pytest.approx(room, abs=1e-6)
        finally:
            dep.batcher._slots.pop(0, None)
            dep.batcher._waiting.clear()
            for stream in dep._starting:
                stream._ended = True            # never begun: nothing to free
            dep._starting.clear()


def test_a_burst_given_room_starts_together_under_the_second_hold(door):
    """Three streams arrive at an idle engine while a hold is out; the
    hop thread's next hold finds the burst still running and lets go of
    the lock without starting anyone, for as long again as the burst has
    lasted; its second hold starts them and the two that came meanwhile,
    in arrival order: one prefill batch of five, not three and two."""
    import asyncio

    dep = door
    lock = dep._lock = _CountedLock()      # the last test at this door
    requests = dep.batcher.prefill_requests
    admitted, admit = [], dep.batcher._admit_waiting

    def counted(*args, **kw):
        before = len(dep.batcher._waiting)
        admit(*args, **kw)
        if before - len(dep.batcher._waiting):
            admitted.append(before - len(dep.batcher._waiting))

    dep.batcher._admit_waiting = counted

    def hops():
        return [n for n in lock.holders if n.startswith("llm-req")]

    async def main():
        lock.acquire()
        tasks = []
        try:
            for i in range(3):
                tasks.append(asyncio.ensure_future(
                    _drive(dep.generate([7, i], 3))))
                await asyncio.sleep(0.15)
        finally:
            lock.release()
        while not hops():                           # its first hold
            await asyncio.sleep(0.002)
        assert len(dep._starting) == 3              # the room is running
        assert dep.batcher.prefill_requests == requests
        for i in range(3, 5):
            tasks.append(asyncio.ensure_future(
                _drive(dep.generate([7, i], 3))))
        return await asyncio.gather(*tasks)

    try:
        assert [len(_flat(s)) for s in asyncio.run(main())] == [3] * 5
    finally:
        dep.batcher._admit_waiting = admit
    assert dep.batcher.prefill_requests == requests + 5
    assert admitted == [5]
