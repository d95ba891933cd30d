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
    """``generate()``'s and ``_route``'s loops sum on locals: over a
    200-token stream each stream counter is touched at most 200 / 64 + 2
    times, and the registry calls made from inside those two functions
    (whatever they call) are those of a 20-token stream plus the
    flushes: bounded by the flushes, not by the tokens."""
    import sys

    loops = {"generate", "_route"}
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
