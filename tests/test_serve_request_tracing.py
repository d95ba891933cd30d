"""Request-path observability tests: one serve request = one connected
trace (ingress → route → engine queue/arena-wait/prefill/decode spans
sharing a trace id), TTFT decomposition that sums to the measured TTFT,
per-replica pressure snapshots, and event-buffer drop accounting."""

import json
import time

import jax.numpy as jnp
import pytest

import ray_tpu
from ray_tpu.models import llama
from ray_tpu.models.continuous_batching import ContinuousBatcher
from ray_tpu.util import tracing

def _prefill_batches(eng):
    """Prefill batches ``eng`` ran: ``CB_PREFILL_MS`` books one each."""
    from ray_tpu._private import metrics_defs as mdefs

    return mdefs.CB_PREFILL_MS.totals(eng._mtags)[1]



class _FakeReporter:
    """Captures span records in-process (engine-level tests don't need a
    cluster; the flush path is covered by the e2e test + test_tracing)."""

    def __init__(self):
        self.records = []

    def add(self, record):
        self.records.append(record)


@pytest.fixture()
def span_capture(monkeypatch):
    monkeypatch.setenv("RAY_TPU_TRACING", "1")
    rep = _FakeReporter()
    monkeypatch.setattr(tracing, "_reporter", rep)
    yield rep


def _trace(request_id="req-1", trace_id="t" * 16, parent="p" * 16,
           deployment="llm", tenant=""):
    return {"request_id": request_id, "trace_id": trace_id,
            "parent_span_id": parent, "deployment": deployment,
            "tenant": tenant}


TINY = dict(num_slots=2, max_len=64)


def test_ttft_components_sum_to_measured_ttft(span_capture):
    """Acceptance: queue + arena_wait + prefill match the measured TTFT
    within 10% (the decomposition must not invent or lose time)."""
    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32)
    eng = ContinuousBatcher(cfg, **TINY)
    rid = eng.submit([1, 2, 3, 4], max_new_tokens=6, trace=_trace())
    out = eng.run_to_completion()
    assert len(out[rid]) == 6
    (bd,) = [b for b in eng.request_breakdowns if b["rid"] == rid]
    assert bd["outcome"] == "finished" and bd["tokens"] == 6
    comp_sum = bd["queue_s"] + bd["arena_wait_s"] + bd["prefill_s"]
    assert comp_sum == pytest.approx(bd["ttft_s"],
                                     rel=0.10, abs=5e-3), bd
    assert bd["tpot_s"] is not None and bd["tpot_s"] >= 0


def test_engine_spans_share_trace_id(span_capture):
    """One submit yields queue + prefill + >=1 decode-window span, all on
    the caller's trace id."""
    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32)
    eng = ContinuousBatcher(cfg, **TINY)
    t = _trace(request_id="req-s1", trace_id="1" * 16)
    rid = eng.submit([1, 2, 3], max_new_tokens=8, trace=t)
    out = eng.run_to_completion()
    assert len(out[rid]) == 8
    spans = span_capture.records
    assert spans and all(s["trace_id"] == t["trace_id"] for s in spans)
    assert all(s["parent_span_id"] == t["parent_span_id"] for s in spans)
    assert all(s.get("request_id") == t["request_id"] for s in spans)
    names = [s["name"] for s in spans]
    assert "engine.queue" in names
    assert "engine.prefill" in names
    windows = [s for s in spans if s["name"] == "engine.decode_window"]
    assert windows, names
    # Every generated token after the first is attributed to exactly
    # one decode window.
    assert sum(s["tokens"] for s in windows) == 8 - 1
    assert names[-1] == "engine.finished"


def test_eviction_path_emits_trace_and_outcome(span_capture):
    """A cancelled (client-disconnect) request still closes its trace:
    mid-decode eviction keeps the queue/prefill spans and emits
    engine.evicted; a never-admitted eviction emits the queue span with
    the outcome attached."""
    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32)
    eng = ContinuousBatcher(cfg, **TINY)
    t1 = _trace(request_id="req-evict", trace_id="e" * 16)
    rid = eng.submit([1, 2, 3], max_new_tokens=30, trace=t1)
    eng.step()  # admits + first decode tick
    assert eng.cancel(rid)
    spans = [s for s in span_capture.records
             if s.get("request_id") == "req-evict"]
    names = {s["name"] for s in spans}
    assert {"engine.queue", "engine.prefill", "engine.evicted"} <= names
    (bd,) = [b for b in eng.request_breakdowns if b["rid"] == rid]
    assert bd["outcome"] == "evicted"

    # Never admitted: cancel straight out of the waiting queue.
    t2 = _trace(request_id="req-waiting", trace_id="f" * 16)
    eng2 = ContinuousBatcher(cfg, **TINY)
    rid2 = eng2.submit([1, 2], max_new_tokens=4, trace=t2)
    assert eng2.cancel(rid2)
    spans2 = [s for s in span_capture.records
              if s.get("request_id") == "req-waiting"]
    assert [s["name"] for s in spans2
            if s["name"] == "engine.queue"], spans2
    assert any(s.get("outcome") == "evicted" for s in spans2)


def test_arena_wait_is_attributed_separately(span_capture):
    """A request blocked on paged-KV arena space (free slot, no blocks)
    books the stall as arena_wait, not queue — the signal KV-pressure
    routing needs."""
    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32)
    # Arena sized so ONE request's reservation fits but two don't.
    eng = ContinuousBatcher(cfg, num_slots=2, max_len=64, block_size=16,
                            num_blocks=3)
    r1 = eng.submit([1, 2, 3], max_new_tokens=20, trace=_trace(
        request_id="req-a", trace_id="a" * 16))
    r2 = eng.submit([4, 5, 6], max_new_tokens=20, trace=_trace(
        request_id="req-b", trace_id="b" * 16))
    out = eng.run_to_completion()
    assert len(out[r1]) == 20 and len(out[r2]) == 20
    bd2 = [b for b in eng.request_breakdowns if b["rid"] == r2][0]
    assert bd2["arena_wait_s"] > 0, bd2
    spans = [s for s in span_capture.records
             if s.get("request_id") == "req-b"]
    assert any(s["name"] == "engine.arena_wait" for s in spans)
    comp = bd2["queue_s"] + bd2["arena_wait_s"] + bd2["prefill_s"]
    assert comp == pytest.approx(bd2["ttft_s"], rel=0.10, abs=5e-3)


def test_tracing_disabled_records_no_windows_but_keeps_metrics():
    """With RAY_TPU_TRACING unset the engine still feeds the TTFT/TPOT
    histograms (breakdowns exist) but records no per-window state and
    emits no spans."""
    rep = _FakeReporter()
    old = tracing._reporter
    tracing._reporter = rep
    try:
        cfg = llama.LlamaConfig.tiny(dtype=jnp.float32)
        eng = ContinuousBatcher(cfg, **TINY)
        rid = eng.submit([1, 2, 3], max_new_tokens=5, trace=_trace())
        eng.run_to_completion()
        assert not rep.records
        assert eng._traced_live == 0
        (bd,) = [b for b in eng.request_breakdowns if b["rid"] == rid]
        assert bd["ttft_s"] is not None and bd["outcome"] == "finished"
    finally:
        tracing._reporter = old


def _hist_totals(*hists):
    """``{histogram name: (sum, count)}``, label sets summed."""
    out = {}
    for h in hists:
        got = {"sum": 0.0, "count": 0.0}
        for name, _, v in h.samples():
            got[name.rsplit("_", 1)[1]] += v
        out[h.name] = (got["sum"], got["count"])
    return out


def test_engine_phases_cover_the_steps_wall_time():
    """Every stretch of ``step()`` is timed: over a run with admissions,
    re-uploads, ticks and finishes each phase histogram gains
    observations, one tick a step and one prefill a batch. A tick's
    clock runs from the previous row's landing to its own, so the ticks
    overlap the host phases (that is the pipeline) but not each other
    nor a prefill: the programs' sum stays inside the wall time of the
    steps, the host phases' sum with the prefills' does too, and between
    them no part of the loop is left untimed."""
    from ray_tpu._private import metrics_defs as mdefs

    host = (mdefs.CB_STEP_ADMIT_MS, mdefs.CB_STEP_UPLOAD_MS,
            mdefs.CB_STEP_ACCOUNT_MS, mdefs.CB_STEP_APPLY_MS)
    programs = (mdefs.CB_PREFILL_MS, mdefs.CB_TICK_MS)
    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32)
    eng = ContinuousBatcher(cfg, num_slots=4, max_len=64, block_size=16)
    for i in range(4):                       # compile outside the clock
        eng.submit([1, 2, 3, i + 1], max_new_tokens=4)
    eng.run_to_completion()
    before = _hist_totals(*host, *programs)
    batches_before = _prefill_batches(eng)
    for i in range(9):                       # 9 requests over 4 slots
        eng.submit([1, 2, 3, i + 1], max_new_tokens=6 + i % 3)
    steps, t0 = 0, time.perf_counter()
    while eng.has_work():
        eng.step()
        steps += 1
    wall_ms = (time.perf_counter() - t0) * 1e3
    after = _hist_totals(*host, *programs)
    gained = {k: (after[k][0] - before[k][0], after[k][1] - before[k][1])
              for k in after}
    assert all(n > 0 for _, n in gained.values()), gained
    assert gained[mdefs.CB_TICK_MS.name][1] == steps
    assert (gained[mdefs.CB_PREFILL_MS.name][1]
            == _prefill_batches(eng) - batches_before)
    host_ms = sum(gained[h.name][0] for h in host)
    prefill_ms, tick_ms = (gained[h.name][0] for h in programs)
    assert prefill_ms + tick_ms <= wall_ms, (gained, wall_ms)
    assert host_ms + prefill_ms <= wall_ms, (gained, wall_ms)
    assert host_ms + prefill_ms + tick_ms >= 0.9 * wall_ms, (gained, wall_ms)


def test_generate_under_a_held_lock_books_the_wait(ray_start_regular,
                                                   span_capture):
    """The head of the TTFT chain: a caller that finds the engine lock
    taken (the tick thread holds it across each step) waits before
    ``submit`` stamps anything. ``generate()`` books that wait in
    SERVE_REQ_LOCK_WAIT and the request's breakdown, and, traced, as
    ``serve.hop`` (router dispatch to entry) and ``engine.submit_wait``
    spans that meet ``engine.queue`` without a gap; the tick thread's
    own wait for the lock has its histogram too."""
    import threading

    from ray_tpu._private import metrics_defs as mdefs
    from ray_tpu.llm import ContinuousLlamaDeployment
    from ray_tpu.serve import context as serve_context

    dep = ContinuousLlamaDeployment._cls_or_fn(
        config=llama.LlamaConfig.tiny(dtype=jnp.float32), num_slots=2,
        max_len=64)
    list(dep.generate([1, 2, 3], 3))                 # compile
    before = _hist_totals(mdefs.SERVE_REQ_LOCK_WAIT,
                          mdefs.CB_STEP_LOCK_WAIT_MS)
    held = 0.25
    route_ts = time.time() - 0.05                    # "dispatched" 50 ms ago
    rctx = _trace(request_id="req-lock", trace_id="c" * 16)
    token = serve_context._set_request_context({**rctx,
                                                "route_ts": route_ts})
    dep._lock.acquire()
    threading.Timer(held, dep._lock.release).start()
    try:
        t0 = time.time()
        out = list(dep.generate([1, 2, 3], 3))
    finally:
        serve_context._reset_request_context(token)
    assert len(out) == 3
    after = _hist_totals(mdefs.SERVE_REQ_LOCK_WAIT,
                         mdefs.CB_STEP_LOCK_WAIT_MS)
    name = mdefs.SERVE_REQ_LOCK_WAIT.name
    assert after[name][1] - before[name][1] == 1
    waited = after[name][0] - before[name][0]
    assert held * 0.8 <= waited <= held + 0.5, waited
    tick = mdefs.CB_STEP_LOCK_WAIT_MS.name
    assert after[tick][1] > before[tick][1]
    (bd,) = [b for b in dep.batcher.request_breakdowns
             if b["request_id"] == "req-lock"]
    assert bd["lock_wait_s"] == pytest.approx(waited)
    spans = {s["name"]: s for s in span_capture.records
             if s.get("request_id") == "req-lock"}
    assert {"serve.hop", "engine.submit_wait", "engine.queue",
            "engine.prefill"} <= set(spans), sorted(spans)
    hop, wait, queue = (spans["serve.hop"], spans["engine.submit_wait"],
                        spans["engine.queue"])
    assert all(s["trace_id"] == rctx["trace_id"]
               and s["parent_span_id"] == rctx["parent_span_id"]
               for s in (hop, wait, queue))
    assert hop["ts"] == route_ts and hop["kind"] == "route"
    assert hop["ts"] + hop["dur"] == pytest.approx(wait["ts"], abs=1e-6)
    assert wait["ts"] == pytest.approx(t0, abs=0.05)
    assert wait["dur"] == pytest.approx(waited)
    # submit stamps its clock right after the lock is got: no gap.
    assert wait["ts"] + wait["dur"] == pytest.approx(queue["ts"], abs=0.02)


def test_pressure_snapshot_and_replica_probe():
    """Engine pressure snapshot carries the router's inputs, and the
    serve Replica wrapper merges a hosted deployment's pressure() into
    its probe reply."""
    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32)
    eng = ContinuousBatcher(cfg, num_slots=1, max_len=64, block_size=16)
    eng.submit([1, 2, 3], max_new_tokens=4)
    eng.submit([1, 2, 3], max_new_tokens=4)  # second waits: 1 slot
    eng.step()
    snap = eng.pressure_snapshot()
    assert snap["queue_depth"] == 1
    assert snap["active_slots"] == 1
    assert snap["inflight_prefill_tokens"] == 3
    assert snap["kv_blocks_total"] > 0
    assert 0 <= snap["kv_blocks_free"] < snap["kv_blocks_total"]

    from ray_tpu.serve.api import Replica

    class Engineish:
        def pressure(self):
            return {"queue_depth": 7, "kv_blocks_free": 9}

        def __call__(self):
            return None

    rep = Replica(Engineish, (), {}, is_function=False, sync_workers=1)
    probe = rep.pressure()
    assert probe["queue_depth"] == 7 and probe["kv_blocks_free"] == 9
    assert probe["ongoing"] == 0 and "total" in probe


def test_controller_pressure_covers_every_replica(ray_start_regular):
    """controller.get_replica_pressure returns a live snapshot for EVERY
    replica of a deployment (the /api/v1/serve/pressure payload)."""
    from ray_tpu import serve

    @serve.deployment(num_replicas=2)
    class Probed:
        def __init__(self):
            self.n = 0

        def pressure(self):
            return {"queue_depth": 0, "kv_blocks_free": 5,
                    "inflight_prefill_tokens": 0}

        def __call__(self, payload):
            return {"ok": True}

    try:
        handle = serve.run(Probed.bind(), name="Probed")
        assert handle.remote({}).result(timeout_s=60) == {"ok": True}
        controller = ray_tpu.get_actor("__serve_controller__")
        deadline = time.monotonic() + 30
        rows = []
        while time.monotonic() < deadline:
            rows = ray_tpu.get(
                controller.get_replica_pressure.remote("Probed"),
                timeout=10)
            if len(rows) == 2 and all(
                    not r.get("unreachable") for r in rows):
                break
            time.sleep(0.3)
        assert len(rows) == 2, rows
        for r in rows:
            assert r["kv_blocks_free"] == 5
            assert r["queue_depth"] == 0
            assert "ongoing" in r
    finally:
        serve.shutdown()


def test_event_buffer_drops_are_counted():
    """Satellite: BufferedPublisher sheds past its cap COUNTED — the
    ray_tpu_events_dropped_total counter moves and the first drop logs
    once per process."""
    from ray_tpu._private import metrics_defs as mdefs
    from ray_tpu._private.events import BufferedPublisher, dropped_counts

    def count():
        return sum(v for _, key, v in mdefs.EVENTS_DROPPED.samples()
                   if dict(key).get("buffer") == "publisher:TEST_DROPS")

    before = count()
    pub = BufferedPublisher("TEST_DROPS", lambda: None, period_s=3600,
                            cap=10)
    for i in range(12):
        pub.add({"i": i})
    assert count() == before + 5  # cap//2 shed on overflow
    assert dropped_counts().get("publisher:TEST_DROPS", 0) >= 5


def test_streamed_request_chain_has_no_gap_and_sums_to_client_ttft(
        ray_start_regular, span_capture):
    """One streamed HTTP request, traced: ``serve.ingress > serve.route >
    serve.hop > engine.submit_wait > engine.queue > engine.prefill`` is
    one trace, each link starts where the one before it ended, and what
    the links add up to is the client's time to first token less the
    first token's way back out (replica -> proxy -> socket), which no
    span covers yet."""
    from ray_tpu import serve
    from ray_tpu.llm import ContinuousLlamaDeployment

    serve.run(ContinuousLlamaDeployment.options(name="ChainLlama").bind(
        num_slots=2, max_len=64), name="chain")
    port = serve.start_http(port=0)
    try:
        _check_streamed_chain(port, span_capture)
    finally:
        serve.stop_http()
        serve.shutdown()


def _check_streamed_chain(port, span_capture):
    import http.client

    def stream(req_id):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        body = json.dumps({"prompt_token_ids": [1, 2, 3], "max_tokens": 4})
        sent = time.time()
        conn.request("POST", "/ChainLlama/stream/generate",
                     body=body, headers={"Content-Type": "application/json",
                                         "x-request-id": req_id})
        resp = conn.getresponse()
        assert resp.status == 200, resp.read()
        first_line = resp.readline()        # http.client undoes the chunks
        first = time.time()
        rest = resp.read()
        conn.close()
        tokens = [json.loads(x) for x in (first_line + rest).splitlines()
                  if x.strip()]
        assert len(tokens) == 4, tokens
        return sent, first

    stream("req-chain-warmup")                   # compiles
    req_id = "req-chain-0123456789abcdef"
    sent, first = stream(req_id)
    deadline = time.monotonic() + 10    # the ingress span closes last
    while time.monotonic() < deadline:
        trace = [e for e in span_capture.records
                 if e.get("request_id") == req_id]
        if any(e["name"] == "serve.ingress" for e in trace):
            break
        time.sleep(0.05)
    assert len({e["trace_id"] for e in trace}) == 1
    by_name = {e["name"]: e for e in trace}
    chain = ["serve.ingress", "serve.route", "serve.hop",
             "engine.submit_wait", "engine.queue", "engine.prefill"]
    assert set(chain) <= set(by_name), sorted(by_name)
    ingress, route, hop, wait, queue, prefill = (by_name[n] for n in chain)
    assert route["parent_span_id"] == ingress["span_id"]
    for e in (hop, wait, queue, prefill):
        assert e["parent_span_id"] == route["span_id"], e["name"]

    def end(e):
        return e["ts"] + e["dur"]

    # Each link starts where the last ended (within scheduling noise).
    assert sent <= ingress["ts"] <= route["ts"] + 1e-3
    assert hop["ts"] == pytest.approx(route["ts"], abs=0.01)
    assert end(hop) == pytest.approx(wait["ts"], abs=1e-4)
    assert end(wait) == pytest.approx(queue["ts"], abs=0.01)
    arena = by_name.get("engine.arena_wait")
    assert end(queue) + (arena["dur"] if arena else 0.0) == pytest.approx(
        prefill["ts"], abs=0.01)
    parts = ((route["ts"] - ingress["ts"]) + hop["dur"] + wait["dur"]
             + queue["dur"] + (arena["dur"] if arena else 0.0)
             + prefill["dur"])
    assert parts == pytest.approx(end(prefill) - ingress["ts"], abs=0.03)
    client_ttft = first - sent
    way_back = first - end(prefill)
    assert 0 <= way_back < 1.0, way_back
    assert parts + (ingress["ts"] - sent) + way_back == pytest.approx(
        client_ttft, abs=0.03)
    assert parts <= client_ttft


@pytest.fixture()
def traced_cluster(monkeypatch):
    monkeypatch.setenv("RAY_TPU_TRACING", "1")
    from ray_tpu.cluster_utils import Cluster

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    c = Cluster(head_node_args={"num_cpus": 4})
    ray_tpu.init(address=c.address)
    yield c
    from ray_tpu import serve

    serve.stop_http()
    serve.shutdown()
    ray_tpu.shutdown()
    c.shutdown()


def _request_spans(request_id, timeout_s=30.0):
    """Poll the cluster span sink until the request's trace is complete
    enough (ingress + engine spans flushed from two processes)."""
    from ray_tpu.util import state

    want = {"serve.ingress", "serve.route", "engine.queue",
            "engine.prefill", "engine.decode_window"}
    deadline = time.monotonic() + timeout_s
    trace = []
    while time.monotonic() < deadline:
        spans = [e for e in state.list_tasks(limit=100000,
                                             include_spans=True)
                 if e.get("state") == "SPAN"]
        tids = {e["trace_id"] for e in spans
                if e.get("request_id") == request_id}
        if tids:
            trace = [e for e in spans if e["trace_id"] in tids]
            if want <= {e["name"] for e in trace}:
                return trace
        time.sleep(0.4)
    return trace


def test_http_chat_request_yields_one_connected_trace(traced_cluster,
                                                      tmp_path):
    """Acceptance: a single chat request against a
    ContinuousLlamaDeployment produces ONE trace (shared trace id) with
    ingress, route, engine queue, prefill, and >=1 decode-window spans,
    and the pressure endpoint reports the replica live."""
    import http.client

    from ray_tpu import serve
    from ray_tpu.llm import build_continuous_llama_app

    app = build_continuous_llama_app(num_slots=2, max_len=64)
    serve.run(app, name="llm")
    port = serve.start_http(port=0)

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    req_id = "req-e2e-0123456789abcdef"
    body = json.dumps({"prompt_token_ids": [1, 2, 3], "max_tokens": 4})
    conn.request("POST", "/ContinuousLlamaDeployment", body=body,
                 headers={"Content-Type": "application/json",
                          "x-request-id": req_id})
    resp = conn.getresponse()
    payload = json.loads(resp.read())
    assert resp.status == 200, payload
    assert len(payload["token_ids"]) == 4
    conn.close()

    trace = _request_spans(req_id)
    assert trace, "no spans reached the cluster sink"
    trace_ids = {e["trace_id"] for e in trace}
    assert len(trace_ids) == 1, trace_ids  # ONE connected trace
    names = {e["name"] for e in trace}
    assert {"serve.ingress", "serve.route", "engine.queue",
            "engine.prefill", "engine.decode_window"} <= names, names
    # The ingress is the root; engine spans parent to the route span.
    by_id = {e["span_id"]: e for e in trace}
    ingress = next(e for e in trace if e["name"] == "serve.ingress")
    assert ingress["parent_span_id"] == ""
    route = next(e for e in trace if e["name"] == "serve.route")
    assert route["parent_span_id"] == ingress["span_id"]
    for e in trace:
        if e["name"].startswith("engine."):
            assert by_id[e["parent_span_id"]]["name"] == "serve.route"

    # `ray-tpu trace request <id>` reconstructs the same trace as a
    # chrome-trace file.
    from ray_tpu.scripts import cli as cli_mod

    trace_out = tmp_path / "trace.json"
    cli_mod.main(["trace", "request", req_id,
                  "--address", traced_cluster.address,
                  "-o", str(trace_out)])
    chrome = json.loads(trace_out.read_text())
    chrome_names = {ev["name"] for ev in chrome
                    if str(ev.get("cat", "")).startswith("span:")}
    assert {"serve.ingress", "engine.prefill"} <= chrome_names

    # Pressure: the controller publishes per-replica snapshots into the
    # GCS KV; the dashboard endpoint serves them.
    from ray_tpu.dashboard import Dashboard

    dash = Dashboard(traced_cluster.address, port=0)
    try:
        deadline = time.monotonic() + 30
        reps = []
        while time.monotonic() < deadline:
            conn = http.client.HTTPConnection("127.0.0.1", dash.port,
                                              timeout=10)
            conn.request("GET", "/api/v1/serve/pressure")
            snap = json.loads(conn.getresponse().read())
            conn.close()
            reps = snap.get("deployments", {}).get(
                "ContinuousLlamaDeployment", [])
            if reps and all(not r.get("unreachable") for r in reps):
                break
            time.sleep(0.4)
        assert reps, "pressure endpoint never reported the replica"
        for r in reps:
            assert "queue_depth" in r and "kv_blocks_free" in r, r
    finally:
        dash.stop()
