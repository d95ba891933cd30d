"""Distributed tracing tests (reference: test_tracing.py over
tracing_helper.py — spans propagate through the TaskSpec so a nested
task graph forms one cross-process trace)."""

import time

import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster
from ray_tpu.util import state, tracing


@pytest.fixture()
def traced_cluster(monkeypatch):
    monkeypatch.setenv("RAY_TPU_TRACING", "1")
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    c = Cluster(head_node_args={"num_cpus": 4})
    ray_tpu.init(address=c.address)
    yield c
    ray_tpu.shutdown()
    c.shutdown()


def _span_events(timeout_s=15.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        events = [e for e in state.list_tasks(limit=100000,
                                              include_spans=True)
                  if e["state"] == "SPAN"]
        if events:
            return events
        time.sleep(0.3)
    return []


def test_nested_task_graph_forms_one_cross_process_trace(traced_cluster):
    @ray_tpu.remote
    def child(x):
        return x * 2

    @ray_tpu.remote
    def parent(x):
        # Submitted INSIDE the parent's execute span: the child's trace
        # context chains through this worker's thread-local.
        return ray_tpu.get(child.remote(x), timeout=60) + 1

    assert ray_tpu.get(parent.remote(10), timeout=120) == 21
    time.sleep(1.0)  # span reporters flush every 0.2s

    events = _span_events()
    by_name = {}
    for e in events:
        # Task names are qualnames (module.<locals>.fn): key by leaf name.
        key = e["name"].rsplit(".", 1)[-1].rsplit(":", 1)[-1]
        kind = "submit:" if e["name"].startswith("submit:") else ""
        by_name.setdefault(kind + key, []).append(e)
    assert "parent" in by_name and "child" in by_name, sorted(by_name)
    p = by_name["parent"][0]
    ch = by_name["child"][0]

    # One trace spans the whole graph.
    assert ch["trace_id"] == p["trace_id"]
    # The child executes in a DIFFERENT process than the parent.
    assert ch["worker_id"] != p["worker_id"]
    # Parent-child linkage: child's parent is the submit span created
    # inside the parent's execute span, whose parent is the parent span.
    submits = {e["span_id"]: e for e in by_name.get("submit:child", [])}
    assert submits, sorted(by_name)
    assert ch["parent_span_id"] in submits
    assert submits[ch["parent_span_id"]]["parent_span_id"] == p["span_id"]
    # And the parent chains up to the driver's submit span — a third
    # process (the driver), distinct from both workers.
    drv = {e["span_id"]: e for e in by_name.get("submit:parent", [])}
    assert p["parent_span_id"] in drv
    assert drv[p["parent_span_id"]]["worker_id"] != p["worker_id"]


def test_timeline_merges_spans_with_flow_arrows(traced_cluster):
    @ray_tpu.remote
    def leaf():
        return 1

    @ray_tpu.remote
    def root():
        return ray_tpu.get(leaf.remote(), timeout=60)

    assert ray_tpu.get(root.remote(), timeout=120) == 1
    time.sleep(1.0)
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        events = state.task_timeline()
        span_events = [e for e in events
                       if str(e.get("cat", "")).startswith("span:")]
        flows = [e for e in events if e.get("cat") == "flow"]
        if any(e["name"] == "leaf" for e in span_events) and flows:
            break
        time.sleep(0.3)
    names = {e["name"].rsplit(".", 1)[-1] for e in span_events}
    assert {"root", "leaf"} <= names, names
    # Flow arrows come in start/finish pairs linking parent to child.
    starts = {e["id"] for e in flows if e["ph"] == "s"}
    finishes = {e["id"] for e in flows if e["ph"] == "f"}
    assert starts & finishes


def test_tracing_disabled_adds_no_spans():
    # No Cluster needed: the disabled path never records, in-process or
    # cross-process, so a local init exercises the same gate.
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    try:
        ray_tpu.init(num_cpus=2)

        @ray_tpu.remote
        def f():
            return 1

        assert ray_tpu.get(f.remote(), timeout=60) == 1
        time.sleep(0.5)
        assert not [e for e in state.list_tasks(limit=10000,
                                                include_spans=True)
                    if e["state"] == "SPAN"]
        assert tracing.current() is None
    finally:
        ray_tpu.shutdown()


def _hist(name):
    from ray_tpu.util.metrics import Histogram

    return Histogram(name, "test", boundaries=(1.0, 10.0),
                     tag_keys=("engine",))


def _sum_count(hist):
    got = {name.rsplit("_", 1)[1]: v for name, _, v in hist.samples()}
    return got.get("sum", 0.0), got.get("count", 0.0)


def test_phase_observes_its_histogram_and_takes_an_inner_phase_out():
    """The counter half of ``tracing.phase``: always on (no
    RAY_TPU_TRACING, no profiler session), one observation a block, and
    an inner phase opened with ``outer=`` is taken out of the outer
    one's observation, so the two add up to the outer interval."""
    assert not tracing.enabled()
    outer_h, inner_h = _hist("t_phase_outer_ms"), _hist("t_phase_inner_ms")
    tags = {"engine": "e0"}
    with tracing.phase("t.outer", outer_h, tags) as outer:
        time.sleep(0.02)
        with tracing.phase("t.inner", inner_h, tags, outer=outer) as inner:
            time.sleep(0.03)
    assert inner.ms >= 30 and outer.ms >= inner.ms + 20
    (o_sum, o_n), (i_sum, i_n) = _sum_count(outer_h), _sum_count(inner_h)
    assert o_n == 1 and i_n == 1
    assert i_sum == pytest.approx(inner.ms)
    assert o_sum == pytest.approx(outer.ms - inner.ms)
    assert o_sum + i_sum == pytest.approx(outer.ms)
    # An exception still closes the interval and books it.
    with pytest.raises(KeyError):
        with tracing.phase("t.outer", outer_h, tags):
            raise KeyError("x")
    assert _sum_count(outer_h)[1] == 2


def test_phase_excludes_time_another_measurement_books():
    """``exclude`` takes a stretch that passed inside the block out of
    its observation (the engine's prefill leaves out its wait for the
    decode tick queued ahead of it, which that tick's own clock
    books); ``ms`` and what an outer phase subtracts stay the whole
    interval."""
    outer_h, inner_h = _hist("t_excl_outer_ms"), _hist("t_excl_inner_ms")
    with tracing.phase("t.outer", outer_h) as outer:
        with tracing.phase("t.inner", inner_h, outer=outer) as inner:
            time.sleep(0.02)
            waited = inner.elapsed_ms()
            inner.exclude(waited)
            time.sleep(0.01)
    assert 20 <= waited <= inner.ms - 10
    assert _sum_count(inner_h) == (pytest.approx(inner.ms - waited), 1)
    assert _sum_count(outer_h)[0] == pytest.approx(outer.ms - inner.ms)


def test_phase_is_a_span_only_while_a_profiler_session_is_active(tmp_path):
    """The span half: a ``TraceAnnotation`` of the same interval, which
    reaches a trace only under a profiler session, at the host tracer
    level the benchmark's harness uses (1), on the profiler's clock."""
    import glob

    import jax
    from jax.profiler import ProfileData

    hist = _hist("t_phase_span_ms")

    def names_in_trace(where):
        paths = glob.glob(str(where / "plugins" / "profile" / "*" /
                              "*.xplane.pb"))
        return {ev.name
                for path in paths
                for plane in ProfileData.from_file(path).planes
                if plane.name.startswith("/host:")
                for line in plane.lines for ev in line.events}

    with tracing.phase("t.before_session", hist):
        pass
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with tracing.phase("t.in_session", hist):
            time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    with tracing.phase("t.after_session", hist):
        pass
    assert _sum_count(hist)[1] == 3          # the counter saw all three
    names = names_in_trace(tmp_path)
    assert "t.in_session" in names
    assert not {"t.before_session", "t.after_session"} & names
