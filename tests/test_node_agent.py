"""Per-node agent tests (reference C21: raylet/agent_manager.h — spawn,
supervise/respawn, runtime-env agent role, dashboard-agent stats role)."""

import json
import os
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu._private import rpc
from ray_tpu._private.agent import AGENT_KV_NS, NodeAgent, read_proc_stats
from ray_tpu.cluster_utils import Cluster
from ray_tpu.protobuf import ray_tpu_pb2 as pb


def _get(url, timeout=5):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


@pytest.fixture
def agent_cluster(monkeypatch):
    monkeypatch.setenv("RAY_TPU_DISABLE_AGENT", "0")
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    c = Cluster(head_node_args={"num_cpus": 2})
    c.wait_for_nodes()
    yield c
    ray_tpu.shutdown()
    c.shutdown()


def _agent_addr(gcs_address, node_id, timeout=30):
    gcs = rpc.get_stub("GcsService", gcs_address)
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        reply = gcs.KvGet(pb.KvRequest(ns=AGENT_KV_NS, key=node_id))
        if reply.found:
            return reply.value.decode()
        time.sleep(0.2)
    raise TimeoutError("agent never registered in the GCS KV")


def test_agent_spawns_and_serves_stats(agent_cluster):
    c = agent_cluster
    node = c.head_node
    addr = _agent_addr(c.address, node.node_id)
    health = _get(f"http://{addr}/healthz")
    assert health["ok"] and health["node_id"] == node.node_id
    stats = _get(f"http://{addr}/stats")
    assert stats["mem_total_bytes"] > 0
    assert stats["mem_available_bytes"] > 0
    assert "loadavg_1m" in stats


def test_agent_prewarms_runtime_env(agent_cluster, tmp_path):
    """A lease carrying a packaged working_dir makes the agent download it
    into the node cache before/while the worker starts."""
    c = agent_cluster
    ray_tpu.init(address=c.address)
    pkg = tmp_path / "proj"
    pkg.mkdir()
    (pkg / "marker.txt").write_text("hello")

    @ray_tpu.remote
    def read_marker():
        with open("marker.txt") as f:
            return f.read()

    out = ray_tpu.get(read_marker.options(
        runtime_env={"working_dir": str(pkg)}).remote(), timeout=60)
    assert out == "hello"
    # The agent observed the env (status map non-empty) — pre-warm ran.
    addr = _agent_addr(c.address, c.head_node.node_id)
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        status = _get(f"http://{addr}/runtime_env/status")
        if status:
            assert all(v in ("building", "ready") or v.startswith("failed")
                       for v in status.values())
            if any(v == "ready" for v in status.values()):
                return
        time.sleep(0.2)
    raise AssertionError(f"agent never pre-warmed: {status}")


def test_agent_respawns_after_death(agent_cluster):
    c = agent_cluster
    node = c.head_node
    _agent_addr(c.address, node.node_id)
    first = node._agent_proc
    assert first is not None
    first_pid = first.pid
    first.kill()
    first.wait(timeout=10)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        proc = node._agent_proc
        if proc is not None and proc.pid != first_pid \
                and proc.poll() is None and node._agent_port:
            health = _get(
                f"http://127.0.0.1:{node._agent_port}/healthz")
            assert health["ok"]
            return
        time.sleep(0.3)
    raise AssertionError("agent was not respawned")


def test_read_proc_stats_standalone():
    stats = read_proc_stats("/tmp")
    assert stats["mem_total_bytes"] > 0
    assert stats["disk_free_bytes"] > 0


def test_embedded_agent_prewarm_pip_failure_reported():
    """A pip env that cannot build reports failed status, not a hang."""
    agent = NodeAgent("127.0.0.1:1", "test-node")  # GCS reg best-effort
    try:
        key = agent.start_prewarm(
            {"pip": ["definitely-not-a-package-xyz==9.9.9"]})
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            with agent._lock:
                status = agent._prewarm[key]
            if status != "building":
                break
            time.sleep(0.5)
        assert status.startswith("failed"), status
    finally:
        agent.stop()


# --------------------------------------------- TPU auto-detection (main())

def test_node_main_auto_detects_tpu_resources(monkeypatch):
    """The node-manager subprocess entry contributes auto-detected TPU
    chips, the slice-head resource, and ICI topology labels (reference:
    TPUAcceleratorManager + TPU-<pod>-head, tpu.py:330)."""
    import subprocess
    import sys

    import ray_tpu
    from ray_tpu._private import rpc
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.protobuf import ray_tpu_pb2 as pb

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    c = Cluster(initialize_head=False)
    env = dict(os.environ,
               RAY_TPU_NUM_CHIPS="8",
               TPU_ACCELERATOR_TYPE="v5litepod-16",
               TPU_WORKER_ID="0",
               TPU_NAME="myslice",
               RAY_TPU_DISABLE_AGENT="1")
    eff = env["TPU_ACCELERATOR_TYPE"]
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
        + list(filter(None, [env.get("PYTHONPATH", "")])))
    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.node_manager.server",
         "--gcs-address", c.address, "--num-cpus", "2"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, env=env,
        text=True)
    try:
        deadline = time.time() + 30
        node_id = None
        while time.time() < deadline and node_id is None:
            line = proc.stdout.readline().strip()
            if line.startswith("NODE_ID="):
                node_id = line.split("=", 1)[1]
        assert node_id
        gcs = rpc.get_stub("GcsService", c.address)
        info = next(n for n in gcs.GetNodes(pb.GetNodesRequest()).nodes
                    if n.node_id == node_id)
        assert info.resources["TPU"] == 8.0
        assert info.resources[f"accelerator_type:{eff}"] == 1.0
        assert info.resources[f"TPU-{eff}-head"] == 1.0
        assert info.resources["TPU-slice:myslice"] == 8.0
        assert info.labels["tpu-pod-type"] == eff
        assert info.labels["tpu-slice"] == "myslice"
    finally:
        proc.terminate()
        c.shutdown()
