"""XLA profiling plane (ISSUE 3): compile/retrace tracking, cost-analysis
registry + achieved gauges, device-memory vitals, on-demand profiler
capture — all exercised under ``JAX_PLATFORMS=cpu``.
"""

import json
import os
import time
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from ray_tpu._private import metrics_defs as mdefs
from ray_tpu._private import xla_monitor as xm
from ray_tpu.protobuf import ray_tpu_pb2 as pb

_uniq = iter(range(10_000))


def _name(prefix: str) -> str:
    # Program records are process-global: every test gets fresh names.
    return f"{prefix}_{next(_uniq)}"


def _counter_value(counter, program: str) -> float:
    for name, key, value in counter.samples():
        if dict(key).get("program") == program:
            return value
    return 0.0


# ------------------------------------------------ compile: errors, cache


def test_failed_compile_raises_once_with_its_own_message():
    """No second attempt through the plain jit: on the chip that would
    double a minutes-long failing compile and bury the first error."""
    traces = []

    @xm.instrument(name=_name("refused"))
    def f(x):
        traces.append(1)
        raise ValueError("the compiler's message")

    with pytest.raises(ValueError, match="the compiler's message"):
        f(jnp.ones((4,)))
    assert len(traces) == 1
    assert f._degraded is False


def test_aot_compile_goes_through_the_persistent_cache():
    """The dispatcher compiles with ``lower().compile()``; a second
    wrapper around identical code must find the first one's entry in the
    persistent cache (tests/conftest.py places it and lowers the
    size/time thresholds to zero)."""
    from ray_tpu.util import compile_cache

    def make():
        def persistent_probe(x):
            return jnp.tanh(x) @ x.T + 7.0
        return persistent_probe

    x = jnp.ones((33, 17))
    xm.instrument(make(), name=_name("cache_a"))(x)    # writes (or hits)
    before = compile_cache.counts()
    xm.instrument(make(), name=_name("cache_b"))(x)
    after = compile_cache.counts()
    assert after["hits"] == before["hits"] + 1, (before, after)
    assert after["misses"] == before["misses"]


def test_a_lowered_kernel_call_names_no_source_line(pallas_interpret):
    """What the persistent cache keys a program by holds no source
    position (``compile_cache.ensure``): the decode tick's lowered text,
    kernels interpreted, debug info ON, names no line of the file it was
    traced in, so an edit that moves lines there re-keys no program."""
    from ray_tpu.models import llama
    from ray_tpu.models.continuous_batching import ContinuousBatcher
    from ray_tpu.util import compile_cache

    compile_cache.ensure()
    assert jax.config.jax_traceback_in_locations_limit == 0
    eng = ContinuousBatcher(llama.LlamaConfig.tiny(dtype=jnp.float32),
                            num_slots=2, max_len=32, block_size=8,
                            use_decode_kernel=True)
    row = jnp.zeros(2, jnp.int32)
    text = eng._tick.lower(
        eng.params, row, row, jnp.zeros((2, eng.max_blocks), jnp.int32), row,
        eng.cache, jnp.int32(0)).as_text(debug_info=True)
    assert "paged_decode_attn" in text          # debug info is there
    assert "continuous_batching.py" not in text and ".py\":" not in text


# -------------------------------------------------- retrace detection


def test_retrace_fires_on_shape_churn():
    name = _name("churn")

    @xm.instrument(name=name)
    def f(x):
        return x * 2

    f(jnp.ones((8,)))
    assert _counter_value(mdefs.XLA_RETRACES, name) == 0
    f(jnp.ones((9,)))          # same treedef, new shape: silent retrace
    f(jnp.ones((10,)))
    stats = xm.program_stats(name)
    assert stats["compiles"] == 3
    assert stats["retraces"] == 2
    assert _counter_value(mdefs.XLA_RETRACES, name) == 2
    assert _counter_value(mdefs.XLA_COMPILES, name) == 3


def test_retrace_silent_on_bucketed_shapes():
    name = _name("bucketed")

    @xm.instrument(name=name, shape_policy="bucketed", allowed_dims=(48,))
    def f(x):
        return x.sum()

    for n in (16, 32, 64, 48):     # pow-2 growth + the declared cap
        f(jnp.ones((n,)))
    assert xm.program_stats(name)["retraces"] == 0
    f(jnp.ones((17,)))             # stray odd shape: a real retrace
    assert xm.program_stats(name)["retraces"] == 1
    # dtype churn is never "bucketed growth".
    f(jnp.ones((16,), jnp.float64)
      if False else jnp.ones((16,), jnp.int32))
    assert xm.program_stats(name)["retraces"] == 2


def test_repeat_calls_do_not_recompile():
    name = _name("stable")

    @xm.instrument(name=name)
    def f(x, i):
        return x + i

    for i in range(5):             # python-int arg: keyed by type
        f(jnp.ones((4,)), i)
    stats = xm.program_stats(name)
    assert stats["compiles"] == 1 and stats["retraces"] == 0


# --------------------------------------------- cost-analysis registry


def test_cost_registry_populated_after_jit_call():
    name = _name("cost")

    @xm.instrument(name=name)
    def f(x):
        return jnp.dot(x, x)

    f(jnp.ones((64, 64)))
    stats = xm.program_stats(name)
    assert stats is not None
    # The CPU backend provides cost analysis: FLOPs and bytes accessed
    # must be real, positive numbers — zero estimation.
    assert stats["flops"] > 0
    assert stats["bytes_accessed"] > 0
    assert stats["compile_seconds"] > 0


def test_note_execution_sets_achieved_gauges():
    name = _name("achieved")

    @xm.instrument(name=name)
    def f(x):
        return jnp.dot(x, x)

    w = f
    w(jnp.ones((32, 32)))
    out = w.note_execution(0.01)
    assert out and out["achieved_flops_per_s"] > 0
    assert out["achieved_bandwidth_bytes_per_s"] > 0
    samples = {dict(k).get("program"): v
               for _, k, v in mdefs.XLA_ACHIEVED_FLOPS.samples()}
    assert samples.get(name, 0) > 0


def test_note_execution_keeps_one_record_over_its_back_to_back_calls():
    """``calls`` back-to-back calls in one reading (a chunked prefill
    batch): the gauges price ONE call, the ring keeps the whole."""
    name = _name("chunked")
    w = xm.instrument(lambda x: jnp.dot(x, x), name=name)
    w(jnp.ones((32, 32)))
    one = w.note_execution(0.01)
    four = w.note_execution(0.04, calls=4, shape=(4, 128))
    assert four["achieved_flops_per_s"] == pytest.approx(
        one["achieved_flops_per_s"])
    first, second = xm._calls.records(last=2)
    assert (first.program, first.calls, first.shape) == (name, 1, None)
    assert (second.program, second.calls, second.shape,
            second.wall_s) == (name, 4, (4, 128), 0.04)
    assert second.seq == first.seq + 1 and not second.slow
    # No ``Dispatched`` came with it: stamped here, silent on the fetch.
    assert second.dispatch_pc is None and second.ready is None
    assert second.landed_ts == pytest.approx(time.time(), abs=5)


def test_train_loop_feeds_one_record_a_window():
    """``AsyncStepLoop.sync`` is one measured execution of
    ``sync_every`` steps: one record, its wall the window's."""
    from ray_tpu.models import llama
    from ray_tpu.models.training import ShardedTrainer, synthetic_batch
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.train.loop import AsyncStepLoop

    config = llama.LlamaConfig.tiny()
    trainer = ShardedTrainer(config, make_mesh(MeshConfig(fsdp=-1)))
    batch = trainer.shard_batch(synthetic_batch(8, 16, config.vocab_size))
    loop = AsyncStepLoop(trainer, trainer.init_state(), sync_every=3)
    seen = xm._calls.records(last=1)
    last_seq = seen[0].seq if seen else 0
    loop.run([batch] * 6)
    mine = [r for r in xm._calls.records(last=64)
            if r.seq > last_seq and r.program == "train_step"]
    assert [r.calls for r in mine] == [3, 3]
    assert sum(r.wall_s for r in mine) == pytest.approx(
        loop.stats()["window_wall_s"])


# --------------------------------- serve tick / train step integration


def test_engine_tick_and_prefill_feed_the_plane():
    from ray_tpu.models import llama
    from ray_tpu.models.continuous_batching import ContinuousBatcher

    eng = ContinuousBatcher(llama.LlamaConfig.tiny(), num_slots=4,
                            max_len=64)
    for rid in range(3):
        eng.submit([1, 2, 3], max_new_tokens=3)
    eng.run_to_completion()
    for prog in ("cb_tick", "cb_prefill"):
        stats = xm.program_stats(prog)
        assert stats and stats["flops"] > 0, prog
    # Measured tick/prefill wall time -> non-null achieved gauges.
    flops = {dict(k).get("program"): v
             for _, k, v in mdefs.XLA_ACHIEVED_FLOPS.samples()}
    bw = {dict(k).get("program"): v
          for _, k, v in mdefs.XLA_ACHIEVED_BW.samples()}
    assert flops.get("cb_tick", 0) > 0 and bw.get("cb_tick", 0) > 0
    assert flops.get("cb_prefill", 0) > 0
    # A same-bucket admission burst reuses ONE compiled prefill program
    # and pow-2 bucket growth never reads as a retrace.
    assert xm.program_stats("cb_prefill")["retraces"] == 0


def test_train_step_feeds_the_plane():
    import jax

    from ray_tpu.models import llama
    from ray_tpu.models.training import ShardedTrainer, synthetic_batch
    from ray_tpu.parallel import MeshConfig, make_mesh

    config = llama.LlamaConfig.tiny()
    # The program record is process-global and other suites (e.g.
    # test_train.py's e2e) may already have compiled a train_step in
    # this process — assert the DELTA, not the absolute count.
    before = (xm.program_stats("train_step") or {}).get("compiles", 0)
    trainer = ShardedTrainer(config, make_mesh(MeshConfig(fsdp=-1)))
    state = trainer.init_state()
    batch = trainer.shard_batch(synthetic_batch(8, 16, config.vocab_size))
    for _ in range(3):
        state, metrics = trainer.train_step(state, batch)
        jax.block_until_ready(metrics["loss"])  # sync: honest cadence
    stats = xm.program_stats("train_step")
    assert stats and stats["flops"] > 0 and stats["bytes_accessed"] > 0
    assert stats["compiles"] == before + 1  # one signature, no retraces
    flops = {dict(k).get("program"): v
             for _, k, v in mdefs.XLA_ACHIEVED_FLOPS.samples()}
    assert flops.get("train_step", 0) > 0


# ------------------------------------------------ device memory vitals


def test_device_memory_sampler_graceful_on_cpu():
    # jax is resident in this process, so the sampler runs; CPU devices
    # report no memory_stats() and the answer is the documented [].
    out = xm.sample_device_memory(node_id="testnode")
    assert out == [] or all("device" in e for e in out)


# ------------------------------------- capture plane + CLI + dashboard


@pytest.fixture
def gcs_server():
    from ray_tpu._private.gcs.server import GcsServer

    server = GcsServer(port=0)
    yield server
    server.shutdown()
    xm.stop_all()


def _wait_profile_subscriber(server, timeout_s: float = 10.0):
    """Pubsub has no replay: block until the capture listener's
    subscription is registered server-side before publishing."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if server._subscribers.get(xm.PROFILE_CHANNEL):
            return
        time.sleep(0.05)
    raise AssertionError("profile listener never subscribed")


def test_capture_rpc_roundtrip_and_listing(gcs_server, tmp_path, capsys,
                                           monkeypatch):
    monkeypatch.setenv("RAY_TPU_SESSION_DIR", str(tmp_path))
    address = f"127.0.0.1:{gcs_server.port}"
    xm.start_profile_listener(address, node_id="testnode123")
    _wait_profile_subscriber(gcs_server)
    # An XLA-active process: the capture wraps real device activity.
    jnp.dot(jnp.ones((32, 32)), jnp.ones((32, 32))).block_until_ready()

    capture_id = xm.request_capture(address, node="testnode",
                                    duration_s=0.3)
    # The first stop_trace in a process pays profiler init/flush (~15s
    # observed on this box); the deadline covers a loaded CI.
    deadline = time.monotonic() + 60
    entry = None
    while time.monotonic() < deadline:
        done = [e for e in xm.list_captures(address)
                if e.get("capture_id") == capture_id
                and e.get("status") in ("done", "failed")]
        if done:
            entry = done[0]
            break
        time.sleep(0.2)
    assert entry is not None, "capture never registered"
    assert entry["status"] == "done", entry
    assert entry["node_id"] == "testnode123"[:12]
    assert os.path.isdir(entry["trace_dir"])
    assert entry["files"] > 0          # jax.profiler wrote a real trace
    assert str(tmp_path) in entry["trace_dir"]

    # `ray-tpu profile list` shows it.
    from ray_tpu.scripts import cli

    cli.main(["profile", "list", "--address", address])
    out = capsys.readouterr().out
    assert capture_id in out and "done" in out

    # The cost-analysis program registry persisted via the GCS KV.
    # Flush is periodic best-effort; poke it directly so the test
    # doesn't sleep through a push interval.
    xm._flush_pending_kv()
    reply = gcs_server.KvKeys(
        pb.KvRequest(ns=xm.PROGRAM_KV_NS, prefix=""), None)
    assert reply.keys, "program registry never reached the GCS KV"

    # Dashboard routes over the same plane.
    from ray_tpu.dashboard import Dashboard

    dash = Dashboard(address, port=0)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{dash.port}/api/v1/profile/list",
                timeout=10) as r:
            entries = json.loads(r.read())
        assert any(e.get("capture_id") == capture_id for e in entries)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{dash.port}/api/v1/xla/programs",
                timeout=10) as r:
            programs = json.loads(r.read())
        assert programs and all("program" in e for e in programs)
        with urllib.request.urlopen(f"http://127.0.0.1:{dash.port}/",
                                    timeout=10) as r:
            html = r.read().decode()
        assert "/api/v1/profile/list" in html and "xlaPanel" in html
    finally:
        dash.stop()


def test_capture_cli_end_to_end(gcs_server, tmp_path, capsys,
                                monkeypatch):
    """`ray-tpu profile capture --duration ...` against a live listener
    prints the registered trace dir (the acceptance-criteria flow)."""
    monkeypatch.setenv("RAY_TPU_SESSION_DIR", str(tmp_path))
    address = f"127.0.0.1:{gcs_server.port}"
    xm.start_profile_listener(address, node_id="clinode")
    _wait_profile_subscriber(gcs_server)
    from ray_tpu.scripts import cli

    cli.main(["profile", "capture", "--address", address,
              "--duration", "0.3", "--node", "clinode",
              "--wait-timeout", "60"])
    out = capsys.readouterr().out
    assert "done" in out and str(tmp_path) in out
    cli.main(["profile", "list", "--address", address])
    assert "done" in capsys.readouterr().out


def test_capture_targets_other_node_is_ignored(gcs_server, tmp_path,
                                               monkeypatch):
    monkeypatch.setenv("RAY_TPU_SESSION_DIR", str(tmp_path))
    address = f"127.0.0.1:{gcs_server.port}"
    xm.start_profile_listener(address, node_id="nodeA")
    _wait_profile_subscriber(gcs_server)
    capture_id = xm.request_capture(address, node="nodeZZZ",
                                    duration_s=0.2)
    time.sleep(1.0)
    assert not [e for e in xm.list_captures(address)
                if e.get("capture_id") == capture_id]


def test_a_stall_capture_is_listed_beside_the_commands(gcs_server, tmp_path,
                                                       capsys, monkeypatch):
    """A stalled stretch's capture goes through the function the
    listener's command uses: same session dir, same ``__profiles__``
    registry, ``reason: stall``, and ``ray-tpu profile list`` shows it."""
    monkeypatch.setenv("RAY_TPU_SESSION_DIR", str(tmp_path))
    monkeypatch.setenv("RAY_TPU_stall_capture_s", "0.2")
    address = f"127.0.0.1:{gcs_server.port}"
    xm.connect(address, node_id="stallnode")
    jnp.dot(jnp.ones((32, 32)), jnp.ones((32, 32))).block_until_ready()
    rec = xm._CallRecord()
    for _ in range(xm.BASELINE_MIN + 1):
        rec.note("p", 1, 0.01, 1, None)
    rec.note("p", 1, 0.5, 1, None)
    stretch = rec._stretch
    assert stretch.capture_thread is None    # one slow call takes none
    rec.note("p", 1, 0.5, 1, None)
    stretch.capture_thread.join(timeout=120)
    assert not stretch.capture_thread.is_alive()
    assert stretch.capture["status"] == "done", stretch.capture
    (entry,) = [e for e in xm.list_captures(address)
                if e.get("reason") == "stall"]
    assert entry["capture_id"].startswith("stall-")
    assert entry["status"] == "done" and entry["node_id"] == "stallnode"
    assert entry["trace_dir"].startswith(str(tmp_path / "profiles"))
    from ray_tpu.scripts import cli

    cli.main(["profile", "list", "--address", address])
    out = capsys.readouterr().out
    assert entry["capture_id"] in out and "done" in out
    xm.disconnect(address)


# --------------------------------------- metrics tail downsample hint


def test_tsdb_reports_tier_counts_and_cli_hints():
    from ray_tpu._private.tsdb import TimeSeriesDB
    from ray_tpu.scripts.cli import _coarse_tier_hint

    db = TimeSeriesDB(retention_s=3600.0, resolution_s=1.0,
                      hires_retention_s=60.0, downsample_s=10.0)
    for t in range(0, 1000):
        db.append("m", {}, float(t), ts=float(t))
    # Window entirely below the hi-res horizon: coarse buckets only.
    [old] = db.query(name="m", since=100.0, until=500.0)
    assert old["coarse_points"] > 0 and old["hires_points"] == 0
    assert "downsampled" in _coarse_tier_hint([old])
    # A recent window has raw points: no hint.
    [fresh] = db.query(name="m", since=950.0)
    assert fresh["hires_points"] > 0
    assert _coarse_tier_hint([fresh]) == ""
    assert _coarse_tier_hint([]) == ""
