"""One process per chip, one chip per replica: chips are detected without
touching JAX, handed to actors as indices, and an engine lives wholly on
the device it is given."""

import jax
import pytest

import ray_tpu
from ray_tpu._private.accelerators import tpu
from ray_tpu.models import llama
from ray_tpu.models.continuous_batching import ContinuousBatcher


def test_chips_per_host_parses_known_generations_only():
    assert tpu._chips_per_host("v5litepod-1") == 1
    assert tpu._chips_per_host("v5litepod-4") == 4
    assert tpu._chips_per_host("v5litepod-8") == 8
    assert tpu._chips_per_host("v5litepod-16") == 4   # multi-host slice
    assert tpu._chips_per_host("v4-8") == 4           # suffix counts cores
    with pytest.raises(ValueError, match="unknown TPU generation"):
        tpu._chips_per_host("v9x-4")
    with pytest.raises(ValueError, match="cannot parse"):
        tpu._chips_per_host("a-tpu")


def test_detection_prefers_what_the_host_exposes(monkeypatch):
    monkeypatch.delenv(tpu.NUM_CHIPS_OVERRIDE_ENV, raising=False)
    monkeypatch.delenv(tpu.VISIBLE_CHIPS_ENV, raising=False)
    monkeypatch.setenv(tpu.ACCELERATOR_TYPE_ENV, "v5litepod-8")
    monkeypatch.setattr(tpu, "_chip_device_files",
                        lambda: ["/dev/accel0", "/dev/accel1"])
    detect = tpu.TPUAcceleratorManager.detect_num_chips
    assert detect() == 2            # device files beat the type string
    monkeypatch.setattr(tpu, "_chip_device_files", lambda: [])
    assert detect() == 8
    monkeypatch.setenv(tpu.VISIBLE_CHIPS_ENV, "1,3,5")
    assert detect() == 3
    monkeypatch.setenv(tpu.NUM_CHIPS_OVERRIDE_ENV, "1")
    assert detect() == 1


@ray_tpu.remote(num_tpus=1)
class _ChipHolder:
    def __init__(self):
        # Known already in the constructor, where a replica builds its
        # engine.
        self.chips = ray_tpu.get_runtime_context().get_accelerator_ids()

    def chips_seen(self):
        return self.chips["TPU"]


def test_local_runtime_hands_each_actor_its_own_chip(shutdown_only):
    ray_tpu.init(num_cpus=4, num_tpus=2)
    a, b = _ChipHolder.remote(), _ChipHolder.remote()
    held = {tuple(ray_tpu.get(x.chips_seen.remote(), timeout=30))
            for x in (a, b)}
    assert held == {("0",), ("1",)}
    # A third waits for a chip; killing a holder frees one for it.
    c = _ChipHolder.remote()
    ref = c.chips_seen.remote()
    ready, _ = ray_tpu.wait([ref], timeout=0.5)
    assert not ready
    ray_tpu.kill(a)
    assert ray_tpu.get(ref, timeout=30) == ["0"]
    # More chips than the host has is an error, not a hang.
    greedy = _ChipHolder.options(num_tpus=3).remote()
    with pytest.raises(ray_tpu.exceptions.RayTpuError):
        ray_tpu.get(greedy.chips_seen.remote(), timeout=30)


def test_engine_lives_on_the_device_it_is_given():
    cfg = llama.LlamaConfig.tiny()
    dev = jax.devices()[3]
    outs = []
    for device in (None, dev):
        eng = ContinuousBatcher(cfg, num_slots=2, max_len=64, block_size=16,
                                device=device)
        rid = eng.submit([5, 6, 7, 8], max_new_tokens=6)
        outs.append(eng.run_to_completion()[rid])
    assert outs[0] == outs[1]
    leaves = jax.tree.leaves((eng.params, eng.cache, eng._d_tokens,
                              eng._d_tables))
    assert all(x.devices() == {dev} for x in leaves)
    assert all(x.committed for x in leaves)


class _SlowFirstStart:
    """First construction outlives the controller's start-up grace."""
    starts = 0

    def __init__(self):
        import time

        type(self).starts += 1
        if type(self).starts == 1:
            time.sleep(8.0)

    def __call__(self, _):
        return ray_tpu.get_runtime_context().get_accelerator_ids()["TPU"]


def test_replica_dropped_at_startup_grace_gives_its_chip_back(
        shutdown_only, monkeypatch):
    """The controller replaces a replica that never answered inside the
    grace. It must kill it too: a chip is exclusive, so a leaked starter
    would leave its replacement waiting for the chip forever."""
    import time

    from ray_tpu import serve
    from ray_tpu.serve import api as serve_api

    monkeypatch.setattr(serve_api.ServeController,
                        "REPLICA_STARTUP_GRACE_S", 1.0)
    # A probe shorter than the slow start, so it is missed (the default
    # waits 10 s for a replica whose loop hundreds of streams share).
    monkeypatch.setattr(serve_api.ServeController,
                        "HEALTH_PROBE_TIMEOUT_S", 2.0)
    ray_tpu.init(num_cpus=4, num_tpus=1)
    dep = serve.deployment(_SlowFirstStart).options(
        ray_actor_options={"num_tpus": 1})
    try:
        handle = serve.run(dep.bind())
        deadline = time.monotonic() + 60
        while True:
            try:
                assert handle.remote(None).result(timeout_s=5) == ["0"]
                break
            except AssertionError:
                raise
            except Exception:  # noqa: BLE001 — not routed yet
                assert time.monotonic() < deadline, "deployment wedged"
                time.sleep(0.5)
        assert _SlowFirstStart.starts >= 2
    finally:
        serve.shutdown()
