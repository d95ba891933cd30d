"""Serve tests (reference: python/ray/serve/tests)."""

import json
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture(scope="module", autouse=True)
def ray8():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def test_class_deployment_roundtrip():
    @serve.deployment
    class Greeter:
        def __init__(self, greeting):
            self.greeting = greeting

        def __call__(self, name):
            return f"{self.greeting}, {name}!"

    handle = serve.run(Greeter.bind("Hello"))
    assert handle.remote("world").result() == "Hello, world!"
    serve.delete("Greeter")


def test_function_deployment():
    @serve.deployment
    def double(x):
        return x * 2

    handle = serve.run(double.bind())
    assert handle.remote(21).result() == 42
    serve.delete("double")


def test_multi_replica_load_balancing():
    @serve.deployment(num_replicas=3)
    class InstanceEcho:
        def __call__(self, _):
            return id(self)

    handle = serve.run(InstanceEcho.bind())
    instances = {handle.remote(None).result() for _ in range(30)}
    assert len(instances) >= 2  # pow-2 routing spreads across replicas
    serve.delete("InstanceEcho")


def test_method_call():
    @serve.deployment
    class Model:
        def __init__(self):
            self.count = 0

        def predict(self, x):
            return x + 1

        def stats(self, _=None):
            return "ok"

    handle = serve.run(Model.bind())
    assert handle.predict.remote(5).result() == 6
    assert handle.stats.remote().result() == "ok"
    serve.delete("Model")


def test_batching():
    @serve.deployment
    class Batched:
        @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.05)
        def __call__(self, xs):
            # xs is a list; record batch size in each result
            return [(x, len(xs)) for x in xs]

    handle = serve.run(Batched.bind())
    responses = [handle.remote(i) for i in range(8)]
    results = [r.result() for r in responses]
    assert sorted(x for x, _ in results) == list(range(8))
    assert max(bs for _, bs in results) >= 2  # some batching happened
    serve.delete("Batched")


def test_replica_recovery():
    @serve.deployment(num_replicas=1)
    class Fragile:
        def __call__(self, x):
            return x

        def die(self, _):
            ray_tpu.exit_actor()

    handle = serve.run(Fragile.bind())
    assert handle.remote(1).result() == 1
    try:
        handle.die.remote(None).result(timeout_s=10)
    except Exception:
        pass
    # Controller reconciliation replaces the dead replica.
    deadline = time.monotonic() + 30
    ok = False
    while time.monotonic() < deadline:
        try:
            if handle.remote(2).result(timeout_s=10) == 2:
                ok = True
                break
        except Exception:
            time.sleep(0.5)
    assert ok
    serve.delete("Fragile")


def test_autoscaling_up_and_down():
    """Load ramp scales replicas toward total_ongoing/target, then idleness
    scales back to min (reference: serve autoscaling_policy.py)."""

    @serve.deployment(autoscaling_config={
        "min_replicas": 1, "max_replicas": 3,
        "target_ongoing_requests": 1.0,
        "upscale_delay_s": 0.2, "downscale_delay_s": 0.5,
    })
    class Slow:
        def __call__(self, x):
            time.sleep(0.4)
            return x

    handle = serve.run(Slow.bind())
    controller = ray_tpu.get_actor("__serve_controller__")

    def replica_count():
        return len(ray_tpu.get(
            controller.get_replicas.remote("Slow"), timeout=10))

    assert replica_count() == 1
    # Sustained concurrent load: keep ~6 requests in flight.
    stop = time.monotonic() + 8
    pending = []
    grew = False
    while time.monotonic() < stop:
        while len(pending) < 6:
            pending.append(handle.remote(1))
        done, pending = pending[:2], pending[2:]
        for d in done:
            try:
                d.result(timeout_s=30)
            except Exception:
                pass
        if replica_count() >= 2:
            grew = True
            break
    for d in pending:
        try:
            d.result(timeout_s=30)
        except Exception:
            pass
    assert grew, "autoscaler never scaled up under sustained load"
    # Idle: scales back down to min_replicas.
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline and replica_count() > 1:
        time.sleep(0.3)
    assert replica_count() == 1, "autoscaler never scaled back down"
    serve.delete("Slow")


def test_routing_table_pushed_on_change():
    """Handles learn about replica-set changes via the pubsub event, not a
    poll TTL: after a scale-up the handle's table refreshes promptly."""

    @serve.deployment(num_replicas=1)
    class Echo:
        def __call__(self, x):
            return x

    handle = serve.run(Echo.bind())
    assert handle.remote(1).result() == 1
    assert len(handle._replicas) == 1
    controller = ray_tpu.get_actor("__serve_controller__")
    #

    ray_tpu.get(controller.deploy.remote(
        "Echo", Echo._cls_or_fn, (), {}, 3, False, 100, None), timeout=30)
    deadline = time.monotonic() + 10
    seen = 0
    while time.monotonic() < deadline:
        handle.remote(2).result(timeout_s=10)
        seen = len(handle._replicas)
        if seen == 3:
            break
        time.sleep(0.1)
    assert seen == 3, f"handle saw {seen} replicas; push event not applied"
    serve.delete("Echo")


def test_http_proxy():
    @serve.deployment
    def echo(payload):
        return {"got": payload}

    serve.run(echo.bind())
    port = serve.start_http(port=0)
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/echo",
        data=json.dumps({"a": 1}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        out = json.loads(resp.read())
    assert out == {"got": {"a": 1}}
    serve.stop_http()
    serve.delete("echo")


def test_a_replica_full_of_requests_still_answers_the_controller():
    """More callers than ``max_ongoing_requests`` keep every request
    place of the replica taken and more calls queued behind them; the
    controller's probes run in a concurrency group of their own
    (``CONTROL_GROUP``), so they are answered at once. In the default
    group they waited behind the requests, and the controller killed the
    full, healthy replica after its grace."""
    @serve.deployment(max_ongoing_requests=2)
    class Slow:
        def __call__(self, x):
            time.sleep(1.5)
            return x

    handle = serve.run(Slow.bind())
    responses = [handle.remote(i) for i in range(6)]   # 2 run, 4 wait
    time.sleep(0.3)
    controller = ray_tpu.get_actor("__serve_controller__")
    replica, = ray_tpu.get(controller.get_replicas.remote("Slow"))
    t0 = time.monotonic()
    assert ray_tpu.get(replica.health.remote(), timeout=1.0) is True
    assert ray_tpu.get(replica.metrics.remote(), timeout=1.0)["ongoing"] >= 2
    assert "ongoing" in ray_tpu.get(replica.pressure.remote(), timeout=1.0)
    assert time.monotonic() - t0 < 1.0
    assert [r.result() for r in responses] == list(range(6))
    serve.delete("Slow")
