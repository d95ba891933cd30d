"""Serve data-plane tests: asyncio HTTP ingress, gRPC ingress, declarative
deploys (reference: serve/tests/test_proxy.py + test_config_files)."""

import http.client
import json

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture(scope="module", autouse=True)
def ray_session():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


@pytest.fixture(scope="module")
def ingress():
    @serve.deployment
    class Echo:
        def __call__(self, payload):
            return {"echo": payload}

        def double(self, payload):
            return {"x2": payload.get("n", 0) * 2}

        def counts(self, payload):
            for i in range(payload.get("n", 3)):
                yield {"i": i}

    serve.run(Echo.bind(), name="Echo")
    http_port = serve.start_http(port=0)
    grpc_port = serve.start_grpc(port=0)
    yield http_port, grpc_port
    serve.stop_http()
    serve.stop_grpc()


def _post(conn, path, payload):
    body = json.dumps(payload)
    conn.request("POST", path, body=body,
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    return resp, resp.read()


def test_http_keep_alive_multiple_requests(ingress):
    """Several requests must ride ONE TCP connection (HTTP/1.1
    keep-alive — the stdlib thread-per-connection server couldn't)."""
    http_port, _ = ingress
    conn = http.client.HTTPConnection("127.0.0.1", http_port)
    for i in range(5):
        resp, body = _post(conn, "/Echo/double", {"n": i})
        assert resp.status == 200
        assert json.loads(body) == {"x2": i * 2}
        assert resp.getheader("Connection") == "keep-alive"
    conn.close()


def test_http_healthz_and_routes(ingress):
    http_port, _ = ingress
    conn = http.client.HTTPConnection("127.0.0.1", http_port)
    conn.request("GET", "/-/healthz")
    assert json.loads(conn.getresponse().read()) == {"status": "ok"}
    conn.request("GET", "/-/routes")
    routes = json.loads(conn.getresponse().read())
    assert "/Echo" in routes
    conn.close()


def test_http_streaming_ndjson(ingress):
    http_port, _ = ingress
    conn = http.client.HTTPConnection("127.0.0.1", http_port)
    resp, body = _post(conn, "/Echo/stream/counts", {"n": 4})
    assert resp.status == 200
    assert resp.getheader("Content-Type") == "application/x-ndjson"
    items = [json.loads(line) for line in body.splitlines() if line]
    assert items == [{"i": i} for i in range(4)]
    # Connection stays usable after a completed stream.
    resp, body = _post(conn, "/Echo/double", {"n": 5})
    assert json.loads(body) == {"x2": 10}
    conn.close()


def test_http_stream_keeps_one_chunk_an_item_however_many_a_pull_takes(
        ingress):
    """A producer far ahead of the ingress: every item still arrives,
    in order, each in a chunk of its own (more than ``_PULL_MAX_ITEMS``
    of them, so one pull cannot take all)."""
    import socket

    from ray_tpu.serve import proxy

    n = proxy._PULL_MAX_ITEMS * 2 + 44
    http_port, _ = ingress
    body = json.dumps({"n": n}).encode()
    with socket.create_connection(("127.0.0.1", http_port)) as sock:
        sock.sendall((f"POST /Echo/stream/counts HTTP/1.1\r\n"
                      f"Host: x\r\nContent-Type: application/json\r\n"
                      f"Content-Length: {len(body)}\r\n"
                      f"Connection: close\r\n\r\n").encode() + body)
        raw = b""
        while chunk := sock.recv(1 << 16):
            raw += chunk
    head, _, rest = raw.partition(b"\r\n\r\n")
    assert b" 200 " in head.split(b"\r\n")[0]
    chunks = []
    while True:
        size, _, rest = rest.partition(b"\r\n")
        size = int(size, 16)
        if size == 0:
            break
        chunks.append(rest[:size])
        assert rest[size:size + 2] == b"\r\n"
        rest = rest[size + 2:]
    assert [json.loads(c) for c in chunks] == [{"i": i} for i in range(n)]
    assert all(c.count(b"\n") == 1 for c in chunks)


class _Scripted:
    """An item stream with a script: what ``next()`` gives in turn (an
    exception instance is raised) and how many of them are stored."""

    def __init__(self, script, stored):
        self.script, self.stored, self.asked = list(script), stored, 0

    def __next__(self):
        if not self.script:
            raise StopIteration
        self.stored -= 1
        item = self.script.pop(0)
        if isinstance(item, BaseException):
            raise item
        return item

    def ready(self):
        self.asked += 1
        return self.stored > 0


@pytest.mark.parametrize("case", ["caught_up", "behind", "capped",
                                  "ends_in_a_pull", "error_in_a_pull",
                                  "no_ready"])
def test_pull_takes_what_is_stored_and_never_waits_twice(case, monkeypatch):
    from ray_tpu.serve import proxy

    end, held = proxy._STREAM_END, []
    if case == "caught_up":
        # Nothing stored beyond the item waited for: one item a pull.
        items = _Scripted([1, 2], stored=1)
        assert proxy._pull_ready(items, held) == [1]
        items.stored = 1
        assert proxy._pull_ready(items, held) == [2]
        assert proxy._pull_ready(items, held) is end
    elif case == "behind":
        # Four stored: one pull brings all four, then waits for the fifth.
        items = _Scripted([1, 2, 3, 4, 5], stored=4)
        assert proxy._pull_ready(items, held) == [1, 2, 3, 4]
        assert items.asked == 4          # three yes, one no
        assert proxy._pull_ready(items, held) == [5]
    elif case == "capped":
        monkeypatch.setattr(proxy, "_PULL_MAX_ITEMS", 3)
        items = _Scripted(range(8), stored=8)
        assert proxy._pull_ready(items, held) == [0, 1, 2]
        assert proxy._pull_ready(items, held) == [3, 4, 5]
        assert proxy._pull_ready(items, held) == [6, 7]
    elif case == "ends_in_a_pull":
        # "Stored" but gone: the end is kept for the next pull.
        items = _Scripted([1, 2], stored=3)
        assert proxy._pull_ready(items, held) == [1, 2]
        assert held == [end]
        assert proxy._pull_ready(items, held) is end
        assert held == []
    elif case == "error_in_a_pull":
        # The items before the error reach the client; the error is
        # raised by the pull after, not swallowed.
        items = _Scripted([1, 2, ValueError("replica gone"), 4], stored=4)
        assert proxy._pull_ready(items, held) == [1, 2]
        with pytest.raises(ValueError, match="replica gone"):
            proxy._pull_ready(items, held)
        assert proxy._pull_ready(items, held) == [4]
    else:
        # A plain iterator (no ``ready``): one item a pull, as before.
        items = iter([1, 2])
        assert proxy._pull_ready(items, held) == [1]
        assert proxy._pull_ready(items, held) == [2]
        assert proxy._pull_ready(items, held) is end


def test_http_error_does_not_kill_connection(ingress):
    http_port, _ = ingress
    conn = http.client.HTTPConnection("127.0.0.1", http_port)
    resp, body = _post(conn, "/Echo/_private", {})
    assert resp.status == 404
    resp, body = _post(conn, "/Echo/double", {"n": 1})
    assert resp.status == 200
    conn.close()


def test_grpc_ingress_shares_deployment(ingress):
    _, grpc_port = ingress
    from ray_tpu._private import rpc
    from ray_tpu.protobuf import ray_tpu_pb2 as pb

    stub = rpc.get_stub("ServeIngress", f"127.0.0.1:{grpc_port}")
    reply = stub.Predict(pb.ServeRequest(
        deployment="Echo", method="double",
        payload=json.dumps({"n": 21}).encode()))
    assert reply.ok, reply.error
    assert json.loads(reply.payload) == {"x2": 42}

    items = [json.loads(r.payload) for r in stub.PredictStream(
        pb.ServeRequest(deployment="Echo", method="counts",
                        payload=json.dumps({"n": 3}).encode())) if r.ok]
    assert items == [{"i": i} for i in range(3)]

    bad = stub.Predict(pb.ServeRequest(deployment="nope"))
    assert not bad.ok and bad.error


def test_declarative_deploy_from_yaml(tmp_path, ingress):
    http_port, _ = ingress
    app_py = tmp_path / "my_serve_app.py"
    app_py.write_text(
        "from ray_tpu import serve\n"
        "@serve.deployment\n"
        "def adder(payload):\n"
        "    return {'sum': payload.get('a', 0) + payload.get('b', 0)}\n")
    cfg = tmp_path / "serve_config.yaml"
    cfg.write_text(
        "applications:\n"
        "  - import_path: my_serve_app:adder\n"
        "    deployments:\n"
        "      - name: adder\n"
        "        num_replicas: 2\n")
    import sys

    sys.path.insert(0, str(tmp_path))
    try:
        names = serve.deploy_config_file(str(cfg))
        assert names == ["adder"]
        conn = http.client.HTTPConnection("127.0.0.1", http_port)
        resp, body = _post(conn, "/adder", {"a": 2, "b": 3})
        assert json.loads(body) == {"sum": 5}
        conn.close()
        controller = ray_tpu.get_actor("__serve_controller__")
        replicas = ray_tpu.get(controller.get_replicas.remote("adder"),
                               timeout=10)
        assert len(replicas) == 2  # override applied
    finally:
        sys.path.remove(str(tmp_path))


def test_declarative_init_kwargs_override(tmp_path, ingress):
    """``init_kwargs`` in a config file retunes replica constructor knobs
    (the LLM engine's num_slots / sync_every ride this) without editing
    the application module."""
    http_port, _ = ingress
    app_py = tmp_path / "my_knob_app.py"
    app_py.write_text(
        "from ray_tpu import serve\n"
        "@serve.deployment\n"
        "class Knobbed:\n"
        "    def __init__(self, num_slots=8, sync_every=1):\n"
        "        self.num_slots = num_slots\n"
        "        self.sync_every = sync_every\n"
        "    def __call__(self, payload):\n"
        "        return {'num_slots': self.num_slots,\n"
        "                'sync_every': self.sync_every}\n")
    cfg = tmp_path / "knob_config.yaml"
    cfg.write_text(
        "applications:\n"
        "  - import_path: my_knob_app:Knobbed\n"
        "    deployments:\n"
        "      - name: Knobbed\n"
        "        init_kwargs: {num_slots: 16, sync_every: 8}\n")
    import sys

    sys.path.insert(0, str(tmp_path))
    try:
        serve.deploy_config_file(str(cfg))
        conn = http.client.HTTPConnection("127.0.0.1", http_port)
        resp, body = _post(conn, "/Knobbed", {})
        assert json.loads(body) == {"num_slots": 16, "sync_every": 8}
        conn.close()
    finally:
        sys.path.remove(str(tmp_path))


def test_rest_deploy_endpoint(tmp_path, ingress):
    """PUT /-/deploy with a YAML body deploys (reference: REST api)."""
    http_port, _ = ingress
    app_py = tmp_path / "rest_app.py"
    app_py.write_text(
        "from ray_tpu import serve\n"
        "@serve.deployment\n"
        "def greeter(payload):\n"
        "    return {'hi': payload.get('who', 'world')}\n")
    import sys

    sys.path.insert(0, str(tmp_path))
    try:
        conn = http.client.HTTPConnection("127.0.0.1", http_port)
        body = ("applications:\n"
                "  - import_path: rest_app:greeter\n")
        conn.request("PUT", "/-/deploy", body=body)
        resp = conn.getresponse()
        assert resp.status == 200, resp.read()
        assert json.loads(resp.read()) == {"deployed": ["greeter"]}
        resp, body = _post(conn, "/greeter", {"who": "tpu"})
        assert json.loads(body) == {"hi": "tpu"}
        conn.close()
    finally:
        sys.path.remove(str(tmp_path))


def test_grpc_private_method_rejected(ingress):
    _, grpc_port = ingress
    from ray_tpu._private import rpc
    from ray_tpu.protobuf import ray_tpu_pb2 as pb

    stub = rpc.get_stub("ServeIngress", f"127.0.0.1:{grpc_port}")
    reply = stub.Predict(pb.ServeRequest(deployment="Echo",
                                         method="__init__"))
    assert not reply.ok and "not found" in reply.error


def test_http_chunked_request_rejected(ingress):
    http_port, _ = ingress
    import socket

    s = socket.create_connection(("127.0.0.1", http_port))
    s.sendall(b"POST /Echo HTTP/1.1\r\nTransfer-Encoding: chunked\r\n\r\n")
    data = s.recv(4096)
    assert b"501" in data.split(b"\r\n")[0]
    s.close()
