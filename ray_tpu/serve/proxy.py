"""Serve data plane: asyncio HTTP ingress + gRPC ingress over one router.

Reference: ``python/ray/serve/_private/proxy.py`` — the reference runs a
uvicorn/asyncio HTTP proxy (:752) and a gRPC proxy (:532) that share
routing state. This build keeps that shape with stdlib asyncio streams:

* keep-alive HTTP/1.1 with pipelined request loop per connection;
* chunked NDJSON streaming whose writes apply real backpressure
  (``await writer.drain()`` — a slow client throttles the generator pull
  instead of buffering unboundedly); a pull takes every item the
  replica has already stored, so a stream that fell behind its producer
  catches up in one write (each item keeps a chunk of its own);
* a bounded executor bridging the blocking DeploymentHandle router calls,
  whose size caps in-flight requests (the asyncio analog of the
  reference's ``max_ongoing_requests`` admission);
* control endpoints: ``GET /-/healthz``, ``GET /-/routes``, and
  ``PUT /-/deploy`` (declarative config — reference ``serve deploy``).

The gRPC ingress (``GrpcProxy``) serves the same deployments through
``ServeIngress.Predict`` / ``PredictStream`` (reference grpc proxy).
"""

from __future__ import annotations

import asyncio
import json
import logging
import os
import threading
import time
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu.util import tracing

logger = logging.getLogger(__name__)

_MAX_BODY = 64 << 20
_STREAM_END = object()
# Most items one pull of a streamed response takes (``_pull_ready``): a
# bound on one write, far above what a stream a few ticks behind holds.
_PULL_MAX_ITEMS = 256


def prefix_fingerprint(payload: Any) -> str:
    """Prefix fingerprint of an LLM request: a hash of the first k
    block-aligned chunks of ``prompt_token_ids`` (chunk size
    ``RAY_TPU_PREFIX_FP_CHUNK``, default 64 — the engine's default KV
    block size — over at most ``RAY_TPU_PREFIX_FP_CHUNKS`` chunks).
    Requests sharing a system prompt hash identically, so the router
    can keep them on the replica whose radix cache already holds the
    prefix. Returns "" for non-LLM payloads and prompts shorter than
    one chunk (nothing block-aligned to share). Collisions only cost
    routing locality — the engine's radix index matches exact token
    tuples, never hashes."""
    if not isinstance(payload, dict):
        return ""
    ids = payload.get("prompt_token_ids")
    if not isinstance(ids, (list, tuple)):
        return ""
    chunk = int(os.environ.get("RAY_TPU_PREFIX_FP_CHUNK", "64"))
    max_chunks = int(os.environ.get("RAY_TPU_PREFIX_FP_CHUNKS", "4"))
    k = min(max_chunks, len(ids) // max(chunk, 1))
    if k <= 0:
        return ""
    try:
        head = ",".join(str(int(t)) for t in ids[:k * chunk])
    except (TypeError, ValueError):
        return ""
    return f"{zlib.crc32(head.encode()):08x}"


class AdmissionGate:
    """Ingress admission control: per-tenant token buckets + pressure-
    thresholded load shedding. At saturation the fabric answers 429 +
    Retry-After (gRPC: RESOURCE_EXHAUSTED) instead of queueing
    unboundedly — clients get an honest back-off signal while admitted
    traffic keeps its latency. Pressure comes from the router handle's
    TTL-cached controller snapshots, so the per-request cost is a clock
    read and a few dict lookups.

    Thresholds (env, read per decision so tests and operators can
    retune live):

    * ``RAY_TPU_SHED_QUEUE_DEPTH`` — shed when EVERY reachable replica's
      congestion (engine queue depth + router ongoing, plus an
      arena-exhausted penalty) is at/above this. 0 disables pressure
      shedding (default 32).
    * ``RAY_TPU_SHED_RETRY_AFTER_S`` — advertised back-off (default 1).
    """

    def __init__(self, router: "_Router"):
        self._router = router

    @staticmethod
    def _congestion(snap: Dict[str, Any]) -> float:
        cost = float(snap.get("queue_depth") or 0)
        cost += float(snap.get("ongoing") or 0)
        total = snap.get("kv_blocks_total") or 0
        if total:
            avail = ((snap.get("kv_blocks_free") or 0)
                     + (snap.get("kv_blocks_cached") or 0))
            if avail <= 0:
                # Nothing to admit with even after LRU reclaim: the
                # next request can only queue.
                cost = max(cost, 1e9)
        return cost

    def check(self, deployment: str,
              tenant: str = "") -> Optional[Tuple[float, str]]:
        """None = admit; else ``(retry_after_s, reason)`` with reason in
        {"tenant_rate_limit", "pressure"} — the caller turns it into
        429 + Retry-After / RESOURCE_EXHAUSTED and the rejection is
        tagged into ``ray_tpu_serve_request_outcomes_total``."""
        from ray_tpu._private import metrics_defs as mdefs
        from ray_tpu.serve import multiplex

        # Pressure first: a pressure shed is the FABRIC's fault, so it
        # must not consume the tenant's bucket — otherwise a saturated
        # window drains every tenant's quota and their honest retries
        # bounce on tenant_rate_limit after pressure clears.
        shed = self._pressure_shed(deployment)
        if shed is not None:
            mdefs.SERVE_REQ_OUTCOMES.inc(tags={
                "deployment": deployment, "tenant": tenant,
                "engine": "ingress", "outcome": "shed_pressure"})
            return shed, "pressure"
        wait = multiplex.tenant_rate_limiter().try_acquire(tenant)
        if wait is not None:
            mdefs.SERVE_REQ_OUTCOMES.inc(tags={
                "deployment": deployment, "tenant": tenant,
                "engine": "ingress", "outcome": "shed_tenant"})
            return max(wait, 0.05), "tenant_rate_limit"
        return None

    def _pressure_shed(self, deployment: str) -> Optional[float]:
        """Retry-after seconds when EVERY reachable replica is at/above
        the shed threshold; None (admit) otherwise — failing open
        whenever pressure data is off, missing, or unreachable."""
        threshold = float(os.environ.get("RAY_TPU_SHED_QUEUE_DEPTH",
                                         "32") or 0)
        if threshold <= 0:
            return None
        try:
            from ray_tpu.serve import api as serve_api

            # A role-group (disaggregated) name has no replicas of its
            # own: the decode group's pressure is the admission signal
            # (its arena is where every request ultimately lives).
            group = serve_api.get_role_group(deployment)
            target = group["decode"] if group else deployment
            snaps = self._router.handle(target)._fetch_shared_pressure()
        except Exception:  # noqa: BLE001 — no controller: fail open
            return None
        reachable = [s for s in snaps
                     if s and not s.get("unreachable")]
        if not reachable:
            return None          # no pressure data: fail open
        if all(self._congestion(s) >= threshold for s in reachable):
            return float(os.environ.get("RAY_TPU_SHED_RETRY_AFTER_S",
                                        "1.0"))
        return None


class _Router:
    """Shared deployment-handle cache for every ingress."""

    #: Recently-dispatched prefix fingerprints the classifier treats as
    #: probably-cached on the decode side (bounded LRU).
    FP_SEEN_CAP = 512

    def __init__(self):
        self._handles: Dict[str, object] = {}
        self._lock = threading.Lock()
        # One admission gate per router: HTTP and gRPC ingresses share
        # its (handle-cached) pressure view and tenant buckets.
        self.gate = AdmissionGate(self)
        # Fingerprint → last-seen order, for the disagg classifier's
        # net-prefill estimate (OrderedDict as LRU).
        from collections import OrderedDict

        self._fp_seen: "OrderedDict[str, None]" = OrderedDict()

    def handle(self, name: str):
        from ray_tpu.serve.api import DeploymentHandle

        with self._lock:
            h = self._handles.get(name)
            if h is None:
                h = self._handles[name] = DeploymentHandle(name)
            return h

    @staticmethod
    def _check_public(method: Optional[str]) -> None:
        # Only public methods are network-routable — enforced here so
        # EVERY ingress (HTTP and gRPC) shares the guard.
        if method and method.startswith("_"):
            raise LookupError("method not found")

    # ------------------------------------------- disaggregated classify
    def _note_fp(self, fp: str) -> None:
        with self._lock:
            self._fp_seen.pop(fp, None)
            self._fp_seen[fp] = None
            while len(self._fp_seen) > self.FP_SEEN_CAP:
                self._fp_seen.popitem(last=False)

    def _classify_disagg(self, group: Dict[str, str], payload) -> bool:
        """True → split dispatch (prefill replica → KV handoff → decode
        replica); False → the decode group runs the request colocated.
        The estimate: NET prefill cost = prompt tokens minus the
        fingerprint-matched prefix a decode replica likely already
        holds (a seen fingerprint means its block-aligned head is hot
        in some radix cache — re-prefilling it locally is cheap, so it
        doesn't justify a transfer). Split when the net cost clears
        ``RAY_TPU_DISAGG_PREFILL_THRESHOLD`` tokens (default 128; <=0
        splits every LLM request — the parity/chaos tests' mode), or
        when the LIVE pressure feed shows every decode replica already
        queueing ``RAY_TPU_DISAGG_QUEUE_TOKENS`` prefill tokens (>0
        enables) — colocated admission would stall their decode ticks
        regardless of this prompt's size."""
        prompt = payload.get("prompt_token_ids") or ()
        plen = len(prompt)
        fp = prefix_fingerprint(payload)
        covered = 0
        if fp:
            chunk = int(os.environ.get("RAY_TPU_PREFIX_FP_CHUNK", "64"))
            max_chunks = int(os.environ.get("RAY_TPU_PREFIX_FP_CHUNKS",
                                            "4"))
            with self._lock:
                seen = fp in self._fp_seen
            if seen:
                covered = min(plen,
                              min(max_chunks, plen // max(chunk, 1))
                              * chunk)
            self._note_fp(fp)
        net_prefill = plen - covered
        threshold = float(os.environ.get(
            "RAY_TPU_DISAGG_PREFILL_THRESHOLD", "128"))
        if net_prefill >= threshold:
            return True
        floor = float(os.environ.get("RAY_TPU_DISAGG_QUEUE_TOKENS",
                                     "0") or 0)
        if floor > 0:
            try:
                snaps = self.handle(
                    group["decode"])._fetch_shared_pressure()
            except Exception:  # noqa: BLE001 — no feed: size-only rule
                snaps = []
            live = [s for s in snaps if s and not s.get("unreachable")]
            if live and all(
                    float(s.get("prefill_queue_tokens") or 0) >= floor
                    for s in live):
                return True
        return False

    def call(self, name: str, method: Optional[str], payload,
             model_id: str = "", timeout_s: float = 60.0,
             request_ctx: Optional[Dict[str, Any]] = None):
        from ray_tpu.serve import api as serve_api

        self._check_public(method)
        group = serve_api.get_role_group(name)
        if group is not None:
            # Unary completions run colocated on the decode group (its
            # engines accept plain submits); only streams split.
            name = group["decode"]
        h = self.handle(name).options(
            method, multiplexed_model_id=model_id,
            request_context=request_ctx,
            prefix_key=prefix_fingerprint(payload))
        return h.remote(payload).result(timeout_s=timeout_s)

    def stream(self, name: str, method: Optional[str], payload,
               model_id: str = "",
               request_ctx: Optional[Dict[str, Any]] = None):
        """Streaming dispatch through the RECOVERY JOURNAL: the returned
        iterator survives replica death (queued/prefilling requests
        resubmit; mid-decode LLM requests resume as prompt + emitted
        tokens, exactly-once under greedy decoding) and drain rejects
        re-route for free. The iterator's ``.journal`` tells the ingress
        whether to surface the ``x-ray-tpu-resumed`` marker.

        A name registered as a ROLE GROUP classifies first: requests
        whose estimated net prefill cost justifies the transfer split
        across the (prefill, decode) pair with a journaled KV handoff
        (:class:`~ray_tpu.serve.recovery.DisaggRecoverableStream`);
        the rest run colocated on the decode group."""
        from ray_tpu.serve import api as serve_api
        from ray_tpu.serve.recovery import (DisaggRecoverableStream,
                                            RecoverableStream,
                                            RequestJournal,
                                            is_llm_payload)

        self._check_public(method)
        group = serve_api.get_role_group(name)
        if group is not None:
            if is_llm_payload(payload) and \
                    self._classify_disagg(group, payload):
                journal = RequestJournal(name, method, payload,
                                         model_id=model_id,
                                         request_ctx=request_ctx)
                return DisaggRecoverableStream(
                    self.handle(group["prefill"]),
                    self.handle(group["decode"]),
                    journal)
            name = group["decode"]
        journal = RequestJournal(name, method, payload,
                                 model_id=model_id,
                                 request_ctx=request_ctx)
        return RecoverableStream(self.handle(name), journal)


def _pull_ready(items, held: List[Any]) -> Any:
    """One pull of a streamed response: wait for the next item, then
    take what else the replica has already stored (``items.ready()``),
    as a list; ``_STREAM_END`` when the stream is over. A pull is a
    round trip between the ingress loop and a pool thread, and under a
    hundred open streams it can take longer than an engine's tick: at
    one item a pull such a stream fell further behind with every token,
    and its caller waited seconds for tokens the engine had long made.
    An error (or the end) met after the first item is left in ``held``
    for the next pull, so the items before it still reach the client."""
    if held:
        end = held.pop()
        if end is _STREAM_END:
            return end
        raise end
    try:
        batch = [next(items)]
    except StopIteration:
        return _STREAM_END
    more = getattr(items, "ready", None)
    try:
        while more is not None and len(batch) < _PULL_MAX_ITEMS and more():
            batch.append(next(items))
    except StopIteration:
        held.append(_STREAM_END)
    except Exception as e:  # noqa: BLE001 — raised by the next pull
        held.append(e)
    return batch


class _StreamPulls:
    """One streamed response's pulls as the ingress loop sees them, on
    ``time.time()`` (the request chain's clock): ``loop`` is a pull's
    two thread hops, neither of which waits for the engine
    (``run_in_executor`` called -> the pull starts on a pool thread, the
    pull returned -> the loop resumes); items over pulls is the burst.
    A pull costs clock reads and float adds on this object; the metrics
    registry is touched at the stream's end and about every
    ``FLUSH_EVERY`` items, never once a pull."""

    FLUSH_EVERY = 64
    __slots__ = ("started", "returned", "pulls", "items", "loop_s",
                 "max_items", "first_write", "last_write", "_hops_s",
                 "_tags", "_flushed")

    def __init__(self, deployment: str):
        self._tags = {"deployment": deployment}
        self.started = self.returned = 0.0      # set on the pool thread
        self.pulls = self.items = self.max_items = 0
        self.loop_s = self._hops_s = 0.0
        self.first_write = self.last_write = 0.0
        self._flushed = (0, 0, 0.0)             # pulls, items, loop_s

    def resumed(self, called: float, now: float) -> None:
        """The loop, which called ``run_in_executor`` at ``called``, has
        the pull's result at ``now``."""
        self._hops_s = (self.started - called) + (now - self.returned)

    def wrote(self, n: int, now: float) -> None:
        """What the last pull brought, ``n`` items, went out at ``now``."""
        self.pulls += 1
        self.items += n
        self.loop_s += self._hops_s
        if n > self.max_items:
            self.max_items = n
        if not self.first_write:
            self.first_write = now
        self.last_write = now
        if self.items - self._flushed[1] >= self.FLUSH_EVERY:
            self.flush()

    def flush(self) -> None:
        from ray_tpu._private import metrics_defs as mdefs

        pulls, items, loop_s = self._flushed
        if self.pulls == pulls:
            return
        mdefs.SERVE_STREAM_PULLS.inc(self.pulls - pulls, tags=self._tags)
        mdefs.SERVE_STREAM_ITEMS.inc(self.items - items, tags=self._tags)
        mdefs.SERVE_STREAM_LOOP_SECONDS.inc(self.loop_s - loop_s,
                                            tags=self._tags)
        self._flushed = (self.pulls, self.items, self.loop_s)

    def close(self, rctx: Optional[Dict[str, Any]]) -> None:
        """The stream is over: flush, and for a traced request close its
        chain on the ingress's side with one summary span,
        ``serve.stream`` (first write to last)."""
        self.flush()
        if rctx is None or not self.pulls:
            return
        tracing.emit_span(
            "serve.stream", trace_id=rctx["trace_id"],
            parent_span_id=rctx["parent_span_id"], ts=self.first_write,
            dur=self.last_write - self.first_write, kind="ingress",
            request_id=rctx["request_id"],
            deployment=rctx.get("deployment", ""), pulls=self.pulls,
            items=self.items, max_items_per_pull=self.max_items,
            first_write_ts=self.first_write, last_write_ts=self.last_write)


def ingress_request_context(deployment: str, tenant: str = "",
                            request_id: str = "") -> Optional[Dict[str, Any]]:
    """Mint the serve request context at an INGRESS: a fresh trace id
    plus a pre-allocated ingress span id the ingress closes when the
    response completes. Returns None when tracing is disabled (the data
    plane then pays one env check per request and nothing else). An
    ``x-request-id`` supplied by the client is honored so external
    systems can correlate."""
    if not tracing.enabled():
        return None
    return {"request_id": request_id or tracing.gen_id(),
            "trace_id": tracing.gen_id(),
            "parent_span_id": tracing.gen_id(),  # = the ingress span id
            "deployment": deployment, "tenant": tenant}


def _close_ingress_span(rctx: Optional[Dict[str, Any]], t0: float,
                        status: Any, path: str) -> None:
    """Emit the root serve.ingress span retrospectively (the span covers
    parse -> route -> full response write, so its id must exist before
    its duration does)."""
    if rctx is None:
        return
    tracing.emit_span("serve.ingress", trace_id=rctx["trace_id"],
                      span_id=rctx["parent_span_id"], ts=t0,
                      dur=time.time() - t0, kind="ingress",
                      request_id=rctx["request_id"],
                      deployment=rctx.get("deployment", ""),
                      http_path=path, status=str(status))


class AsyncHttpProxy:
    """Asyncio HTTP/1.1 ingress (keep-alive, streaming, backpressure)."""

    def __init__(self, host: str = "127.0.0.1", port: int = 8000,
                 max_concurrency: int = 1024, router: Optional[_Router] = None):
        self.router = router or _Router()
        # The executor bounds concurrent blocking router calls: requests
        # beyond it queue in asyncio (cheap futures), not in threads.
        # An open STREAM holds a thread nearly all its life (each pull
        # blocks until the engine's next token, the first one through
        # the engine's whole queue), so this is also the cap on open
        # streams: under it new submits queue behind blocked pulls and
        # the engine's slots run empty while callers wait (at 64, 96
        # callers over a 48-slot engine lost up to half their rate; at
        # 256, 512 callers over a 256-slot engine would leave no request
        # waiting in the engine for the slot an ending frees).
        # Threads start on demand, so a quiet ingress pays nothing.
        self._pool = ThreadPoolExecutor(max_workers=max_concurrency,
                                        thread_name_prefix="serve-http")
        self._loop = asyncio.new_event_loop()
        self._started = threading.Event()
        self._host, self._want_port = host, port
        self.port: int = 0
        self._server = None
        self._boot_error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="serve-http-loop")
        self._thread.start()
        if not self._started.wait(10) or self.port == 0:
            if self._boot_error is not None:
                raise RuntimeError(
                    f"HTTP proxy failed to bind {host}:{port}: "
                    f"{self._boot_error}") from self._boot_error
            raise RuntimeError("HTTP proxy failed to start")

    def _run(self):
        asyncio.set_event_loop(self._loop)

        async def boot():
            self._server = await asyncio.start_server(
                self._handle_conn, self._host, self._want_port)
            self.port = self._server.sockets[0].getsockname()[1]

        try:
            self._loop.run_until_complete(boot())
        except BaseException as e:  # noqa: BLE001 — surface bind errors
            self._boot_error = e
            self._started.set()
            self._loop.close()
            return
        self._started.set()
        try:
            self._loop.run_forever()
        finally:
            self._loop.close()

    # ------------------------------------------------------------- parsing
    async def _read_request(self, reader):
        """One request, or None on clean EOF, or (status, message) for a
        protocol error the connection must answer-then-close."""
        try:
            line = await reader.readline()
        except (asyncio.LimitOverrunError, ValueError):
            return 431, "request line too long"
        if not line or line in (b"\r\n", b"\n"):
            return None
        try:
            method, path, version = line.decode("latin1").strip().split(" ", 2)
        except ValueError:
            return 400, "malformed request line"
        headers: Dict[str, str] = {}
        while True:
            try:
                h = await reader.readline()
            except (asyncio.LimitOverrunError, ValueError):
                return 431, "header too long"
            if not h or h in (b"\r\n", b"\n"):
                break
            k, _, v = h.decode("latin1").partition(":")
            headers[k.strip().lower()] = v.strip()
        if "chunked" in headers.get("transfer-encoding", "").lower():
            # Parsing chunked request bodies is unimplemented; accepting
            # the request with an empty body would desync the keep-alive
            # loop (the body bytes would parse as the next request line).
            return 501, "chunked request bodies are not supported"
        try:
            length = int(headers.get("content-length", 0) or 0)
        except ValueError:
            return 400, "malformed Content-Length"
        if length < 0:
            return 400, "malformed Content-Length"
        if length > _MAX_BODY:
            return 413, "request body too large"
        body = await reader.readexactly(length) if length else b""
        return method, path, version, headers, body

    @staticmethod
    def _response(status: int, body: bytes,
                  content_type: str = "application/json",
                  keep_alive: bool = True,
                  extra_headers: Optional[Dict[str, str]] = None) -> bytes:
        import http as _http

        try:
            reason = _http.HTTPStatus(status).phrase
        except ValueError:
            reason = "Unknown"
        conn = "keep-alive" if keep_alive else "close"
        extra = "".join(f"{k}: {v}\r\n"
                        for k, v in (extra_headers or {}).items())
        return (f"HTTP/1.1 {status} {reason}\r\n"
                f"Content-Type: {content_type}\r\n"
                f"Content-Length: {len(body)}\r\n"
                f"{extra}"
                f"Connection: {conn}\r\n\r\n").encode() + body

    # ---------------------------------------------------------- connection
    async def _handle_conn(self, reader, writer):
        try:
            while True:
                try:
                    req = await self._read_request(reader)
                except (asyncio.IncompleteReadError, ConnectionError):
                    return
                if req is None:
                    return
                if len(req) == 2:  # protocol error: answer, then close
                    status, msg = req
                    writer.write(self._response(
                        status, json.dumps({"error": msg}).encode(),
                        keep_alive=False))
                    await writer.drain()
                    return
                method, path, version, headers, body = req
                close = (headers.get("connection", "").lower() == "close"
                         or version == "HTTP/1.0")
                try:
                    done = await self._route(method, path, headers, body,
                                             writer, keep_alive=not close)
                except (ConnectionError, asyncio.CancelledError):
                    return
                except Exception as e:  # noqa: BLE001
                    data = json.dumps({"error": str(e)}).encode()
                    writer.write(self._response(500, data,
                                                keep_alive=not close))
                    await writer.drain()
                    done = True
                if not done or close:
                    return
        finally:
            try:
                writer.close()
            except Exception:  # noqa: BLE001
                pass

    async def _route(self, method: str, path: str, headers, body: bytes,
                     writer, keep_alive: bool) -> bool:
        """Handle one request; returns False to drop the connection."""
        loop = asyncio.get_running_loop()
        path = path.split("?", 1)[0]
        if method == "GET" and path == "/-/healthz":
            writer.write(self._response(200, b'{"status":"ok"}',
                                        keep_alive=keep_alive))
            await writer.drain()
            return True
        if method == "GET" and path == "/-/routes":
            routes = await loop.run_in_executor(self._pool, _list_routes)
            writer.write(self._response(
                200, json.dumps(routes).encode(), keep_alive=keep_alive))
            await writer.drain()
            return True
        if method in ("PUT", "POST") and path == "/-/deploy":
            from ray_tpu.serve.config import deploy_config_data

            cfg = await loop.run_in_executor(
                self._pool, deploy_config_data, body.decode())
            writer.write(self._response(
                200, json.dumps({"deployed": cfg}).encode(),
                keep_alive=keep_alive))
            await writer.drain()
            return True
        if method != "POST":
            writer.write(self._response(404, b'{"error":"not found"}',
                                        keep_alive=keep_alive))
            await writer.drain()
            return True

        parts = path.strip("/").split("/")
        name = parts[0]
        stream = len(parts) >= 2 and parts[1] == "stream"
        call_method = (parts[2] if stream and len(parts) > 2 else
                       parts[1] if len(parts) > 1 else None)
        if not name or (call_method and call_method.startswith("_")):
            writer.write(self._response(
                404, json.dumps({"error": "method not found"}).encode(),
                keep_alive=keep_alive))
            await writer.drain()
            return True
        model_id = headers.get("serve_multiplexed_model_id", "")
        payload = json.loads(body) if body else {}
        # ADMISSION GATE before any dispatch work: per-tenant token
        # buckets + pressure-thresholded load shedding. A saturated
        # fabric answers 429 + Retry-After so clients back off honestly
        # instead of piling into an unbounded queue.
        shed = await loop.run_in_executor(
            self._pool, self.router.gate.check, name, model_id)
        if shed is not None:
            retry_after, reason = shed
            writer.write(self._response(
                429,
                json.dumps({"error": f"overloaded: {reason}",
                            "retry_after_s": retry_after}).encode(),
                keep_alive=keep_alive,
                extra_headers={"Retry-After":
                               f"{max(retry_after, 0.05):.3f}"}))
            await writer.drain()
            return True
        # Request-path tracing starts HERE: the ingress mints the trace
        # context (one trace per request) and every downstream hop —
        # route decision, replica dispatch, engine admission, prefill,
        # decode windows — parents into it.
        rctx = ingress_request_context(
            name, tenant=model_id,
            request_id=headers.get("x-request-id", ""))
        ing_t0 = time.time()

        if not stream:
            try:
                result = await loop.run_in_executor(
                    self._pool, self.router.call, name, call_method,
                    payload, model_id, 60.0, rctx)
            except Exception:
                _close_ingress_span(rctx, ing_t0, "error", path)
                raise
            writer.write(self._response(
                200, json.dumps(result).encode(), keep_alive=keep_alive))
            await writer.drain()
            _close_ingress_span(rctx, ing_t0, 200, path)
            return True

        # Streaming: pull the first item BEFORE committing to 200 so
        # pre-stream failures surface as errors, not empty streams.
        try:
            items = await loop.run_in_executor(
                self._pool, self.router.stream, name, call_method,
                payload, model_id, rctx)
        except Exception:
            _close_ingress_span(rctx, ing_t0, "error", path)
            raise

        held: List[Any] = []
        pulls = _StreamPulls(name)

        def pull():
            pulls.started = time.time()
            try:
                return _pull_ready(items, held)
            finally:
                pulls.returned = time.time()

        async def next_batch():
            called = time.time()
            batch = await loop.run_in_executor(self._pool, pull)
            pulls.resumed(called, time.time())
            return batch

        try:
            first = await next_batch()
        except Exception:
            _close_ingress_span(rctx, ing_t0, "error", path)
            raise
        journal = getattr(items, "journal", None)
        conn = "keep-alive" if keep_alive else "close"
        marker_sent = False
        extra = ""
        if journal is not None and journal.needs_marker:
            # A SAMPLED request was already resumed during the first
            # pull: the continuation is a re-seeded draw, and the
            # header says so before any token reaches the client.
            from ray_tpu.serve.recovery import RESUMED_MARKER

            extra = f"{RESUMED_MARKER}: {journal.resumes}\r\n"
            marker_sent = True
        writer.write((f"HTTP/1.1 200 OK\r\n"
                      f"Content-Type: application/x-ndjson\r\n"
                      f"Transfer-Encoding: chunked\r\n"
                      f"{extra}"
                      f"Connection: {conn}\r\n\r\n").encode())
        batch = first
        try:
            while batch is not _STREAM_END:
                # One chunk an item, as ever; what one pull brought
                # goes out in one write.
                out = bytearray()
                for item in batch:
                    chunk = json.dumps(item).encode() + b"\n"
                    out += f"{len(chunk):x}\r\n".encode() + chunk + b"\r\n"
                writer.write(bytes(out))
                await writer.drain()  # backpressure: slow client, slow pull
                pulls.wrote(len(batch), time.time())
                batch = await next_batch()
            if journal is not None and journal.needs_marker \
                    and not marker_sent:
                # The sampled resume happened MID-stream (headers long
                # gone): a trailing NDJSON control object carries the
                # marker instead.
                from ray_tpu.serve.recovery import RESUMED_MARKER

                chunk = json.dumps(
                    {RESUMED_MARKER: journal.resumes}).encode() + b"\n"
                writer.write(f"{len(chunk):x}\r\n".encode() + chunk
                             + b"\r\n")
            pulls.close(rctx)       # booked before the client sees the end
            writer.write(b"0\r\n\r\n")
            await writer.drain()
            _close_ingress_span(rctx, ing_t0, 200, path)
            return True
        except Exception:  # noqa: BLE001 — mid-stream failure: abort the
            # connection so the client sees truncation, not completion.
            logger.exception("streaming response for %s failed mid-stream",
                             name)
            pulls.close(rctx)
            _close_ingress_span(rctx, ing_t0, "aborted", path)
            return False

    def stop(self):
        def _shutdown():
            if self._server is not None:
                self._server.close()
            self._loop.stop()

        self._loop.call_soon_threadsafe(_shutdown)
        self._thread.join(timeout=5)
        self._pool.shutdown(wait=False)


def _list_routes() -> Dict[str, str]:
    import ray_tpu
    from ray_tpu.serve.api import CONTROLLER_NAME

    try:
        controller = ray_tpu.get_actor(CONTROLLER_NAME)
        deployments = ray_tpu.get(controller.list_deployments.remote(),
                                  timeout=10)
        return {f"/{d}": d for d in deployments}
    except Exception:  # noqa: BLE001
        return {}


class GrpcProxy:
    """gRPC ingress sharing the HTTP router (reference: grpc proxy,
    ``serve/_private/proxy.py:532``). Payloads are JSON bytes; streaming
    deployments map to a server-streaming RPC."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 router: Optional[_Router] = None):
        from ray_tpu._private import rpc

        self.router = router or _Router()
        self._server, self.port = rpc.serve("ServeIngress", self, port=port,
                                            host=host)

    # ------------------------------------------------------------ handlers
    @staticmethod
    def _shed(context, shed) -> None:
        """Reject with RESOURCE_EXHAUSTED + the advertised back-off (the
        gRPC analog of 429 + Retry-After)."""
        import grpc as _grpc

        retry_after, reason = shed
        context.abort(_grpc.StatusCode.RESOURCE_EXHAUSTED,
                      f"overloaded: {reason}; retry after "
                      f"{retry_after:.3f}s")

    def Predict(self, request, context):
        from ray_tpu.protobuf import ray_tpu_pb2 as pb

        shed = self.router.gate.check(request.deployment,
                                      request.multiplexed_model_id)
        if shed is not None:
            self._shed(context, shed)
        rctx = ingress_request_context(
            request.deployment, tenant=request.multiplexed_model_id)
        t0 = time.time()
        try:
            payload = json.loads(request.payload) if request.payload else {}
            result = self.router.call(
                request.deployment, request.method or None, payload,
                request.multiplexed_model_id, request_ctx=rctx)
            _close_ingress_span(rctx, t0, "ok", "grpc:Predict")
            return pb.ServeReply(ok=True,
                                 payload=json.dumps(result).encode())
        except Exception as e:  # noqa: BLE001
            _close_ingress_span(rctx, t0, "error", "grpc:Predict")
            return pb.ServeReply(ok=False, error=str(e))

    def PredictStream(self, request, context):
        import grpc as _grpc

        from ray_tpu.protobuf import ray_tpu_pb2 as pb

        shed = self.router.gate.check(request.deployment,
                                      request.multiplexed_model_id)
        if shed is not None:
            self._shed(context, shed)
        rctx = ingress_request_context(
            request.deployment, tenant=request.multiplexed_model_id)
        t0 = time.time()
        status = "aborted"  # client cancellation raises GeneratorExit,
        try:                # which except Exception would never see
            payload = json.loads(request.payload) if request.payload else {}
            items = self.router.stream(
                request.deployment, request.method or None, payload,
                request.multiplexed_model_id, request_ctx=rctx)
            for item in items:
                yield pb.ServeReply(ok=True,
                                    payload=json.dumps(item).encode())
            journal = getattr(items, "journal", None)
            if journal is not None and journal.needs_marker:
                # Sampled request resumed mid-decode: a trailing control
                # reply surfaces the re-seed (the gRPC analog of the
                # x-ray-tpu-resumed header/NDJSON marker).
                from ray_tpu.serve.recovery import RESUMED_MARKER

                yield pb.ServeReply(ok=True, payload=json.dumps(
                    {RESUMED_MARKER: journal.resumes}).encode())
            status = "ok"
        except Exception as e:  # noqa: BLE001
            # Terminate with an RPC error, NOT a trailing ok=False item:
            # consumers filtering on ok would read a truncated stream as a
            # successful short one (the HTTP plane aborts the connection
            # for the same reason).
            status = "error"
            context.abort(_grpc.StatusCode.INTERNAL, str(e))
        finally:
            _close_ingress_span(rctx, t0, status, "grpc:PredictStream")

    def stop(self):
        self._server.stop(grace=0.5)


__all__ = ["AdmissionGate", "AsyncHttpProxy", "GrpcProxy",
           "ingress_request_context", "prefix_fingerprint"]
