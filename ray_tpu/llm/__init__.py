"""ray_tpu.llm: LLM serving + batch inference on ray_tpu serve.

Reference: ``python/ray/llm`` — vLLM-backed deployments
(``llm/_internal/serve``) and batch processors (``llm/_internal/batch``).
ray_tpu serves its own jit-compiled models (``ray_tpu.models.inference``)
instead of hosting an external engine: a deployment wraps a
``LlamaGenerator`` whose prefill/decode are one compiled program per shape,
with ``@serve.batch`` merging concurrent requests into one batched decode
(the continuous-batching analog at request granularity).
"""

from __future__ import annotations

import asyncio
import collections
import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ray_tpu import serve
from ray_tpu._private import chaos
from ray_tpu.models import llama
from ray_tpu.models.inference import LlamaGenerator
from ray_tpu.serve.api import StreamBatch
from ray_tpu.serve.recovery import STREAM_ITEM_TIMEOUT_S


@serve.deployment
class LlamaDeployment:
    """Batched text-completion replica (token-id interface; tokenizers are
    the caller's concern, as in the reference's processor configs)."""

    def __init__(self, config: Optional[llama.LlamaConfig] = None,
                 params=None, max_len: int = 512,
                 max_batch_size: int = 8,
                 checkpoint_path: Optional[str] = None):
        self.config = config or llama.LlamaConfig.tiny()
        if params is None and checkpoint_path:
            params = _params_from_checkpoint(checkpoint_path)
        self.generator = LlamaGenerator(self.config, params=params,
                                        max_len=max_len)
        self.max_batch_size = max_batch_size

    @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.02)
    def __call__(self, requests: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
        # Pad prompts to a common length, run one batched generate.
        prompts = [np.asarray(r["prompt_token_ids"], np.int32)
                   for r in requests]
        max_new = max(int(r.get("max_tokens", 16)) for r in requests)
        temperature = float(requests[0].get("temperature", 0.0))
        plen = max(len(p) for p in prompts)
        batch = np.zeros((len(prompts), plen), np.int32)
        for i, p in enumerate(prompts):
            batch[i, plen - len(p):] = p  # left-pad
        out = np.asarray(self.generator.generate(
            batch, max_new_tokens=max_new, temperature=temperature))
        return [
            {"token_ids": out[i, : int(r.get("max_tokens", 16))].tolist()}
            for i, r in enumerate(requests)
        ]


def _params_from_checkpoint(path: str):
    """Cold-start params from a training run's committed checkpoint
    (checkpoint plane, ``ray_tpu/checkpoint/plane.py``): the newest
    committed manifest under ``path`` — a plane root, run dir, or
    anything ``load_latest`` accepts. A saved ``TrainState`` contributes
    its ``params``; a bare params pytree loads as-is. The serving mesh
    need not match the training topology (elastic restore)."""
    from ray_tpu.checkpoint import load_latest

    state = load_latest(path)
    return getattr(state, "params", state)


def build_llama_app(config: Optional[llama.LlamaConfig] = None,
                    num_replicas: int = 1, max_len: int = 512,
                    checkpoint_path: Optional[str] = None):
    dep = LlamaDeployment.options(num_replicas=num_replicas)
    return dep.bind(config, None, max_len,
                    checkpoint_path=checkpoint_path)


__all__ = ["LlamaDeployment", "build_llama_app"]


class _StreamLag:
    """One stream's tokens on their way out of the replica, on
    ``time.time()`` (the request chain's clock): ``handoff`` from a
    token's landing on the host (the engine's stamp) to the stream's
    consumer taking it out of the request's buffer (the replica's loop,
    which got the landing in one call), ``store`` from the stream
    handing it out to its consumer coming back for more (the runtime
    stored and announced the item). A token costs a share of two clock
    reads and a few float adds on this object; the metrics registry is
    touched at the stream's end and every ``FLUSH_EVERY`` items, never
    once a token."""

    FLUSH_EVERY = 64
    __slots__ = ("items", "handoff_s", "handoff_max_s", "store_s",
                 "first_landed", "last_landed", "last_got", "_tags",
                 "_flushed")

    def __init__(self, tags: Dict[str, str]):
        self._tags = tags
        self.items = 0
        self.handoff_s = self.handoff_max_s = self.store_s = 0.0
        self.first_landed = self.last_landed = self.last_got = 0.0
        self._flushed = (0, 0.0, 0.0)   # items, handoff_s, store_s

    def note(self, landed: float, got: float, resumed: float) -> None:
        """A token that landed at ``landed`` left the buffer at ``got``
        and the consumer came back for the next at ``resumed``."""
        handoff = got - landed
        self.handoff_s += handoff
        if handoff > self.handoff_max_s:
            self.handoff_max_s = handoff
        self.store_s += resumed - got
        if not self.items:
            self.first_landed = landed
        self.last_landed, self.last_got = landed, got
        self.items += 1
        if self.items % self.FLUSH_EVERY == 0:
            self.flush()

    @property
    def handoff_mean_s(self) -> float:
        return self.handoff_s / self.items if self.items else 0.0

    def flush(self) -> None:
        from ray_tpu._private import metrics_defs as mdefs

        items, handoff_s, store_s = self._flushed
        if self.items == items:
            return
        mdefs.SERVE_STREAM_REPLICA_ITEMS.inc(self.items - items,
                                             tags=self._tags)
        mdefs.SERVE_STREAM_HANDOFF_SECONDS.inc(self.handoff_s - handoff_s,
                                               tags=self._tags)
        mdefs.SERVE_STREAM_STORE_SECONDS.inc(self.store_s - store_s,
                                             tags=self._tags)
        self._flushed = (self.items, self.handoff_s, self.store_s)

    def close(self, trace: Optional[Dict[str, Any]]) -> None:
        """The stream is over: flush, and for a traced request close its
        chain on the replica's side with one summary span,
        ``engine.stream`` (first landing to the last token leaving the
        buffer)."""
        self.flush()
        if trace is None or not self.items:
            return
        from ray_tpu.util import tracing

        tracing.emit_span(
            "engine.stream", trace_id=trace.get("trace_id", ""),
            parent_span_id=trace.get("parent_span_id", ""),
            ts=self.first_landed, dur=self.last_got - self.first_landed,
            kind="engine", request_id=trace.get("request_id", ""),
            tokens=self.items, handoff_mean_s=self.handoff_mean_s,
            handoff_max_s=self.handoff_max_s,
            store_mean_s=self.store_s / self.items,
            landed_first_ts=self.first_landed,
            landed_last_ts=self.last_landed)


_STREAM_END = object()


def _spread(entries) -> None:
    """The consumers' side of a hand-over: each ``(stream, entry)`` into
    its stream's buffer. On the replica's loop this is ONE callback a
    landing, whatever the number of open streams."""
    for stream, entry in entries:
        stream._take(entry)


class _TokenStream:
    """What ``generate`` and ``decode_from`` return: ONE request's tokens
    over ONE buffer, for either kind of consumer. The replica drives it
    from its event loop (``async for``): no thread exists for the
    stream, the tick thread's one call a landing appends to the buffer
    and resolves the future the consumer awaits, and a stream the loop
    reaches late hands out everything it holds as one
    :class:`~ray_tpu.serve.api.StreamBatch` (token by token where it
    keeps up). A direct caller iterates it (``for``, ``list``) and waits
    on its own thread. Buffer entries: ``(token, landed_ts)``, a control
    object (a dict), ``None`` for the end, an exception to raise in
    their place (an engine error, the item timeout, a simulated death).

    The engine lock (submit or import at the start; cancel or the
    request's lag at the end) is never taken on the loop: a stream that
    starts or ends leaves itself in one of the deployment's two queues
    and a task with the deployment's one hop thread, where ONE hold of
    the lock does everything either queue holds
    (:meth:`ContinuousLlamaDeployment._turn`); what the engine refuses
    comes back through the buffer like any error. A consumer that leaves
    early calls ``close()`` (``aclose()`` on the loop), which frees the
    slot; one that drops the stream without (a ``break`` out of a
    ``for``) leaves that to ``__del__``: the deployment holds its
    streams weakly."""

    def __init__(self, dep: "ContinuousLlamaDeployment",
                 open_request: Optional[Callable[[], int]],
                 trace: Optional[Dict[str, Any]],
                 chaos_tokens: Optional[int] = None,
                 first: Optional[int] = None):
        """``open_request`` submits or imports under the engine lock
        and returns the engine's request id (None: a stream with nothing
        to say); ``chaos_tokens`` is the prompt length of the
        ``phase=prefill`` chaos site in front of it; ``first`` a token
        the stream opens with (an imported handoff's)."""
        self._dep = dep
        self._open = open_request
        self._trace = trace
        self._chaos_tokens = chaos_tokens
        self._first = first
        self._entered = time.time()
        self._buf: collections.deque = collections.deque()
        self._lag = _StreamLag(dep.batcher._mtags)
        self.rid: Optional[int] = None
        self._started = self._done = self._ended = False
        self._emitted = 0
        # The tokens handed out at the last turn: their landing stamps,
        # and when they left the buffer.
        self._out: List[float] = []
        self._got = 0.0
        # The loop's consumer: its loop, the future it awaits, the timer
        # of ``STREAM_ITEM_TIMEOUT_S``. A synchronous one: its event.
        self._loop = self._waiter = self._timer = None
        self._event: Optional[threading.Event] = None
        if open_request is None:
            self._started = True
            self._buf.append(None)

    # ------------------------------------------------ the engine's side
    def _take(self, entry) -> None:
        self._buf.append(entry)
        waiter = self._waiter
        if waiter is not None:
            self._waiter = None
            try:
                if not waiter.done():
                    waiter.set_result(None)
            except RuntimeError:    # its loop is closed: nobody waits
                pass
        elif self._event is not None:
            self._event.set()

    def _start(self, locked: float) -> List[tuple]:
        """Under the engine lock (held since ``locked``): submit or
        import, and register the stream with the deployment (under the
        same hold: no landing can fall between the two). Returns what
        the stream opens with, as ``(stream, entry)`` pairs to ship
        under that hold too, ahead of the next landing."""
        if self._ended:         # the consumer left before it began
            return []
        dep = self._dep
        rid = self._open()
        # Registered and named BEFORE the check below: a consumer that
        # leaves now either finds the id (and settles it) or has set
        # ``_ended`` for this thread to find.
        dep._streams[rid] = self
        self.rid = rid
        dep.batcher.note_submit_wait(rid, self._entered, locked)
        if self._ended:
            dep._streams.pop(rid, None)
            dep.batcher.cancel(rid)
            return []
        if self._first is None:
            return []
        # The engine made its first-token hand-over during the import,
        # before the stream could be registered under the fresh rid.
        return [(self, (self._first, dep.batcher.landed_ts))]

    # ---------------------------------------------- the consumer's side
    def _begin(self) -> None:
        """The consumer's first turn, in the request's own context, before
        the engine lock: the ``phase=prefill`` chaos site, ``serve.hop``
        (the router's ``remote()`` to the replica method's entry; only a
        traced request carries ``route_ts``), and a place among the
        streams that wait to start."""
        self._started = True
        if self._chaos_tokens is not None and chaos.enabled():
            chaos.inject("serve_replica", phase="prefill",
                         tokens=self._chaos_tokens)
        trace = self._trace
        if trace is not None and trace.get("route_ts") is not None:
            from ray_tpu.util import tracing

            tracing.emit_span(
                "serve.hop", trace_id=trace.get("trace_id", ""),
                parent_span_id=trace.get("parent_span_id", ""),
                ts=trace["route_ts"],
                dur=self._entered - trace["route_ts"],
                kind="route", request_id=trace.get("request_id", ""),
                deployment=trace.get("deployment", ""))
        self._dep._starting.append(self)

    def _pop(self):
        """The buffer's next entry as the item to hand out."""
        entry = self._buf.popleft()
        if entry is None:
            self._done = True
            return _STREAM_END
        if isinstance(entry, BaseException):
            raise entry
        if isinstance(entry, dict):     # a control object
            return entry
        token, landed = entry
        if chaos.enabled():
            # Fires BEFORE the token is handed out: a rule with token=N
            # dies with exactly N tokens delivered downstream.
            chaos.inject("serve_replica", phase="decode",
                         token=self._emitted)
        self._emitted += 1
        self._out.append(landed)
        return token

    def _more(self) -> bool:
        """Whether the buffer's next entry is an item: not the end of
        the stream, not an error."""
        return bool(self._buf) and self._buf[0] is not None \
            and not isinstance(self._buf[0], BaseException)

    def _resumed(self) -> None:
        """The consumer is back for more: book the last turn's tokens."""
        if self._out:
            now = time.time()
            for landed in self._out:
                self._lag.note(landed, self._got, now)
            del self._out[:]

    def _finish(self, inline: bool = True) -> None:
        """The stream is closing: book its lag, and either keep it
        beside the request's record or, for a stream abandoned before
        its end (client gone, cancel, simulated process death), free
        the slot so the ghost request stops burning decode ticks. Both
        need the engine lock: the stream leaves them in the deployment's
        queue, for the hop thread or, a synchronous consumer
        (``inline``), for its own."""
        if self._ended:
            return
        self._ended = True
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
        if self.rid is None:    # not started: ``_start`` finds ``_ended``
            return
        dep = self._dep
        dep._streams.pop(self.rid, None)
        self._lag.close(self._trace)
        dep._settling.append((self.rid, self._lag, self._done))
        if inline and self._loop is None:
            dep._turn()
        else:
            dep._hops.submit(dep._turn)

    def __del__(self):
        # Dropped without ``close()``. Never inline: the last reference
        # may go on the tick thread, under the engine lock.
        try:
            self._finish(inline=False)
        except Exception:  # noqa: BLE001 — the interpreter is going down
            pass

    def __iter__(self):
        return self

    def __next__(self):
        if self._ended:
            raise StopIteration
        try:
            self._resumed()
            if not self._started:
                self._event = threading.Event()
                self._begin()
                self._dep._turn()
            while not self._buf:
                self._event.clear()
                if not self._buf and not self._event.wait(
                        STREAM_ITEM_TIMEOUT_S):
                    raise TimeoutError(
                        f"no token in {STREAM_ITEM_TIMEOUT_S} s")
            self._got = time.time()
            item = self._pop()
            if item is _STREAM_END:
                raise StopIteration
            return item
        except BaseException:
            self._finish()
            raise

    def close(self) -> None:
        self._finish()

    def __aiter__(self):
        return self

    async def __anext__(self):
        if self._ended:
            raise StopAsyncIteration
        try:
            self._resumed()
            if not self._started:
                self._loop = self._dep._loop = asyncio.get_running_loop()
                self._begin()
                self._dep._hops.submit(self._dep._turn)
                self._got = time.time()
                self._timer = self._loop.call_later(
                    STREAM_ITEM_TIMEOUT_S, self._overdue)
            while not self._buf:
                self._waiter = self._loop.create_future()
                await self._waiter
            self._got = time.time()
            item = self._pop()
            if item is _STREAM_END:
                raise StopAsyncIteration
            if self._more():
                # The loop reached this stream late: what piled up
                # ships as one object, cut in front of the end and of
                # a simulated death, which come a turn later.
                item = StreamBatch((item,))
                try:
                    while self._more():
                        item.append(self._pop())
                except BaseException as e:  # noqa: BLE001 — raised next turn
                    self._buf.appendleft(e)
            return item
        except BaseException:
            self._finish()
            raise

    async def aclose(self) -> None:
        self._finish()

    def _overdue(self) -> None:
        """``STREAM_ITEM_TIMEOUT_S`` on the loop, as ONE timer a stream
        that re-arms itself for what is left while items keep leaving
        (or lie there untaken): a token costs the loop no timer."""
        left = STREAM_ITEM_TIMEOUT_S - (time.time() - self._got)
        if left > 0 or self._buf:
            self._timer = self._loop.call_later(
                max(left, 1.0), self._overdue)
            return
        self._timer = None
        self._take(TimeoutError(f"no token in {STREAM_ITEM_TIMEOUT_S} s"))


@serve.deployment
class ContinuousLlamaDeployment:
    """Continuous-batching completion replica (reference: the vLLM engine
    behind ``ray.serve.llm``): one shared slot pool per replica; requests
    join mid-flight and stream tokens as decode ticks produce them. Use
    with handle ``stream=True`` (or plain calls for full completions)."""

    # Constructor options that are gone, and what a serve config whose
    # ``init_kwargs`` still sets one is told when it deploys.
    removed_init_kwargs = {
        "paged": "paged= was removed in PR 27: the paged arena is the "
                 "only KV plane, drop the key"}

    def __init__(self, config: Optional[llama.LlamaConfig] = None,
                 params=None, num_slots: int = 8, max_len: int = 512,
                 eos_token: Optional[int] = None,
                 use_decode_kernel: Optional[bool] = None,
                 block_size: int = 64,
                 kv_dtype: Optional[str] = None,
                 num_blocks: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 sampling=None,
                 spec_k: Optional[int] = None,
                 spec_draft_layers: Optional[int] = None,
                 spec_adaptive: Optional[bool] = None,
                 checkpoint_path: Optional[str] = None,
                 role: Optional[str] = None,
                 prefill_chunk: Optional[int] = None):
        """Engine knobs (``num_slots``, ``max_len``,
        ``use_decode_kernel``, and the paged-KV plane's
        ``block_size`` / ``kv_dtype`` / ``num_blocks`` / ``sampling``)
        pass straight to the ContinuousBatcher and are overridable
        per-deploy via the serve config ``init_kwargs`` (see
        serve/config.py) — no application-module edits to retune a
        replica. ``sampling`` accepts a
        :class:`~ray_tpu.models.sampling.SamplingParams` or a plain dict
        (``{"temperature": 0.7, "top_p": 0.9, "seed": 0}``), which is
        what YAML-sourced deploy configs produce. ``checkpoint_path``
        cold-starts params from a training run's newest committed
        checkpoint (manifest plane).

        Speculative decoding rides the same path: ``spec_k`` (or
        ``RAY_TPU_SPEC_K``) enables draft-and-verify decode at depth k,
        ``spec_draft_layers`` sizes the truncated self-drafter, and
        ``spec_adaptive`` lets the accept-rate controller ladder k (down
        to 0 = the plain tick). All three are ordinary ``init_kwargs``
        overrides, so a YAML deploy config can turn speculation on per
        deployment.

        ``role`` (or ``RAY_TPU_SERVE_ROLE``) makes this a disaggregated
        replica: ``"prefill"`` replicas serve :meth:`prefill` (admission
        + paged prefill, then export the KV handoff), ``"decode"``
        replicas serve :meth:`decode_from` / :meth:`reserve_kv` (import
        the handoff and run the decode ticks) — plus every colocated
        entry point. The default ``"both"`` is the ordinary colocated
        engine."""
        import uuid
        from concurrent.futures import ThreadPoolExecutor

        import ray_tpu
        from ray_tpu._private import metrics_defs as mdefs
        from ray_tpu._private.accelerators.tpu import TPUAcceleratorManager
        from ray_tpu.models.continuous_batching import ContinuousBatcher

        # A replica that holds a chip puts its whole engine on THAT chip;
        # one that holds none (CPU runs) leaves placement to JAX.
        chips = ray_tpu.get_runtime_context().get_accelerator_ids()["TPU"]
        self.device = (TPUAcceleratorManager.jax_device(int(chips[0]))
                       if chips else None)
        self.config = config or llama.LlamaConfig.tiny()
        if params is None and checkpoint_path:
            params = _params_from_checkpoint(checkpoint_path)
        # The open streams by engine request id, each a buffer (a
        # deque: its append takes no Python-level lock, so the hand-over
        # never waits for a consumer). The tick thread hands a
        # landing's tokens to ``_loop``, the event loop that drives the
        # streams (the replica's; learned from the first stream driven
        # that way), in ONE ``call_soon_threadsafe``, and the loop
        # spreads them: no thread exists per open stream and none is
        # woken per token. With a thread a stream (until PR 41) a tick
        # over 256 rows made 256 sleeping threads runnable, and the tick
        # thread queued for the interpreter lock behind them every time
        # it let go of it. While no loop drives a stream (a direct
        # caller iterating on its own thread) the tick thread spreads
        # the landing itself.
        # Held weakly: a stream its consumer dropped without closing
        # (a ``break`` out of a ``for``) is collected, and frees its
        # slot from ``__del__``.
        self._streams: "weakref.WeakValueDictionary[int, _TokenStream]" \
            = weakref.WeakValueDictionary()
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        # Where a request takes the engine lock, off the loop: once at
        # its start (submit), once at its end (cancel, or its lag). It
        # leaves itself in ``_starting`` or ``_settling`` and a task
        # with the ONE hop thread, whose every hold of the lock does
        # everything both queues hold (``_turn``): a wave of requests
        # that arrives during a prefill is admitted together, and a
        # burst of endings keeps no start from the lock. A burst that
        # is still arriving at an engine with nothing live is given
        # room once before it starts (``_burst_room``).
        self._hops = ThreadPoolExecutor(max_workers=1,
                                        thread_name_prefix="llm-req")
        self._starting: collections.deque = collections.deque()
        self._settling: collections.deque = collections.deque()
        self._handoffs = mdefs.SERVE_STREAM_HANDOFFS
        self._lock = threading.Lock()
        self._pressure: Dict[str, Any] = {}     # the last snapshot read
        self._work = threading.Event()
        self.batcher = ContinuousBatcher(
            self.config, params=params, num_slots=num_slots,
            max_len=max_len, eos_token=eos_token,
            landing_callback=self._on_landing,
            use_decode_kernel=use_decode_kernel,
            block_size=block_size, kv_dtype=kv_dtype,
            num_blocks=num_blocks, prefix_cache=prefix_cache,
            sampling=sampling, spec_k=spec_k,
            spec_draft_layers=spec_draft_layers,
            spec_adaptive=spec_adaptive, role=role, device=self.device,
            # None leaves the engine's own default.
            **({} if prefill_chunk is None
               else {"prefill_chunk": prefill_chunk}))
        # Reservation tickets are engine-local ids; the nonce scopes a
        # ticket to THIS replica so a router whose reserve and
        # decode_from calls landed on different replicas cannot spend
        # one replica's ticket against another's arena.
        self._nonce = uuid.uuid4().hex[:16]
        threading.Thread(target=self._tick_loop, daemon=True,
                         name="llm-ticks").start()

    def _on_landing(self, tokens: List[tuple], landed: float) -> None:
        """On the tick thread, once a landing, right after its tokens
        are booked: each token of a request whose stream is open rides
        with the landing's stamp (the stream's handoff clock,
        ``_StreamLag``) to the streams' side in ONE call. A token of a
        request with no stream registered NOW is dropped here, not on
        the loop later (an import's first token, which ``decode_from``
        delivers itself)."""
        streams = self._streams
        entries = [(stream, (token, landed)) for rid, token in tokens
                   if (stream := streams.get(rid)) is not None]
        if entries:
            self._handoffs.inc(tags=self.batcher._mtags)
            self._ship(entries)

    def _ship(self, entries: List[tuple]) -> None:
        """``(stream, entry)`` pairs to the streams' buffers: one call
        into the loop that drives them, which spreads them there."""
        loop = self._loop
        if loop is not None:
            try:
                loop.call_soon_threadsafe(_spread, entries)
                return
            except RuntimeError:    # closed, and its streams with it
                self._loop = None
        _spread(entries)

    def _tick_loop(self) -> None:
        import logging

        from ray_tpu._private import metrics_defs as mdefs
        from ray_tpu.util import tracing

        log = logging.getLogger(__name__)
        tags = self.batcher._mtags
        while True:
            self._work.wait()
            try:
                # Callers take this lock to submit and cancel. A step
                # leaves one tick queued on the device, so the device
                # idles only if this thread gets the lock back later
                # than that tick ends.
                with tracing.phase("engine.lock_wait",
                                   mdefs.CB_STEP_LOCK_WAIT_MS, tags):
                    self._lock.acquire()
                try:
                    if not self.batcher.has_work():
                        self._work.clear()
                        continue
                    finished = self.batcher.step()
                finally:
                    self._lock.release()
                with tracing.phase("engine.apply", mdefs.CB_STEP_APPLY_MS,
                                   tags):
                    ends = []
                    for rid in finished:
                        stream = self._streams.get(rid)
                        routes = self.batcher.take_routes(rid)
                        if stream is not None:
                            if routes is not None:
                                ends.append((stream, {"routes": routes}))
                            ends.append((stream, None))  # end-of-stream
                    if ends:
                        # Behind the step's landings in the loop's
                        # queue: after each request's last token.
                        self._ship(ends)
            except Exception as e:  # noqa: BLE001
                # Engine error (OOM, bad request reaching the kernel):
                # fail every in-flight stream explicitly and reset the
                # slot pool, instead of dying silently and leaving
                # clients waiting on their streams.
                log.exception("continuous-batching tick failed; "
                              "aborting in-flight requests")
                with self._lock:
                    self.batcher.reset()
                    streams = list(self._streams.values())
                self._ship([(stream, e) for stream in streams])

    @staticmethod
    def _request_trace() -> Optional[Dict[str, Any]]:
        """The serve request context of the CALLING request (set by the
        replica before user code runs; rides the contextvar through the
        sync executor hop), normalized into the engine's trace dict. The
        tenant falls back to the multiplexed model id so per-tenant
        TTFT/TPOT attribution works even for callers that built their
        own context."""
        from ray_tpu.serve import multiplex
        from ray_tpu.serve.context import get_request_context

        rctx = get_request_context()
        if rctx is None:
            return None
        trace = dict(rctx)
        trace.setdefault("tenant", multiplex.get_request_tenant())
        return trace

    def _turn(self) -> None:
        """Under ONE hold of the engine lock, which the tick thread holds
        across each step: everything that waits for it. Every stream
        that has closed is settled (one read to its end leaves its
        tokens' mean lag beside the request's record; one abandoned
        before it frees the slot), then every stream that waits to start
        is submitted (or imported), in arrival order. What precedes the
        engine's own TTFT clock is the wait for the lock, which each
        stream hands to ``batcher.note_submit_wait`` with the request id
        its submit returned; what the engine refuses goes to that
        stream's consumer alone. A task that finds both queues empty was
        served by an earlier hold and takes none. A burst still running
        at an idle engine's door is given room once, without the lock
        (:meth:`_burst_room`), and started under a second hold."""
        if not (self._starting or self._settling):
            return
        with self._lock:
            locked = time.time()
            self._settle_waiting()
            room = self._burst_room(locked)
            if not room:
                self._start_waiting(locked)
        if room:
            time.sleep(room)
            with self._lock:
                self._start_waiting(time.time())
        self._work.set()

    def _settle_waiting(self) -> None:
        """Under the engine lock: every stream that has closed."""
        while self._settling:
            rid, lag, done = self._settling.popleft()
            try:
                if not done:
                    self.batcher.cancel(rid)
                elif lag.items:
                    self.batcher.note_stream(rid, lag.handoff_mean_s)
            except Exception:  # noqa: BLE001 — the others still settle
                import logging

                logging.getLogger(__name__).exception(
                    "settling stream %s failed", rid)

    def _start_waiting(self, locked: float) -> None:
        """Under the engine lock (held since ``locked``): every stream
        that waits to start, in arrival order."""
        opened: List[tuple] = []
        while self._starting:
            stream = self._starting.popleft()
            try:
                opened.extend(stream._start(locked))
            except BaseException as e:  # noqa: BLE001 — that stream's
                # alone, a simulated death (``kv_transfer``'s chaos
                # site) included: its consumer raises it.
                opened.append((stream, e))
        if opened:
            self._ship(opened)

    def _burst_room(self, now: float) -> float:
        """Under the engine lock: how long to hold back the streams that
        wait to start, or 0. Several streams at the door of an engine
        with no request live or queued came in while its last step ran:
        a burst. If its newest member came more recently than the burst
        has lasted, the burst is likely still running, and an engine
        that starts now spends the next step (a prefill batch, with the
        lock) on a part of it while the rest waits a whole step for a
        second, padded batch: the burst is given as long again as it
        has lasted, once, and started together. One stream, a live
        engine or a burst that has paused waits for nothing."""
        if len(self._starting) < 2 or self.batcher._slots \
                or self.batcher._waiting:
            return 0.0
        oldest, newest = self._starting[0], self._starting[-1]
        lasted = newest._entered - oldest._entered
        return lasted if now - newest._entered < lasted else 0.0

    def engine_info(self) -> Dict[str, Any]:
        """What this replica's engine actually runs and where: the
        resolved data-plane switches, the device its arrays live on and
        that device's memory (``chip_smoke.py`` checks these per
        replica)."""
        import jax

        eng = self.batcher
        dev = self.device or jax.devices()[0]
        return {"use_decode_kernel": eng.use_decode_kernel,
                "kv_dtype": eng.kv_dtype,
                "device": {"id": dev.id, "platform": dev.platform,
                           "kind": dev.device_kind},
                "params_device_ids": sorted({
                    d.id for x in jax.tree.leaves(eng.params)
                    for d in x.devices()}),
                "arena_device_ids": sorted(
                    d.id for d in eng.cache.k.devices()),
                "memory_stats": dev.memory_stats()}

    def pressure(self) -> Dict[str, Any]:
        """Live engine pressure for the serve pressure endpoint (queue
        depth, KV blocks free, in-flight prefill tokens — the
        prefix/KV-pressure router's input). Under the engine lock: the
        snapshot iterates the waiting queue, which the tick thread
        mutates."""
        # ... but never WAITING for it: the tick thread holds that lock
        # for most of every step and every waiting submit queues on it,
        # so under a full engine a snapshot could wait for seconds, on
        # one of the replica's eight control threads; a few of those and
        # the controller's health probes, which share them, went
        # unanswered for a minute and it killed a replica serving 256
        # streams (PR 38). A busy lock returns the last snapshot read.
        if self._lock.acquire(timeout=0.05):
            try:
                self._pressure = self.batcher.pressure_snapshot()
            finally:
                self._lock.release()
        return self._pressure

    def request_breakdowns(self, n: int = 100) -> List[Dict[str, Any]]:
        """The newest ``n`` ended requests' records, oldest first: why
        was a request slow? Before its first token ``lock_wait_s``,
        ``queue_s``, ``arena_wait_s``, ``prefill_s`` (``ttft_s``); after
        it ``tpot_s``, ``stalled_s`` / ``stall_count`` (other requests'
        prefill batches it stood still through) and ``handoff_mean_s``
        (its tokens' mean lag from landing to leaving this replica's
        queue); with ``request_id`` and ``trace_id`` to find its spans."""
        with self._lock:
            recs = list(self.batcher.request_breakdowns)
        return [dict(rec) for rec in recs[max(len(recs) - int(n), 0):]]

    # ---------------------------------------- RL weight-sync plane (rl/)
    def weight_version(self) -> int:
        """Version of the params currently serving (0 = cold-start)."""
        return self.batcher.weight_version

    def swap_weights(self, weights, version: Optional[int] = None,
                     cause: str = "publish", manifest: Optional[dict] = None,
                     run: Optional[str] = None) -> int:
        """Swap the live params at a tick boundary.

        Taking ``self._lock`` IS the tick-boundary guarantee: the tick
        thread holds the same lock around ``batcher.step()``, so the swap
        lands strictly between steps (the one tick a step leaves queued
        on the device finishes on the old weights) — in-flight requests
        keep their KV cache and continue under the new weights,
        un-dropped. Emits the
        ``rl.weight_swap`` flight event (caused by the trainer's publish
        event when a ``manifest`` is supplied, so ``ray-tpu why run``
        reconstructs the publish→swap chain) and counts the swap by
        cause. Returns the version now live."""
        import time as _time

        from ray_tpu._private import events as _events
        from ray_tpu._private import metrics_defs as mdefs

        manifest = manifest or {}
        run = run or manifest.get("run") or "rl"
        with self._lock:
            v = self.batcher.swap_params(weights, version=version)
        attrs = {"version": v, "swap_cause": cause}
        if manifest.get("ts"):
            # Trainer-publish → generator-live end-to-end latency.
            attrs["e2e_seconds"] = round(
                max(_time.time() - float(manifest["ts"]), 0.0), 6)
        _events.emit("rl.weight_swap", cause=manifest.get("event_id", ""),
                     subject={"run": run}, **attrs)
        mdefs.RL_SWAPS.inc(tags={"run": run, "cause": cause})
        mdefs.RL_VERSION.set(v, tags={"run": run, "role": "generator"})
        return v

    def enable_weight_sync(self, spec, run: str = "rl",
                           poll_s: float = 0.05,
                           target_shardings=None) -> None:
        """Start the subscriber poll thread: fast path reads the trainer's
        weight channel (``spec`` = a pickled channel reader attach-spec),
        and when the fast path breaks (writer gone, shed while lagging)
        the ladder falls back to the crc32-verified checkpoint manifest —
        both land through :meth:`swap_weights`, never mid-tick."""
        import logging
        import threading
        import time as _time

        from ray_tpu.rl.weight_sync import WeightSubscriber

        log = logging.getLogger(__name__)
        sub = (spec if isinstance(spec, WeightSubscriber)
               else WeightSubscriber(spec, run=run,
                                     target_shardings=target_shardings))
        self._subscriber = sub
        self._sync_stop = threading.Event()

        def _loop():
            while not self._sync_stop.is_set():
                try:
                    got = sub.poll(timeout=poll_s)
                except Exception:  # noqa: BLE001 — fast path down
                    try:
                        manifest, params = sub.restore_fallback()
                        if int(manifest["version"]) > \
                                self.batcher.weight_version:
                            self.swap_weights(
                                params, version=int(manifest["version"]),
                                cause="fallback", manifest=manifest,
                                run=run)
                    except Exception:  # noqa: BLE001
                        log.exception("rl: weight-sync fallback failed")
                    _time.sleep(max(poll_s, 0.05))
                    continue
                if got is None:
                    continue
                manifest, params = got
                self.swap_weights(params,
                                  version=int(manifest["version"]),
                                  cause="publish", manifest=manifest,
                                  run=run)

        t = threading.Thread(target=_loop, daemon=True,
                             name="rl-weight-sync")
        t.start()
        self._sync_thread = t

    def disable_weight_sync(self) -> None:
        stop = getattr(self, "_sync_stop", None)
        if stop is not None:
            stop.set()

    def score_logprobs(self, prompt_token_ids,
                       token_ids) -> List[float]:
        """Teacher-forced behavior logprobs of ``token_ids`` given
        ``prompt_token_ids`` under the CURRENT live params (the RL
        experience path's behavior policy). Under the engine lock so the
        params can't swap mid-score."""
        with self._lock:
            lp = self.batcher.score_logprobs(list(prompt_token_ids),
                                             list(token_ids))
        return [float(x) for x in lp]

    def generate(self, prompt_token_ids,
                 max_tokens: int = 16):
        """A stream of token ids (serve stream=True surface): a
        :class:`_TokenStream`, which the replica drives from its loop
        (``async for``) and a direct caller iterates (``for``, ``list``).
        Accepts either the token-id list directly or the ingress payload
        dict (``{"prompt_token_ids": [...], "max_tokens": N}``) — the
        HTTP/gRPC streaming routes (``POST /<name>/stream/generate``)
        hand the whole JSON payload through as one argument, and the
        recovery journal resubmits exactly that payload shape.

        Chaos sites (``_private/chaos.py`` ``kill_replica``): before the
        engine submit (``phase=prefill`` — the request is queued-or-
        prefilling, nothing streamed) and before handing out the Nth
        token (``phase=decode,token=N`` — mid-decode, N tokens already
        streamed: what had piled up in front of it ships first). The
        raised ``SimulatedProcessDeath`` unwinds through the replica
        actor's task machinery into genuine actor death — exactly what
        the ingress journal recovers from.

        ``"return_routes": true`` in the payload (a held expert share
        alone): after the last token the stream carries one control
        object, ``{"routes": [position][routed layer][k]}``, the experts
        each decoded position routed to
        (``ContinuousBatcher.take_routes``)."""
        resumed_tokens = 0
        keep_routes = False
        if isinstance(prompt_token_ids, dict):
            payload = prompt_token_ids
            prompt_token_ids = payload["prompt_token_ids"]
            max_tokens = payload.get("max_tokens", max_tokens)
            keep_routes = bool(payload.get("return_routes", False))
            resumed_tokens = int(payload.get("resumed_tokens", 0) or 0)
        trace = self._request_trace()
        if resumed_tokens and self.batcher.eos_token is not None \
                and prompt_token_ids \
                and prompt_token_ids[-1] == self.batcher.eos_token:
            # Mid-decode RESUME whose last already-delivered token was
            # EOS: the original generation had finished — only the
            # end-of-stream sentinel died with the replica. Decoding
            # the leftover budget would append post-EOS garbage the
            # un-killed run never produced. (Only resumes check this:
            # an ORIGINAL prompt may legitimately end with EOS.)
            return _TokenStream(self, None, trace)
        prompt = list(prompt_token_ids)

        def submit() -> int:
            return self.batcher.submit(prompt,
                                       max_new_tokens=int(max_tokens),
                                       trace=trace, keep_routes=keep_routes)

        return _TokenStream(self, submit, trace, chaos_tokens=len(prompt))

    # ------------------------------------ disaggregated prefill/decode
    def _req_deployment(self) -> str:
        from ray_tpu.serve.context import get_request_context

        rctx = get_request_context()
        return (rctx or {}).get("deployment", "")

    def prefill(self, payload: Dict[str, Any]) -> Dict[str, Any]:
        """Prefill-role unary: admission + paged prefill for the
        payload, then export the finished arena blocks as a KV handoff.
        Returns the transfer MANIFEST (staging bytes already staged in
        a shm channel; the manifest carries the reader attach-spec) —
        the router journals it and opens the decode stream. Requests
        that finish AT the first token (``max_tokens == 1``, an EOS
        first token, or a resumed prompt already ending in EOS) return
        ``{"done": [...]}`` instead: the whole completion happened
        here, nothing to hand off.

        Chaos: ``serve_replica``/``phase=prefill`` before the submit
        (nothing journaled — the router resubmits) and
        ``kv_transfer``/``stage=export`` inside the transfer helper
        (prefill death mid-export — same resubmit leg)."""
        from ray_tpu.serve import kv_transfer

        prompt = list(payload["prompt_token_ids"])
        max_tokens = int(payload.get("max_tokens", 16))
        resumed_tokens = int(payload.get("resumed_tokens", 0) or 0)
        if resumed_tokens and self.batcher.eos_token is not None \
                and prompt and prompt[-1] == self.batcher.eos_token:
            # Mid-decode resume whose last delivered token was EOS: the
            # generation had finished — only the end-of-stream sentinel
            # died with the replica (see generate()).
            return {"done": []}
        trace = self._request_trace()
        stream = _TokenStream(
            self, lambda: self.batcher.submit(
                prompt, max_new_tokens=max_tokens, trace=trace),
            trace, chaos_tokens=len(prompt))
        tokens: List[int] = list(stream)
        rid = stream.rid
        with self._lock:
            if rid not in self.batcher.handoff_ready():
                # Finished entirely at prefill — a complete (short)
                # generation, not a handoff.
                return {"done": tokens}
            return kv_transfer.send_handoff(
                self.batcher, rid, deployment=self._req_deployment())

    def reserve_kv(self, prompt_len: int, max_new: int):
        """Pre-reserve decode arena blocks for an incoming handoff (the
        router calls this BEFORE dispatching prefill). Returns a
        replica-scoped ticket, or None when the arena cannot cover it
        (the import then allocates on arrival). Unspent tickets expire
        engine-side (``RAY_TPU_KV_RESERVE_TTL_S``)."""
        with self._lock:
            res = self.batcher.reserve_import(int(prompt_len),
                                              int(max_new))
        if res is None:
            return None
        return {"res_id": res, "nonce": self._nonce}

    def cancel_reserve(self, ticket) -> bool:
        if not isinstance(ticket, dict) or \
                ticket.get("nonce") != self._nonce:
            return False
        with self._lock:
            return self.batcher.cancel_reservation(ticket["res_id"])

    def decode_from(self, request: Dict[str, Any]):
        """Decode-role streaming entry: collect the journaled KV
        handoff named by ``request["manifest"]`` (shm channel read, crc
        verify, table-scatter into reserved blocks, radix insert) and
        stream EVERY token — the prefill-produced first token included.
        It reaches the caller only through this stream (the unary
        prefill response carries it solely inside the manifest), so the
        router's journal stays the single delivery ledger and greedy
        decode remains exactly-once across deaths.

        Chaos: ``kv_transfer``/``stage=import`` inside the transfer
        helper (decode death after the journaled handoff — the router
        replays as a fresh prefill, ``cause=resume``) and the usual
        ``serve_replica``/``phase=decode`` per-token site."""
        from ray_tpu.serve import kv_transfer

        manifest = request["manifest"]
        ticket = request.get("reservation")
        res_id = None
        if isinstance(ticket, dict) and \
                ticket.get("nonce") == self._nonce:
            res_id = ticket.get("res_id")
        trace = self._request_trace()
        deployment = self._req_deployment()

        def receive() -> int:
            return kv_transfer.receive_handoff(
                self.batcher, manifest, reservation=res_id,
                trace=trace, deployment=deployment)

        # The engine hands over the first token during the import,
        # before a stream could be registered under the fresh rid: the
        # stream opens with the manifest's instead (``_start``).
        return _TokenStream(self, receive, trace,
                            first=int(manifest["first_token"]))

    def __call__(self, request: Dict[str, Any]) -> Dict[str, Any]:
        """Non-streaming completion."""
        tokens = list(self.generate(request["prompt_token_ids"],
                                    request.get("max_tokens", 16)))
        return {"token_ids": tokens}


def _chip_per_replica() -> Dict[str, Any]:
    """Actor options for an engine replica: one whole chip where there
    are chips (each replica must own the device its arena lives on),
    nothing on a CPU-only cluster."""
    import ray_tpu
    from ray_tpu._private.accelerators.tpu import TPUAcceleratorManager

    chips = (ray_tpu.cluster_resources().get("TPU", 0)
             if ray_tpu.is_initialized()
             else TPUAcceleratorManager.detect_num_chips())
    return {"num_tpus": 1} if chips >= 1 else {}


def _ongoing_for(num_slots: int) -> int:
    """A replica's ``max_ongoing_requests`` for an engine of ``num_slots``:
    a stream holds one of those places for as long as it holds a slot
    (and while it waits for one), so under ``num_slots`` places the
    engine's slots cannot all be taken, and with none to spare no request
    waits in the engine's queue for the slot the next ending frees. The
    deployment default of 100 where it leaves an engine four to spare
    (up to 96 slots: those engines keep the places they had), twice the
    slots beyond."""
    default = 100
    return default if num_slots + 4 <= default else 2 * num_slots


def build_continuous_llama_app(config: Optional[llama.LlamaConfig] = None,
                               num_replicas: int = 1, num_slots: int = 8,
                               max_len: int = 512,
                               use_decode_kernel: Optional[bool] = None,
                               block_size: int = 64,
                               kv_dtype: Optional[str] = None,
                               num_blocks: Optional[int] = None,
                               prefix_cache: Optional[bool] = None,
                               sampling=None,
                               spec_k: Optional[int] = None,
                               spec_draft_layers: Optional[int] = None,
                               spec_adaptive: Optional[bool] = None,
                               checkpoint_path: Optional[str] = None,
                               prefill_chunk: Optional[int] = None):
    dep = ContinuousLlamaDeployment.options(
        num_replicas=num_replicas, ray_actor_options=_chip_per_replica(),
        max_ongoing_requests=_ongoing_for(num_slots))
    # Keyword bind so per-deploy ``init_kwargs`` overrides (serve config
    # files) can retarget any engine knob without positional conflicts.
    return dep.bind(config=config, num_slots=num_slots, max_len=max_len,
                    use_decode_kernel=use_decode_kernel,
                    block_size=block_size, kv_dtype=kv_dtype,
                    num_blocks=num_blocks, prefix_cache=prefix_cache,
                    sampling=sampling, spec_k=spec_k,
                    spec_draft_layers=spec_draft_layers,
                    spec_adaptive=spec_adaptive,
                    checkpoint_path=checkpoint_path,
                    prefill_chunk=prefill_chunk)


def build_disagg_llama_apps(name: str = "llm",
                            config: Optional[llama.LlamaConfig] = None,
                            num_prefill: int = 1, num_decode: int = 1,
                            **engine_kwargs):
    """(prefill_app, decode_app) Application pair for disaggregated
    serving, named ``<name>-prefill`` / ``<name>-decode``: the same
    engine knobs on both sides (geometry MUST match — the import
    rejects mismatched block_size/kv_dtype/model dims). Deploy both
    and declare the role group, or use :func:`deploy_disagg_llama`
    which does all three."""
    chip = _chip_per_replica()
    prefill = ContinuousLlamaDeployment.options(
        name=f"{name}-prefill", num_replicas=num_prefill,
        ray_actor_options=chip).bind(
        config=config, role="prefill", **engine_kwargs)
    decode = ContinuousLlamaDeployment.options(
        name=f"{name}-decode", num_replicas=num_decode,
        ray_actor_options=chip).bind(
        config=config, role="decode", **engine_kwargs)
    return prefill, decode


def deploy_disagg_llama(name: str = "llm",
                        config: Optional[llama.LlamaConfig] = None,
                        num_prefill: int = 1, num_decode: int = 1,
                        **engine_kwargs) -> Dict[str, str]:
    """Deploy a disaggregated (prefill, decode) pair and register the
    role group under the logical ``name`` — streaming requests to
    ``/<name>/stream/...`` classify-and-split at the ingress from then
    on. Returns the group mapping."""
    prefill_app, decode_app = build_disagg_llama_apps(
        name=name, config=config, num_prefill=num_prefill,
        num_decode=num_decode, **engine_kwargs)
    serve.run(prefill_app, name=f"{name}-prefill")
    serve.run(decode_app, name=f"{name}-decode")
    serve.register_role_group(name, prefill=f"{name}-prefill",
                              decode=f"{name}-decode")
    return {"prefill": f"{name}-prefill", "decode": f"{name}-decode"}


__all__ += ["ContinuousLlamaDeployment", "build_continuous_llama_app",
            "build_disagg_llama_apps", "deploy_disagg_llama"]

from ray_tpu.llm.batch import LLMBatchWorker, batch_generate  # noqa: E402

__all__ += ["LLMBatchWorker", "batch_generate"]
