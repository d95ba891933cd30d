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

import contextlib
import time
from typing import Any, Dict, List, Optional

import numpy as np

from ray_tpu import serve
from ray_tpu.models import llama
from ray_tpu.models.inference import LlamaGenerator
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
    token's landing on the host (the engine's stamp) to the generator
    thread taking it off the request's queue, ``store`` from the
    generator's yield to its resumption (the runtime stored and
    announced the item). A token costs two clock reads and a few float
    adds on this object; the metrics registry is touched at the stream's
    end and every ``FLUSH_EVERY`` items, never once a token."""

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
        """A token that landed at ``landed`` left the queue at ``got``
        and the generator was resumed after its yield at ``resumed``."""
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
        ``engine.stream`` (first landing to last dequeue)."""
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
                 eos_token: Optional[int] = None, sync_every: int = 1,
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
        """Engine knobs (``num_slots``, ``max_len``, ``sync_every``,
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
        import queue
        import threading
        import uuid

        import ray_tpu
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
        # One stream queue a request. A SimpleQueue's put takes no
        # Python-level lock, so the tick thread's token callbacks never
        # wait for a consumer: with a hundred stream threads sharing the
        # interpreter lock, a consumer descheduled inside Queue.get()
        # held the queue's mutex, and the tick thread with it, for
        # seconds (PR 28, serve_moe_decode).
        self._queues: Dict[int, "queue.SimpleQueue"] = {}
        self._lock = threading.Lock()
        self._pressure: Dict[str, Any] = {}     # the last snapshot read
        self._work = threading.Event()
        self._queue_mod = queue
        self.batcher = ContinuousBatcher(
            self.config, params=params, num_slots=num_slots,
            max_len=max_len, eos_token=eos_token,
            token_callback=self._on_token, sync_every=sync_every,
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

    def _on_token(self, rid: int, token: int) -> None:
        # Called on the tick thread as a landing's tokens are booked:
        # the token rides with that landing's stamp, for the stream's
        # handoff clock (``_StreamLag``).
        q = self._queues.get(rid)
        if q is not None:
            q.put((token, self.batcher.landed_ts))

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
                    for rid in finished:
                        q = self._queues.get(rid)
                        routes = self.batcher.take_routes(rid)
                        if q is not None:
                            if routes is not None:
                                q.put({"routes": routes})
                            q.put(None)  # end-of-stream
            except Exception as e:  # noqa: BLE001
                # Engine error (OOM, bad request reaching the kernel):
                # fail every in-flight stream explicitly and reset the
                # slot pool, instead of dying silently and leaving
                # clients blocked on their queues.
                log.exception("continuous-batching tick failed; "
                              "aborting in-flight requests")
                with self._lock:
                    self.batcher.reset()
                    queues = dict(self._queues)
                for q in queues.values():
                    q.put(e)

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

    @contextlib.contextmanager
    def _submitting(self, entered: float,
                    trace: Optional[Dict[str, Any]]):
        """Hold the engine lock for a submit or import; yields when it
        was got. With ``entered``, the replica method's entry, that is
        what precedes the engine's own TTFT clock: ``serve.hop`` (the
        router's ``remote()`` to ``entered``, emitted here; only a
        traced request carries ``route_ts``) and the wait for the lock,
        which the tick thread holds across each step and which the
        caller hands to ``batcher.note_submit_wait`` with the request
        id its submit returned."""
        from ray_tpu.util import tracing

        if trace is not None and trace.get("route_ts") is not None:
            tracing.emit_span(
                "serve.hop", trace_id=trace.get("trace_id", ""),
                parent_span_id=trace.get("parent_span_id", ""),
                ts=trace["route_ts"], dur=entered - trace["route_ts"],
                kind="route", request_id=trace.get("request_id", ""),
                deployment=trace.get("deployment", ""))
        with self._lock:
            yield time.time()

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
        """Streaming generator of token ids (serve stream=True surface).
        Accepts either the token-id list directly or the ingress payload
        dict (``{"prompt_token_ids": [...], "max_tokens": N}``) — the
        HTTP/gRPC streaming routes (``POST /<name>/stream/generate``)
        hand the whole JSON payload through as one argument, and the
        recovery journal resubmits exactly that payload shape.

        Chaos sites (``_private/chaos.py`` ``kill_replica``): before the
        engine submit (``phase=prefill`` — the request is queued-or-
        prefilling, nothing streamed) and before yielding the Nth token
        (``phase=decode,token=N`` — mid-decode, N tokens already
        streamed). The raised ``SimulatedProcessDeath`` unwinds through
        the replica actor's task machinery into genuine actor death —
        exactly what the ingress journal recovers from.

        ``"return_routes": true`` in the payload (a held expert share
        alone): after the last token the stream carries one control
        object, ``{"routes": [position][routed layer][k]}``, the experts
        each decoded position routed to
        (``ContinuousBatcher.take_routes``)."""
        from ray_tpu._private import chaos

        entered = time.time()
        resumed_tokens = 0
        keep_routes = False
        if isinstance(prompt_token_ids, dict):
            payload = prompt_token_ids
            prompt_token_ids = payload["prompt_token_ids"]
            max_tokens = payload.get("max_tokens", max_tokens)
            keep_routes = bool(payload.get("return_routes", False))
            resumed_tokens = int(payload.get("resumed_tokens", 0) or 0)
        if resumed_tokens and self.batcher.eos_token is not None \
                and prompt_token_ids \
                and prompt_token_ids[-1] == self.batcher.eos_token:
            # Mid-decode RESUME whose last already-delivered token was
            # EOS: the original generation had finished — only the
            # end-of-stream sentinel died with the replica. Decoding
            # the leftover budget would append post-EOS garbage the
            # un-killed run never produced. (Only resumes check this:
            # an ORIGINAL prompt may legitimately end with EOS.)
            return
        q = self._queue_mod.SimpleQueue()
        trace = self._request_trace()
        if chaos.enabled():
            chaos.inject("serve_replica", phase="prefill",
                         tokens=len(prompt_token_ids))
        with self._submitting(entered, trace) as locked:
            rid = self.batcher.submit(list(prompt_token_ids),
                                      max_new_tokens=int(max_tokens),
                                      trace=trace, keep_routes=keep_routes)
            self.batcher.note_submit_wait(rid, entered, locked)
            self._queues[rid] = q
        self._work.set()
        done = False
        emitted = 0
        lag = _StreamLag(self.batcher._mtags)
        try:
            while True:
                token = q.get(timeout=STREAM_ITEM_TIMEOUT_S)
                if token is None:
                    done = True
                    return
                if isinstance(token, Exception):
                    done = True
                    raise token
                if isinstance(token, dict):     # a control object
                    yield token
                    continue
                token, landed = token
                got = time.time()
                if chaos.enabled():
                    # Fires BEFORE the yield: a rule with token=N dies
                    # with exactly N tokens delivered downstream.
                    chaos.inject("serve_replica", phase="decode",
                                 token=emitted)
                emitted += 1
                yield token
                lag.note(landed, got, time.time())
        finally:
            self._stream_ended(rid, lag, trace, done)

    def _stream_ended(self, rid: int, lag: _StreamLag,
                      trace: Optional[Dict[str, Any]], done: bool) -> None:
        """A token stream's generator is closing: book its lag, and
        either keep it beside the request's record or, for a stream
        abandoned before its end (client disconnect, simulated process
        death), free the slot so the ghost request stops burning decode
        ticks."""
        self._queues.pop(rid, None)
        lag.close(trace)
        with self._lock:
            if not done:
                self.batcher.cancel(rid)
            elif lag.items:
                self.batcher.note_stream(rid, lag.handoff_mean_s)

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
        from ray_tpu._private import chaos
        from ray_tpu.serve import kv_transfer

        entered = time.time()
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
        if chaos.enabled():
            chaos.inject("serve_replica", phase="prefill",
                         tokens=len(prompt))
        q = self._queue_mod.SimpleQueue()
        with self._submitting(entered, trace) as locked:
            rid = self.batcher.submit(prompt,
                                      max_new_tokens=max_tokens,
                                      trace=trace)
            self.batcher.note_submit_wait(rid, entered, locked)
            self._queues[rid] = q
        self._work.set()
        tokens: List[int] = []
        try:
            while True:
                item = q.get(timeout=STREAM_ITEM_TIMEOUT_S)
                if item is None:
                    break
                if isinstance(item, Exception):
                    raise item
                tokens.append(item[0])
        finally:
            self._queues.pop(rid, None)
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
        from ray_tpu._private import chaos
        from ray_tpu.serve import kv_transfer

        entered = time.time()
        manifest = request["manifest"]
        ticket = request.get("reservation")
        res_id = None
        if isinstance(ticket, dict) and \
                ticket.get("nonce") == self._nonce:
            res_id = ticket.get("res_id")
        trace = self._request_trace()
        q = self._queue_mod.SimpleQueue()
        with self._submitting(entered, trace) as locked:
            # The engine fires its first-token callback during the
            # import, before any queue could be registered under the
            # fresh rid — the manifest's first_token is delivered
            # explicitly below instead.
            rid = kv_transfer.receive_handoff(
                self.batcher, manifest, reservation=res_id,
                trace=trace, deployment=self._req_deployment())
            self.batcher.note_submit_wait(rid, entered, locked)
            self._queues[rid] = q
        self._work.set()
        done = False
        emitted = 0
        lag = _StreamLag(self.batcher._mtags)
        try:
            if chaos.enabled():
                chaos.inject("serve_replica", phase="decode", token=0)
            emitted = 1
            yield int(manifest["first_token"])
            while True:
                token = q.get(timeout=STREAM_ITEM_TIMEOUT_S)
                if token is None:
                    done = True
                    return
                if isinstance(token, Exception):
                    done = True
                    raise token
                token, landed = token
                got = time.time()
                if chaos.enabled():
                    chaos.inject("serve_replica", phase="decode",
                                 token=emitted)
                emitted += 1
                yield token
                lag.note(landed, got, time.time())
        finally:
            self._stream_ended(rid, lag, trace, done)

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
                               max_len: int = 512, sync_every: int = 1,
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
                    sync_every=sync_every,
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
