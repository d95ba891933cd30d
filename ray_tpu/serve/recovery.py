"""Serve in-flight request recovery: the journal + resume plane.

The serve twin of ``train/elastic.py``'s restart machinery: a replica
death must not cost the caller their request. Every streaming request
dispatched through the ingress router is journaled — its *immutable
submission* (the payload: prompt token ids, sampling knobs, max_tokens),
the tenant, the request's trace context, and the items already streamed
to the caller. When the serving replica dies mid-flight
(``ActorDiedError`` surfacing out of the response stream), the journal
decides the recovery:

* **queued or prefilling** (zero items streamed): the submission is
  simply resubmitted to a live replica — nothing was delivered, so the
  retry is invisible (``cause="resubmit"``).
* **mid-decode** (tokens already streamed): the journal rebuilds the
  request as ``prompt + already-emitted tokens`` with the remaining
  token budget and replays it as a fresh prefill on a live replica
  (``cause="resume"``). Under greedy decoding this is **exactly-once by
  construction**: the next token is a pure function of the context, so
  the resumed stream continues bit-identically (verified by the chaos
  e2e tests). A *sampled* request re-seeds at the resume point — its
  continuation is a fresh draw, surfaced to the client via the
  ``x-ray-tpu-resumed`` marker so exactly-once consumers can tell.
* **draining replica** (clean reject at dispatch,
  ``ReplicaDrainingError``): re-routed to another replica without
  consuming the resume budget — the replica did no work.

Budget: ``RAY_TPU_SERVE_MAX_RESUMES`` (default 2) death recoveries per
request; exhaustion raises the typed
:class:`~ray_tpu.exceptions.ResumeExhaustedError` and tags the request
``resume_exhausted`` in ``ray_tpu_serve_request_outcomes_total``. A
stream that completes after >=1 recovery is tagged ``resumed``.

Every router dispatch path (unary retry in
``serve/api.py::DeploymentResponse.result`` and the streaming path here)
handles ``ActorDiedError`` through this module — a tier-1 source lint
(tests/test_metrics_lint.py) enforces that no bare retry creeps back in.
"""

from __future__ import annotations

import logging
import os
import time
import uuid
from typing import Any, Dict, List, Optional

from ray_tpu import exceptions
from ray_tpu._private import events as _events

logger = logging.getLogger(__name__)


def _flight_resume(j: "RequestJournal", mode: str) -> str:
    """Flight-recorder record of one stream re-route/resume. The
    request id comes from the trace context when present; otherwise one
    is minted at the first recovery and stuck to the journal, so every
    recovery of the request chains under the same subject. The cause is
    inferred best-effort from the in-process ring (newest drain-begin
    for this deployment, else newest drain/injection anywhere)."""
    rid = (j.request_ctx or {}).get("request_id", "")
    if not rid:
        rid = getattr(j, "flight_request_id", "")
        if not rid:
            rid = j.flight_request_id = uuid.uuid4().hex[:16]
    cause = _events.latest_event_id(
        ["serve.drain_begin"], subject={"deployment": j.deployment}) or \
        _events.latest_event_id(["serve.drain_begin", "chaos.inject"])
    return _events.emit(
        "serve.resume", cause=cause,
        subject={"deployment": j.deployment, "request_id": rid},
        mode=mode, emitted=len(j.emitted), attempt=j.resumes)

#: Stream/header marker a client sees when a SAMPLED request was resumed
#: mid-decode (its continuation re-seeded — not the draw the dead
#: replica would have produced). Greedy resumes are exactly-once and
#: carry no marker.
RESUMED_MARKER = "x-ray-tpu-resumed"

#: Sentinel from :meth:`RequestJournal.resume_payload`: every requested
#: token was already delivered before the death — the stream is complete,
#: nothing to resume.
COMPLETE = object()


def max_resumes() -> int:
    """Per-request death-recovery budget (``RAY_TPU_SERVE_MAX_RESUMES``,
    read per decision so tests/operators retune live)."""
    return int(os.environ.get("RAY_TPU_SERVE_MAX_RESUMES", "2"))


#: Drain rejects a single request tolerates before giving up — rejects
#: are free (the replica did no work) but must be bounded so a
#: deployment whose every replica is draining cannot spin a dispatch
#: loop forever. Shared by the streaming journal and the unary path in
#: ``serve/api.py`` (ONE policy, no drift).
DRAIN_REJECT_CAP = 16


def exhausted_error(deployment: str,
                    resumes: int) -> "exceptions.ResumeExhaustedError":
    """The one typed terminal error both dispatch paths raise when the
    resume budget runs out."""
    return exceptions.ResumeExhaustedError(
        f"replica serving {deployment!r} died and the resume budget "
        f"(RAY_TPU_SERVE_MAX_RESUMES={max_resumes()}) is spent",
        resumes=resumes)


def is_llm_payload(payload: Any) -> bool:
    """True for the LLM completion payload shape (``prompt_token_ids``)
    whose streams are token-id items — the only shape resumable
    *mid-stream* (the emitted tokens extend the prompt)."""
    return (isinstance(payload, dict)
            and isinstance(payload.get("prompt_token_ids"), (list, tuple)))


def is_sampled(payload: Any) -> bool:
    """True when the request explicitly asks for sampled decoding —
    the case whose mid-decode resume re-seeds (and gets the
    ``x-ray-tpu-resumed`` marker). Engine-default decoding is greedy
    argmax, so an unannotated payload counts as greedy."""
    if not isinstance(payload, dict):
        return False
    try:
        if float(payload.get("temperature") or 0.0) > 0.0:
            return True
    except (TypeError, ValueError):
        return True  # unparseable knob: assume sampled (be honest)
    s = payload.get("sampling")
    if isinstance(s, dict):
        try:
            return float(s.get("temperature") or 0.0) > 0.0
        except (TypeError, ValueError):
            return True
    return False


class RequestJournal:
    """The immutable submission + delivery ledger of ONE streaming
    request. The payload is never mutated; resume payloads are derived
    copies. ``emitted`` holds exactly the items the consumer has been
    handed (recorded *after* a successful pull, so an item lost in
    flight is replayed, never skipped)."""

    def __init__(self, deployment: str, method: Optional[str],
                 payload: Any, model_id: str = "",
                 request_ctx: Optional[Dict[str, Any]] = None):
        self.deployment = deployment
        self.method = method
        self.payload = payload
        self.model_id = model_id
        # The SAME request context rides every attempt, so a resumed
        # request's spans across two replicas land in ONE trace
        # (`ray-tpu trace request` shows both replicas' engine spans).
        self.request_ctx = request_ctx
        self.emitted: List[Any] = []
        self.resumes = 0          # death recoveries (budgeted)
        self.drain_rejects = 0    # clean re-routes (not budgeted)
        self.resumed_midstream = False
        # Disaggregated prefill/decode: one ledger entry per KV handoff
        # the router committed on this request's behalf (crc32, bytes,
        # attempt). Exactly-once billing hangs off this list — a clean
        # split request journals EXACTLY ONE handoff, and a decode death
        # after the noted handoff recovers as a "resume" (the first
        # token crossed replicas) rather than an invisible resubmit.
        self.handoffs: List[Dict[str, Any]] = []

    # ------------------------------------------------------------ queries
    @property
    def llm(self) -> bool:
        return is_llm_payload(self.payload)

    @property
    def sampled(self) -> bool:
        return is_sampled(self.payload)

    @property
    def needs_marker(self) -> bool:
        """The client must be told: a sampled request was resumed
        mid-decode, so its continuation is a re-seeded draw."""
        return self.resumed_midstream and self.sampled

    def record(self, item: Any) -> None:
        self.emitted.append(item)

    def note_handoff(self, meta: Dict[str, Any]) -> Dict[str, Any]:
        """Journal one prefill→decode KV handoff — idempotent PER
        ATTEMPT, so a retried bookkeeping call cannot double-bill the
        transfer (the double-billing regression asserts a clean split
        request ends with exactly one ledger entry). The entry is the
        manifest's billing-relevant core: crc32, byte/block counts, and
        the attempt that shipped it."""
        attempt = int(meta.get("attempt", self.resumes))
        for entry in self.handoffs:
            if entry.get("attempt") == attempt:
                return entry
        entry = {**meta, "attempt": attempt}
        self.handoffs.append(entry)
        return entry

    def tags(self, engine: str = "router") -> Dict[str, str]:
        return {"deployment": self.deployment, "tenant": self.model_id,
                "engine": engine}

    # ------------------------------------------------------------- resume
    def resume_payload(self) -> Any:
        """The next attempt's submission, derived from the journal:

        * nothing emitted -> the original payload (plain resubmission);
        * mid-stream LLM request -> prompt extended by the emitted
          tokens, ``max_tokens`` reduced by them (:data:`COMPLETE` when
          zero remain);
        * mid-stream non-LLM request -> ``None`` (items already reached
          the caller and the stream has no replay semantics — not
          resumable)."""
        if not self.emitted:
            return self.payload
        if not self.llm:
            return None
        toks: List[int] = []
        for it in self.emitted:
            if isinstance(it, bool) or not isinstance(it, int):
                return None  # non-token items: no replay semantics
            toks.append(int(it))
        try:
            budget = int(self.payload.get("max_tokens", 16))
        except (TypeError, ValueError):
            return None
        remaining = budget - len(toks)
        if remaining <= 0:
            return COMPLETE
        ids = list(self.payload["prompt_token_ids"]) + toks
        # resumed_tokens marks this as a mid-decode REPLAY: the serving
        # deployment uses it to honor an EOS that was already streamed
        # (the generation had finished; only the end-of-stream sentinel
        # was lost with the replica) instead of decoding past it with
        # the leftover budget.
        return {**self.payload, "prompt_token_ids": ids,
                "max_tokens": remaining, "resumed_tokens": len(toks)}


# How long a stream's consumer waits for its NEXT item, at the ingress
# and on the replica alike (``llm.generate`` waits as long on its
# engine). A request that waits for a slot behind a full engine has no
# item to give: a worker pool of twice the engine's slots on minute-long
# answers waits over a minute for its first token, so an ingress less
# patient than the replica it fronts would fail requests the replica
# goes on to serve. A dead replica is not found by this clock
# (``ActorDiedError`` is), only a hung one.
STREAM_ITEM_TIMEOUT_S = 300.0


class RecoverableStream:
    """Iterator over a streaming deployment call that survives replica
    death and drain. Wraps the handle dispatch: every pull that raises
    ``ActorDiedError`` goes through the journal (resubmit / resume /
    typed exhaustion), and a ``ReplicaDrainingError`` reject re-routes
    to a live replica for free. This is the ONLY place the streaming
    router path handles ``ActorDiedError`` (source-linted)."""

    def __init__(self, handle, journal: RequestJournal,
                 per_item_timeout_s: Optional[float] = STREAM_ITEM_TIMEOUT_S):
        self._handle = handle
        self.journal = journal
        self._timeout = per_item_timeout_s
        self._inner = None
        self._replica = None
        self._completion_reported = False

    def __iter__(self) -> "RecoverableStream":
        return self

    # ---------------------------------------------------------- dispatch
    def _dispatch(self, payload: Any) -> None:
        from ray_tpu.serve.proxy import prefix_fingerprint

        j = self.journal
        # The SAME trace context rides every attempt (one trace across
        # both replicas); the attempt counter is stamped in so the two
        # replicas' engine spans are tell-apart-able in the transcript.
        rctx = j.request_ctx
        if rctx is not None and (j.resumes or j.drain_rejects):
            rctx = {**rctx, "attempt": j.resumes + j.drain_rejects}
        # The prefix key is recomputed from the attempt's payload: after
        # an eviction the rendezvous ring has one fewer replica, so the
        # key re-homes onto the dead replica's second choice.
        h = self._handle.options(
            j.method, stream=True, multiplexed_model_id=j.model_id,
            request_context=rctx,
            prefix_key=prefix_fingerprint(payload))
        gen = h.remote(payload)
        gen._timeout = self._timeout
        self._replica = getattr(gen, "_replica", None)
        self._inner = iter(gen)

    def _evict(self) -> None:
        if self._replica is not None:
            try:
                self._handle._evict(self._replica)
            except Exception:  # noqa: BLE001 — eviction is best-effort
                pass
            self._replica = None

    def _death_cause(self) -> str:
        """Recovery tag for a replica death: "resume" once items reached
        the caller, else an invisible "resubmit". The disaggregated
        stream overrides this — a decode death after the journaled
        handoff is a resume even before the first token streamed."""
        return "resume" if self.journal.emitted else "resubmit"

    # ------------------------------------------------------------ recover
    def _reroute_drained(self) -> None:
        """The chosen replica is draining (clean reject — it did no
        work): evict it locally and redispatch the same submission.
        Free — no resume budget consumed — but bounded by the replica
        count so a fully-draining deployment cannot spin forever."""
        from ray_tpu._private import metrics_defs as mdefs

        j = self.journal
        j.drain_rejects += 1
        if j.drain_rejects > DRAIN_REJECT_CAP:
            raise exceptions.ReplicaDrainingError(
                f"every replica of {j.deployment!r} rejected the request "
                f"as draining ({j.drain_rejects} rejects)")
        self._evict()
        mdefs.SERVE_REPLICA_RESUMES.inc(tags={
            "deployment": j.deployment, "cause": "drain_reject"})
        _flight_resume(j, "drain_reject")
        # A drain reject happens at dispatch, before anything streamed,
        # so the original submission redispatches verbatim.
        self._dispatch(j.resume_payload() if j.emitted else j.payload)

    def _resume_after_death(self, err: BaseException) -> None:
        from ray_tpu._private import metrics_defs as mdefs
        from ray_tpu.util import tracing

        j = self.journal
        self._evict()
        payload = j.resume_payload()
        if payload is None:
            # Items already reached the caller and the stream has no
            # replay semantics: recovery would duplicate or reorder
            # delivered items, so surface the death honestly.
            raise err
        if payload is COMPLETE:
            # Every requested token was delivered before the death: the
            # stream is COMPLETE, not failed (only the end-of-stream
            # notification was lost) — no budget consumed, so this
            # check precedes the exhaustion gate.
            self._inner = iter(())
            return
        if j.resumes >= max_resumes():
            mdefs.SERVE_REQ_OUTCOMES.inc(tags={
                **j.tags(), "outcome": "resume_exhausted"})
            raise exhausted_error(j.deployment, j.resumes) from err
        cause = self._death_cause()
        j.resumes += 1
        if j.emitted:
            j.resumed_midstream = True
        mdefs.SERVE_REPLICA_RESUMES.inc(tags={
            "deployment": j.deployment, "cause": cause})
        _flight_resume(j, cause)
        rctx = j.request_ctx or {}
        if rctx and tracing.enabled():
            # A zero-duration marker span in the request's trace: the
            # recovery point between the two replicas' engine spans.
            tracing.emit_span(
                "serve.resume", trace_id=rctx.get("trace_id", ""),
                parent_span_id=rctx.get("parent_span_id", ""),
                ts=time.time(), dur=0.0, kind="route",
                request_id=rctx.get("request_id", ""),
                deployment=j.deployment, cause=cause,
                emitted=len(j.emitted), attempt=j.resumes)
        logger.warning(
            "serve: %s request to %r after replica death "
            "(%d item(s) already streamed, attempt %d/%d)",
            cause, j.deployment, len(j.emitted), j.resumes,
            max_resumes())
        self._dispatch(payload)

    # ------------------------------------------------------------ iterate
    def __next__(self) -> Any:
        from ray_tpu._private import metrics_defs as mdefs

        j = self.journal
        if self._inner is None:
            self._dispatch(j.payload)
        while True:
            try:
                item = next(self._inner)
            except StopIteration:
                if j.resumes and not self._completion_reported:
                    self._completion_reported = True
                    mdefs.SERVE_REQ_OUTCOMES.inc(tags={
                        **j.tags(), "outcome": "resumed"})
                raise
            except exceptions.ReplicaDrainingError:
                self._reroute_drained()
                continue
            except exceptions.ActorDiedError as e:
                self._resume_after_death(e)
                continue
            j.record(item)
            return item

    def ready(self) -> bool:
        """Whether the replica has already stored the next item, so
        that ``next()`` returns it without waiting: the ingress takes
        what is ready in one pull, and a stream that fell behind its
        engine catches up instead of lagging one pull a token. Never
        blocks and never recovers: a dead replica reads as not ready
        and is met by the ``next()`` that waits."""
        ready = getattr(self._inner, "ready", None)
        return ready is not None and ready()


class DisaggRecoverableStream(RecoverableStream):
    """Recoverable stream over a (prefill, decode) ROLE-GROUP pair —
    the disaggregated twin of :class:`RecoverableStream`. Dispatch is
    staged: pre-reserve the decode slot, run the unary ``prefill`` on
    the prefill group (it returns the KV handoff manifest; the staging
    bytes ride the shm channel named inside it), journal the handoff,
    then open the ``decode_from`` stream on the decode group. Every
    token — including the prefill-produced first one — reaches the
    caller only through the decode stream, so the journal's ``emitted``
    ledger stays the single source of delivery truth.

    Death on either side lands in the SAME journal:

    * **prefill death** (unary — nothing delivered, nothing journaled):
      the submission resubmits verbatim to another prefill replica
      (``cause="resubmit"``, budgeted like any death retry);
    * **decode death after the handoff**: replay from the journal as a
      fresh prefill wherever capacity exists (``cause="resume"`` — the
      first token crossed replicas, so the recovery is visible state,
      not an invisible reroute). The journaled handoff means the
      request is never billed twice: the replay journals a NEW attempt
      entry, and :meth:`RequestJournal.note_handoff` refuses duplicate
      entries for the same attempt.

    This class is the only place the disaggregated router path handles
    ``ActorDiedError`` (the same source lint that pins the colocated
    path to this module covers it)."""

    def __init__(self, prefill_handle, decode_handle,
                 journal: RequestJournal,
                 per_item_timeout_s: Optional[float] = STREAM_ITEM_TIMEOUT_S):
        super().__init__(decode_handle, journal, per_item_timeout_s)
        self._prefill_handle = prefill_handle
        # True between note_handoff and clean stream end: a death in
        # that window is a decode death AFTER the handoff.
        self._handoff_live = False

    def _death_cause(self) -> str:
        return ("resume" if (self.journal.emitted or self._handoff_live)
                else "resubmit")

    def _resume_after_death(self, err: BaseException) -> None:
        from ray_tpu._private import metrics_defs as mdefs

        handoff_was_live = self._handoff_live
        if handoff_was_live:
            mdefs.SERVE_HANDOFFS.inc(tags={
                "deployment": self.journal.deployment,
                "outcome": "decode_died"})
        pre = self.journal.resumes
        super()._resume_after_death(err)
        if handoff_was_live and self.journal.resumes > pre:
            # The death post-dates a journaled handoff: the first token
            # crossed replicas, so even with zero tokens DELIVERED the
            # replay is visible state — a sampled request must carry
            # the resumed marker to the client.
            self.journal.resumed_midstream = True

    # ---------------------------------------------------------- dispatch
    def _prefill_attempt(self, payload: Any, rctx, fp: str):
        """One journaled prefill attempt: returns the manifest, or None
        when the chosen prefill replica died/drained (the journal was
        advanced and the caller retries)."""
        import ray_tpu
        from ray_tpu._private import metrics_defs as mdefs

        j = self.journal
        h = self._prefill_handle.options(
            "prefill", multiplexed_model_id=j.model_id,
            request_context=rctx, prefix_key=fp)
        resp = h.remote(payload)
        try:
            return ray_tpu.get(resp._ref, timeout=self._timeout)
        except exceptions.ReplicaDrainingError:
            # Clean reject — free reroute, bounded by the shared cap.
            j.drain_rejects += 1
            if j.drain_rejects > DRAIN_REJECT_CAP:
                raise exceptions.ReplicaDrainingError(
                    f"every prefill replica of {j.deployment!r} rejected "
                    f"the request as draining ({j.drain_rejects} rejects)")
            try:
                self._prefill_handle._evict(resp._replica)
            except Exception:  # noqa: BLE001 — eviction is best-effort
                pass
            mdefs.SERVE_REPLICA_RESUMES.inc(tags={
                "deployment": j.deployment, "cause": "drain_reject"})
            _flight_resume(j, "drain_reject")
            return None
        except exceptions.ActorDiedError as e:
            # Prefill death: ZERO bytes reached the caller and no
            # handoff was journaled, so the immutable submission
            # resubmits to another prefill replica — budgeted.
            try:
                self._prefill_handle._evict(resp._replica)
            except Exception:  # noqa: BLE001
                pass
            mdefs.SERVE_HANDOFFS.inc(tags={
                "deployment": j.deployment, "outcome": "prefill_died"})
            if j.resumes >= max_resumes():
                mdefs.SERVE_REQ_OUTCOMES.inc(tags={
                    **j.tags(), "outcome": "resume_exhausted"})
                raise exhausted_error(j.deployment, j.resumes) from e
            j.resumes += 1
            mdefs.SERVE_REPLICA_RESUMES.inc(tags={
                "deployment": j.deployment, "cause": "resubmit"})
            _flight_resume(j, "resubmit")
            logger.warning(
                "serve: resubmitting prefill for %r after replica death "
                "(attempt %d/%d)", j.deployment, j.resumes, max_resumes())
            return None

    def _dispatch(self, payload: Any) -> None:
        import ray_tpu
        from ray_tpu._private import metrics_defs as mdefs
        from ray_tpu.serve.proxy import prefix_fingerprint

        j = self.journal
        self._handoff_live = False
        fp = prefix_fingerprint(payload)
        prompt = (payload.get("prompt_token_ids") or ()
                  if isinstance(payload, dict) else ())
        try:
            budget = int(payload.get("max_tokens", 16)) \
                if isinstance(payload, dict) else 16
        except (TypeError, ValueError):
            budget = 16
        # (1) PRE-RESERVE the decode slot before any prefill work: the
        # payload must never race arena pressure on arrival. Best-effort
        # — a miss (arena full, replica mismatch) just means the import
        # allocates on arrival; the replica-nonce inside the ticket
        # keeps a ticket from one decode replica from being spent on
        # another, and unspent tickets expire engine-side (TTL).
        reservation = None
        try:
            reservation = ray_tpu.get(
                self._handle.options(
                    "reserve_kv", multiplexed_model_id=j.model_id,
                    prefix_key=fp).remote(len(prompt), budget)._ref,
                timeout=5)
        except Exception:  # noqa: BLE001 — reservation is advisory
            reservation = None
        # (2) PREFILL (journaled unary retry loop).
        while True:
            rctx = j.request_ctx
            if rctx is not None and (j.resumes or j.drain_rejects):
                rctx = {**rctx, "attempt": j.resumes + j.drain_rejects}
            manifest = self._prefill_attempt(payload, rctx, fp)
            if manifest is not None:
                break
        if isinstance(manifest, dict) and "done" in manifest:
            # The request finished entirely at prefill (max_tokens == 1,
            # EOS at the first token, or a resumed prompt already ending
            # in EOS): nothing to hand off — the completed tokens stream
            # straight out and are journaled like any other items.
            self._replica = None
            self._inner = iter(list(manifest["done"]))
            return
        # (3) JOURNAL the handoff before the decode side can touch it:
        # the manifest only becomes importable once stamped (the
        # transfer helper refuses unstamped manifests), so a request
        # can never be billed for an un-journaled transfer.
        j.note_handoff({
            "crc32": manifest.get("crc32"),
            "nbytes": manifest.get("nbytes"),
            "num_blocks": manifest.get("num_blocks"),
            "attempt": j.resumes,
        })
        manifest = {**manifest, "journaled": True}
        self._handoff_live = True
        # (4) DECODE stream: every token (first included) arrives here.
        dh = self._handle.options(
            "decode_from", stream=True, multiplexed_model_id=j.model_id,
            request_context=rctx, prefix_key=fp)
        gen = dh.remote({"manifest": manifest,
                         "reservation": reservation})
        gen._timeout = self._timeout
        self._replica = getattr(gen, "_replica", None)
        self._inner = iter(gen)
        mdefs.SERVE_HANDOFFS.inc(tags={
            "deployment": j.deployment, "outcome": "ok"})


def note_unary_resumed(deployment: str, tenant: str) -> None:
    """Metrics for a unary call that completed after >=1 death retry
    (the ``serve/api.py`` unary journal path)."""
    from ray_tpu._private import metrics_defs as mdefs

    mdefs.SERVE_REQ_OUTCOMES.inc(tags={
        "deployment": deployment, "tenant": tenant, "engine": "router",
        "outcome": "resumed"})


def note_unary_exhausted(deployment: str, tenant: str) -> None:
    from ray_tpu._private import metrics_defs as mdefs

    mdefs.SERVE_REQ_OUTCOMES.inc(tags={
        "deployment": deployment, "tenant": tenant, "engine": "router",
        "outcome": "resume_exhausted"})


def note_unary_retry(deployment: str, cause: str) -> None:
    from ray_tpu._private import metrics_defs as mdefs

    mdefs.SERVE_REPLICA_RESUMES.inc(tags={
        "deployment": deployment, "cause": cause})


__all__ = ["COMPLETE", "DRAIN_REJECT_CAP", "DisaggRecoverableStream",
           "RESUMED_MARKER",
           "RecoverableStream", "RequestJournal", "exhausted_error",
           "is_llm_payload", "is_sampled", "max_resumes",
           "note_unary_exhausted", "note_unary_resumed",
           "note_unary_retry"]
