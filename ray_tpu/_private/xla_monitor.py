"""Per-process XLA observability: the compile/retrace/cost/capture plane.

The PR-1 observability stack (TSDB, push plane, dashboard) stops at the
Python layer; this module makes the XLA layer itself a first-class
surface, feeding the same planes:

* :func:`instrument` wraps ``jax.jit`` for every framework-owned entry
  point. Dispatch goes through jax's AOT path (``lower().compile()``)
  keyed by the call signature, so each compile is observed exactly once
  with its true wall time — no double compilation, no guessing. A
  **retrace detector** flags a second compile of the same logical
  function with a new shape/dtype signature (``shape_policy`` declares
  which shape growth is legitimate: the serve engine's power-of-two
  bucketed prefill stays silent; arbitrary shape churn fires
  ``ray_tpu_xla_retraces_total`` and logs the signature diff).
* after each compile the executable's ``cost_analysis()`` (FLOPs, bytes
  accessed) is harvested into a per-process **program registry**,
  persisted best-effort in the GCS KV under ``__xla_programs__`` and
  exported as gauges. Call sites that measure real step/tick wall time
  feed it back via :meth:`InstrumentedJit.note_execution`, yielding
  achieved-FLOPs / achieved-HBM-bandwidth / MFU gauges with zero
  estimation; absent an explicit measurement the wrapper falls back to
  call cadence (honest in loops that sync per step).
* :func:`sample_device_memory` publishes per-device ``memory_stats()``
  vitals (graceful no-op on CPU, and never *imports* jax into a process
  that doesn't already hold devices — a fresh import on a TPU host would
  steal the chips from the workers).
* a **capture listener** subscribes to the GCS ``PROFILE`` pubsub
  channel; an on-demand command (CLI ``ray-tpu profile capture``,
  dashboard ``/api/v1/profile/capture``) makes every XLA-active process
  on the target node run ``jax.profiler`` trace capture for N seconds,
  write the trace under the session dir and register it in the GCS KV
  under ``__profiles__``.

* :meth:`InstrumentedJit.note_execution` also keeps ONE RECORD a
  measured execution in a bounded per-process ring (:class:`_CallRecord`):
  its wall time, when it was dispatched and when it landed, whether its
  result was READY when the host came to fetch it and how long the fetch
  then blocked. A run of calls several times over their shape's own
  median is a STALLED STRETCH: it books the seconds it cost, says whose
  they were (``host``: the results waited for the thread; ``device``:
  the thread waited for them), writes itself to
  ``<session dir>/stalls/`` and, where ``stall_capture_s`` asks for it,
  keeps a device trace of itself through the capture plane below.

Everything degrades gracefully on CPU (cost analysis works, memory
stats return None, profiler traces still capture), so tier-1 exercises
the full plane under ``JAX_PLATFORMS=cpu``. ``RAY_TPU_XLA_MONITOR=0``
turns the wrapper into a transparent ``jax.jit``.
"""

from __future__ import annotations

import functools
import gc
import itertools
import json
import logging
import os
import resource
import statistics
import sys
import tempfile
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

logger = logging.getLogger(__name__)

PROFILE_CHANNEL = "PROFILE"
PROFILE_KV_NS = "__profiles__"
PROGRAM_KV_NS = "__xla_programs__"

# bf16/fp16 peak FLOPs per chip by device kind (prefix match, like the
# HBM table in bench_serve.py). MFU is only emitted when the kind is
# known; CPU reports achieved FLOPs/bandwidth without a utilization.
PEAK_FLOPS = {
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5": 459e12,        # v5p
    "TPU v4": 275e12,
    "TPU v6 lite": 918e12,   # v6e
}


def _enabled() -> bool:
    return os.environ.get("RAY_TPU_XLA_MONITOR", "1") != "0"


def session_dir() -> str:
    """Where captures and stall dumps go: ``RAY_TPU_SESSION_DIR``, else
    a folder under the process's own temporary directory (``TMPDIR``)."""
    return os.environ.get("RAY_TPU_SESSION_DIR") or os.path.join(
        tempfile.gettempdir(), "ray_tpu_state")


# --------------------------------------------------------------- connection
# Where this process's XLA telemetry goes: set by the same call sites
# that start the metrics pusher (driver/worker runtime, node manager).
_state_lock = threading.Lock()
_gcs_address: Optional[str] = None
_node_id: Optional[str] = None
_conn_refs: Dict[str, int] = {}               # address -> connect() count
_listeners: Dict[str, threading.Event] = {}   # address -> stop event
_maintenance_stop: Optional[threading.Event] = None
_maintenance_wake = threading.Event()         # a stalled stretch closed
# (ns, key) -> [payload, tries]; insertion-ordered for bounded eviction.
_pending_kv: OrderedDict = OrderedDict()
_programs: Dict[str, "_ProgramRecord"] = {}
_capture_lock = threading.Lock()              # jax.profiler can't nest


def connect(gcs_address: str, node_id: Optional[str] = None) -> None:
    """Record where XLA telemetry for this process should land. The
    profile-capture listener starts lazily at the first instrumented
    compile — processes that never touch XLA pay nothing. Refcounted:
    each connect() is balanced by a disconnect() (mirrors the metrics
    pusher's claims, so one driver's shutdown can't silence a
    co-resident node manager's capture plane)."""
    global _gcs_address, _node_id
    if not gcs_address or not _enabled():
        return
    with _state_lock:
        _conn_refs[gcs_address] = _conn_refs.get(gcs_address, 0) + 1
        _gcs_address = gcs_address
        if node_id:
            _node_id = node_id
    if _programs:
        # XLA already active in this process: bring the planes up now.
        _ensure_listener(gcs_address)
        _ensure_maintenance()


def disconnect(gcs_address: str) -> None:
    """Drop one component's claim on the address; the listener stops
    only when the last claimant disconnects."""
    global _gcs_address
    stop = None
    with _state_lock:
        n = _conn_refs.get(gcs_address, 0) - 1
        if n > 0:
            _conn_refs[gcs_address] = n
            return
        _conn_refs.pop(gcs_address, None)
        stop = _listeners.pop(gcs_address, None)
        if _gcs_address == gcs_address:
            _gcs_address = next(iter(_conn_refs), None)
    if stop is not None:
        stop.set()


def stop_all() -> None:
    """Stop listener/maintenance threads (sequential test clusters)."""
    global _maintenance_stop
    with _state_lock:
        stops = list(_listeners.values())
        _listeners.clear()
        _conn_refs.clear()
        if _maintenance_stop is not None:
            stops.append(_maintenance_stop)
            _maintenance_stop = None
    for s in stops:
        s.set()
    _maintenance_wake.set()


def _on_xla_activity() -> None:
    with _state_lock:
        address = _gcs_address
    if address:
        _ensure_listener(address)
        _ensure_maintenance()


# ----------------------------------------------------------- program registry
class _ProgramRecord:
    __slots__ = ("name", "compiles", "retraces", "signatures", "cost",
                 "compile_seconds")

    def __init__(self, name: str):
        self.name = name
        self.compiles = 0
        self.retraces = 0
        # signature key -> {"signature", "flops", "bytes_accessed", ...}
        self.signatures: Dict[Any, Dict[str, Any]] = {}
        self.cost: Optional[Dict[str, float]] = None   # latest compile's
        self.compile_seconds = 0.0


def _record(name: str) -> _ProgramRecord:
    with _state_lock:
        rec = _programs.get(name)
        if rec is None:
            rec = _programs[name] = _ProgramRecord(name)
        return rec


def program_stats(name: str) -> Optional[Dict[str, Any]]:
    """Latest compile stats for a program (bench_serve reads the
    cost-analysis bytes instead of hand-estimating HBM traffic)."""
    rec = _programs.get(name)
    if rec is None:
        return None
    out = {"name": rec.name, "compiles": rec.compiles,
           "retraces": rec.retraces,
           "compile_seconds": rec.compile_seconds,
           "signatures": len(rec.signatures)}
    if rec.cost:
        out.update(rec.cost)
    return out


def all_program_stats() -> List[Dict[str, Any]]:
    return [s for s in (program_stats(n) for n in list(_programs))
            if s is not None]


def _queue_kv(ns: str, key: str, payload: Dict[str, Any]) -> None:
    with _state_lock:
        # Keyed: a burst of compiles for one program coalesces into one
        # pending write of the latest record.
        _pending_kv[(ns, key)] = [payload, 0]
        while len(_pending_kv) > 512:   # bounded: telemetry, not truth
            _pending_kv.popitem(last=False)


def _flush_pending_kv() -> None:
    with _state_lock:
        address = _gcs_address
        batch = list(_pending_kv.items())
    if address is None or not batch:
        return
    from ray_tpu._private import rpc
    from ray_tpu.protobuf import ray_tpu_pb2 as pb

    gcs = rpc.get_stub("GcsService", address)
    for (ns, key), entry in batch:
        payload, tries = entry
        try:
            gcs.KvPut(pb.KvRequest(
                ns=ns, key=key, value=json.dumps(payload).encode(),
                overwrite=True), timeout=5)
        except Exception:  # noqa: BLE001 — head briefly unreachable
            with _state_lock:
                if _pending_kv.get((ns, key)) is entry:
                    if tries >= 3:
                        _pending_kv.pop((ns, key), None)
                    else:
                        entry[1] = tries + 1
            return
        with _state_lock:
            if _pending_kv.get((ns, key)) is entry:
                _pending_kv.pop((ns, key))


# ------------------------------------------------------------- signatures
def _leaf_sig(x) -> Tuple:
    aval = getattr(x, "aval", None)
    if aval is not None:
        return (tuple(aval.shape), str(aval.dtype),
                bool(getattr(aval, "weak_type", False)))
    if isinstance(x, (bool, int, float, complex)):
        # Python scalars trace as weak-typed values: keyed by TYPE, never
        # by value, or a decode loop's position arg would recompile
        # per token.
        return (type(x).__name__, "weak")
    shape, dtype = getattr(x, "shape", None), getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return (tuple(shape), str(dtype), False)
    return ("opaque", type(x).__name__)


def _fmt_sig(leaf_sigs: Sequence[Tuple]) -> str:
    parts = []
    for s in leaf_sigs:
        if isinstance(s[0], tuple):
            parts.append(f"{s[1]}[{','.join(map(str, s[0]))}]"
                         + ("w" if s[2] else ""))
        else:
            parts.append(f"{s[0]}:{s[1]}")
    return "(" + ", ".join(parts) + ")"


def _is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


def _changed_dims(old: Sequence[Tuple], new: Sequence[Tuple]) -> \
        Optional[List[int]]:
    """Dims (new values) that differ between two same-structure leaf-sig
    tuples; None when the signatures differ beyond shapes (dtype/type)."""
    if len(old) != len(new):
        return None
    dims: List[int] = []
    for o, n in zip(old, new):
        if o == n:
            continue
        if not (isinstance(o[0], tuple) and isinstance(n[0], tuple)) \
                or o[1:] != n[1:] or len(o[0]) != len(n[0]):
            return None                  # dtype / structure change
        dims.extend(nd for od, nd in zip(o[0], n[0]) if od != nd)
    return dims


# --------------------------------------------------------------- the wrapper
class InstrumentedJit:
    """``jax.jit`` with compile/retrace/cost observability.

    Dispatch: per-signature AOT executables (``lower().compile()``) so
    compile events are first-class; nested calls under an outer trace
    inline through the plain jit. A failed compile raises the compiler's
    error; a compiled executable that fails at DISPATCH degrades the
    wrapper to plain jit (observability must never take the hot path
    down).

    ``shape_policy``:

    * ``"static"`` — the program has ONE legitimate signature; any
      second compile is a retrace.
    * ``"bucketed"`` — new signatures are expected as long as every
      changed dim is a power of two (or listed in ``allowed_dims``):
      the serve engine's bucketed prefill compiles log(N)·log(L)
      programs by design, but a stray odd shape is a real retrace.
    * ``"free"`` — compile tracking only (utility entry points that
      legitimately see arbitrary shapes).
    """

    def __init__(self, fn, name: str, shape_policy: str = "static",
                 allowed_dims: Sequence[int] = (), aot: bool = True,
                 **jit_kwargs):
        import jax

        assert shape_policy in ("static", "bucketed", "free"), shape_policy
        self.name = name
        self.shape_policy = shape_policy
        self.allowed_dims = frozenset(int(d) for d in allowed_dims)
        self._jitted = jax.jit(fn, **jit_kwargs)
        # Static args are baked into the lowered program, and the AOT
        # executable is called WITHOUT them — rather than re-deriving
        # jax's static/dynamic arg split here, those wrappers dispatch
        # through the plain jit (compile time observed as first-call
        # wall time) with the static VALUES folded into the signature
        # key so two static variants never share a cache entry.
        self._static_argnums = tuple(
            jit_kwargs.get("static_argnums") or ())
        if isinstance(jit_kwargs.get("static_argnums"), int):
            self._static_argnums = (jit_kwargs["static_argnums"],)
        names = jit_kwargs.get("static_argnames") or ()
        self._static_argnames = (names,) if isinstance(names, str) \
            else tuple(names)
        self._aot = aot and not (self._static_argnums
                                 or self._static_argnames)
        self._degraded = False
        # With donated inputs a failed dispatch may already have consumed
        # its buffers: retrying through the plain jit would hit deleted
        # arrays, so those programs re-raise and only degrade the NEXT
        # call.
        self._donates = bool(jit_kwargs.get("donate_argnums")
                             or jit_kwargs.get("donate_argnames"))
        self._compiled: Dict[Any, Any] = {}       # sig key -> executable
        self._sigs: Dict[Any, List[Tuple]] = {}   # sig key -> leaf sigs
        self._last_key: Optional[Any] = None
        # Timing state is PER WRAPPER: two engines sharing a program
        # name must not freeze or garble each other's achieved gauges.
        self._last_call: Optional[float] = None
        self._external_timing = False
        self._lock = threading.Lock()
        self._tracer = jax.core.Tracer

    # Anything not overridden (``lower``, ``eval_shape``, ...) behaves
    # like the underlying jit.
    def __getattr__(self, item):
        jitted = self.__dict__.get("_jitted")
        if jitted is None:
            raise AttributeError(item)
        return getattr(jitted, item)

    def _cache_size(self) -> int:
        """Compiled-program count — mirrors jax's private jit cache
        counter for signature-reuse acceptance checks."""
        if self._degraded or not self._aot or not _enabled():
            real = getattr(self._jitted, "_cache_size", None)
            return real() if real is not None else len(self._sigs)
        return len(self._compiled)

    def __call__(self, *args, **kwargs):
        if not _enabled():
            return self._jitted(*args, **kwargs)
        import jax

        leaves, treedef = jax.tree.flatten((args, kwargs))
        if any(isinstance(x, self._tracer) for x in leaves):
            # Called inside an outer trace: inline, don't observe.
            return self._jitted(*args, **kwargs)
        leaf_sigs = tuple(_leaf_sig(x) for x in leaves)
        key = (treedef, leaf_sigs, self._static_key(args, kwargs))
        self._note_cadence()
        self._last_key = key
        entry = None if self._degraded else self._compiled.get(key)
        if entry is not None:
            try:
                return entry(*args, **kwargs)
            except Exception:  # noqa: BLE001 — AOT quirk: degrade, stay up
                return self._dispatch_failed(key, args, kwargs)
        return self._compile_and_call(key, leaf_sigs, args, kwargs)

    def _static_key(self, args, kwargs) -> Tuple:
        if not (self._static_argnums or self._static_argnames):
            return ()
        return (tuple(repr(args[i]) for i in self._static_argnums
                      if i < len(args)),
                tuple((k, repr(kwargs[k])) for k in self._static_argnames
                      if k in kwargs))

    def _dispatch_failed(self, key, args, kwargs):
        """An AOT executable failed: degrade the wrapper (plain jit from
        here on) and evict the executable so no path retries it. Donated
        inputs may already be consumed — re-raise rather than touch
        deleted buffers."""
        self._degraded = True
        self._compiled.pop(key, None)
        if self._donates:
            logger.exception(
                "xla_monitor: AOT dispatch of %r failed with donated "
                "inputs; degrading to plain jit for subsequent calls",
                self.name)
            raise
        logger.exception("xla_monitor: AOT dispatch of %r failed; "
                         "degrading to plain jit", self.name)
        return self._jitted(*args, **kwargs)

    # ------------------------------------------------------------ compile
    def _compile_and_call(self, key, leaf_sigs, args, kwargs):
        with self._lock:
            entry = self._compiled.get(key)
            if entry is not None:
                pass  # lost the race: dispatch below
            elif self._degraded or not self._aot:
                t0 = time.perf_counter()
                out = self._jitted(*args, **kwargs)
                # First-call wall time (compile + one execution): the
                # honest proxy when the AOT path is unavailable.
                if key not in self._sigs:
                    self._observe_compile(key, leaf_sigs,
                                          time.perf_counter() - t0,
                                          cost=None)
                return out
            else:
                # A compile error propagates: retrying the same program
                # through the plain jit would compile it a second time
                # (minutes, on the chip) and bury the first message.
                t0 = time.perf_counter()
                entry = self._jitted.lower(*args, **kwargs).compile()
                dt = time.perf_counter() - t0
                self._compiled[key] = entry
                self._observe_compile(key, leaf_sigs, dt,
                                      cost=_harvest_cost(entry))
        try:
            return entry(*args, **kwargs)
        except Exception:  # noqa: BLE001
            return self._dispatch_failed(key, args, kwargs)

    def _observe_compile(self, key, leaf_sigs, seconds: float,
                         cost: Optional[Dict[str, float]]) -> None:
        from ray_tpu._private import metrics_defs as mdefs

        rec = _record(self.name)
        tags = {"program": self.name}
        retrace_from = self._detect_retrace(leaf_sigs)
        self._sigs[key] = list(leaf_sigs)
        rec.compiles += 1
        rec.compile_seconds += seconds
        rec.cost = cost
        sig_str = _fmt_sig(leaf_sigs)
        if len(key) > 2 and key[2]:
            sig_str += f" static={key[2]}"
        rec.signatures[key] = {"signature": sig_str, "seconds": seconds,
                               **(cost or {})}
        mdefs.XLA_COMPILES.inc(tags=tags)
        mdefs.XLA_COMPILE_SECONDS.observe(seconds, tags=tags)
        if cost:
            if cost.get("flops"):
                mdefs.XLA_PROGRAM_FLOPS.set(cost["flops"], tags=tags)
            if cost.get("bytes_accessed"):
                mdefs.XLA_PROGRAM_BYTES.set(cost["bytes_accessed"],
                                            tags=tags)
        if retrace_from is not None:
            rec.retraces += 1
            mdefs.XLA_RETRACES.inc(tags=tags)
            logger.warning(
                "xla retrace: %s recompiled for a new signature "
                "(policy=%s)\n  was: %s\n  now: %s",
                self.name, self.shape_policy, _fmt_sig(retrace_from),
                sig_str)
        with _state_lock:
            node = (_node_id or "local")[:12]
        # ONE record per (program, process), overwritten with the latest
        # compile plus cumulative counters — a shape-churning program
        # must not grow the head KV by one key per retrace forever.
        _queue_kv(PROGRAM_KV_NS, f"{self.name}:{node}:{os.getpid()}",
                  {"program": self.name, "node_id": node,
                   "pid": os.getpid(), "signature": sig_str,
                   "compile_seconds": seconds,
                   "compiles": rec.compiles, "retraces": rec.retraces,
                   "retrace": retrace_from is not None,
                   "policy": self.shape_policy, "ts": time.time(),
                   **(cost or {})})
        _on_xla_activity()

    def _detect_retrace(self, leaf_sigs) -> Optional[List[Tuple]]:
        """Returns the closest prior signature when this compile is a
        retrace, else None. Must run before the new signature is
        recorded."""
        if self.shape_policy == "free" or not self._sigs:
            return None
        prior = list(self._sigs.values())
        if self.shape_policy == "static":
            return prior[-1]
        # bucketed: expected growth = every changed dim is a power of
        # two (or explicitly allowed, e.g. a non-pow2 max_len cap).
        best = prior[-1]
        for old in prior:
            dims = _changed_dims(old, leaf_sigs)
            if dims is None:
                continue
            if all(_is_pow2(d) or d in self.allowed_dims for d in dims):
                return None
            best = old
        return best

    # ------------------------------------------------------------- timing
    def _note_cadence(self) -> None:
        now = time.perf_counter()
        prev, self._last_call = self._last_call, now
        if prev is not None and not self._external_timing:
            dt = now - prev
            if dt > 0:
                _set_achieved(_record(self.name),
                              self._cost_for(self._last_key), dt)

    def note_execution(self, seconds: float,
                       bytes_hint: Optional[float] = None, *,
                       calls: int = 1, shape: Any = None,
                       call: Optional["Dispatched"] = None
                       ) -> Optional[Dict[str, float]]:
        """Feed back a MEASURED wall time for the most recent call (the
        serve tick measures dispatch→fetch, prefill measures
        dispatch→first-token sync). Disables the cadence fallback for
        this wrapper and returns the achieved figures.

        ``bytes_hint`` overrides the compiler cost-analysis bytes for
        the achieved-bandwidth gauge: programs whose real traffic is
        data-dependent (the paged decode tick reads only LIVE KV blocks)
        would otherwise be priced at the compiled worst case — the
        gauge must scale with live tokens, not ``S_max``.

        ``seconds`` is the time of ``calls`` back-to-back calls of the
        program (a chunked prefill batch's). The measurement is also one
        record of the process's call record (:class:`_CallRecord`),
        under ``shape``, whatever of the arguments its time depends on
        (a tick's member count, a prefill batch's padded shape), with
        the stamps and the fetch's reading ``call`` carries from the
        dispatch (:class:`Dispatched`; without one the record is
        stamped here and says nothing of the fetch)."""
        self._external_timing = True
        if seconds <= 0:
            return None
        _calls.note(self.name, shape, seconds, calls, call)
        seconds /= calls
        cost = self._cost_for(self._last_key)
        if bytes_hint is not None and bytes_hint > 0:
            cost = dict(cost) if cost else {}
            cost["bytes_accessed"] = float(bytes_hint)
        return _set_achieved(_record(self.name), cost, seconds)

    def _cost_for(self, key) -> Optional[Dict[str, Any]]:
        rec = _programs.get(self.name)
        if rec is None:
            return None
        if key is not None and key in rec.signatures:
            return rec.signatures[key]
        return rec.cost


def _harvest_cost(compiled) -> Optional[Dict[str, float]]:
    """FLOPs / bytes-accessed from the executable's compiler cost
    analysis (per-device figures; None when the backend offers none)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 - backend without cost analysis
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    out = {}
    for ours, theirs in (("flops", "flops"),
                         ("bytes_accessed", "bytes accessed")):
        v = ca.get(theirs)
        if v is not None and v == v:     # drop NaN
            out[ours] = float(v)
    return out or None


def _set_achieved(rec: _ProgramRecord, cost, seconds: float
                  ) -> Optional[Dict[str, float]]:
    if not cost:
        return None
    from ray_tpu._private import metrics_defs as mdefs

    tags = {"program": rec.name}
    out: Dict[str, float] = {}
    flops = cost.get("flops")
    nbytes = cost.get("bytes_accessed")
    if flops:
        out["achieved_flops_per_s"] = flops / seconds
        mdefs.XLA_ACHIEVED_FLOPS.set(out["achieved_flops_per_s"],
                                     tags=tags)
        peak = _device_peak_flops()
        if peak:
            out["model_flops_utilization"] = flops / seconds / peak
            mdefs.XLA_MFU.set(out["model_flops_utilization"], tags=tags)
    if nbytes:
        out["achieved_bandwidth_bytes_per_s"] = nbytes / seconds
        mdefs.XLA_ACHIEVED_BW.set(
            out["achieved_bandwidth_bytes_per_s"], tags=tags)
    return out or None


_peak_cache: List[Optional[float]] = []


def _device_peak_flops() -> Optional[float]:
    if not _peak_cache:
        peak = None
        try:
            import jax

            kind = getattr(jax.devices()[0], "device_kind", "")
            for name, flops in PEAK_FLOPS.items():
                if kind.startswith(name):
                    peak = flops
                    break
        except Exception:  # noqa: BLE001
            pass
        _peak_cache.append(peak)
    return _peak_cache[0]


def instrument(fn=None, *, name: Optional[str] = None,
               shape_policy: str = "static",
               allowed_dims: Sequence[int] = (), aot: bool = True,
               **jit_kwargs):
    """``jax.jit`` through the XLA monitor. Drop-in: all jit kwargs
    (``donate_argnums``, ``in_shardings``, ...) pass through."""
    if fn is None:
        return functools.partial(instrument, name=name,
                                 shape_policy=shape_policy,
                                 allowed_dims=allowed_dims, aot=aot,
                                 **jit_kwargs)
    return InstrumentedJit(fn, name or getattr(fn, "__name__", "jit_fn"),
                           shape_policy=shape_policy,
                           allowed_dims=allowed_dims, aot=aot,
                           **jit_kwargs)


# ------------------------------------------------------------ call record
# The rule for a stalled stretch. Constants, not options: CHANGES.md
# (PR 48) says why these, from the ledger's ticks of 10.6 to 23.3 ms and
# stalls of up to ten times that.
RING_CALLS = 16384       # two minutes of the fastest cell's 10.6 ms ticks
BASELINE_CALLS = 64      # a shape's median: over its last calls not slow,
BASELINE_MIN = 8         # a baseline from this many of them on,
BASELINE_EVERY = 32      # recomputed once in this many
SLOW_FACTOR = 3.0        # slow: over this many medians of its shape
SLOW_FLOOR_MS = 30.0     # and over this, a call
CLOSE_AFTER = 8          # calls in a row under the rule close a stretch,
IDLE_CLOSE_S = 2.0       # or this long with no call dispatched or landed
LEAD_IN_CALLS = 256      # records a dump keeps from before its stretch
DUMPS_KEPT = 256         # dumps of its own a process keeps; its oldest goes
CAPTURE_AT_SLOW = 2      # a stretch's capture starts at this slow call,
CAPTURE_EVERY_S = 60.0   # at most one in this long


class Call(NamedTuple):
    """One measured execution, as the ring and a dump keep it."""
    seq: int
    program: str
    shape: Any
    wall_s: float                   # what the caller booked, ``calls`` calls
    calls: int
    dispatch_ts: Optional[float]    # wall clock | perf_counter
    dispatch_pc: Optional[float]
    landed_ts: float
    landed_pc: float
    ready: Optional[bool]           # the whole result was there at the fetch
    waited_s: Optional[float]       # the fetch then blocked this long
    slow: bool


class Dispatched:
    """One measured call between its dispatch and its landing. The
    caller mints it where it dispatches the program, asks
    :meth:`fetching` just before it blocks on the result, stamps
    :meth:`landed` after, and hands it to ``note_execution``. ``seq`` is
    what the caller's dispatch and fetch annotations carry as keyword
    metadata, so a dump's record and a trace's annotations are one
    thing. One that never lands (its program raised) leaves nothing
    behind."""

    __slots__ = ("seq", "ts", "pc", "ready", "fetch_pc", "landed_ts",
                 "landed_pc")

    def __init__(self):
        self.ts, self.pc = time.time(), time.perf_counter()
        self.seq = _calls.mint(self.pc)
        self.ready = self.fetch_pc = self.landed_ts = self.landed_pc = None

    def fetching(self, parts) -> None:
        """The host has come for ``parts``, the arrays of the result:
        were they all there? A host call each, which does not block."""
        self.ready = all(part.is_ready() for part in parts)
        self.fetch_pc = time.perf_counter()

    def landed(self) -> float:
        """The result is on the host; returns the ``perf_counter``."""
        self.landed_pc, self.landed_ts = time.perf_counter(), time.time()
        return self.landed_pc


class _Gen2Clock:
    """``gc.callbacks`` hook: count and milliseconds of generation-2
    collections; generations 0 and 1 return at once."""

    def __init__(self):
        self.count, self.ms, self._t0 = 0, 0.0, None

    def __call__(self, phase: str, info: Dict[str, int]) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.count += 1
            self.ms += (time.perf_counter() - self._t0) * 1e3
            self._t0 = None


_gen2 = _Gen2Clock()


def _snapshot() -> Dict[str, float]:
    """What the process and its device look like now: taken as a stretch
    opens and as it closes, never outside one."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    out = {"ts": time.time(), "cpu_user_s": ru.ru_utime,
           "cpu_system_s": ru.ru_stime, "ctx_voluntary": ru.ru_nvcsw,
           "ctx_involuntary": ru.ru_nivcsw, "major_faults": ru.ru_majflt,
           "threads": threading.active_count(),
           "gc2_collections": _gen2.count, "gc2_ms": _gen2.ms}
    try:
        out["load_1m"] = os.getloadavg()[0]
    except OSError:
        pass
    jax = sys.modules.get("jax")
    try:
        stats = jax.local_devices()[0].memory_stats() if jax else None
    except Exception:  # noqa: BLE001 - no backend, or one without stats
        stats = None
    for field in ("bytes_in_use", "peak_bytes_in_use"):
        if stats and stats.get(field) is not None:
            out["device_" + field] = int(stats[field])
    return out


class _Baseline:
    """A (program, shape)'s last readings that were not slow, and their
    median."""

    __slots__ = ("recent", "median", "since")

    def __init__(self):
        self.recent: Optional[deque] = None     # None: no reading yet
        self.median: Optional[float] = None
        self.since = 0

    def add(self, seconds: float) -> None:
        recent = self.recent
        if recent is None:
            # A shape's first reading holds its compilation or its
            # program's load: never a baseline.
            self.recent = deque(maxlen=BASELINE_CALLS)
            return
        recent.append(seconds)
        self.since += 1
        if (self.since >= BASELINE_EVERY or self.median is None) \
                and len(recent) >= BASELINE_MIN:
            self.median = statistics.median(recent)
            self.since = 0


class _Stretch:
    """An open stalled stretch: what its close books and dumps."""

    __slots__ = ("program", "opened_n", "opened_ts", "closed_ts", "before",
                 "slow", "normal_run", "excess_s", "host_sided",
                 "device_sided", "capture", "capture_thread")

    def __init__(self, rec: Call, opened_n: int):
        self.program = rec.program
        self.opened_n = opened_n
        # From the slow call's dispatch where it is known: the stretch
        # began when that call did, not when it at last came back.
        self.opened_ts = rec.dispatch_ts or rec.landed_ts - rec.wall_s
        self.before = _snapshot()
        self.slow = self.normal_run = 0
        self.excess_s = 0.0
        self.host_sided = self.device_sided = 0
        self.capture: Any = None
        self.capture_thread: Optional[threading.Thread] = None

    def add(self, rec: Call, median: float) -> None:
        self.slow += 1
        self.normal_run = 0
        self.excess_s += rec.wall_s - median * rec.calls
        # The stretch ends with its last slow call, not with the calls
        # under the rule that show it has.
        self.closed_ts = rec.landed_ts
        if rec.ready is None or rec.waited_s is None:
            return
        if rec.ready and rec.waited_s < rec.wall_s / 2:
            self.host_sided += 1
        elif not rec.ready and rec.waited_s >= rec.wall_s / 2:
            self.device_sided += 1

    def side(self) -> str:
        """Whose the stretch was, read off its slow calls: ``host``
        where most results were ready before the host came for them (the
        device idled inside the caller's clock), ``device`` where they
        were not and the fetch's wait is most of their wall time (the
        thread waited on the device, its runtime, the transfer back or
        on getting the interpreter lock back after the wait)."""
        if self.host_sided * 2 > self.slow:
            return "host"
        if self.device_sided * 2 > self.slow:
            return "device"
        return "mixed"


class _CallRecord:
    """The process's record of measured executions: a bounded ring of
    :class:`Call`, a median per (program, shape), and the stalled
    stretch, if one is open. One instance serves the process
    (``_calls``); the tests of the rule make their own."""

    def __init__(self):
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=RING_CALLS)
        self._n = 0                          # records ever appended
        self._seq = itertools.count(1)
        self._last_pc = time.perf_counter()  # last dispatch or landing
        # One median a (program, shape): as many as the callers' programs
        # have shapes (a tick's member counts, the prefill's buckets).
        self._baselines: Dict[Tuple[str, Any], _Baseline] = {}
        self._stretch: Optional[_Stretch] = None
        self._closed: List[Dict[str, Any]] = []  # stretches not yet written
        self._written: deque = deque()           # this process's own dumps
        self._last_capture: Optional[float] = None
        self._hooked = False

    def mint(self, pc: float) -> int:
        """A call's ``seq``, at its dispatch. No lock: ``next`` on a
        count is one step of the interpreter."""
        self._last_pc = pc
        return next(self._seq)

    @property
    def stretch_open(self) -> bool:
        return self._stretch is not None

    def records(self, last: int = RING_CALLS) -> List[Call]:
        """The newest ``last`` records, oldest first."""
        with self._lock:
            return self._between(self._n - last, self._n)

    def _between(self, first: int, last: int) -> List[Call]:
        """Lock held. The records numbered ``first`` to ``last - 1`` (in
        the order they were appended, from 0) that the ring still holds,
        read from its newest end: a dump's few hundred cost that many
        steps, not the ring's length."""
        first = max(first, self._n - len(self._ring))
        return list(itertools.islice(
            reversed(self._ring), self._n - last, self._n - first))[::-1]

    def note(self, program: str, shape: Any, wall_s: float, calls: int,
             call: Optional[Dispatched]) -> bool:
        """One measured execution: a record in the ring, the two
        counters, a reading for its shape's median unless it is slow, a
        step of the stalled stretch's rule. Returns whether the call was
        slow. This runs on the caller's thread once a call
        (``account_ms``): it is kept short on purpose."""
        from ray_tpu._private import metrics_defs as mdefs

        if not self._hooked:
            self._hooked = True
            if _gen2 not in gc.callbacks:
                gc.callbacks.append(_gen2)
        if call is None:
            seq = next(self._seq)
            dispatch_ts = dispatch_pc = ready = waited = None
            landed_pc, landed_ts = time.perf_counter(), time.time()
        else:
            seq, dispatch_ts, dispatch_pc = call.seq, call.ts, call.pc
            landed_pc, landed_ts, ready = (call.landed_pc, call.landed_ts,
                                           call.ready)
            waited = (None if call.fetch_pc is None
                      else landed_pc - call.fetch_pc)
        if ready:
            mdefs.XLA_RESULTS_READY.inc(tags={"program": program})
        if waited:
            mdefs.XLA_FETCH_WAIT_SECONDS.inc(waited,
                                             tags={"program": program})
        per_call = wall_s / calls
        closed = False
        with self._lock:
            self._last_pc = landed_pc
            base = self._baselines.get((program, shape))
            if base is None:
                base = self._baselines[program, shape] = _Baseline()
            median = base.median
            slow = (median is not None and per_call > SLOW_FACTOR * median
                    and per_call * 1e3 > SLOW_FLOOR_MS)
            if not slow:
                # Slow calls never enter the median: a 20 s stretch does
                # not become the baseline.
                base.add(per_call)
            rec = Call(seq, program, shape, wall_s, calls, dispatch_ts,
                       dispatch_pc, landed_ts, landed_pc, ready, waited,
                       slow)
            self._ring.append(rec)
            self._n += 1
            stretch = self._stretch
            if slow:
                if stretch is None:
                    stretch = self._stretch = _Stretch(rec, self._n - 1)
                    _ensure_maintenance()
                stretch.add(rec, median)
                if stretch.slow == CAPTURE_AT_SLOW:
                    self._start_capture(stretch)
            elif stretch is not None:
                stretch.normal_run += 1
                if stretch.normal_run >= CLOSE_AFTER:
                    self._close("calls")
                    closed = True
        if closed:
            _maintenance_wake.set()
        return slow

    def _start_capture(self, stretch: _Stretch) -> None:
        """Where ``stall_capture_s`` asks for it, a device trace of the
        open stretch, on a thread of its own (``start_trace`` takes
        hundreds of milliseconds). From its SECOND slow call: one slow
        call alone is over when it is known, a capture after it would
        hold nothing of it, and it would take the minute's one capture
        from the stretch of seconds that may follow. ``busy`` where
        somebody holds the profiler or the minute's capture is spent.
        Lock held."""
        from ray_tpu._private.config import GLOBAL_CONFIG

        seconds = GLOBAL_CONFIG.stall_capture_s
        if seconds <= 0:
            return
        now = time.monotonic()
        if _capture_lock.locked() or (
                self._last_capture is not None
                and now - self._last_capture < CAPTURE_EVERY_S):
            stretch.capture = "busy"
            return
        self._last_capture = now
        with _state_lock:
            address = _gcs_address
        stamp = time.strftime("%Y%m%dT%H%M%S", time.gmtime(stretch.opened_ts))

        def run() -> None:
            try:
                got = _capture_trace(f"stall-{stamp}", seconds, address,
                                     reason="stall", lean=True)
            except Exception as e:  # noqa: BLE001 - nothing reaches callers
                logger.exception("stall capture failed")
                got = {"status": "failed", "error": repr(e)}
            if got.get("status") == "busy":
                # Another session held the profiler: no trace was taken,
                # so the next stretch need not wait a minute for one.
                self._last_capture = None
                got = "busy"
            stretch.capture = got

        stretch.capture = "starting"
        stretch.capture_thread = threading.Thread(
            target=run, daemon=True, name="xla-stall-capture")
        stretch.capture_thread.start()

    def _close(self, why: str) -> None:
        """Book the open stretch and queue it for the maintenance
        thread, which writes its file and its line. Lock held."""
        from ray_tpu._private import metrics_defs as mdefs

        stretch, self._stretch = self._stretch, None
        side = stretch.side()
        mdefs.XLA_STALL_STRETCHES.inc(tags={"side": side})
        mdefs.XLA_STALL_EXCESS_SECONDS.inc(stretch.excess_s,
                                           tags={"side": side})
        self._closed.append({"stretch": stretch, "after": _snapshot(),
                             "closed_n": self._n, "side": side,
                             "closed_by": why})

    def maintain(self) -> None:
        """The maintenance thread's part: close a stretch left open
        when the calls stopped coming (the engine has no work), and
        write what has closed."""
        with self._lock:
            if (self._stretch is not None
                    and time.perf_counter() - self._last_pc > IDLE_CLOSE_S):
                self._close("idle")
            closed, self._closed = self._closed, []
            for one in closed:
                one["records"] = self._between(
                    one["stretch"].opened_n - LEAD_IN_CALLS,
                    one["closed_n"])
        for one in closed:
            try:
                self._written.append(_write_stall(**one))
                if len(self._written) > DUMPS_KEPT:
                    os.remove(self._written.popleft())
            except Exception:  # noqa: BLE001 - telemetry is best-effort
                logger.exception("stall dump failed")


def _write_stall(stretch: _Stretch, records: List[Call],
                 after: Dict[str, float], closed_n: int, side: str,
                 closed_by: str) -> str:
    """ONE file and ONE warning line a closed stretch; returns the
    file's path."""
    if stretch.capture_thread is not None:
        # The capture says what it got (a directory, or "busy") when it
        # ends, a few seconds after the stretch opened.
        stretch.capture_thread.join(timeout=120.0)
    before = stretch.before
    opened = time.strftime("%Y%m%dT%H%M%S", time.gmtime(stretch.opened_ts))
    folder = os.path.join(session_dir(), "stalls")
    path = os.path.join(
        folder,
        f"{opened}.{int(stretch.opened_ts % 1 * 1e3):03d}-{os.getpid()}.json")
    os.makedirs(folder, exist_ok=True)
    seconds = stretch.closed_ts - stretch.opened_ts
    calls = closed_n - stretch.opened_n
    doc = {
        "program": stretch.program, "pid": os.getpid(),
        "opened_ts": stretch.opened_ts, "closed_ts": stretch.closed_ts,
        "seconds": seconds, "calls": calls,
        "slow_calls": stretch.slow, "excess_s": stretch.excess_s,
        "side": side, "closed_by": closed_by, "capture": stretch.capture,
        "rule": {"slow_factor": SLOW_FACTOR, "slow_floor_ms": SLOW_FLOOR_MS,
                 "baseline_calls": BASELINE_CALLS,
                 "close_after": CLOSE_AFTER},
        "before": before, "after": after,
        "delta": {k: after[k] - before[k] for k in before if k in after},
        "records": [r._asdict() for r in records]}
    # Whole or not there: a reader (an operator's tail, a test) may list
    # the directory while this thread writes.
    partial = path + ".tmp"
    with open(partial, "w") as f:
        json.dump(doc, f, default=str)
    os.replace(partial, path)
    logger.warning(
        "xla stall: %s went slow at %sZ for %.2f s: %d calls, %d of them "
        "slow, %.3f s over their shapes' medians; side=%s; capture=%s; "
        "dump %s", stretch.program, opened, seconds, calls,
        stretch.slow, stretch.excess_s, side,
        (stretch.capture.get("trace_dir") if isinstance(stretch.capture, dict)
         else stretch.capture or "off"), path)
    return path


_calls = _CallRecord()


# -------------------------------------------------------- device memory
def sample_device_memory(node_id: Optional[str] = None,
                         force: bool = False) -> List[Dict[str, Any]]:
    """Per-device ``memory_stats()`` vitals as tagged gauges.

    Never triggers a fresh jax import unless ``force`` — importing jax
    grabs the accelerator, and a supervisor process (the node agent on a
    TPU host) must not steal chips from its workers. CPU devices report
    no memory stats; that's the documented graceful None."""
    if not force and "jax" not in sys.modules:
        return []
    try:
        import jax

        devices = jax.local_devices()
    except Exception:  # noqa: BLE001 - no backend at all
        return []
    from ray_tpu._private import metrics_defs as mdefs

    with _state_lock:
        node = (node_id or _node_id or "local")[:12]
    out = []
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001
            stats = None
        if not stats:
            continue
        tags = {"node_id": node, "device": f"{d.platform}:{d.id}"}
        entry: Dict[str, Any] = {"device": tags["device"],
                                 "kind": getattr(d, "device_kind", "?")}
        for field, gauge in (
                ("bytes_in_use", mdefs.DEVICE_MEM_USED),
                ("peak_bytes_in_use", mdefs.DEVICE_MEM_PEAK),
                ("bytes_limit", mdefs.DEVICE_MEM_LIMIT)):
            v = stats.get(field)
            if v is not None:
                gauge.set(float(v), tags=tags)
                entry[field] = int(v)
        out.append(entry)
    return out


# --------------------------------------------------------- capture plane
def request_capture(gcs_address: str, node: str = "*",
                    duration_s: float = 2.0,
                    capture_id: Optional[str] = None) -> str:
    """Publish an on-demand profiler capture command (CLI/dashboard
    entry point). Every XLA-active process on a matching node captures
    for ``duration_s`` and registers its trace dir under
    ``__profiles__/<capture_id>/...``."""
    import pickle

    from ray_tpu._private import rpc
    from ray_tpu.protobuf import ray_tpu_pb2 as pb

    if not capture_id:
        capture_id = f"cap-{int(time.time())}-{os.getpid() % 10000:04d}"
    gcs = rpc.get_stub("GcsService", gcs_address)
    gcs.Publish(pb.PublishRequest(
        channel=PROFILE_CHANNEL,
        data=pickle.dumps({"capture_id": capture_id, "node": node or "*",
                           "duration_s": float(duration_s),
                           "ts": time.time()})), timeout=10)
    return capture_id


def _kv_scan(gcs_address: str, ns: str) -> List[Dict[str, Any]]:
    from ray_tpu._private import rpc
    from ray_tpu.protobuf import ray_tpu_pb2 as pb

    gcs = rpc.get_stub("GcsService", gcs_address)
    out = []
    for key in gcs.KvKeys(pb.KvRequest(ns=ns, prefix="")).keys:
        reply = gcs.KvGet(pb.KvRequest(ns=ns, key=key))
        if not reply.found:
            continue
        try:
            out.append(json.loads(reply.value))
        except ValueError:
            continue
    return out


def list_captures(gcs_address: str) -> List[Dict[str, Any]]:
    """Registered captures, newest first."""
    out = _kv_scan(gcs_address, PROFILE_KV_NS)
    out.sort(key=lambda e: e.get("ts", 0), reverse=True)
    return out


def list_programs(gcs_address: str) -> List[Dict[str, Any]]:
    """The persisted cost-analysis program registry (CLI `ray-tpu
    profile programs` and the dashboard read through this)."""
    out = _kv_scan(gcs_address, PROGRAM_KV_NS)
    out.sort(key=lambda e: (e.get("program", ""), e.get("ts", 0)))
    return out


def start_profile_listener(gcs_address: str,
                           node_id: Optional[str] = None) -> None:
    """Explicitly start this process's capture listener (tests, embedded
    engines); production processes get it lazily via :func:`connect` +
    first compile."""
    connect(gcs_address, node_id=node_id)
    _ensure_listener(gcs_address)
    _ensure_maintenance()


def _ensure_listener(address: str) -> None:
    with _state_lock:
        if address in _listeners:
            return
        stop = _listeners[address] = threading.Event()
    threading.Thread(target=_listener_loop, args=(address, stop),
                     daemon=True, name="xla-profile-listener").start()


def _ensure_maintenance() -> None:
    global _maintenance_stop
    with _state_lock:
        if _maintenance_stop is not None:
            return
        stop = _maintenance_stop = threading.Event()
    threading.Thread(target=_maintenance_loop, args=(stop,), daemon=True,
                     name="xla-monitor-maintenance").start()


def _maintenance_loop(stop: threading.Event) -> None:
    from ray_tpu._private import metrics_pusher

    interval = metrics_pusher.push_interval_s()
    push_at = time.monotonic() + interval
    while not stop.is_set():
        # Woken when a stalled stretch closes (its dump is written
        # here, never on the caller's thread); while one is open, once a
        # second, to close it if the engine has run out of work.
        _maintenance_wake.wait(min(max(push_at - time.monotonic(), 0.0),
                                   1.0 if _calls.stretch_open else interval))
        _maintenance_wake.clear()
        if stop.is_set():
            return
        _calls.maintain()
        if time.monotonic() < push_at:
            continue
        push_at = time.monotonic() + interval
        try:
            _flush_pending_kv()
        except Exception:  # noqa: BLE001 — telemetry is best-effort
            pass
        try:
            sample_device_memory()
        except Exception:  # noqa: BLE001
            pass


def _listener_loop(address: str, stop: threading.Event) -> None:
    import pickle

    from ray_tpu._private import rpc
    from ray_tpu.protobuf import ray_tpu_pb2 as pb

    failures = 0
    while not stop.is_set() and failures < 10:
        try:
            gcs = rpc.get_stub("GcsService", address)
            stream = gcs.Subscribe(pb.SubscribeRequest(
                channels=[PROFILE_CHANNEL],
                subscriber_id=f"xla-{os.getpid()}"),
                timeout=365 * 86400.0)
            for msg in stream:
                failures = 0
                if stop.is_set():
                    break
                try:
                    cmd = pickle.loads(msg.data)
                except Exception:  # noqa: BLE001
                    continue
                if _matches_node(cmd.get("node", "*")):
                    threading.Thread(
                        target=_do_capture, args=(cmd, address),
                        daemon=True, name="xla-profile-capture").start()
        except Exception:  # noqa: BLE001 — cluster down or restarting
            failures += 1
            stop.wait(min(0.5 * failures, 5.0))
    with _state_lock:
        if _listeners.get(address) is stop:
            del _listeners[address]


def _matches_node(target: str) -> bool:
    if target in ("", "*", "all"):
        return True
    with _state_lock:
        node = _node_id
    return bool(node) and (node == target or node.startswith(target))


def _do_capture(cmd: Dict[str, Any], address: str) -> None:
    _capture_trace(str(cmd.get("capture_id") or "cap-unnamed"),
                   float(cmd.get("duration_s", 2.0)), address,
                   reason="command")


def _capture_trace(capture_id: str, duration_s: float,
                   address: Optional[str], reason: str,
                   lean: bool = False) -> Dict[str, Any]:
    """Run ``jax.profiler`` for ``duration_s`` seconds, write the trace
    under the session dir and, where a GCS ``address`` is known,
    register it under ``__profiles__``: the listener's command and a
    stalled stretch (``reason``) both come through here. ``lean``: the
    host tracer at level 1 and the Python tracer off, as the benchmark
    traces, for a capture that starts inside the stretch it watches.
    Returns the registered record; ``status`` is ``busy`` where another
    capture, or anybody else's profiler session, holds the profiler."""
    from ray_tpu._private import metrics_defs as mdefs

    duration = max(duration_s, 0.1)
    with _state_lock:
        node = (_node_id or "local")[:12]
    tag = f"{node}-{os.getpid()}"
    key = f"{capture_id}/{tag}"
    record: Dict[str, Any] = {
        "capture_id": capture_id, "node_id": node, "pid": os.getpid(),
        "duration_s": duration, "reason": reason, "ts": time.time()}

    def register() -> None:
        if not address:
            return
        from ray_tpu._private import rpc
        from ray_tpu.protobuf import ray_tpu_pb2 as pb

        try:
            gcs = rpc.get_stub("GcsService", address)
            gcs.KvPut(pb.KvRequest(ns=PROFILE_KV_NS, key=key,
                                   value=json.dumps(record).encode(),
                                   overwrite=True), timeout=10)
        except Exception:  # noqa: BLE001
            logger.exception("profile capture %s: registration failed",
                             capture_id)

    if not _capture_lock.acquire(blocking=False):
        # Registered under a DISTINCT key: a duplicate command must not
        # clobber the in-flight capture's record.
        key = f"{capture_id}/{tag}-busy"
        record.update(status="busy",
                      error="a capture is already in progress")
        register()
        return record
    try:
        trace_dir = os.path.join(session_dir(), "profiles", capture_id,
                                 tag)
        os.makedirs(trace_dir, exist_ok=True)
        record.update(status="capturing", trace_dir=trace_dir)
        register()
        import jax

        options = None
        if lean:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
        try:
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        except RuntimeError as e:
            if "already" not in str(e):
                raise
            # Somebody else's session (a benchmark's ``--trace 1``
            # window): "Profile has already been started. Only one
            # profile may be run at a time."
            record.update(status="busy", error=str(e), end_ts=time.time())
            return record
        try:
            time.sleep(duration)
        finally:
            jax.profiler.stop_trace()
        files = sum(len(fs) for _, _, fs in os.walk(trace_dir))
        record.update(status="done", files=files, end_ts=time.time())
        mdefs.PROFILE_CAPTURES.inc(tags={"status": "done"})
    except Exception as e:  # noqa: BLE001
        record.update(status="failed", error=repr(e), end_ts=time.time())
        mdefs.PROFILE_CAPTURES.inc(tags={"status": "failed"})
        logger.exception("profile capture %s failed", capture_id)
    finally:
        _capture_lock.release()
        register()
    return record
