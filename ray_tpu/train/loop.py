"""Async step pipelining: dispatch-ahead training with windowed syncs.

The synced loop fetches the loss every step — one device→host round trip
per step, and the XLA pipe drains while the host formats a float. This
loop keeps up to ``sync_every`` steps dispatched and pulls their metrics
off-device in one windowed fetch, so the device runs back-to-back steps
while the host stays out of the hot path (the training-side analog of
the buffered serve engine's ``sync_every`` speculative decode).

Gauge honesty: ``xla_monitor``'s call-cadence fallback for the
achieved-FLOPs/MFU gauges is only right when every call syncs. This loop
disables that fallback by feeding MEASURED window wall time through
``InstrumentedJit.note_execution`` (window wall / steps in window), the
same windowed accounting the serve engine uses — so MFU stays honest
with K steps in flight.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Iterable, List, Optional, Tuple


class AsyncStepLoop:
    """Drive ``trainer.train_step`` with at most ``sync_every`` un-synced
    steps; metrics land in ``history`` (host floats) at each window sync.

    Exactly the same programs run as in a synced loop — only the fetch
    cadence changes, so losses are bit-identical to per-step syncing.
    """

    def __init__(self, trainer, state, *, sync_every: int = 4,
                 name: str = "async_loop", ledger=None):
        if sync_every < 1:
            raise ValueError(f"sync_every must be >= 1, got {sync_every}")
        self.trainer = trainer
        self.state = state
        self.sync_every = sync_every
        self.name = name
        # Goodput attribution: the windowed fetch's host-blocked wall
        # time is the ledger's "sync" component — explicit ledger wins,
        # else the ambient training session's (resolved per sync).
        self._ledger = ledger
        self.history: List[Dict[str, float]] = []
        self.steps = 0
        self._pending: List[Dict[str, Any]] = []
        self._window_t0: Optional[float] = None
        self._window_wall_s = 0.0
        self._synced_steps = 0

    # ------------------------------------------------------------- steps
    def step(self, batch) -> None:
        """Dispatch one train step; syncs only at window boundaries."""
        if self._window_t0 is None:
            self._window_t0 = time.perf_counter()
        self.state, metrics = self.trainer.train_step(self.state, batch)
        self._pending.append(metrics)
        self.steps += 1
        if len(self._pending) >= self.sync_every:
            self.sync()

    def sync(self) -> None:
        """Fetch every pending metrics pytree (blocks until the dispatched
        steps complete) and feed the measured window cadence to the MFU
        gauges."""
        if not self._pending:
            return
        import jax

        from ray_tpu._private import metrics_defs as mdefs
        from ray_tpu.train import goodput

        n = len(self._pending)
        t_fetch = time.perf_counter()
        fetched = jax.device_get(self._pending)
        now = time.perf_counter()
        # "sync" = host blocked in the windowed fetch. Under sync_every=1
        # this is where device compute drains (the honest reading is
        # "syncing too often"), with steps in flight it is pure overhead.
        ledger = self._ledger or goodput.current_ledger()
        if ledger is not None:
            ledger.note("sync", now - t_fetch)
        wall = now - self._window_t0
        # Windows are CONTIGUOUS: the next one starts here, not at its
        # first step(), so the stall fetching a window's first batch —
        # or any host work between windows — lands inside a window under
        # the direct ``loop.step(batch)`` spelling too. Idle time can
        # only inflate measured wall: MFU errs LOW, never high.
        self._window_t0 = now
        self._window_wall_s += wall
        self._synced_steps += n
        per_step = wall / n
        step_jit = getattr(self.trainer, "_step", None)
        if step_jit is not None and hasattr(step_jit, "note_execution"):
            # Windowed accounting: dispatch-of-first → fetch-complete,
            # split across the window's steps. Input stalls inside the
            # window inflate it — MFU errs LOW, never high.
            step_jit.note_execution(wall, calls=n)
        tags = {"trainer": self.name}
        for m in fetched:
            mdefs.TRAIN_STEP_SECONDS.observe(per_step, tags=tags)
            self.history.append({k: float(v) for k, v in m.items()})
        self._pending.clear()

    def run(self, batches: Iterable[Any],
            max_steps: Optional[int] = None) -> Tuple[Any, List[Dict]]:
        """Consume ``batches`` (host iterator or a
        :class:`~ray_tpu.train.ingest.DevicePrefetcher`) to exhaustion or
        ``max_steps``, then drain the window. Returns (state, history)."""
        it = iter(batches)
        while True:
            # Stamp the very first window before pulling the first batch
            # so its fetch stall is measured; sync() keeps later windows
            # contiguous from there.
            if self._window_t0 is None:
                self._window_t0 = time.perf_counter()
            try:
                batch = next(it)
            except StopIteration:
                break
            self.step(batch)
            if max_steps is not None and self.steps >= max_steps:
                break
        return self.finish()

    def finish(self) -> Tuple[Any, List[Dict]]:
        self.sync()
        return self.state, self.history

    # ------------------------------------------------------------- stats
    def stats(self) -> Dict[str, float]:
        return {
            "steps": float(self.steps),
            "synced_steps": float(self._synced_steps),
            "window_wall_s": self._window_wall_s,
            "step_s": (self._window_wall_s / self._synced_steps
                       if self._synced_steps else 0.0),
            "pending": float(len(self._pending)),
        }
