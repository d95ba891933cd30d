"""Why did the chip idle: a profiler trace's idle gaps by host cause.

    python3 -m ray_tpu.util.profile_gaps <file.xplane.pb>

The engine thread wraps each stretch of its loop in a ``tracing.phase``
(``engine.lock_wait``, ``engine.admit``, ``engine.prefill`` with its
``.dispatch`` and ``.fetch``, ``engine.upload``, ``engine.tick.dispatch``
of tick n+1 and ``engine.tick.fetch`` of tick n (the step keeps one tick
queued behind the one that runs; traces older than PR 28 have an
``engine.tick`` around both, of one tick), ``engine.account``,
``engine.apply``), which a profiler session records as host events on
the device events' clock. A gap is a stretch in which no instruction ran on the chip (the
``XLA Ops`` line of its ``/device:TPU:<n>`` plane). Each gap is split
among the annotations that overlap it; where they nest, the innermost
takes its part, and what no annotation covers is ``unattributed``. So a
gap under ``engine.tick.dispatch`` is launch latency, one under
``engine.tick.fetch`` a device that ran out of queued ticks before the
host came back for the row, one under ``engine.admit`` host bookkeeping
while the device had nothing queued.

For a ``ray-tpu profile`` capture of a serving replica, or a
``benchmark/run.py --trace 1 --keep-trace DIR`` run. The profiler's host
tracer must be at level 1 or above (its default is 2). ``split`` works
on plain tuples, so tests feed it a hand-made trace; ``load`` turns a
file into those tuples.
"""

from __future__ import annotations

import bisect
import sys
from collections import defaultdict
from typing import Any, Dict, Iterable, List, Tuple

Event = Tuple[str, int, int]            # name, start ns, duration ns
Segment = Tuple[int, int, str]          # start ns, end ns, name

OPS_LINE = "XLA Ops"
PREFIX = "engine."
UNATTRIBUTED = "unattributed"


def innermost(events: Iterable[Event]) -> List[Segment]:
    """One thread's annotations, flattened: disjoint segments in time
    order, each named after the innermost annotation open in it. A
    thread's events nest properly; one that outlasts the event around
    it is cut at that event's end."""
    out: List[Segment] = []
    stack: List[Tuple[int, str]] = []    # (end, name), outermost first
    cursor = 0

    def close(upto: int) -> None:
        """Book ``cursor..upto`` to the top of the stack."""
        nonlocal cursor
        if stack and upto > cursor:
            out.append((cursor, upto, stack[-1][1]))
        cursor = max(cursor, upto)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= start:
            close(stack[-1][0])
            stack.pop()
        close(start)
        end = start + dur
        if stack:
            end = min(end, stack[-1][0])
        stack.append((end, name))
    while stack:
        close(stack[-1][0])
        stack.pop()
    return out


def _union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], end))
        else:
            out.append((start, end))
    return out


def split(ops: Iterable[Event], host_lines: Iterable[Iterable[Event]],
          prefix: str = PREFIX) -> Dict[str, Any]:
    """One chip's idle time by the annotation the host was in.

    ``ops`` are the chip's instruction events, ``host_lines`` the host
    threads' events, one list a thread; only names that start with
    ``prefix`` count. Returns seconds: ``window_s`` (first device event
    to last), ``busy_s``, ``idle_s``, and ``by_phase``, ``[name,
    seconds, share of idle_s]`` largest first, ``unattributed`` among
    them. Threads are taken to run their annotated stretches one at a
    time (the engine's lock sees to that); where two overlap, a gap
    under both is booked to both."""
    busy = _union((s, s + d) for _, s, d in ops if d > 0)
    if not busy:
        return {"window_s": 0.0, "busy_s": 0.0, "idle_s": 0.0, "by_phase": []}
    segments: List[Segment] = []
    for line in host_lines:
        segments.extend(innermost(e for e in line
                                  if e[0].startswith(prefix)))
    segments.sort()
    starts = [s for s, _, _ in segments]
    longest = max((e - s for s, e, _ in segments), default=0)
    seconds: Dict[str, float] = defaultdict(float)
    idle = 0
    for (_, gap_start), (gap_end, _) in zip(busy[:-1], busy[1:]):
        idle += gap_end - gap_start
        named = 0
        # Segments that can reach into the gap start after
        # gap_start - longest and before gap_end.
        i = bisect.bisect_left(starts, gap_start - longest)
        while i < len(segments) and segments[i][0] < gap_end:
            start, end, name = segments[i]
            part = min(end, gap_end) - max(start, gap_start)
            if part > 0:
                seconds[name] += part / 1e9
                named += part
            i += 1
        seconds[UNATTRIBUTED] += max(gap_end - gap_start - named, 0) / 1e9
    idle_s = idle / 1e9
    return {
        "window_s": (busy[-1][1] - busy[0][0]) / 1e9,
        "busy_s": sum(e - s for s, e in busy) / 1e9,
        "idle_s": idle_s,
        "by_phase": [[name, s, s / idle_s if idle_s else 0.0]
                     for name, s in sorted(seconds.items(),
                                           key=lambda kv: -kv[1])
                     if s > 0]}


def load(path: str, prefix: str = PREFIX) \
        -> Tuple[Dict[str, List[Event]], List[List[Event]]]:
    """``({chip plane: its instruction events}, [one host thread's
    annotation events, ...])`` of a trace file."""
    from jax.profiler import ProfileData

    chips: Dict[str, List[Event]] = {}
    host_lines: List[List[Event]] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    chips[plane.name] = [
                        (ev.name, int(ev.start_ns), int(ev.duration_ns))
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                          for ev in line.events
                          if ev.name.startswith(prefix)]
                if events:
                    host_lines.append(events)
    return chips, host_lines


def main(argv: List[str]) -> int:
    if len(argv) != 1:
        print(__doc__.split("\n\n")[1].strip() + "\n\n(see the module's "
              "docstring)", file=sys.stderr)
        return 2
    chips, host_lines = load(argv[0])
    if not chips:
        print(f"{argv[0]}: no /device:TPU:<n> plane with an "
              f"'{OPS_LINE}' line", file=sys.stderr)
        return 1
    for chip, ops in sorted(chips.items()):
        got = split(ops, host_lines)
        print(f"{chip}: window {got['window_s']:.3f} s, busy "
              f"{got['busy_s']:.3f} s, idle {got['idle_s']:.3f} s "
              f"({100 * got['idle_s'] / max(got['window_s'], 1e-12):.1f}% "
              f"of the window), {len(host_lines)} annotated host thread(s)")
        for name, s, share in got["by_phase"]:
            print(f"  {name:28s} {s:9.4f} s  {100 * share:5.1f}% of idle")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
