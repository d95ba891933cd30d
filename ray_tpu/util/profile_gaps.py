"""Why did the chip idle: a profiler trace's idle gaps by host cause.

    python3 -m ray_tpu.util.profile_gaps <file.xplane.pb> [--gaps N]

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
while the device had nothing queued. ``--gaps N`` also lists the N
longest gaps one by one: when, how long, the programs that ran on either
side (the chip's ``XLA Modules`` line; the instructions where a trace
has none) and the annotations over each, which is how one 65 ms stall
in a 40 s window is found.

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
MODULES_LINE = "XLA Modules"
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


class _Host:
    """The host threads' annotations, flattened and sorted, and what of
    them lies over a stretch of time."""

    def __init__(self, host_lines: Iterable[Iterable[Event]], prefix: str):
        self.segments: List[Segment] = []
        for line in host_lines:
            self.segments.extend(innermost(e for e in line
                                           if e[0].startswith(prefix)))
        self.segments.sort()
        self.starts = [s for s, _, _ in self.segments]
        self.longest = max((e - s for s, e, _ in self.segments), default=0)

    def over(self, gap_start: int, gap_end: int) -> Dict[str, int]:
        """ns of ``gap_start..gap_end`` under each annotation, and under
        none (``unattributed``)."""
        parts: Dict[str, int] = defaultdict(int)
        named = 0
        # Segments that can reach into the gap start after
        # gap_start - longest and before gap_end.
        i = bisect.bisect_left(self.starts, gap_start - self.longest)
        while i < len(self.segments) and self.segments[i][0] < gap_end:
            start, end, name = self.segments[i]
            part = min(end, gap_end) - max(start, gap_start)
            if part > 0:
                parts[name] += part
                named += part
            i += 1
        if gap_end - gap_start > named:
            parts[UNATTRIBUTED] += gap_end - gap_start - named
        return parts


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
    host = _Host(host_lines, prefix)
    seconds: Dict[str, float] = defaultdict(float)
    idle = 0
    for (_, gap_start), (gap_end, _) in zip(busy[:-1], busy[1:]):
        idle += gap_end - gap_start
        for name, part in host.over(gap_start, gap_end).items():
            seconds[name] += part / 1e9
    idle_s = idle / 1e9
    return {
        "window_s": (busy[-1][1] - busy[0][0]) / 1e9,
        "busy_s": sum(e - s for s, e in busy) / 1e9,
        "idle_s": idle_s,
        "by_phase": [[name, s, s / idle_s if idle_s else 0.0]
                     for name, s in sorted(seconds.items(),
                                           key=lambda kv: -kv[1])
                     if s > 0]}


def longest_gaps(ops: Iterable[Event],
                 host_lines: Iterable[Iterable[Event]], n: int,
                 programs: Iterable[Event] = (),
                 prefix: str = PREFIX) -> List[Dict[str, Any]]:
    """The ``n`` longest gaps of one chip, longest first: ``at_s`` (from
    the first device event), ``ms``, ``after`` and ``before`` (the
    program that ended at the gap's start and the one that began at its
    end, from ``programs``, the chip's ``XLA Modules`` events; from
    ``ops`` where there are none) and ``host``, ``[annotation, ms]``
    over the gap, largest first."""
    ops = [e for e in ops if e[2] > 0]
    busy = _union((s, s + d) for _, s, d in ops)
    named = sorted((e for e in programs if e[2] > 0),
                   key=lambda e: e[1]) or sorted(ops, key=lambda e: e[1])
    starts = [s for _, s, _ in named]
    host = _Host(host_lines, prefix)
    gaps = sorted(((gap_end - gap_start, gap_start, gap_end)
                   for (_, gap_start), (gap_end, _)
                   in zip(busy[:-1], busy[1:])), reverse=True)[:max(n, 0)]
    out = []
    for length, gap_start, gap_end in gaps:
        # The last program that began before the gap; the first that
        # began in or after it and runs past its end (a program's event
        # opens a little before its first instruction).
        j = bisect.bisect_left(starts, gap_start) - 1
        i = j + 1
        while i < len(named) and named[i][1] + named[i][2] <= gap_end:
            i += 1
        out.append({
            "at_s": (gap_start - busy[0][0]) / 1e9, "ms": length / 1e6,
            "after": named[j][0] if j >= 0 else "",
            "before": named[i][0] if i < len(named) else "",
            "host": [[name, part / 1e6] for name, part in sorted(
                host.over(gap_start, gap_end).items(),
                key=lambda kv: -kv[1])]})
    return out


def load(path: str, prefix: str = PREFIX, line: str = OPS_LINE) \
        -> Tuple[Dict[str, List[Event]], List[List[Event]]]:
    """``({chip plane: the events of its ``line``}, [one host thread's
    annotation events, ...])`` of a trace file; ``line`` is the
    instructions' (``XLA Ops``) unless the programs' (``XLA Modules``)
    is asked for."""
    from jax.profiler import ProfileData

    chips: Dict[str, List[Event]] = {}
    host_lines: List[List[Event]] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            for device_line in plane.lines:
                if device_line.name == line:
                    chips[plane.name] = [
                        (ev.name, int(ev.start_ns), int(ev.duration_ns))
                        for ev in device_line.events]
        elif plane.name.startswith("/host:"):
            for host_line in plane.lines:
                events = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                          for ev in host_line.events
                          if ev.name.startswith(prefix)]
                if events:
                    host_lines.append(events)
    return chips, host_lines


def main(argv: List[str]) -> int:
    argv, gaps = list(argv), 0
    if "--gaps" in argv:
        at = argv.index("--gaps")
        try:
            gaps = int(argv[at + 1])
        except (IndexError, ValueError):
            argv = []
        del argv[at:at + 2]
    if len(argv) != 1:
        print(__doc__.split("\n\n")[1].strip() + "\n\n(see the module's "
              "docstring)", file=sys.stderr)
        return 2
    chips, host_lines = load(argv[0])
    if not chips:
        print(f"{argv[0]}: no /device:TPU:<n> plane with an "
              f"'{OPS_LINE}' line", file=sys.stderr)
        return 1
    programs = load(argv[0], line=MODULES_LINE)[0] if gaps else {}
    for chip, ops in sorted(chips.items()):
        got = split(ops, host_lines)
        print(f"{chip}: window {got['window_s']:.3f} s, busy "
              f"{got['busy_s']:.3f} s, idle {got['idle_s']:.3f} s "
              f"({100 * got['idle_s'] / max(got['window_s'], 1e-12):.1f}% "
              f"of the window), {len(host_lines)} annotated host thread(s)")
        for name, s, share in got["by_phase"]:
            print(f"  {name:28s} {s:9.4f} s  {100 * share:5.1f}% of idle")
        for gap in longest_gaps(ops, host_lines, gaps,
                                programs.get(chip, ())):
            over = ", ".join(f"{name} {ms:.3f}" for name, ms in gap["host"])
            print(f"  gap at {gap['at_s']:8.4f} s {gap['ms']:9.3f} ms  "
                  f"{gap['after']} -> {gap['before']}  [{over}]")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
