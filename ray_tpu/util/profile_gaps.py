"""Why did the chip idle: a profiler trace's idle gaps by host cause.

    python3 -m ray_tpu.util.profile_gaps <file.xplane.pb> [--gaps N] [--calls]

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

``--calls`` reads the trace call by call instead (PR 48): the engine's
``engine.tick.dispatch`` / ``.fetch`` and ``engine.prefill.dispatch`` /
``.fetch`` annotations carry the call's ``seq`` (the one its record in
``xla_monitor``'s ring and in a stall's dump has), and each call is split
into **launch** (the end of its dispatch annotation to its program's
start on the chip, where the chip was free), **device** (its programs'
own time on the chip, the ``XLA Modules`` line), **return** (their end
to the end of the fetch annotation: the transfer back and the thread's
wake-up) and **late** (their end to the START of the fetch annotation,
where the host came for a result that was long done: the host's own
lateness, which the record calls ``ready``), with each part's median
over the calls of one program, the calls furthest over it, and the
instructions (``XLA Ops``) whose own time grew most in the slow calls
against the others. A stalled stretch's ten-fold is then in one of the
four, and if it is ``device``, in named instructions or spread over all
of them.

For a ``ray-tpu profile`` capture of a serving replica, a stalled
stretch's own capture (``RAY_TPU_stall_capture_s``), or a
``benchmark/run.py --trace 1 --keep-trace DIR`` run. The profiler's host
tracer must be at level 1 or above (its default is 2). ``split`` and ``calls``
work on plain tuples, so tests feed them a hand-made trace; ``load`` and
``load_calls`` turn a file into those tuples.
"""

from __future__ import annotations

import bisect
import statistics
import sys
from collections import defaultdict
from typing import Any, Dict, Iterable, List, Tuple

Event = Tuple[str, int, int]            # name, start ns, duration ns
Segment = Tuple[int, int, str]          # start ns, end ns, name
Annotation = Tuple[str, int, int, Any]  # name, start ns, duration ns, seq

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
PREFIX = "engine."
UNATTRIBUTED = "unattributed"
DISPATCH, FETCH = ".dispatch", ".fetch"
PARTS = ("launch", "device", "return", "late")
CALL_SLOW_FACTOR = 2.0      # a slow call: its three parts, over their median


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


def _instruction(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return name.partition(" = ")[0].lstrip("%")


def calls(annotations: Iterable[Annotation], modules: Iterable[Event],
          ops: Iterable[Event] = (), top: int = 5) -> Dict[str, Any]:
    """One chip's engine calls, each split into launch | device | return.

    ``annotations`` are the host's ``<kind>.dispatch`` and
    ``<kind>.fetch`` events with the ``seq`` they carry, ``modules`` the
    chip's ``XLA Modules`` events, ``ops`` its ``XLA Ops``. A program
    belongs to the last call dispatched before it started (the chip runs
    them in dispatch order, and the engine dispatches call n+2 only
    after call n has landed). Returns ``calls``, one object a call that
    has both annotations and a program in the trace, in dispatch order
    (``seq``, ``kind``, ``program``, ``at_s`` from the first device
    event, ``launch_ms``, ``device_ms``, ``return_ms``, ``late_ms``,
    ``slow``), and ``by_program``, an object a (kind, program): ``n``,
    ``median_ms`` of the four parts, ``furthest`` (a part's ``[seq, ms
    over the median]``, the ``top`` largest), ``slow`` (the seqs whose
    parts together are over ``CALL_SLOW_FACTOR`` medians) and ``grew``
    (``[instruction, ms a slow call, ms a call of the others]``, by the
    difference, the ``top`` largest; empty with no slow call or no
    ``ops``)."""
    dispatch: Dict[Any, Annotation] = {}
    fetch: Dict[Any, Annotation] = {}
    for ann in annotations:
        if ann[3] is None:
            continue
        if ann[0].endswith(DISPATCH):
            dispatch[ann[3]] = ann
        elif ann[0].endswith(FETCH):
            fetch[ann[3]] = ann
    order = sorted(dispatch.values(), key=lambda a: a[1])
    starts = [a[1] for a in order]
    programs = sorted((e for e in modules if e[2] > 0), key=lambda e: e[1])
    mine: Dict[Any, List[Event]] = defaultdict(list)
    for event in programs:
        i = bisect.bisect_right(starts, event[1]) - 1
        if i < 0:
            continue
        landing = fetch.get(order[i][3])
        # A program that starts after the call's result is on the host
        # is somebody else's (a program no annotation carries a seq for).
        if landing is None or event[1] < landing[1] + landing[2]:
            mine[order[i][3]].append(event)
    ends = sorted(s + d for _, s, d in programs)
    origin = programs[0][1] if programs else 0
    # Own time of each instruction, as disjoint segments in time order.
    segments = innermost(e for e in ops if e[2] > 0)
    segment_starts = [s for s, _, _ in segments]
    out: List[Dict[str, Any]] = []
    own: Dict[Any, Dict[str, int]] = {}
    for name, start, dur, seq in order:
        got, landing = mine.get(seq), fetch.get(seq)
        if not got or landing is None:
            continue            # cut off by the trace's start or end
        first, last = got[0][1], max(s + d for _, s, d in got)
        # The chip was free from the end of the program before this
        # call's first (it may have been another call's).
        j = bisect.bisect_right(ends, first) - 1
        free_from = max(start + dur, ends[j] if j >= 0 else 0)
        main = max(got, key=lambda e: e[2])[0]
        out.append({
            "seq": seq, "kind": name[:-len(DISPATCH)], "program": main,
            "at_s": (start - origin) / 1e9,
            "launch_ms": max(first - free_from, 0) / 1e6,
            "device_ms": sum(e - s for s, e in _union(
                (s, s + d) for _, s, d in got)) / 1e6,
            "return_ms": max(landing[1] + landing[2]
                             - max(last, landing[1]), 0) / 1e6,
            "late_ms": max(landing[1] - last, 0) / 1e6,
            "slow": False})
        per: Dict[str, int] = defaultdict(int)
        for _, s, d in got:
            i = bisect.bisect_left(segment_starts, s)
            while i < len(segments) and segments[i][0] < s + d:
                per[_instruction(segments[i][2])] += (
                    min(segments[i][1], s + d) - segments[i][0])
                i += 1
        own[seq] = per
    groups: Dict[Tuple[str, str], List[Dict[str, Any]]] = defaultdict(list)
    for call in out:
        groups[call["kind"], call["program"]].append(call)
    by_program = []
    for (kind, program), group in sorted(groups.items()):
        median = {p: statistics.median(c[p + "_ms"] for c in group)
                  for p in PARTS}
        whole = sum(median.values())
        for call in group:
            call["slow"] = (sum(call[p + "_ms"] for p in PARTS)
                            > CALL_SLOW_FACTOR * whole)
        slow = [c["seq"] for c in group if c["slow"]]
        others = [c["seq"] for c in group if not c["slow"]]
        grew = []
        if slow and others:
            names = {n for seq in slow for n in own[seq]}
            for n in names:
                a = sum(own[seq].get(n, 0) for seq in slow) / len(slow)
                b = sum(own[seq].get(n, 0) for seq in others) / len(others)
                grew.append([n, a / 1e6, b / 1e6])
            grew.sort(key=lambda g: g[2] - g[1])
        by_program.append({
            "kind": kind, "program": program, "n": len(group),
            "median_ms": median,
            "furthest": {p: sorted(
                ([c["seq"], c[p + "_ms"] - median[p]] for c in group
                 if c[p + "_ms"] > median[p]),
                key=lambda f: -f[1])[:top] for p in PARTS},
            "slow": slow, "grew": grew[:top]})
    return {"calls": out, "by_program": by_program}


def load_calls(path: str, prefix: str = PREFIX) \
        -> Tuple[Dict[str, Tuple[List[Event], List[Event]]],
                 List[Annotation]]:
    """``({chip plane: (its ``XLA Modules`` events, its ``XLA Ops``
    events)}, the host's dispatch and fetch annotations with their
    ``seq``)`` of a trace file: what :func:`calls` takes."""
    from jax.profiler import ProfileData

    chips: Dict[str, Tuple[List[Event], List[Event]]] = {}
    annotations: List[Annotation] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: [
                (ev.name, int(ev.start_ns), int(ev.duration_ns))
                for ev in line.events] for line in plane.lines
                if line.name in (MODULES_LINE, OPS_LINE)}
            chips[plane.name] = (lines.get(MODULES_LINE, []),
                                 lines.get(OPS_LINE, []))
        elif plane.name.startswith("/host:"):
            for host_line in plane.lines:
                for ev in host_line.events:
                    if ev.name.startswith(prefix) and ev.name.endswith(
                            (DISPATCH, FETCH)):
                        annotations.append((
                            ev.name, int(ev.start_ns), int(ev.duration_ns),
                            dict(ev.stats).get("seq")))
    return chips, annotations


def _print_calls(path: str) -> int:
    chips, annotations = load_calls(path)
    if not chips:
        print(f"{path}: no /device:TPU:<n> plane", file=sys.stderr)
        return 1
    for chip, (modules, ops) in sorted(chips.items()):
        got = calls(annotations, modules, ops)
        print(f"{chip}: {len(got['calls'])} engine calls with a seq, a "
              f"program on the chip and a fetch (launch | device | return | "
              f"late, ms; * = over {CALL_SLOW_FACTOR:g} medians of its program)")
        for call in got["calls"]:
            print(f"  {'*' if call['slow'] else ' '} seq {call['seq']:>8} "
                  f"{call['kind']:14s} at {call['at_s']:8.4f} s  "
                  f"{call['launch_ms']:9.3f} | {call['device_ms']:9.3f} | "
                  f"{call['return_ms']:9.3f} | {call['late_ms']:9.3f}  "
                  f"{call['program']}")
        for group in got["by_program"]:
            median = group["median_ms"]
            print(f"  {group['kind']} {group['program']}: {group['n']} "
                  f"calls, median " + " | ".join(
                      f"{p} {median[p]:.3f}" for p in PARTS) + " ms")
            for p in PARTS:
                far = ", ".join(f"seq {seq} +{ms:.3f}"
                                for seq, ms in group["furthest"][p])
                print(f"    furthest over in {p}: {far or 'none'}")
            if group["slow"]:
                print(f"    slow calls: "
                      + ", ".join(f"seq {seq}" for seq in group["slow"]))
            for name, a, b in group["grew"]:
                print(f"    grew in them: {name:32s} {a:9.3f} ms a slow "
                      f"call, {b:9.3f} ms a call of the others")
    return 0


def main(argv: List[str]) -> int:
    argv, gaps = list(argv), 0
    by_call = "--calls" in argv
    if by_call:
        argv.remove("--calls")
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
    if by_call:
        return _print_calls(argv[0])
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
