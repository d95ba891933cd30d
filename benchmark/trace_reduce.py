"""From a profiler trace (``.xplane.pb``) to numbers.

What a v5e trace holds (looked at by hand, PR 22): one plane per chip,
``/device:TPU:<n>``. Its line ``XLA Modules`` has one event per executed
program (``jit_tick(<hash>)``, ``jit_step_fn(<hash>)``). Its line
``XLA Ops`` has one event per executed HLO instruction, NAMED BY THE
INSTRUCTION'S WHOLE TEXT (``%fusion.215 = bf16[4,2047,2048]{...}
fusion(...)``), properly nested: a ``while`` spans its body's events. So

* an event's own time is its duration less its children's;
* a Pallas kernel is a ``custom-call`` whose text carries
  ``custom_call_target="tpu_custom_call"`` (JAX names the instruction
  after the surrounding computation: ``closed_call``, ``checkpoint``,
  ``rematted_computation``; it says nothing of which kernel it is);
* a collective is known by its opcode.

Everything here is derived from those two lines, so it needs no name
inside the program; what needs one (a single kernel's time, a host
cause for an idle gap) is listed in PERF.md. ``reduce`` works on plain
tuples, so the tests feed it a recorded trace or a hand-made one;
``load`` turns a file into those tuples.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

Event = Tuple[str, int, int]          # name, start ns, duration ns
Plane = Dict[str, List[Event]]        # line name -> events

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'
COLLECTIVES = {
    base + suffix
    for base in ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                 "collective-permute", "collective-broadcast")
    for suffix in ("", "-start", "-done")}
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"[a-z][a-z0-9]*\[[0-9,]*\]")


def find(trace_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def load(path: str) -> Dict[str, Plane]:
    """The chips' planes of a trace file: ``{plane: {line: [events]}}``,
    the two lines the reduction reads."""
    from jax.profiler import ProfileData

    planes: Dict[str, Plane] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        planes[plane.name] = {
            line.name: [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                        for ev in line.events]
            for line in plane.lines if line.name in (OPS_LINE, MODULES_LINE)}
    return planes


def opcode(name: str) -> str:
    """``%all-gather-start.3 = (...) all-gather-start(...)`` ->
    ``all-gather-start``. A name that is not instruction text (a
    hand-made trace) is its own opcode, less any ``.<n>``."""
    head, eq, rest = name.partition(" = ")
    if eq:
        match = _OPCODE.search(" " + rest)
        if match:
            return match.group(1)
    return re.sub(r"\.\d+$", "", head.lstrip("%"))


def label(name: str) -> str:
    """A short, readable name for the breakdown: the instruction's own
    name, its opcode where that says more, and its first result shape."""
    head, eq, rest = name.partition(" = ")
    short = head.lstrip("%")
    if not eq:
        return short
    parts = [short]
    op = opcode(name)
    if not short.startswith(op):
        parts.append(op)
    if MOSAIC_TARGET in name:
        parts.append("mosaic")
    shape = _SHAPE.search(rest)
    if shape:
        parts.append(shape.group(0))
    return " ".join(parts)


def self_times(ops: List[Event]) -> List[Tuple[Event, int, bool]]:
    """``(event, own ns, is a leaf)``. An event is another's child when
    it lies wholly inside it; the chip's line is properly nested, and
    events that merely overlap are taken as siblings."""
    order = sorted(ops, key=lambda e: (e[1], -e[2]))
    child_ns = [0] * len(order)
    has_child = [False] * len(order)
    stack: List[int] = []
    for i, (_, start, dur) in enumerate(order):
        while stack and (order[stack[-1]][1] + order[stack[-1]][2]
                         < start + dur):
            stack.pop()
        if stack:
            child_ns[stack[-1]] += dur
            has_child[stack[-1]] = True
        stack.append(i)
    return [(ev, max(ev[2] - child_ns[i], 0), not has_child[i])
            for i, ev in enumerate(order)]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], end))
        else:
            out.append((start, end))
    return out


def _covered(intervals: List[Tuple[int, int]],
             cover: List[Tuple[int, int]]) -> int:
    """Nanoseconds of the disjoint ``intervals`` inside the union
    ``cover``."""
    total, j = 0, 0
    for start, end in intervals:
        while j < len(cover) and cover[j][1] <= start:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < end:
            total += min(end, cover[k][1]) - max(start, cover[k][0])
            k += 1
    return total


def reduce(planes: Dict[str, Plane], top: int = 10) -> Dict[str, Any]:
    """Busy and idle time, own time by instruction, the Mosaic share,
    exposed collectives and idle gaps, averaged over the chips that ran
    anything. Seconds throughout. ``window_s`` runs from the first
    device event to the last, ``busy_s`` is the union of the events."""
    per_chip = []
    # A layer loop repeats each instruction's (long) text thousands of
    # times: parse each distinct text once.
    opcodes: Dict[str, str] = {}
    labels: Dict[str, str] = {}

    def memo(table, fn, name):
        if name not in table:
            table[name] = fn(name)
        return table[name]

    op_seconds: Dict[str, float] = defaultdict(float)
    gap_seconds: Dict[str, float] = defaultdict(float)
    for lines in planes.values():
        ops = [e for e in lines.get(OPS_LINE, []) if e[2] > 0]
        if not ops:
            continue
        timed = self_times(ops)
        busy = _union([(s, s + d) for _, s, d in ops])
        leaves = [(ev, memo(opcodes, opcode, ev[0]) in COLLECTIVES)
                  for ev, _, leaf in timed if leaf]
        compute = _union([(s, s + d) for (_, s, d), coll in leaves if not coll])
        collective = _union([(s, s + d) for (_, s, d), coll in leaves if coll])
        in_collective = sum(e - s for s, e in collective)
        per_chip.append({
            "window_s": (busy[-1][1] - busy[0][0]) / 1e9,
            "busy_s": sum(e - s for s, e in busy) / 1e9,
            "mosaic_s": sum(own for ev, own, _ in timed
                            if MOSAIC_TARGET in ev[0]) / 1e9,
            "collective_s": in_collective / 1e9,
            "collective_exposed_s":
                (in_collective - _covered(collective, compute)) / 1e9})
        for ev, own, _ in timed:
            op_seconds[memo(labels, label, ev[0])] += own / 1e9
        modules = sorted(lines.get(MODULES_LINE, []), key=lambda e: e[1])
        ends = [s + d for _, s, d in modules]
        for (_, prev_end), (next_start, _) in zip(busy[:-1], busy[1:]):
            # Named after the program the device ran next: that is what
            # the host was late with.
            i = bisect.bisect_right(ends, next_start)
            name = (re.sub(r"\(\d+\)$", "", modules[i][0])
                    if i < len(modules) else "end")
            gap_seconds["before:" + name] += (next_start - prev_end) / 1e9
    if not per_chip:
        return {"chips": 0, "window_s": 0.0, "busy_s": 0.0, "mosaic_s": 0.0,
                "collective_s": 0.0, "collective_exposed_s": 0.0,
                "device_ops": [], "idle_gaps": []}
    n = len(per_chip)
    out = {key: sum(c[key] for c in per_chip) / n for key in per_chip[0]}
    out["chips"] = n
    out["device_ops"] = [[k, v / n] for k, v in sorted(
        op_seconds.items(), key=lambda kv: -kv[1])[:top]]
    out["idle_gaps"] = [[k, v / n] for k, v in sorted(
        gap_seconds.items(), key=lambda kv: -kv[1])[:top]]
    return out
