#!/usr/bin/env python3
"""Every (per-layer metric, cell) pair of another checkout's benchmark
against this one's: is the pair still listed, under the name
``benchmark/renamed.json`` gives, with the same reader, arguments, unit,
better, source, layer and moves; and which pairs are new here.

    python3 benchmark/tests/pairs_against.py <parent checkout>

Prints the pairs lost or changed (exit 1 if any), then the pairs added.
Pure JSON: no JAX, no program.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIELDS = ("unit", "better", "source", "layer", "moves")


def load(path):
    with open(path) as f:
        return json.load(f)


def pairs(root):
    """``{(name, cell): what is measured there}`` of one checkout."""
    bench = load(os.path.join(root, "BENCHMARK.json"))
    cells = [w["name"] for w in bench["workloads"]]
    out = {}
    for m in bench["per_layer"]:
        spec = load(os.path.join(root, "benchmark", "metrics",
                                 m["name"] + ".json"))
        what = (spec["reader"], json.dumps(spec.get("args", {}),
                                           sort_keys=True),
                tuple(m[k] for k in FIELDS))
        for cell in m.get("workloads", cells):
            out[m["name"], cell] = what
    return out


def main(parent_root):
    renamed = load(os.path.join(HERE, "renamed.json"))["renamed"]
    was, now = pairs(parent_root), pairs(os.path.dirname(HERE))
    lost, kept = [], set()
    for (name, cell), what in sorted(was.items()):
        new = tuple(renamed[name]) if name in renamed else (name, cell)
        if name in renamed and new[1] != cell:
            lost.append(f"{name} @ {cell}: renamed.json sends it to {new}")
        elif now.get(new) != what:
            lost.append(f"{name} @ {cell} -> {new}: {now.get(new)} != {what}")
        kept.add(new)
    added = sorted(set(now) - kept)
    print(f"{len(was)} pairs at the parent, {len(now)} here; "
          f"{len(lost)} pairs lost or changed, {len(added)} pairs added")
    for line in lost:
        print("LOST", line)
    by_name = {}
    for name, cell in added:
        by_name.setdefault(name, []).append(cell)
    for name, cells in by_name.items():
        print(f"added {name}: {', '.join(cells)}")
    return 1 if lost else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
