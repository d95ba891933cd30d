#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process owns the chip(s): it loads, warms every shape the cell's
traffic uses, measures for ``--seconds``, checks the outputs against
the plain reference, and prints as the LAST line of standard output one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics with ``--trace 0``, its per-layer metrics
with ``--trace 1``) and ``device``; with ``--trace 1`` also
``breakdown``. Without a TPU it exits non-zero and prints no result.
``--rehearse`` is the CPU dry run at the tiny sizes the cell's files
give: same control flow, kernels interpreted, measures nothing.
"""

from __future__ import annotations

import time

T_START = time.monotonic()      # set-up is counted from here

import argparse                  # noqa: E402
import importlib                 # noqa: E402
import json                      # noqa: E402
import os                        # noqa: E402
import sys                       # noqa: E402
import threading                 # noqa: E402

# A run may take 360 s, and the first in a checkout, which compiles,
# 1200 s. A replica that deadlocks would otherwise hold the chip until
# someone kills it: give up, print no result, exit non-zero.
WATCHDOG_S = 1150.0

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _give_up() -> None:
    print(f"benchmark: no result after {WATCHDOG_S:.0f}s; giving up",
          file=sys.stderr, flush=True)
    os._exit(70)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the .xplane.pb here before it is deleted")
    ap.add_argument("--keep-records", default=None, metavar="DIR",
                    help="serve cells: write the load generator's "
                         "per-request records here, to study a metric")
    opts = ap.parse_args()
    opts.t_start = T_START
    watchdog = threading.Timer(WATCHDOG_S, _give_up)
    watchdog.daemon = True
    watchdog.start()
    if opts.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["RAY_TPU_PALLAS_INTERPRET"] = "1"

    import ray_tpu  # noqa: F401 — fail here, before any work, without the program

    from benchmark import manifest
    from benchmark.harness import say

    cell = manifest.cell(opts.workload)
    if opts.rehearse:
        cell = manifest.rehearsal(cell)
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell['chips']}")
        say("REHEARSAL on the CPU at tiny sizes: control flow only; no "
            "number below is a measurement")
    runner = importlib.import_module(
        "benchmark.runners." + cell["workload"]["runner"])
    result = runner.run(cell, opts)

    units = {m["name"]: m["unit"]
             for m in cell["end_to_end"] + cell["per_layer"]}
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "metrics": {},
            "device": result["device"]}
    if opts.trace:
        reduced = result["trace"].reduce()
        if not reduced["chips"] and not opts.rehearse:
            raise RuntimeError("the trace shows no operation on any chip")
        line["device"].update(busy_s=reduced["busy_s"],
                              window_s=reduced["window_s"])
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
        ctx = dict(result["ctx"], trace=reduced, device=result["device"],
                   chips=cell["chips"], config=cell["config"],
                   traffic=cell["traffic"])
        for metric in cell["per_layer"]:
            spec = manifest.metric_file(metric["name"])
            reader = importlib.import_module(
                "benchmark.readers." + spec["reader"])
            value = reader.read(ctx, **spec.get("args", {}))
            if value is not None:
                line["metrics"][metric["name"]] = value
    else:
        line["metrics"] = {m["name"]: result["end_to_end"][m["name"]]
                           for m in cell["end_to_end"]
                           if m["name"] in result["end_to_end"]}
    line["metrics"] = {k: {"value": v, "unit": units[k]}
                       for k, v in line["metrics"].items()}
    line["detail"] = dict(result["detail"], workload=opts.workload,
                          seed=opts.seed, seconds=opts.seconds,
                          rehearsal=bool(opts.rehearse),
                          end_to_end=result["end_to_end"])
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    # Replica and prefetch threads are daemons; nothing is left to wait for.
    os._exit(code)
