"""What both runners share: the device check, the snapshots a window is
bracketed by, the profiler capture, and the result line."""

from __future__ import annotations

import os
import shutil
import sys
import time
from typing import Any, Dict, Optional

from benchmark import manifest, trace_reduce

TRACE_DIR = os.path.join(manifest.ROOT, ".benchmark_trace")


def say(msg: str) -> None:
    """Progress goes to standard error; standard output ends with the
    result line and nothing else follows it."""
    print(f"[benchmark +{time.monotonic() - say.t0:7.2f}s] {msg}",
          file=sys.stderr, flush=True)


say.t0 = time.monotonic()


def open_device(chips: int, rehearse: bool) -> Dict[str, Any]:
    """First touch of JAX: place the compile cache where the program
    keeps it (``$JAX_COMPILATION_CACHE_DIR``, else ``.jax_compile_cache/``
    in the checkout), report the device, refuse to measure anything but
    a TPU with the chips the cell asks for."""
    import jax

    from ray_tpu.util import compile_cache

    cache_dir = compile_cache.ensure()
    # Small programs (uploads, slices) would otherwise compile anew in
    # every process: keep them too.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    say(f"platform {info['platform']}, device_kind {info['kind']}, "
        f"{len(devices)} device(s), cell takes {chips}; compile cache "
        f"{cache_dir}")
    if rehearse:
        if info["platform"] != "cpu":
            raise SystemExit("--rehearse is a CPU dry run; JAX reports "
                             f"{info['platform']!r}")
    elif info["platform"] != "tpu":
        raise SystemExit(f"no accelerator: JAX reports platform "
                         f"{info['platform']!r}. The benchmark measures the "
                         "chip and never falls back (--rehearse is the CPU "
                         "dry run, which measures nothing).")
    if len(devices) < chips:
        raise SystemExit(f"the cell asks for {chips} chip(s), JAX sees "
                         f"{len(devices)}")
    return info


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest chip, as the allocator counts."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def registry_snapshot() -> Dict[str, float]:
    """Every sample of the program's metrics registry, label sets
    summed: ``{sample name: value}``."""
    from ray_tpu._private import metrics_defs
    from ray_tpu.util.metrics import Metric

    out: Dict[str, float] = {}
    for metric in vars(metrics_defs).values():
        if isinstance(metric, Metric):
            for name, _labels, value in metric.samples():
                out[name] = out.get(name, 0.0) + float(value)
    return out


def compile_snapshot() -> Dict[str, int]:
    from ray_tpu._private import xla_monitor
    from ray_tpu.util import compile_cache

    out = {p["name"]: int(p["compiles"])
           for p in xla_monitor.all_program_stats()}
    cache = compile_cache.counts()
    out["persistent_cache_lookups"] = cache["hits"] + cache["misses"]
    return out


class Trace:
    """A ``jax.profiler`` capture of a few seconds of the steady window.
    The Python tracer stays off: it slows the host threads it watches
    and the reduction reads device lines only."""

    def __init__(self, enabled: bool, keep_dir: Optional[str] = None):
        self.keep_dir = keep_dir
        self._state = "new" if enabled else "off"

    def start(self) -> None:
        if self._state != "new":
            return
        self._state = "on"
        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        os.makedirs(TRACE_DIR, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
        say("profiler on")

    def stop(self) -> None:
        if self._state != "on":
            return
        self._state = "done"
        import jax

        jax.profiler.stop_trace()
        say("profiler off")

    def reduce(self) -> Optional[Dict[str, Any]]:
        if self._state == "off":
            return None
        path = trace_reduce.find(TRACE_DIR)
        if path is None:
            raise RuntimeError(f"the profiler wrote no trace under {TRACE_DIR}")
        reduced = trace_reduce.reduce(trace_reduce.load(path))
        say(f"trace {path} ({os.path.getsize(path) / 1e6:.1f} MB) reduced")
        if self.keep_dir:
            os.makedirs(self.keep_dir, exist_ok=True)
            shutil.copy(path, self.keep_dir)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        return reduced
