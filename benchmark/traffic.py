"""The one traffic generator: a mix file's parameters and a seed in,
requests or training batches out. Imports NumPy only (the load
generator's process never touches JAX).

Steadiness: a run offers a FIXED amount of work drawn from the seed. An
open loop's arrivals are a Poisson process conditioned on its count
(``round(rate x seconds)`` sorted uniform instants, which is exactly
that process given N), and lengths are a stratified sample of their
distribution (one draw per equal-probability stratum, shuffled), so two
seeds differ in order and bunching, not in how many tokens they ask for.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, Iterator, List

import numpy as np


def _stratified_uniform(rng: np.random.Generator, n: int,
                        block: int) -> np.ndarray:
    """``n`` uniforms of which every consecutive ``block`` is a
    stratified sample: one per equal-probability stratum, shuffled."""
    out = []
    for start in range(0, n, block):
        m = min(block, n - start)
        u = (np.arange(m) + rng.random(m)) / m
        rng.shuffle(u)
        out.append(u)
    return np.concatenate(out) if out else np.zeros(0)


def lengths(spec: Dict[str, Any], n: int, rng: np.random.Generator,
            block: int = 0) -> np.ndarray:
    """``n`` token counts from ``spec``: a log-normal given by its median
    and sigma, clipped to [min, max]; or a constant. Stratified over all
    ``n``, or over every ``block`` in a row where only a run-dependent
    part of the list is used (a closed loop's pool)."""
    if spec["dist"] == "constant":
        return np.full(n, int(spec["value"]), np.int64)
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    inv = NormalDist().inv_cdf
    u = np.clip(_stratified_uniform(rng, n, block or max(n, 1)),
                1e-9, 1 - 1e-9)
    z = np.array([inv(float(x)) for x in u])
    raw = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def arrivals(spec: Dict[str, Any], seconds: float,
             rng: np.random.Generator) -> np.ndarray:
    """Due times in [0, seconds) for an open loop."""
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    n = int(round(float(spec["rate_per_s"]) * seconds))
    return np.sort(rng.random(n) * seconds)


def requests(traffic: Dict[str, Any], seed: int, vocab_size: int,
             seconds: float) -> List[Dict[str, Any]]:
    """The run's requests, in sending order. Open loop: one per arrival,
    with ``due_s`` from the window's start. Closed loop: a pool larger
    than the clients can finish in the window, taken in order."""
    rng = np.random.default_rng([int(seed), 0x5e17e])
    if traffic["loop"] == "open":
        due = arrivals(traffic["arrivals"], seconds, rng)
    elif traffic["loop"] == "closed":
        n = int(math.ceil(traffic["pool_per_s"] * seconds))
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    n = len(due)
    block = int(traffic.get("stratify_block", 0))
    prompt_len = lengths(traffic["prompt_tokens"], n, rng, block)
    max_tokens = lengths(traffic["output_tokens"], n, rng, block)
    if traffic.get("sharing", "none") != "none":
        raise ValueError("only unshared prompts are generated so far")
    return [{"due_s": float(due[i]),
             "prompt": rng.integers(1, vocab_size, int(prompt_len[i])).tolist(),
             "max_tokens": int(max_tokens[i])} for i in range(n)]


def prompt_buckets(traffic: Dict[str, Any], bucket, floor: int) -> List[int]:
    """Every padded prompt length the mix can produce under the engine's
    bucketing rule ``bucket`` (never shorter than one block, ``floor``)."""
    spec = traffic["prompt_tokens"]
    lo, hi = ((spec["value"],) * 2 if spec["dist"] == "constant"
              else (spec["min"], spec["max"]))
    return sorted({max(bucket(n), floor) for n in range(lo, hi + 1)})


def train_batches(job: Dict[str, Any], seed: int,
                  vocab_size: int) -> Iterator[Dict[str, np.ndarray]]:
    """An endless stream of fresh seeded batches for a training job."""
    rng = np.random.default_rng([int(seed), 0x7a1])
    shape = (int(job["batch_sequences"]), int(job["sequence_tokens"]))
    if job.get("documents", "full_sequences") != "full_sequences":
        raise ValueError("only whole-sequence batches are generated so far")
    while True:
        tokens = rng.integers(0, vocab_size, shape, dtype=np.int32)
        yield {"tokens": tokens, "mask": np.ones_like(tokens)}
