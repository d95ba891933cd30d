"""The load generator: an asyncio HTTP client for the streamed route.

Run as a child process it never imports JAX, so it shares neither the
chip nor the interpreter lock with the engine it loads. It prints
``READY``, waits for ``GO <t0>`` on its standard input (``t0`` on
``time.monotonic()``, which all processes of one machine share), offers
the mix, and prints one JSON line of per-request records. The parent
turns records into metrics; nothing here judges.

A record holds, on the monotonic clock: ``due`` (open loop: when the
request was due; closed loop: when its client was free), ``sent``,
``first`` and ``last`` token instants, ``t`` (the arrival instant of
every token, so ``first`` is ``t[0]``), ``n`` tokens received, ``done``
(the stream ended properly) and ``bad`` ids outside the vocabulary.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

ROUTE = "/ContinuousLlamaDeployment/stream/generate"


async def _stream(port: int, body: bytes, rec: Dict[str, Any],
                  vocab_size: int, keep_tokens: bool) -> None:
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        writer.write((f"POST {ROUTE} HTTP/1.1\r\nHost: 127.0.0.1\r\n"
                      "Content-Type: application/json\r\n"
                      f"Content-Length: {len(body)}\r\n"
                      "Connection: close\r\n\r\n").encode() + body)
        await writer.drain()
        status = await reader.readline()
        if b" 200 " not in status:
            rec["error"] = status.decode("latin1").strip()[:200]
            return
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        while True:
            size = await reader.readline()
            if not size:
                rec["error"] = "connection closed mid-stream"
                return
            n = int(size.strip() or b"0", 16)
            if n == 0:
                rec["done"] = True
                return
            chunk = await reader.readexactly(n + 2)
            now = time.monotonic()
            for line in chunk.splitlines():
                if not line:
                    continue
                item = json.loads(line)
                if not isinstance(item, int):
                    continue         # a control object, not a token
                if rec["n"] == 0:
                    rec["first"] = now
                rec["last"] = now
                rec["t"].append(now)
                rec["n"] += 1
                if not 0 <= item < vocab_size:
                    rec["bad"] += 1
                if keep_tokens:
                    rec["tokens"].append(item)
    except (OSError, asyncio.IncompleteReadError, ValueError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"[:200]
    finally:
        writer.close()


def _record(index: int, req: Dict[str, Any]) -> Dict[str, Any]:
    return {"i": index, "prompt_tokens": len(req["prompt"]),
            "max_tokens": req["max_tokens"], "due": None, "sent": None,
            "first": None, "last": None, "n": 0, "bad": 0, "done": False,
            "error": None, "t": [], "tokens": []}


def _body(req: Dict[str, Any]) -> bytes:
    return json.dumps({"prompt_token_ids": req["prompt"],
                       "max_tokens": req["max_tokens"]}).encode()


async def open_loop(port: int, reqs: List[Dict[str, Any]], t0: float,
                    seconds: float, drain_s: float, vocab_size: int,
                    keep_tokens: bool = False) -> List[Dict[str, Any]]:
    """Send each request at ``t0 + due_s`` whatever the server does;
    wait for the streams until ``drain_s`` past the window, then give
    up on the rest (their records stay not ``done``)."""
    bodies = [_body(r) for r in reqs]
    recs = [_record(i, r) for i, r in enumerate(reqs)]
    tasks = []
    for req, body, rec in zip(reqs, bodies, recs):
        rec["due"] = t0 + req["due_s"]
        delay = rec["due"] - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        rec["sent"] = time.monotonic()
        tasks.append(asyncio.ensure_future(
            _stream(port, body, rec, vocab_size, keep_tokens)))
    if tasks:
        left = t0 + seconds + drain_s - time.monotonic()
        _, pending = await asyncio.wait(tasks, timeout=max(left, 0.0))
        for task in pending:
            task.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
    return recs


async def closed_loop(port: int, reqs: List[Dict[str, Any]], t0: float,
                      seconds: float, drain_s: float, clients: int,
                      vocab_size: int, keep_tokens: bool = False
                      ) -> List[Dict[str, Any]]:
    """``clients`` callers, each sending its next request when its last
    completes; none starts a request once the window has ended. What is
    in flight then is left to finish (it ends after the window, so it is
    outside the measured set) for up to ``drain_s``: a stream dropped
    half-way can deadlock the replica (PERF.md, PR 22)."""
    bodies = [_body(r) for r in reqs]
    recs: List[Dict[str, Any]] = []
    nxt = iter(range(len(reqs)))
    end = t0 + seconds

    async def client() -> None:
        while True:
            free = max(time.monotonic(), t0)
            if free >= end:
                return
            i = next(nxt, None)
            if i is None:
                raise RuntimeError("closed loop ran out of requests: raise "
                                   "the mix's pool_per_s")
            rec = _record(i, reqs[i])
            recs.append(rec)
            rec["due"] = free
            if free > time.monotonic():
                await asyncio.sleep(free - time.monotonic())
            rec["sent"] = time.monotonic()
            await _stream(port, bodies[i], rec, vocab_size, keep_tokens)

    tasks = [asyncio.ensure_future(client()) for _ in range(clients)]
    _, pending = await asyncio.wait(tasks, timeout=max(
        end + drain_s - time.monotonic(), 0.0))
    for task in pending:
        task.cancel()
    for result in await asyncio.gather(*tasks, return_exceptions=True):
        if isinstance(result, Exception) and not isinstance(
                result, asyncio.CancelledError):
            raise result
    return recs


async def wave(port: int, reqs: List[Dict[str, Any]], vocab_size: int,
               timeout_s: float = 120.0) -> List[Dict[str, Any]]:
    """Every request at once, tokens kept: warm-up and correctness."""
    return await open_loop(port, [dict(r, due_s=0.0) for r in reqs],
                           time.monotonic(), 0.0, timeout_s, vocab_size,
                           keep_tokens=True)


def offer(port: int, traffic: Dict[str, Any], reqs: List[Dict[str, Any]],
          t0: float, seconds: float, vocab_size: int) -> List[Dict[str, Any]]:
    if traffic["loop"] == "open":
        coro = open_loop(port, reqs, t0, seconds, traffic["drain_s"],
                         vocab_size)
    else:
        coro = closed_loop(port, reqs, t0, seconds, traffic["drain_s"],
                           traffic["clients"], vocab_size)
    return asyncio.run(coro)


def main(argv: Optional[List[str]] = None) -> int:
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmark import traffic as traffic_mod

    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--traffic", required=True, help="the mix, as JSON")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--vocab", type=int, required=True)
    args = ap.parse_args(argv)
    mix = json.loads(args.traffic)
    reqs = traffic_mod.requests(mix, args.seed, args.vocab, args.seconds)
    print("READY", flush=True)
    word, t0 = sys.stdin.readline().split()
    if word != "GO":
        return 2
    recs = offer(args.port, mix, reqs, float(t0), args.seconds, args.vocab)
    for rec in recs:
        del rec["tokens"]
    print(json.dumps({"records": recs}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
