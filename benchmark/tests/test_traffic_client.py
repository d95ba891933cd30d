"""The traffic generator and the load generator, against a fake server
that streams NDJSON chunks the way ``serve/proxy.py`` does."""

import asyncio
import json
import time

import numpy as np
import pytest

from benchmark import client, manifest, stats, traffic
from benchmark.readers import client_clock

CHAT = manifest.load_json(manifest.HERE + "/traffic/chat.json")
HEAVY = manifest.load_json(manifest.HERE + "/traffic/prefill_heavy.json")


def test_same_seed_same_schedule_other_seed_another():
    a = traffic.requests(CHAT, 7, 32768, 20.0)
    b = traffic.requests(CHAT, 7, 32768, 20.0)
    c = traffic.requests(CHAT, 8, 32768, 20.0)
    assert a == b and a != c


def test_open_loop_offers_a_fixed_amount_of_work():
    rate = CHAT["arrivals"]["rate_per_s"]
    totals = []
    for seed in range(5):
        reqs = traffic.requests(CHAT, seed, 32768, 40.0)
        assert len(reqs) == round(rate * 40.0)
        due = [r["due_s"] for r in reqs]
        assert due == sorted(due) and 0 <= due[0] and due[-1] < 40.0
        p, o = CHAT["prompt_tokens"], CHAT["output_tokens"]
        assert all(p["min"] <= len(r["prompt"]) <= p["max"] for r in reqs)
        assert all(o["min"] <= r["max_tokens"] <= o["max"] for r in reqs)
        assert all(1 <= t < 32768 for r in reqs for t in r["prompt"])
        totals.append(sum(len(r["prompt"]) + r["max_tokens"] for r in reqs))
    # Stratified lengths: seeds differ in order, hardly in total.
    assert (max(totals) - min(totals)) / np.mean(totals) < 0.03
    med = np.median([len(r["prompt"]) for r in reqs])
    assert 0.85 * CHAT["prompt_tokens"]["median"] < med < 1.15 * CHAT["prompt_tokens"]["median"]


def test_closed_loop_pool_is_stratified_in_every_block():
    means = []
    for seed in range(4):
        reqs = traffic.requests(HEAVY, seed, 32768, 40.0)
        assert len(reqs) == HEAVY["pool_per_s"] * 40
        block = HEAVY["stratify_block"]
        means += [np.mean([len(r["prompt"]) for r in reqs[i:i + block]])
                  for i in range(0, 320, block)]
    assert (max(means) - min(means)) / np.mean(means) < 0.02


def test_prompt_buckets_follow_the_engines_rule():
    from ray_tpu.models.continuous_batching import _bucket

    assert traffic.prompt_buckets(CHAT, _bucket, 64) == [64, 128, 256, 512, 1024]
    assert traffic.prompt_buckets(HEAVY, _bucket, 64) == [1024]


def test_train_batches_are_seeded_and_fresh():
    job = {"batch_sequences": 2, "sequence_tokens": 16}
    a, b = traffic.train_batches(job, 4, 100), traffic.train_batches(job, 4, 100)
    first, second = next(a), next(a)
    assert np.array_equal(first["tokens"], next(b)["tokens"])
    assert not np.array_equal(first["tokens"], second["tokens"])
    assert first["tokens"].shape == (2, 16) and first["tokens"].dtype == np.int32


async def _fake_server(service_s, seen):
    """Answers the streamed route: ``max_tokens`` chunks of one token id
    each, after ``service_s`` of thinking."""
    async def handle(reader, writer):
        head = await reader.readuntil(b"\r\n\r\n")
        length = int([ln for ln in head.split(b"\r\n")
                      if ln.lower().startswith(b"content-length")][0].split(b":")[1])
        body = json.loads(await reader.readexactly(length))
        seen.append((time.monotonic(), body))
        await asyncio.sleep(service_s)
        writer.write(b"HTTP/1.1 200 OK\r\nTransfer-Encoding: chunked\r\n\r\n")
        for i in range(body["max_tokens"]):
            chunk = json.dumps(i).encode() + b"\n"
            writer.write(f"{len(chunk):x}\r\n".encode() + chunk + b"\r\n")
            await writer.drain()
        writer.write(b"0\r\n\r\n")
        await writer.drain()
        writer.close()

    server = await asyncio.start_server(handle, "127.0.0.1", 0)
    return server, server.sockets[0].getsockname()[1]


def test_open_loop_counts_lateness_from_the_due_time():
    reqs = [{"due_s": 0.0, "prompt": [1, 2, 3], "max_tokens": 4},
            {"due_s": 0.05, "prompt": [4], "max_tokens": 2}]

    async def go():
        seen = []
        server, port = await _fake_server(0.02, seen)
        async with server:
            # The window "opened" 0.3 s ago: the generator is late by that.
            t0 = time.monotonic() - 0.3
            return t0, await client.open_loop(port, reqs, t0, 1.0, 5.0, 100)

    t0, recs = asyncio.run(go())
    assert all(stats.whole(r) for r in recs)
    assert [r["n"] for r in recs] == [4, 2]
    # Every token's arrival is kept: the gaps between them are the ITL.
    assert [len(r["t"]) for r in recs] == [4, 2]
    assert all(r["t"][0] == r["first"] and r["t"][-1] == r["last"]
               for r in recs)
    assert len(stats.itl_ms(recs)) == 3 + 1
    for rec, req in zip(recs, reqs):
        assert rec["due"] == t0 + req["due_s"]
        assert rec["sent"] - rec["due"] > 0.2           # late, and it shows
    assert min(stats.ttft_ms(recs)) > 200.0              # TTFT counts from due
    lag = client_clock.read({"measured": recs}, stat="gen_lag", q=95)
    assert 200.0 < lag < 1000.0


def test_open_loop_gives_up_after_the_drain_limit_and_flags_bad_ids():
    reqs = [{"due_s": 0.0, "prompt": [1], "max_tokens": 3}]

    async def go(service_s, vocab):
        server, port = await _fake_server(service_s, [])
        async with server:
            return await client.open_loop(port, reqs, time.monotonic(),
                                          0.05, 0.1, vocab)

    (slow,) = asyncio.run(go(1.0, 100))
    assert not slow["done"] and not stats.whole(slow)
    (bad,) = asyncio.run(go(0.0, 2))          # ids 0, 1, 2 with vocabulary 2
    assert bad["done"] and bad["bad"] == 1 and not stats.whole(bad)


def test_closed_loop_sends_the_next_when_the_last_completes():
    reqs = [{"due_s": 0.0, "prompt": [1], "max_tokens": 2} for _ in range(400)]

    async def go():
        seen = []
        server, port = await _fake_server(0.02, seen)
        async with server:
            t0 = time.monotonic() + 0.05
            recs = await client.closed_loop(port, reqs, t0, 0.5, 5.0, 3, 100)
            return t0, recs, seen

    t0, recs, seen = asyncio.run(go())
    # What was in flight when the window closed is left to finish.
    assert all(stats.whole(r) for r in recs)
    done = [r for r in recs if r["last"] <= t0 + 0.5]
    # 3 clients x 0.5 s / 0.02 s a request is 75 at the very most.
    assert 20 <= len(done) <= 75
    assert all(t0 <= r["sent"] < t0 + 0.5 for r in recs)
    # Never more than `clients` requests in flight.
    edges = sorted([(r["sent"], 1) for r in recs]
                   + [(r["last"], -1) for r in recs])
    depth = peak = 0
    for _, step in edges:
        depth += step
        peak = max(peak, depth)
    assert peak <= 3
    assert len(recs) - len(done) <= 3          # ended after the window


def test_itl_pools_every_gap_and_one_stall_moves_its_tail_little():
    steady = [{"t": [0.07 * k for k in range(200)], "n": 200,
               "first": 0.0, "last": 0.07 * 199} for _ in range(30)]
    gaps = stats.itl_ms(steady)
    assert len(gaps) == 30 * 199
    assert stats.percentile(gaps, 50) == pytest.approx(70.0)
    # One 3 s stall hits all 30 streams once: 30 gaps of 5970.
    stalled = [dict(r, t=[t + (3.0 if k >= 100 else 0.0)
                          for k, t in enumerate(r["t"])],
                    last=r["last"] + 3.0) for r in steady]
    assert stats.percentile(stats.itl_ms(stalled), 99) == pytest.approx(70.0)
    assert stats.percentile(stats.tpot_ms(stalled), 50) > 84.0

