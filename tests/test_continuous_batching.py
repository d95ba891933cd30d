"""Continuous batching engine (reference: the vLLM-style iteration-level
scheduler behind ``ray.serve.llm``)."""

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.models.continuous_batching import ContinuousBatcher
from ray_tpu.models.inference import LlamaGenerator
from ray_tpu.models.paged_kv import GARBAGE_BLOCK

def _prefill_batches(eng):
    """Prefill batches ``eng`` ran: ``CB_PREFILL_MS`` books one each."""
    from ray_tpu._private import metrics_defs as mdefs

    return mdefs.CB_PREFILL_MS.totals(eng._mtags)[1]



@pytest.fixture(scope="module")
def setup():
    config = llama.LlamaConfig.tiny(dtype=jnp.float32)
    gen = LlamaGenerator(config, max_len=128, seed=3)
    batcher = ContinuousBatcher(config, params=gen.params, num_slots=3,
                                max_len=128, seed=3)
    return config, gen, batcher


def _reference(gen, prompt, n):
    return list(np.asarray(
        gen.generate(np.asarray([prompt], np.int32),
                     max_new_tokens=n))[0])


def test_matches_sequential_generation(setup):
    """Greedy outputs are exactly the single-request generator's, despite
    slot batching, padded prefill, and interleaved membership."""
    _, gen, batcher = setup
    rng = np.random.default_rng(0)
    reqs = {}
    for n_prompt, n_new in [(5, 6), (9, 3), (17, 8), (3, 12)]:
        prompt = list(rng.integers(1, 250, size=n_prompt))
        rid = batcher.submit(prompt, max_new_tokens=n_new)
        reqs[rid] = (prompt, n_new)
    results = batcher.run_to_completion()
    assert set(results) == set(reqs)
    for rid, (prompt, n_new) in reqs.items():
        assert results[rid] == _reference(gen, prompt, n_new), rid


def test_mid_flight_arrival_joins_running_batch(setup):
    """A request submitted while others are mid-generation joins without
    waiting for them to finish (the point of continuous batching)."""
    _, gen, batcher = setup
    rng = np.random.default_rng(1)
    p1 = list(rng.integers(1, 250, size=4))
    p2 = list(rng.integers(1, 250, size=6))
    r1 = batcher.submit(p1, max_new_tokens=10)
    done = {}
    done.update(batcher.step())
    done.update(batcher.step())  # r1 is now 3 tokens in
    r2 = batcher.submit(p2, max_new_tokens=5)
    joined_at = batcher.active_count
    while batcher.has_work():
        done.update(batcher.step())
        joined_at = max(joined_at, batcher.active_count)
    assert joined_at == 2, "second request never ran concurrently"
    assert done[r1] == _reference(gen, p1, 10)
    assert done[r2] == _reference(gen, p2, 5)


def test_slot_reuse_after_finish(setup):
    """More requests than slots: finished slots are recycled and every
    request still completes exactly."""
    _, gen, batcher = setup
    rng = np.random.default_rng(2)
    reqs = {}
    for i in range(7):  # > num_slots=3
        prompt = list(rng.integers(1, 250, size=3 + i))
        reqs[batcher.submit(prompt, max_new_tokens=2 + i % 3)] = prompt
    results = batcher.run_to_completion()
    assert set(results) == set(reqs)
    for rid, prompt in reqs.items():
        n = len(results[rid])
        assert results[rid] == _reference(gen, prompt, n)


# --------------------------------------------------------- serve surface

def test_continuous_llm_serving_streams_tokens():
    """The serve deployment streams tokens from the shared slot pool and
    matches the sequential generator exactly."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import ContinuousLlamaDeployment

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, num_tpus=0)
    try:
        config = llama.LlamaConfig.tiny(dtype=jnp.float32)
        gen = LlamaGenerator(config, max_len=128, seed=0)
        h = serve.run(ContinuousLlamaDeployment.options(
            num_replicas=1).bind(config, None, 4, 128))

        rng = np.random.default_rng(7)
        p1 = list(rng.integers(1, 250, size=5))
        p2 = list(rng.integers(1, 250, size=8))

        streamed = list(h.options("generate", stream=True).remote(p1, 6))
        assert streamed == _reference(gen, p1, 6)

        full = h.remote({"prompt_token_ids": p2, "max_tokens": 4}).result()
        assert full["token_ids"] == _reference(gen, p2, 4)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def test_zero_max_tokens_and_bucket_clamp():
    config = llama.LlamaConfig.tiny(dtype=jnp.float32)
    b = ContinuousBatcher(config, num_slots=2, max_len=100, seed=0)
    # max_new_tokens=0: finishes immediately with no tokens, no slot.
    rid0 = b.submit([1, 2, 3], max_new_tokens=0)
    # prompt whose pow2 bucket (128) exceeds the non-pow2 max_len (100):
    # padding must clamp instead of crashing the admission scatter.
    rid1 = b.submit(list(range(1, 91)), max_new_tokens=5)
    results = b.run_to_completion()
    assert results[rid0] == []
    assert len(results[rid1]) == 5


def test_cancel_frees_slot():
    config = llama.LlamaConfig.tiny(dtype=jnp.float32)
    b = ContinuousBatcher(config, num_slots=1, max_len=64, seed=0)
    r1 = b.submit([1, 2, 3], max_new_tokens=50)
    r2 = b.submit([4, 5, 6], max_new_tokens=2)   # waits behind r1
    b.step()
    assert b.active_count == 1
    assert b.cancel(r1)                           # client went away
    results = b.run_to_completion()
    assert r1 not in results and len(results[r2]) == 2


# ------------------------------------------------- offline batch inference

def test_batch_generate_over_dataset():
    """llm.batch_generate: a Data pipeline of prompts through pool actors
    each owning a continuous batcher; greedy outputs must exactly match
    direct generation (reference: llm/_internal/batch processors)."""
    import jax

    import ray_tpu
    from ray_tpu import data as rdata
    from ray_tpu import llm

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8, num_tpus=0)
    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    host_params = jax.tree.map(lambda x: np.asarray(x), params)
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(1, cfg.vocab_size, size=n)))
               for n in (5, 9, 3, 12, 7, 4)]

    ds = rdata.from_items([{"prompt_ids": p} for p in prompts])
    out = llm.batch_generate(ds, cfg, params=host_params, concurrency=2,
                             max_new_tokens=8, num_slots=4, max_len=64)
    rows = out.take_all()
    assert len(rows) == len(prompts)
    by_prompt = {tuple(r["prompt_ids"]): list(r["generated_ids"])
                 for r in rows}

    ref_batcher = ContinuousBatcher(cfg, params=params, num_slots=4,
                                    max_len=64)
    for p in prompts:
        rid = ref_batcher.submit(p, 8)
        expect = ref_batcher.run_to_completion()[rid]
        assert by_prompt[tuple(p)] == list(expect), p
    ray_tpu.shutdown()


def test_cancel_of_the_last_request_with_a_tick_in_flight_does_not_wedge(
        setup):
    """Cancelling the only live request while its next tick is queued
    on the device must drain the tick in flight, not wedge admission."""
    config, gen, _ = setup
    eng = ContinuousBatcher(config, params=gen.params, num_slots=2,
                            max_len=128)
    rid = eng.submit([1, 2, 3], max_new_tokens=50)
    for _ in range(5):
        eng.step()
    assert eng._inflight, "no tick queued behind the one that ran"
    eng.cancel(rid)
    for _ in range(3):
        eng.step()
        if not eng.has_work():
            break
    assert not eng.has_work(), "engine wedged after cancel"
    rid2 = eng.submit([4, 5], max_new_tokens=3)
    out = eng.run_to_completion()
    assert rid not in out
    assert out[rid2] == _reference(gen, [4, 5], 3)


# ------------------------------------- fused decode kernel / batched prefill

def test_decode_kernel_on_off_bit_identical(setup, pallas_interpret):
    """The fused pallas decode kernel (interpret mode on CPU) produces
    token-for-token identical greedy output to the XLA reference path,
    and to the sequential generator."""
    config, gen, _ = setup
    rng = np.random.default_rng(11)
    reqs = [(list(rng.integers(1, 250, size=n)), m)
            for n, m in [(5, 7), (9, 4), (17, 6)]]
    results = {}
    for use_kernel in (False, True):
        eng = ContinuousBatcher(config, params=gen.params, num_slots=2,
                                max_len=128, use_decode_kernel=use_kernel)
        assert eng.use_decode_kernel is use_kernel
        rids = [eng.submit(p, max_new_tokens=m) for p, m in reqs]
        out = eng.run_to_completion()
        results[use_kernel] = [out[r] for r in rids]
    assert results[True] == results[False]
    for (prompt, m), toks in zip(reqs, results[True]):
        assert toks == _reference(gen, prompt, m)


def test_decode_kernel_on_against_off_with_a_tick_in_flight(
        setup, pallas_interpret):
    """Kernel on against kernel off over requests that end on different
    ticks (a membership change with a tick in flight at each): the fused
    kernel in the tick changes no token."""
    config, gen, _ = setup
    rng = np.random.default_rng(12)
    reqs = [(list(rng.integers(1, 250, size=n)), m)
            for n, m in [(4, 9), (12, 5)]]
    results = {}
    for use_kernel in (True, False):
        eng = ContinuousBatcher(config, params=gen.params, num_slots=2,
                                max_len=128, use_decode_kernel=use_kernel)
        rids = [eng.submit(p, max_new_tokens=m) for p, m in reqs]
        out = eng.run_to_completion()
        results[use_kernel] = [out[r] for r in rids]
    assert results[True] == results[False]
    for (prompt, m), toks in zip(reqs, results[True]):
        assert toks == _reference(gen, prompt, m)


def test_burst_admission_is_one_prefill_program(setup):
    """A burst of same-bucket requests admits in ONE batched prefill
    dispatch (not one per request), the batch dim buckets to a power of
    two so compiled program count stays logarithmic, and outputs are
    identical to one-at-a-time admission."""
    config, gen, _ = setup
    rng = np.random.default_rng(13)
    prompts = [list(rng.integers(1, 250, size=n)) for n in (5, 9, 12, 7)]

    eng = ContinuousBatcher(config, params=gen.params, num_slots=4,
                            max_len=128)
    rids = [eng.submit(p, max_new_tokens=3) for p in prompts]  # one bucket
    assert _prefill_batches(eng) == 0
    eng.step()
    assert _prefill_batches(eng) == 1, "burst took >1 prefill dispatch"
    assert eng.prefill_requests == 4
    assert eng.prefill_tokens == sum(len(p) for p in prompts)
    assert eng.prefill_cache_misses() == 1
    burst_out = eng.run_to_completion()

    # A 3-request burst pads its batch dim to 4 and REUSES the compiled
    # [4, 16] program: no new jit cache miss.
    for p in prompts[:3]:
        eng.submit(p, max_new_tokens=2)
    eng.step()
    assert _prefill_batches(eng) == 2
    assert eng.prefill_cache_misses() == 1, "N-bucketing failed to reuse"
    burst_out.update(eng.run_to_completion())

    # One-at-a-time admission (a step between submits => burst of 1).
    seq = ContinuousBatcher(config, params=gen.params, num_slots=4,
                            max_len=128)
    seq_out = {}
    for p in prompts:
        rid = seq.submit(p, max_new_tokens=3)
        seq.step()
        seq_out[rid] = None
        while seq.has_work():
            out = seq.step()
            for r in out:
                seq_out[r] = out[r]
    seq_toks = list(seq_out.values())
    assert [burst_out[r] for r in rids] == seq_toks
    for p, toks in zip(prompts, seq_toks):
        assert toks == _reference(gen, p, 3)
    # Singleton admissions share one compiled [1, 16] program.
    assert seq.prefill_cache_misses() == 1


def test_mixed_bucket_burst_admits_per_bucket(setup):
    """Requests spanning two length buckets admit in exactly two batched
    dispatches, results still exact."""
    config, gen, _ = setup
    rng = np.random.default_rng(14)
    short = [list(rng.integers(1, 250, size=n)) for n in (5, 9)]    # 16
    long = [list(rng.integers(1, 250, size=n)) for n in (20, 25)]   # 32
    # block_size=16 keeps the paged engine's padding floor below both
    # buckets (paged prompts pad to at least one block).
    eng = ContinuousBatcher(config, params=gen.params, num_slots=4,
                            max_len=128, block_size=16)
    rids = [eng.submit(p, max_new_tokens=3) for p in short + long]
    eng.step()
    assert _prefill_batches(eng) == 2
    assert eng.prefill_requests == 4
    out = eng.run_to_completion()
    for p, rid in zip(short + long, rids):
        assert out[rid] == _reference(gen, p, 3)


def test_bf16_lm_head_argmax_parity():
    """lm_head in bf16 with fp32 accumulation picks the SAME greedy token
    as the old fp32-upcast projection on a seeded model — the decode
    de-fattening must not change sampled text."""
    import jax

    from ray_tpu.models.inference import lm_head_logits

    cfg = llama.LlamaConfig.tiny(dtype=jnp.bfloat16)
    params = llama.init_params(cfg, jax.random.PRNGKey(5))
    rng = np.random.default_rng(5)
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, size=(4, 16)),
                         jnp.int32)
    # Stand-in final hidden states: embeddings are the same scale/dtype
    # the final norm emits.
    x = params["embed"].astype(cfg.dtype)[tokens]
    new = lm_head_logits(x, params, cfg)
    old = jnp.einsum("bse,ev->bsv", x.astype(jnp.float32),
                     params["lm_head"].astype(jnp.float32))
    assert new.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(jnp.argmax(new, axis=-1)),
        np.asarray(jnp.argmax(old, axis=-1)))


# ------------------------------------------------- paged KV + sampling

@pytest.mark.parametrize("block_size", [32, 64])
def test_block_sizes_match_the_generator(setup, block_size):
    """The arena data plane (block tables, arena scatter, paged
    attention) produces token-for-token the sequential generator's
    greedy output at either block size, with slot churn."""
    config, gen, _ = setup
    rng = np.random.default_rng(21)
    reqs = [(list(rng.integers(1, 250, size=n)), m)
            for n, m in [(5, 7), (33, 4), (17, 9), (9, 3), (40, 6)]]
    eng = ContinuousBatcher(config, params=gen.params, num_slots=3,
                            max_len=128, block_size=block_size)
    rids = [eng.submit(p, max_new_tokens=m) for p, m in reqs]
    out = eng.run_to_completion()
    for (prompt, m), rid in zip(reqs, rids):
        assert out[rid] == _reference(gen, prompt, m)


def test_paged_kernel_engine_parity(setup, pallas_interpret):
    """The engine with the fused paged kernels (interpret mode on CPU)
    == the engine on the XLA path == the generator, greedy."""
    config, gen, _ = setup
    rng = np.random.default_rng(22)
    reqs = [(list(rng.integers(1, 250, size=n)), m)
            for n, m in [(5, 7), (33, 5)]]
    results = {}
    for uk in (False, True):
        eng = ContinuousBatcher(config, params=gen.params, num_slots=2,
                                max_len=128, block_size=32,
                                use_decode_kernel=uk)
        rids = [eng.submit(p, max_new_tokens=m) for p, m in reqs]
        out = eng.run_to_completion()
        results[uk] = [out[r] for r in rids]
    assert results[True] == results[False]
    for (prompt, m), toks in zip(reqs, results[True]):
        assert toks == _reference(gen, prompt, m)


def test_tick_books_the_share_of_table_entries_it_visits(setup):
    """Every paged tick observes ``ray_tpu_cb_paged_live_block_share``:
    live blocks over slots x table entries, what the attention kernel
    visits. One request at positions 40..44 of 32-token blocks holds 2
    of the 4 x 4 entries in every tick; the three free slots hold none."""
    from ray_tpu._private import metrics_defs as mdefs

    config, gen, _ = setup
    eng = ContinuousBatcher(config, params=gen.params, num_slots=4,
                            max_len=128, block_size=32)

    def read():
        """(sum, count) over every engine's label set."""
        samples = mdefs.CB_PAGED_LIVE_BLOCK_SHARE.samples()
        return tuple(sum(v for name, _, v in samples if name.endswith(end))
                     for end in ("_sum", "_count"))

    sum0, count0 = read()
    eng.submit(list(range(1, 41)), max_new_tokens=5)
    eng.run_to_completion()
    ticks = eng.base_tick_count
    total, count = read()
    assert ticks >= 4 and count - count0 == ticks
    assert total - sum0 == pytest.approx(ticks * 2 / 16)


def test_tick_books_how_full_the_kernels_grid_steps_run(setup):
    """Every tick with a live slot observes
    ``ray_tpu_cb_paged_visit_fill_share``: blocks the attention kernel
    reads over ``visit_blocks`` x the grid steps it takes, a slot's
    blocks in runs of ``visit_blocks`` and its last run short. Two
    requests at positions 40..44 and 70..74 of 32-token blocks hold 2
    and 3 blocks in every tick; the two free slots take no step."""
    from ray_tpu._private import metrics_defs as mdefs
    from ray_tpu.ops.paged_decode_attention import visit_blocks

    config, gen, _ = setup
    eng = ContinuousBatcher(config, params=gen.params, num_slots=4,
                            max_len=128, block_size=32)

    def read():
        samples = mdefs.CB_PAGED_VISIT_FILL_SHARE.samples()
        return tuple(sum(v for name, _, v in samples if name.endswith(end))
                     for end in ("_sum", "_count"))

    sum0, count0 = read()
    eng.submit(list(range(1, 41)), max_new_tokens=5)
    eng.submit(list(range(1, 71)), max_new_tokens=5)
    eng.run_to_completion()
    ticks = eng.base_tick_count
    total, count = read()
    assert ticks >= 4 and count - count0 == ticks
    per = visit_blocks(eng.cache.k)
    steps = -(-2 // per) + -(-3 // per)
    assert total - sum0 == pytest.approx(ticks * 5 / (per * steps))
    assert eng._attended_blocks() == ([], [])       # nothing live any more


def test_live_rows_never_share_a_write_block(setup, pallas_interpret):
    """``paged_kv_write`` merges each row into the block's tile as
    fetched, so two rows of one tick may name the same tile of a block
    (the engine promises the stronger thing: the same block) only if
    nothing reads it. Over a run that crosses block boundaries, frees
    and re-admits slots and splices shared prefix blocks: every live row
    of every tick writes a block no other slot's table holds (shared
    prefix blocks are full, hence never written), freed rows all aim at
    the garbage block, and the kernel path's greedy tokens are the
    reference path's."""
    from ray_tpu.models.paged_kv import GARBAGE_BLOCK

    config, gen, _ = setup
    bs = 16
    rng = np.random.default_rng(23)
    shared = list(rng.integers(1, 250, size=2 * bs))      # two full blocks
    reqs = [(shared + list(rng.integers(1, 250, size=n)), m)
            for n, m in [(3, 20), (9, 37), (1, 5), (14, 18), (6, 33)]]
    reqs.append((list(rng.integers(1, 250, size=11)), 24))
    ticks = []

    def run(use_kernel):
        eng = ContinuousBatcher(config, params=gen.params, num_slots=3,
                                max_len=128, block_size=bs,
                                use_decode_kernel=use_kernel)
        run_tick = eng._run_tick

        def watched():
            ticks.append((np.asarray(eng._d_positions),
                          np.asarray(eng._d_tables),
                          np.asarray(eng._d_limits),
                          sorted(s for s, _ in eng._d_members)))
            return run_tick()

        if use_kernel:
            eng._run_tick = watched
        rids = [eng.submit(p, max_new_tokens=m) for p, m in reqs]
        out = eng.run_to_completion()
        assert eng.prefix_hit_tokens > 0
        return [out[r] for r in rids]

    assert run(True) == run(False)
    assert len(ticks) > 2 * bs
    freed_rows = crossings = 0
    for positions, tables, limits, live in ticks:
        written = np.where(positions < limits,
                           tables[np.arange(3), positions // bs],
                           GARBAGE_BLOCK)
        crossings += int(np.sum(positions[live] % bs == 0))
        for slot in range(3):
            if slot not in live:
                freed_rows += 1
                assert written[slot] == GARBAGE_BLOCK
                continue
            assert written[slot] != GARBAGE_BLOCK
            others = np.delete(tables, slot, axis=0)
            assert written[slot] not in others, (slot, written, tables)
    assert freed_rows and crossings > len(reqs)


def test_paged_int8_generates_plausibly(setup):
    """int8 arena: exact greedy parity is not promised (quantization
    perturbs logits), but generation must complete, reuse blocks, and
    keep every token in-vocab."""
    config, gen, _ = setup
    rng = np.random.default_rng(23)
    eng = ContinuousBatcher(config, params=gen.params, num_slots=2,
                            max_len=128, block_size=32,
                            kv_dtype="int8")
    assert eng.cache.quantized
    reqs = [(list(rng.integers(1, 250, size=n)), m)
            for n, m in [(5, 6), (20, 4), (9, 8)]]
    rids = [eng.submit(p, max_new_tokens=m) for p, m in reqs]
    out = eng.run_to_completion()
    for rid, (_, m) in zip(rids, reqs):
        assert len(out[rid]) == m
        assert all(0 <= t < config.vocab_size for t in out[rid])
    assert eng.allocator.used_count == 0, "finished slots leaked blocks"


def test_paged_block_accounting_and_arena_exhaustion(setup):
    """Admission reserves blocks all-or-nothing: with an arena smaller
    than the slot pool's worst case, a request WAITS for blocks (not a
    crash), joins when a finishing request frees them, and the free
    count round-trips."""
    config, gen, _ = setup
    # 6 usable blocks of 16 => at most 96 reservable tokens. Prefix
    # caching off: this test pins the BASE all-or-nothing reservation
    # arithmetic (with it on, finished prompts park blocks in the radix
    # LRU instead of freeing them — covered by test_prefix_cache.py).
    eng = ContinuousBatcher(config, params=gen.params, num_slots=3,
                            max_len=128, block_size=16,
                            num_blocks=7, prefix_cache=False)
    r1 = eng.submit(list(range(1, 30)), max_new_tokens=3)   # 2 blocks
    r2 = eng.submit(list(range(1, 40)), max_new_tokens=25)  # 4 blocks
    r3 = eng.submit([1, 2, 3], max_new_tokens=3)            # 1 block: waits
    eng.step()
    assert eng.allocator.free_count == 0
    assert eng.active_count == 2, "arena-exhausted request admitted anyway"
    out = eng.run_to_completion()
    assert len(out[r1]) == 3 and len(out[r2]) == 25 and len(out[r3]) == 3
    assert out[r3] == _reference(gen, [1, 2, 3], 3)
    assert eng.allocator.free_count == 6
    stats = eng.kv_block_stats()
    assert stats["used"] == 0 and stats["total"] == 6
    # A request that could NEVER be reserved (needs more blocks than the
    # arena holds) is rejected at submit, not left wedging the FIFO.
    with pytest.raises(ValueError, match="KV blocks"):
        eng.submit(list(range(1, 101)), max_new_tokens=27)  # 8 > 6 blocks
    # max_new_tokens=0 reserves nothing: it must finish immediately even
    # when the prompt alone would exceed the arena.
    r0 = eng.submit(list(range(1, 120)), max_new_tokens=0)
    assert eng.run_to_completion()[r0] == []


def test_an_arena_blocked_head_does_not_stop_the_live_slots_ticks(setup):
    """A waiting request the arena has no blocks for (a slot is free):
    the live slot's ticks go on, one queued behind the one that runs,
    until blocks free; then the waiter is admitted and finishes."""
    config, gen, _ = setup
    eng = ContinuousBatcher(config, params=gen.params, num_slots=3,
                            max_len=128, block_size=16, num_blocks=5)
    p1 = list(range(1, 40))
    r1 = eng.submit(p1, max_new_tokens=20)                  # 4 blocks
    r2 = eng.submit([1, 2, 3], max_new_tokens=3)            # waits: 0 free
    for _ in range(4):
        eng.step()
    assert eng.active_count == 1 and not eng._head_fits()
    assert eng._inflight and eng.base_tick_count == 5, \
        "an arena-blocked waiter stopped the tick queued ahead"
    out = eng.run_to_completion()
    assert out[r1] == _reference(gen, p1, 20)
    assert out[r2] == _reference(gen, [1, 2, 3], 3)


@pytest.mark.parametrize("overrun", [33, 32, 64], ids=[
    "past_the_reservation", "first_row_past_a_full_last_block",
    "past_the_table"])
def test_paged_overrun_write_lands_in_garbage_block(overrun):
    """A tick that runs past a slot's reservation (a request whose
    ``prompt + max_new`` fills its last block exactly has its very next
    row there)
    must NOT write into its last live block via the tail-repeated table:
    overrun writes redirect to the garbage block, live blocks stay
    byte-identical."""
    import jax

    from ray_tpu.models.continuous_batching import _decode_tick_paged
    from ray_tpu.models.paged_kv import PagedKVCache

    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32)
    bs = 16
    cache = PagedKVCache.create(cfg, num_blocks=5, block_size=bs)
    cache = cache._replace(k=cache.k.at[:, 2].set(7.7),
                           v=cache.v.at[:, 2].set(7.7))  # sentinel
    tables = jnp.asarray([[1, 2, 2, 2]], jnp.int32)  # 2 reserved blocks
    limits = jnp.asarray([32], jnp.int32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    _, _, new_cache, _ = _decode_tick_paged(
        params, jnp.asarray([3], jnp.int32),
        jnp.asarray([overrun], jnp.int32),       # OVERRUN position
        tables, limits, cache, jnp.int32(0), cfg)
    np.testing.assert_array_equal(
        np.asarray(new_cache.k[:, 2]),
        np.full_like(np.asarray(new_cache.k[:, 2]), 7.7))
    np.testing.assert_array_equal(np.asarray(new_cache.k[:, 1]),
                                  np.asarray(cache.k[:, 1]))
    # In-reservation writes still land in the mapped block.
    _, _, new_cache, _ = _decode_tick_paged(
        params, jnp.asarray([3], jnp.int32),
        jnp.asarray([17], jnp.int32), tables, limits, cache,
        jnp.int32(0), cfg)
    assert not np.all(np.asarray(new_cache.k[:, 2])[:, 1] == 7.7)


_FORWARD_MODELS = {
    "dense": dict(),
    "routed": dict(num_experts=8, num_experts_per_tok=2, qk_norm=True,
                   intermediate_size=32, num_kv_heads=4),
}


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["xla", "kernels_interpreted"])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("model", list(_FORWARD_MODELS))
def test_one_forward_serves_tick_draft_and_verify(pallas_interpret, model,
                                                  kv_dtype, use_kernel):
    """``_forward_paged`` is the tick (a window of 1), the verify pass
    (k+1) and the self-draft (1, the first layers) at once, so: position
    j of a k+1 window gives the logits and leaves the arena bytes that
    j+1 successive windows of 1 do, and ``n_layers=n`` writes exactly
    layers [0:n) of what the full forward writes and leaves the rest
    alone. Slot 0 straddles a block boundary, slot 2 overruns its
    reservation into the garbage block, slot 3 is freed."""
    import jax

    from ray_tpu.models.continuous_batching import _forward_paged
    from ray_tpu.models.paged_kv import GARBAGE_BLOCK, PagedKVCache

    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32,
                                 **_FORWARD_MODELS[model])
    params = llama.init_params(cfg, jax.random.PRNGKey(1))
    bs, k = 16, 3
    rng = np.random.default_rng(5)
    # A resident context: every block holds bytes of its own.
    cache = PagedKVCache.create(cfg, num_blocks=12, block_size=bs,
                                kv_dtype=kv_dtype)
    if kv_dtype == "int8":
        cache = PagedKVCache(
            *(jnp.asarray(rng.integers(-127, 128, a.shape), jnp.int8)
              for a in (cache.k, cache.v)),
            *(jnp.asarray(rng.uniform(0.001, 0.02, a.shape), jnp.float32)
              for a in (cache.k_scale, cache.v_scale)))
    else:
        cache = PagedKVCache(*(
            jnp.asarray(rng.standard_normal(a.shape), a.dtype)
            for a in (cache.k, cache.v)))
    tables = jnp.asarray([[1, 2, 3, 3], [4, 5, 5, 5], [6, 7, 7, 7],
                          [GARBAGE_BLOCK] * 4], jnp.int32)
    limits = jnp.asarray([48, 32, 32, 0], jnp.int32)
    first = jnp.asarray([14, 3, 30, 0], jnp.int32)
    tokens = jnp.asarray(rng.integers(1, 250, (4, k + 1)), jnp.int32)
    positions = first[:, None] + jnp.arange(k + 1)[None, :]
    forward = jax.jit(_forward_paged, static_argnums=(6, 7, 8))

    def live(c):
        """Every arena array less the garbage block, which holds
        whichever overrun or freed row landed last."""
        return [np.asarray(a)[:, 1:] for a in c if a is not None]

    window_logits, window_cache, _ = forward(
        params, tokens, positions, tables, limits, cache, cfg, use_kernel,
        None)
    stepped = cache
    for j in range(k + 1):
        logits, stepped, rows = forward(
            params, tokens[:, j:j + 1], positions[:, j:j + 1], tables,
            limits, stepped, cfg, use_kernel, None)
        assert (rows is None) == (model == "dense")
        np.testing.assert_array_equal(np.asarray(window_logits[:3, j]),
                                      np.asarray(logits[:3, 0]))
    for got, want in zip(live(window_cache), live(stepped)):
        np.testing.assert_array_equal(got, want)
    # The writes landed: slot 0's window crosses from block 1 to 2.
    assert not np.array_equal(live(window_cache)[0][:, 0:2],
                              live(cache)[0][:, 0:2])

    n = 1
    _, drafted, _ = forward(params, tokens[:, :1], positions[:, :1], tables,
                            limits, cache, cfg, use_kernel, n)
    _, full, _ = forward(params, tokens[:, :1], positions[:, :1], tables,
                         limits, cache, cfg, use_kernel, None)
    for got, whole, before in zip(live(drafted), live(full), live(cache)):
        np.testing.assert_array_equal(got[:n], whole[:n])
        np.testing.assert_array_equal(got[n:], before[n:])


def test_removed_engine_switches_are_refused(setup):
    """PR 27 removed the dense KV plane and its ``paged`` switch: an old
    script's ``ContinuousBatcher(paged=...)`` fails naming the argument,
    and a serve config whose ``init_kwargs`` still carries it is refused
    when it deploys, with the removal named, not by a ``TypeError``
    inside a replica."""
    from ray_tpu.llm import ContinuousLlamaDeployment
    from ray_tpu.serve.api import _deploy_application

    config, gen, _ = setup
    with pytest.raises(TypeError, match="paged"):
        ContinuousBatcher(config, params=gen.params, paged=False)
    app = ContinuousLlamaDeployment.options(
        init_kwargs={"paged": False, "num_slots": 4}).bind(config=config)
    with pytest.raises(ValueError, match="removed in PR 27"):
        _deploy_application(None, app, {})


def test_overrun_by_the_tick_in_flight_matches_the_reference(setup):
    """Reservations that fill their last block exactly, in an arena with
    no block to spare: a request ended by EOS has the tick in flight run
    one more row for it, and the waiter admitted into the blocks it
    freed, like the neighbour that decoded beside it, gets the
    reference's tokens."""
    config, gen, _ = setup
    rng = np.random.default_rng(99)
    pa = list(rng.integers(1, 250, size=5))
    pb = list(rng.integers(1, 250, size=4))
    pc = list(rng.integers(1, 250, size=6))
    ref_a = _reference(gen, pa, 27)              # 5 + 27: two full blocks
    eos = ref_a[20]
    want_a = ref_a[:ref_a.index(eos) + 1]
    want_b = _reference(gen, pb, 28)             # 4 + 28: two full blocks
    want_c = _reference(gen, pc, 26)             # 6 + 26: two full blocks
    assert eos not in want_b and eos not in want_c
    eng = ContinuousBatcher(config, params=gen.params, num_slots=2,
                            max_len=64, block_size=16, num_blocks=5,
                            eos_token=eos)
    rows, dispatch = [], eng._dispatch_tick
    eng._dispatch_tick = lambda members: (rows.append(len(members)),
                                          dispatch(members))[1]
    ra = eng.submit(pa, max_new_tokens=27)
    rb = eng.submit(pb, max_new_tokens=28)
    rc = eng.submit(pc, max_new_tokens=26)       # waits for ra's blocks
    out = eng.run_to_completion()
    assert (out[ra], out[rb], out[rc]) == (want_a, want_b, want_c)
    assert sum(rows) - eng.decoded_tokens == 1      # the one overrun row


def test_paged_rejects_non_pow2_block_size():
    """Prompt padding buckets are powers of two, so a non-pow2 block
    size would break the prefill block reshape — reject it up front
    instead of dying on the first admission."""
    config = llama.LlamaConfig.tiny(dtype=jnp.float32)
    with pytest.raises(ValueError, match="power of two"):
        ContinuousBatcher(config, num_slots=2, max_len=128,
                          block_size=96)
    with pytest.raises(ValueError, match="power of two"):
        ContinuousBatcher(config, num_slots=2, max_len=128,
                          block_size=4)


def test_sampling_deterministic_and_distinct():
    """temperature/top-p sampling inside the tick jit: a fixed seed
    replays bit-identically (fresh engine, same submissions), differs
    from greedy and differs across seeds."""
    from ray_tpu.models.sampling import SamplingParams

    config = llama.LlamaConfig.tiny(dtype=jnp.float32)
    gen = LlamaGenerator(config, max_len=128, seed=3)
    rng = np.random.default_rng(31)
    reqs = [(list(rng.integers(1, 250, size=n)), m)
            for n, m in [(5, 8), (17, 6)]]

    def run(**kwargs):
        eng = ContinuousBatcher(config, params=gen.params, num_slots=2,
                                max_len=128, **kwargs)
        rids = [eng.submit(p, max_new_tokens=m) for p, m in reqs]
        out = eng.run_to_completion()
        return [out[r] for r in rids]

    sp = SamplingParams(temperature=0.8, top_p=0.9, seed=42)
    a = run(sampling=sp)
    b = run(sampling=sp)
    assert a == b, "fixed-seed sampling is not deterministic"
    assert a == run(sampling=dict(temperature=0.8, top_p=0.9, seed=42)), \
        "dict-coerced sampling params diverge"
    assert a != run(), "sampled output equals greedy"
    assert a != run(sampling=SamplingParams(temperature=0.8, top_p=0.9,
                                            seed=43)), \
        "seed does not steer sampling"
    for toks, (_, m) in zip(a, reqs):
        assert len(toks) == m
        assert all(0 <= t < config.vocab_size for t in toks)


def test_admission_is_not_starved_behind_a_running_request(setup):
    """A request submitted mid-run with a free slot joins behind the tick
    in flight and finishes in its own few ticks, not when the running
    request does."""
    config, gen, _ = setup
    eng = ContinuousBatcher(config, params=gen.params, num_slots=2,
                            max_len=128)
    r_long = eng.submit([1, 2, 3], max_new_tokens=100)
    for _ in range(6):
        eng.step()
    r_short = eng.submit([4, 5, 6], max_new_tokens=3)
    finished = {}
    for i in range(5):  # << the ~100 ticks r_long needs
        finished.update(eng.step())
        if r_short in finished:
            break
    assert r_short in finished, "waiting request starved behind the run"
    assert r_long not in finished
    out = eng.run_to_completion()
    # The long request's output is unaffected by the mid-run admission.
    assert out[r_long] == _reference(gen, [1, 2, 3], 100)


def test_token_callbacks_are_whole_and_in_order_when_a_request_ends(setup):
    """Token callbacks are made as a tick's tokens are booked (the
    next tick is queued on the device by then). By the time ``step`` REPORTS a request finished
    the callbacks have delivered all its tokens, in order: a stream's
    end-marker is put right after ``step`` returns."""
    config, gen, _ = setup
    seen = {}
    eng = ContinuousBatcher(
        config, params=gen.params, num_slots=2, max_len=64,
        token_callback=lambda rid, tok: seen.setdefault(rid, []).append(tok))
    rng = np.random.default_rng(0)
    want = {eng.submit(rng.integers(1, config.vocab_size, n).tolist(), m): m
            for n, m in ((5, 3), (9, 7), (4, 1), (12, 6))}
    done = {}
    while eng.has_work():
        finished = eng.step()
        for rid, out in finished.items():
            assert seen[rid] == out and len(out) == want[rid], rid
        done.update(finished)
    assert set(done) == set(want)
    assert set(seen) == set(want)


# ------------------------------------------ one tick queued behind the one
# that runs (PR 28): scripted runs against the unbatched reference

def _pipelined(config, gen, **kw):
    kw.setdefault("num_slots", 3)
    kw.setdefault("max_len", 128)
    kw.setdefault("block_size", 16)
    return ContinuousBatcher(config, params=gen.params, **kw)


def _prompts(seed, *lengths):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(1, 250, size=n)) for n in lengths]


def _drain(eng, done):
    while eng.has_work():
        done.update(eng.step())
    assert not eng._inflight
    return done


def _eos_for(gen, prompt, n, at):
    """(eos token, expected output) such that greedy decode of
    ``prompt`` first emits the token at 0-based index ``at``."""
    ref = _reference(gen, prompt, n)
    eos = ref[at]
    cut = ref.index(eos)
    return eos, ref[:cut + 1]


def _case_max_new_staggered(config, gen):
    """Five requests over three slots that end on different ticks by
    ``max_new``: every end is foreseen, every freed slot refilled."""
    eng = _pipelined(config, gen)
    prompts = _prompts(101, 5, 9, 17, 3, 12)
    news = [6, 3, 9, 12, 2]
    rids = [eng.submit(p, max_new_tokens=m) for p, m in zip(prompts, news)]
    done = _drain(eng, {})
    # No row ran for a request the host knew to be over.
    assert eng.decoded_tokens == sum(m - 1 for m in news)
    return [(done[r], _reference(gen, p, m))
            for r, p, m in zip(rids, prompts, news)]


def _case_eos_leaves_one_overrun_row(config, gen):
    """An EOS ends a request a tick before the host can know: the tick
    in flight ran one row for it, whose token is dropped; the stream
    gets no token after its end, and its end after its last token."""
    long_p, eos_p = _prompts(102, 7, 11)
    eos, want = _eos_for(gen, eos_p, 20, at=4)
    assert eos not in _reference(gen, long_p, 16)
    seen, rows = {}, []
    eng = _pipelined(
        config, gen, eos_token=eos,
        token_callback=lambda rid, tok: seen.setdefault(rid, []).append(tok))
    dispatch = eng._dispatch_tick
    eng._dispatch_tick = lambda members: (rows.append(len(members)),
                                          dispatch(members))[1]
    r_long = eng.submit(long_p, max_new_tokens=16)
    r_eos = eng.submit(eos_p, max_new_tokens=20)
    done = {}
    while eng.has_work():
        finished = eng.step()
        for rid, out in finished.items():
            assert seen[rid] == out, "tokens after (or missing at) the end"
        done.update(finished)
    assert len(want) < 20 and seen[r_eos] == want
    assert sum(rows) - eng.decoded_tokens == 1      # the one overrun row
    return [(done[r_eos], want), (done[r_long], _reference(gen, long_p, 16))]


def _case_admission_with_a_tick_in_flight(config, gen):
    eng = _pipelined(config, gen)
    p1, p2, p3 = _prompts(103, 4, 21, 6)
    r1 = eng.submit(p1, max_new_tokens=14)
    done = dict(eng.step())
    done.update(eng.step())
    assert eng._inflight, "no tick queued behind the one that ran"
    r2 = eng.submit(p2, max_new_tokens=5)
    done.update(eng.step())
    assert eng.active_count == 2 and eng._inflight
    r3 = eng.submit(p3, max_new_tokens=7)
    _drain(eng, done)
    return [(done[r1], _reference(gen, p1, 14)),
            (done[r2], _reference(gen, p2, 5)),
            (done[r3], _reference(gen, p3, 7))]


def _case_cancel_with_a_tick_in_flight(config, gen):
    """A cancel between steps frees a slot the tick in flight still
    decodes for: that row is dropped, and the request admitted into the
    freed slot and blocks decodes as if alone."""
    eng = _pipelined(config, gen, num_slots=2, num_blocks=9)
    p1, p2, p3 = _prompts(104, 30, 5, 28)
    r1 = eng.submit(p1, max_new_tokens=30)       # 4 of the 8 blocks
    r2 = eng.submit(p2, max_new_tokens=11)
    r3 = eng.submit(p3, max_new_tokens=9)        # waits for r1's slot
    done = dict(eng.step())
    done.update(eng.step())
    assert eng._inflight and eng.cancel(r1)
    _drain(eng, done)
    assert r1 not in done
    return [(done[r2], _reference(gen, p2, 11)),
            (done[r3], _reference(gen, p3, 9))]


def _case_reset_with_a_tick_in_flight(config, gen):
    eng = _pipelined(config, gen)
    p1, p2, p3 = _prompts(105, 8, 13, 19)
    r1 = eng.submit(p1, max_new_tokens=20)
    r2 = eng.submit(p2, max_new_tokens=20)
    eng.step()
    eng.step()
    assert eng._inflight
    assert sorted(eng.reset()) == [r1, r2]
    assert not eng._inflight and not eng.has_work()
    r3 = eng.submit(p3, max_new_tokens=6)
    r4 = eng.submit(p1, max_new_tokens=4)
    done = _drain(eng, {})
    return [(done[r3], _reference(gen, p3, 6)),
            (done[r4], _reference(gen, p1, 4))]


def _case_import_into_a_running_decode_engine(config, gen):
    """A decode-role engine admits by ``import_kv_payload``, whose first
    token the host knows: with a tick in flight it is merged in on the
    device like a prefill's."""
    from ray_tpu.serve import kv_transfer

    kw = dict(num_slots=3, num_blocks=40)
    pre = _pipelined(config, gen, role="prefill", **kw)
    dst = _pipelined(config, gen, role="decode", **kw)
    prompts = _prompts(106, 33, 7, 18)
    news = [12, 9, 6]
    payloads = []
    for p, m in zip(prompts, news):
        rid = pre.submit(p, max_new_tokens=m)
        pre.run_to_completion()
        payloads.append(kv_transfer.export_kv(pre, rid))
    assert not pre._inflight and pre.base_tick_count == 0
    rids = [kv_transfer.import_kv(dst, payloads[0])]
    done = dict(dst.step())
    done.update(dst.step())
    assert dst._inflight
    rids.append(kv_transfer.import_kv(dst, payloads[1]))
    done.update(dst.step())
    rids.append(kv_transfer.import_kv(dst, payloads[2]))
    _drain(dst, done)
    return [(done[r], _reference(gen, p, m))
            for r, p, m in zip(rids, prompts, news)]


def _case_prefix_blocks_reused_after_an_overrun(config, gen):
    """Prefix cache on: a request ended by EOS (so the tick in flight
    wrote one more K/V row for it) parks its prompt blocks, the next
    admission splices them in, and reads what the prefill wrote: the
    overrun row fell behind the prompt, never in an indexed block."""
    shared = _prompts(107, 32)[0]                # two full blocks
    tail_a, tail_b, other = _prompts(108, 3, 5, 9)
    eos, want_a = _eos_for(gen, shared + tail_a, 24, at=3)
    want_b = _reference(gen, shared + tail_b, 8)
    want_o = _reference(gen, other, 30)
    assert eos not in want_b and eos not in want_o
    eng = _pipelined(config, gen, num_slots=2, eos_token=eos,
                     prefix_cache=True)
    r_o = eng.submit(other, max_new_tokens=30)
    r_a = eng.submit(shared + tail_a, max_new_tokens=24)
    done = {}
    while r_a not in done:
        done.update(eng.step())
    assert eng._inflight and eng.prefix_hit_tokens == 0
    r_b = eng.submit(shared + tail_b, max_new_tokens=8)
    _drain(eng, done)
    assert eng.prefix_hit_tokens == 32
    return [(done[r_a], want_a), (done[r_b], want_b), (done[r_o], want_o)]


def _case_block_multiple_and_a_poisoned_garbage_block(config, gen):
    """``prompt + max_new`` a multiple of the block size, so a slot's
    last row sits at the end of its reservation, with ends foreseen
    (``max_new``) and not (EOS), and the garbage block full of NaN: the
    rows a tick computes for slots it left out, and every write past a
    reservation, go there, and nothing a live row reads comes from it."""
    p1, p2, p3, p4 = _prompts(109, 10, 20, 5, 9)
    eos, want3 = _eos_for(gen, p3, 11, at=6)
    wants = [_reference(gen, p1, 6), _reference(gen, p2, 12), want3,
             _reference(gen, p4, 23)]
    assert all(eos not in w for w in wants[:2] + wants[3:])
    eng = _pipelined(config, gen, num_slots=2, eos_token=eos)
    eng.cache = eng.cache._replace(
        k=eng.cache.k.at[:, GARBAGE_BLOCK].set(jnp.nan),
        v=eng.cache.v.at[:, GARBAGE_BLOCK].set(jnp.nan))
    rids = [eng.submit(p, max_new_tokens=m)
            for p, m in ((p1, 6), (p2, 12), (p3, 11), (p4, 23))]
    done = _drain(eng, {})
    return [(done[r], w) for r, w in zip(rids, wants)]


_PIPELINE_CASES = [
    _case_max_new_staggered, _case_eos_leaves_one_overrun_row,
    _case_admission_with_a_tick_in_flight,
    _case_cancel_with_a_tick_in_flight, _case_reset_with_a_tick_in_flight,
    _case_import_into_a_running_decode_engine,
    _case_prefix_blocks_reused_after_an_overrun,
    _case_block_multiple_and_a_poisoned_garbage_block,
]


@pytest.mark.parametrize("case", _PIPELINE_CASES,
                         ids=[c.__name__[6:] for c in _PIPELINE_CASES])
def test_pipelined_engine_matches_the_reference(setup, case):
    """With one tick always queued behind the one that runs, every
    request still gets, token for token, the unbatched generator's
    greedy answer."""
    config, gen, _ = setup
    for i, (got, want) in enumerate(case(config, gen)):
        assert got == want, (case.__name__, i)


@pytest.mark.parametrize("script", ["staggered", "mid_run_admissions"])
def test_pipelined_sampled_decode_reproduces(setup, script):
    """Sampled decode keys every tick off the device-carried step
    counter, which a re-upload with a tick in flight sets to the applied
    count plus the ticks in flight. With requests that end on different
    ticks (a re-upload at each), and with admissions mid-run, a replay
    of the same script draws the same tokens."""
    from ray_tpu.models.sampling import SamplingParams

    config, gen, _ = setup
    sp = SamplingParams(temperature=0.9, top_p=0.95, seed=7)
    prompts = _prompts(110, 5, 17, 9, 4, 11)
    news = [9, 4, 13, 6, 8]

    def run(n_requests, **kw):
        eng = _pipelined(config, gen, sampling=sp, **kw)
        rids = [eng.submit(p, max_new_tokens=m)
                for p, m in list(zip(prompts, news))[:n_requests]]
        done = _drain(eng, {})
        return [done[r] for r in rids]

    if script == "staggered":
        out = run(3)
        assert out == run(3)
        greedy = [_reference(gen, p, m) for p, m in zip(prompts, news)][:3]
        assert out != greedy
    else:
        out = run(5)
        assert out == run(5)
    assert [len(o) for o in out] == news[:len(out)]


def test_tick_is_dispatched_before_the_one_ahead_is_fetched(setup):
    """The order, with the dispatch and the host fetch instrumented: in
    a run of N ticks with A admissions after the first, tick n+1 is
    dispatched before tick n's row reaches the host, except where an
    admission's prefill ran between them (the prefill queues behind tick
    n and lands it first). The counter reads N - 1 - A, ``CB_TICK_MS``
    has N observations, and every step books exactly one tick."""
    from ray_tpu._private import metrics_defs as mdefs

    config, gen, _ = setup
    eng = _pipelined(config, gen)
    events = []
    dispatch, land = eng._dispatch_tick, eng._land

    def watched_dispatch(members):
        events.append(("dispatch", eng.base_tick_count))
        dispatch(members)
        eng._inflight[-1]["n"] = eng.base_tick_count - 1

    def watched_land(tick, **kwargs):
        if tick["wall"] is None:
            events.append(("land", tick["n"]))
        return land(tick, **kwargs)

    eng._dispatch_tick, eng._land = watched_dispatch, watched_land

    def totals():
        overlapped = sum(v for _, _, v in mdefs.CB_TICK_OVERLAPPED.samples())
        ticks = sum(v for name, _, v in mdefs.CB_TICK_MS.samples()
                    if name.endswith("_count"))
        return overlapped, ticks

    before = totals()
    p1, p2, p3 = _prompts(111, 6, 9, 4)
    eng.submit(p1, max_new_tokens=25)
    admissions = {4: (p2, 8), 11: (p3, 5)}        # step -> request
    steps = 0
    while eng.has_work():
        if steps in admissions:
            p, m = admissions[steps]
            eng.submit(p, max_new_tokens=m)
        booked = eng.decoded_tokens
        eng.step()
        assert eng.decoded_tokens > booked         # one tick's tokens
        steps += 1
    n = eng.base_tick_count
    assert n == steps == 24                        # 25 tokens, 1 prefilled
    order = {ev: i for i, ev in enumerate(events)}
    assert len(order) == 2 * n
    behind_a_prefill = set()
    for t in range(n - 1):
        if order[("dispatch", t + 1)] > order[("land", t)]:
            behind_a_prefill.add(t + 1)
        assert order[("dispatch", t)] < order[("dispatch", t + 1)]
        assert order[("land", t)] < order[("land", t + 1)]
    assert len(behind_a_prefill) == len(admissions)
    overlapped, ticks = (a - b for a, b in zip(totals(), before))
    assert ticks == n
    assert overlapped == n - 1 - len(admissions)


def test_speculative_tick_never_runs_ahead(setup):
    """A speculative tick advances each slot by a count the device
    decides, so its row is fetched before anything else is dispatched:
    no tick overlaps another, and the outputs are the reference's."""
    from ray_tpu._private import metrics_defs as mdefs

    config, gen, _ = setup
    eng = _pipelined(config, gen, spec_k=2, spec_adaptive=False)
    dispatch = eng._dispatch_tick

    def watched(members):
        assert not eng._inflight
        return dispatch(members)

    eng._dispatch_tick = watched
    before = sum(v for _, _, v in mdefs.CB_TICK_OVERLAPPED.samples())
    prompts = _prompts(112, 5, 12, 8, 3)
    news = [9, 5, 12, 7]
    rids = [eng.submit(p, max_new_tokens=m) for p, m in zip(prompts, news)]
    done = {}
    while eng.has_work():
        done.update(eng.step())
        assert not eng._inflight
    assert eng.spec_tick_count > 0 and eng.base_tick_count == 0
    assert sum(v for _, _, v in mdefs.CB_TICK_OVERLAPPED.samples()) == before
    for r, p, m in zip(rids, prompts, news):
        assert done[r] == _reference(gen, p, m)


def test_tick_overlap_share_reads_the_engines_counter(setup):
    """``benchmark/metrics/tick_overlap_share.json`` through its reader,
    on registry snapshots around a run: the share of ticks that were
    dispatched behind one still in flight; 0 where the program has no
    such counter (the parent), nothing where no tick ran."""
    import json
    import os

    from benchmark.readers import registry_delta
    from ray_tpu._private import metrics_defs as mdefs

    path = os.path.join(os.path.dirname(__file__), os.pardir, "benchmark",
                        "metrics", "tick_overlap_share.json")
    with open(path) as fh:
        spec = json.load(fh)
    assert spec["reader"] == "registry_delta"

    def snapshot():
        out = {}
        for metric in (mdefs.CB_TICK_OVERLAPPED, mdefs.CB_TICK_MS):
            for name, _, v in metric.samples():
                out[name] = out.get(name, 0.0) + v
        return out

    config, gen, _ = setup
    eng = _pipelined(config, gen)
    before = snapshot()
    eng.submit(_prompts(113, 6)[0], max_new_tokens=11)     # 10 ticks
    eng.run_to_completion()
    after = snapshot()
    ctx = {"registry_before": before, "registry_after": after}
    assert registry_delta.read(ctx, **spec["args"]) == pytest.approx(90.0)
    counter = mdefs.CB_TICK_OVERLAPPED.name
    parent = {"registry_before": {k: v for k, v in before.items()
                                  if k != counter},
              "registry_after": {k: v for k, v in after.items()
                                 if k != counter}}
    assert registry_delta.read(parent, **spec["args"]) == 0.0
    idle = {"registry_before": after, "registry_after": after}
    assert registry_delta.read(idle, **spec["args"]) is None


# ------------------------------------------------- gathered admission
# A saturated engine may leave free slots empty for a few ticks so that
# the slots that free next join one prefill call (ISSUE 40). The
# decision reads the engine's own clocks; these tests give it readings.

def _read_counter(eng, counter):
    key = tuple(sorted(eng._mtags.items()))
    return sum(v for _, k, v in counter.samples() if k == key)


def _price(eng, batch_ms, restart_ms=0.0, tick_ms=None, length=32):
    """Give ``eng`` a table: ``batch_ms(padded rows)`` and ``restart_ms``
    for the one-call shape of prompts that pad to ``length``, and the
    tick's cadence; from here on it keeps them (its own CPU readings
    would say nothing about the rule)."""
    for rows in (1, 2, 4, 8, 16):
        shape = (min(rows, eng.num_slots), length, 0, 1)
        eng._batch_ms[shape] = [float(batch_ms(shape[0]))]
        eng._restart_ms[shape] = [float(restart_ms)]
    eng._note_reading = lambda table, shape, ms: None
    if tick_ms is not None:
        eng._tick_ms = float(tick_ms)
        eng._note_tick_ms = lambda ms: None


def _closed_loop(eng, seed, total, first, lengths=(17, 32), new=(4, 24)):
    """``first`` requests at once, then one more for each that ends, up
    to ``total``: a queue always waits. Returns every request's tokens
    and the order of first tokens."""
    rng = np.random.default_rng(seed)
    order, done = [], {}
    eng.token_callback = lambda rid, tok: (
        order.append(rid) if rid not in order else None)

    def submit():
        prompt = list(rng.integers(1, 250, size=int(rng.integers(*lengths))))
        return eng.submit(prompt, max_new_tokens=int(rng.integers(*new)))

    rids = [submit() for _ in range(first)]
    while eng.has_work():
        out = eng.step()
        done.update(out)
        for _ in out:
            if len(rids) < total:
                rids.append(submit())
    assert sorted(done) == sorted(rids)
    return done, order


def test_gathered_admission_changes_no_token_and_no_order(setup):
    """The same seeded closed loop with holds and with none: every
    request's tokens and the order of first tokens are the same, in
    fewer prefill batches, and the held slots' time is booked."""
    from ray_tpu._private import metrics_defs as mdefs

    config, gen, _ = setup
    runs = {}
    for holds in (True, False):
        eng = _pipelined(config, gen, num_slots=4, prefix_cache=False)
        _price(eng, lambda rows: 10.0, restart_ms=2.0, tick_ms=0.5)
        if not holds:
            eng._holds_admission = lambda: False
        runs[holds] = (*_closed_loop(eng, 7, total=40, first=12),
                       _prefill_batches(eng),
                       _read_counter(eng, mdefs.CB_ADMIT_HELD_TICKS),
                       _read_counter(eng, mdefs.CB_ADMIT_HELD_SLOT_MS))
    held, plain = runs[True], runs[False]
    assert held[0] == plain[0] and held[1] == plain[1]
    assert held[2] < plain[2]
    assert held[3] > 0 and held[4] > 0
    assert plain[3] == 0 and plain[4] == 0


def _saturated(config, gen, remaining, free=1, waiting=12, slots=8, **kw):
    """An engine's books as a saturated loop leaves them, without a
    device: ``free`` empty slots, a live slot for each entry of
    ``remaining`` (tokens still to book), ``waiting`` one-bucket
    requests that have waited a minute."""
    import time

    eng = _pipelined(config, gen, num_slots=slots, prefix_cache=False, **kw)
    eng._free = list(range(slots - free, slots))
    eng._slots = {i: {"rid": 1000 + i, "out": [1] * 3, "max_new": 3 + left}
                  for i, left in enumerate(remaining)}
    for rid in range(waiting):
        eng._waiting.append({"rid": rid, "prompt": [1] * 20, "max_new": 4,
                             "routes": None})
        eng._req_meta[rid] = {"rid": rid, "submit": time.time() - 60.0}
    eng._tick_ms = 1.0
    return eng


def _cheapest(free, ends, most, batch_ms, restart_ms, tick_ms, slots):
    """The rule by hand: slots to fill for the least device time a
    request, among ``free``..``most``."""
    def cost(n):
        wait = ends[n - free - 1] if n > free else 0
        empty = free * wait + sum(wait - e for e in ends[:n - free])
        pad = 1 << (n - 1).bit_length()
        return ((batch_ms(min(pad, slots)) + restart_ms) / n
                + empty * tick_ms / slots / n)
    return min(range(free, most + 1), key=lambda n: (cost(n), n))


_TABLES = {
    # A call's time is fixed cost (weights read once whatever the rows):
    # gather as many as one call takes.
    "bytes_like": (lambda rows: 300.0, 5.0, 2.0, 8),
    # A second row costs what the first does and nothing is shared:
    # never worth an empty slot.
    "compute_like": (lambda rows: 30.0 * rows, 0.0, 2.0, 1),
    # Between: the fixed part amortises until the empty slots cost more.
    "between": (lambda rows: 20.0 + 12.0 * rows, 4.0, 16.0, 4),
}


@pytest.mark.parametrize("table", sorted(_TABLES))
def test_gathered_size_is_the_costs_minimum(setup, table):
    config, gen, _ = setup
    batch_ms, restart_ms, tick_ms, want = _TABLES[table]
    ends = [2, 3, 5, 9, 14, 20, 27]
    eng = _saturated(config, gen, remaining=ends)
    _price(eng, batch_ms, restart_ms, tick_ms)
    assert want == _cheapest(1, ends, 8, batch_ms, restart_ms, tick_ms, 8)
    plan = eng._gather()
    if want == 1:
        assert plan is None and not eng._holds_admission()
    else:
        assert (plan["slots"], plan["rows"]) == (want, want)
        assert eng._head_fits() and eng._holds_admission()


def _case_a_slot_for_everyone(config, gen):
    return _saturated(config, gen, remaining=[4, 5, 6], free=5, waiting=5)


def _case_no_stream_live(config, gen):
    return _saturated(config, gen, remaining=[], free=8, waiting=12)


def _case_a_bucket_never_measured(config, gen):
    eng = _saturated(config, gen, remaining=[2] * 7)
    for rows in (2, 4, 8):
        eng._batch_ms[(rows, 32, 0, 1)] = []   # seen once: not kept
    return eng


def _case_admitting_now_has_no_price(config, gen):
    eng = _saturated(config, gen, remaining=[2] * 7)
    eng._batch_ms[(1, 32, 0, 1)] = []
    return eng


def _case_another_group_waits_behind_the_head(config, gen):
    eng = _saturated(config, gen, remaining=[2] * 7)
    for req in list(eng._waiting)[1:]:
        req["prompt"] = [1] * 40      # pads to 64: another call
    return eng


def _case_the_head_is_blocked_on_the_arena(config, gen):
    eng = _saturated(config, gen, remaining=[2] * 7)
    eng.allocator.alloc(eng.allocator.free_count)
    return eng


def _case_no_tick_has_been_timed(config, gen):
    eng = _saturated(config, gen, remaining=[2] * 7)
    eng._tick_ms = 0.0
    return eng


_NO_HOLD = [_case_a_slot_for_everyone, _case_no_stream_live,
            _case_a_bucket_never_measured, _case_admitting_now_has_no_price,
            _case_another_group_waits_behind_the_head,
            _case_the_head_is_blocked_on_the_arena,
            _case_no_tick_has_been_timed]


@pytest.mark.parametrize("case", _NO_HOLD,
                         ids=lambda f: f.__name__[len("_case_"):])
def test_nothing_is_held_when(setup, case):
    """Under a table that makes every gathered batch look free, these
    engines still admit as they always did."""
    config, gen, _ = setup
    eng = case(config, gen)
    for rows in (1, 2, 4, 8):
        for length in (32, 64):
            eng._batch_ms.setdefault((rows, length, 0, 1), [50.0])
    assert eng._gather() is None
    assert not eng._holds_admission() and eng._hold is None


def test_a_prefill_role_engine_never_holds(setup):
    """It parks every request at its first token, so no stream is ever
    live: a queue longer than its slots is admitted as the slots free."""
    from ray_tpu._private import metrics_defs as mdefs

    config, gen, _ = setup
    eng = _pipelined(config, gen, num_slots=2, prefix_cache=False,
                     role="prefill")
    _price(eng, lambda rows: 50.0, tick_ms=1e-3)
    rids = [eng.submit(p, max_new_tokens=6) for p in _prompts(5, *[20] * 7)]
    done = {}
    for _ in range(10):
        done.update(eng.step())
        for rid in eng.handoff_ready():
            eng.abandon_handoff(rid)
    assert sorted(done) == rids and eng._hold is None
    assert _read_counter(eng, mdefs.CB_ADMIT_HELD_TICKS) == 0


@pytest.mark.parametrize("bound", ["slot_time", "queue_age"])
def test_a_hold_ends_at_its_bound_under_readings_that_lie(setup, bound):
    """Three streams with 60 tokens to go, one free slot, a queue. The
    table says a second row is free and the tick clock says an empty
    slot forgoes nothing, so the rule would wait 60 ticks for the pair.
    ``slot_time``: the pair saves 0.2 ms, which the empty slot has cost
    after a tick or two. ``queue_age``: it saves a second, but the
    head has waited only as long as a few ticks take."""
    import time

    from ray_tpu._private import metrics_defs as mdefs

    config, gen, _ = setup
    eng = _pipelined(config, gen, num_slots=4, prefix_cache=False)
    _price(eng, lambda rows: 0.2 if bound == "slot_time" else 1000.0,
           tick_ms=1e-9)
    live = [eng.submit(p, max_new_tokens=64) for p in _prompts(9, 20, 21, 22)]
    for _ in range(4):
        eng.step()
    assert len(eng._slots) == 3 and len(eng._free) == 1
    late = [eng.submit(p, max_new_tokens=3) for p in _prompts(10, 20, 21)]
    if bound == "slot_time":
        for rid in late:
            eng._req_meta[rid]["submit"] -= 60.0
    submitted, batches = time.time(), _prefill_batches(eng)
    assert eng._gather()["slots"] == 2
    steps = 0
    while _prefill_batches(eng) == batches:
        eng.step()
        steps += 1
        assert steps < 40, "the hold outlived its bound"
    waited = time.time() - submitted
    held_ticks = _read_counter(eng, mdefs.CB_ADMIT_HELD_TICKS)
    assert 1 <= held_ticks <= steps < 40
    assert len(eng._slots) == 4 and eng._hold is None
    if bound == "queue_age":
        # Held no longer than it had waited, give or take a step.
        rec = eng._req_meta[late[0]]
        assert rec["admit"] - rec["held"] <= (
            rec["held"] - rec["submit"] + waited / steps + 0.05)
    done = _drain(eng, {})
    assert sorted(done) == sorted(live + late)


def test_run_to_completion_leaves_no_request_behind_a_hold(setup):
    """A batch job: the queue only shrinks. Holds engage while more wait
    than are free, end as the streams end, and the last requests are
    admitted by an engine whose streams have all ended."""
    from ray_tpu._private import metrics_defs as mdefs

    config, gen, _ = setup
    eng = _pipelined(config, gen, num_slots=4, prefix_cache=False)
    _price(eng, lambda rows: 10.0, restart_ms=2.0, tick_ms=0.5)
    prompts = _prompts(21, *range(17, 32))
    rids = [eng.submit(p, max_new_tokens=5 + i % 7)
            for i, p in enumerate(prompts)]
    done = eng.run_to_completion()
    assert sorted(done) == rids
    assert _read_counter(eng, mdefs.CB_ADMIT_HELD_TICKS) > 0
    assert not (eng._waiting or eng._slots or eng._hold)
    for rid, prompt in list(zip(rids, prompts))[::5]:
        assert done[rid] == _reference(gen, prompt, 5 + rids.index(rid) % 7)


def test_a_head_that_fits_is_not_admitted_while_admit_holds(setup):
    """``_holds_admission`` is what ``_admit`` obeys: a head the arena
    and a free slot have room for stays in the queue through a hold, and
    the step that holds runs no prefill."""
    config, gen, _ = setup
    eng = _pipelined(config, gen, num_slots=4, prefix_cache=False)
    _price(eng, lambda rows: 10.0, restart_ms=2.0, tick_ms=0.5)
    for i, p in enumerate(_prompts(3, 20, 21, 22, 23)):
        eng.submit(p, max_new_tokens=8 + 8 * i)
    for p in _prompts(4, 20, 21, 22):
        eng.submit(p, max_new_tokens=4)
    while len(eng._free) != 1:
        eng.step()
    assert len(eng._waiting) == 3 and eng._head_fits()
    batches = _prefill_batches(eng)
    assert eng._holds_admission()
    eng.step()
    assert _prefill_batches(eng) == batches and len(eng._waiting) == 3
    _drain(eng, {})
    assert _prefill_batches(eng) == batches + 1   # the three, in one call
