"""Cross-request prefix caching: radix KV-block reuse in the paged
continuous-batching engine (reference: SGLang RadixAttention / vLLM
automatic prefix caching; ROADMAP item 2).

Contracts under test:

* greedy outputs are BIT-IDENTICAL with the prefix cache on vs off —
  across the paged kernel on/off and bf16/int8 arenas (int8 prefill
  quantizes in-loop so a sharer reads back exactly what the original
  prefill attended);
* a repeated prefix admits as a table splice: only the novel suffix is
  prefilled (hit/miss token accounting proves it);
* eviction under pressure is safe: refcounted shared blocks are never
  reclaimed while live, LRU-cached blocks ARE reclaimed before
  admission blocks on the arena, and evicting a prefix-sharing sibling
  mid-decode leaves the survivor's output untouched.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.models.continuous_batching import ContinuousBatcher
from ray_tpu.models.inference import LlamaGenerator
from ray_tpu.models.paged_kv import RadixBlockIndex, prompt_chunks

BS = 16  # block size used throughout: small enough for tiny prompts


@pytest.fixture(scope="module")
def setup():
    config = llama.LlamaConfig.tiny(dtype=jnp.float32)
    gen = LlamaGenerator(config, max_len=128, seed=3)
    return config, gen


def _reference(gen, prompt, n):
    return list(np.asarray(
        gen.generate(np.asarray([prompt], np.int32),
                     max_new_tokens=n))[0])


def _engine(config, gen, **kwargs):
    kwargs.setdefault("num_slots", 3)
    kwargs.setdefault("max_len", 128)
    kwargs.setdefault("block_size", BS)
    return ContinuousBatcher(config, params=gen.params, **kwargs)


# ------------------------------------------------------- radix index unit

def test_radix_index_match_insert_release_evict():
    idx = RadixBlockIndex()
    prompt = list(range(100, 100 + 3 * BS + 5))
    chunks = prompt_chunks(prompt, BS)
    assert len(chunks) == 3

    created = idx.insert(chunks, [5, 6, 7])
    assert [n.block for n in created] == [5, 6, 7]
    assert idx.shared_count == 3 and idx.cached_count == 0

    # A second reader pins the same nodes; a divergent tail stops the walk.
    matched = idx.match(chunks[:2])
    assert [n.block for n in matched] == [5, 6]
    other = idx.insert(chunks[:2] + [tuple(range(7000, 7000 + BS))],
                       [5, 6, 9], start=2)
    assert [n.block for n in other] == [9]

    # Conflicting insert (same chunk, different block) indexes nothing.
    assert idx.insert([chunks[0]], [42]) == []

    # Release to refcount 0 parks in the LRU; nothing is evictable while
    # pinned.
    idx.release(created)          # root chain now held only by `matched`
    assert idx.evict(10) == [7]   # leaf-first: only the unpinned tail
    idx.release(matched)
    idx.release(other)
    assert idx.shared_count == 0 and idx.cached_count == 3
    # Leaf-first eviction: the divergent leaf 9 and chain tail 6 go
    # before the root 5.
    got = idx.evict(10)
    assert set(got) == {5, 6, 9}
    assert got.index(5) == len(got) - 1, "root evicted before its leaves"
    assert idx.cached_count == 0 and idx.indexed_count == 0

    # Matching after eviction finds nothing.
    assert idx.match(chunks) == []


def test_match_is_capped_so_one_prompt_token_remains():
    """A prompt of exactly k full blocks may match at most k-1: the
    first generated token samples from the last prompt position's
    logits, which only a prefill can produce."""
    idx = RadixBlockIndex()
    prompt = list(range(1, 1 + 2 * BS))     # exactly 2 blocks
    idx.insert(prompt_chunks(prompt, BS), [3, 4])
    # The engine-side cap (match_chunks) is (len - 1) // BS == 1.
    assert (len(prompt) - 1) // BS == 1


# ------------------------------------------------- reuse skips prefill

def test_prefix_reuse_skips_prefill_and_stays_exact(setup):
    config, gen = setup
    rng = np.random.default_rng(5)
    shared = list(map(int, rng.integers(1, 250, size=2 * BS + 3)))
    tails = [list(map(int, rng.integers(1, 250, size=4)))
             for _ in range(3)]

    eng = _engine(config, gen, prefix_cache=True)
    outs = []
    for t in tails:
        rid = eng.submit(shared + t, max_new_tokens=5)
        out = eng.run_to_completion()
        outs.append(out[rid])
    # First request is cold; the two followers each reuse 2 full blocks.
    assert eng.prefix_hit_tokens == 2 * 2 * BS
    assert eng.prefix_hit_requests == 2
    assert 0 < eng.prefix_hit_rate < 1
    # prefill_tokens counts only NOVEL tokens: full first prompt, then
    # suffixes.
    first_len = len(shared) + 4
    assert eng.prefill_tokens == first_len + 2 * (first_len - 2 * BS)
    for t, toks in zip(tails, outs):
        assert toks == _reference(gen, shared + t, 5)


def test_prefix_cache_on_off_bit_identical_across_paths(
        setup, pallas_interpret):
    """The tentpole parity contract: greedy outputs are identical with
    the prefix cache on vs off, for the XLA reference and the fused
    paged kernel (interpret mode on CPU), on bf16 and int8 arenas —
    and the bf16 outputs match the sequential generator exactly."""
    config, gen = setup
    rng = np.random.default_rng(6)
    shared = list(map(int, rng.integers(1, 250, size=35)))
    reqs = [(shared + list(map(int, rng.integers(1, 250, size=n))), m)
            for n, m in [(5, 6), (2, 4), (9, 7)]]
    reqs.append((list(map(int, rng.integers(1, 250, size=20))), 5))

    for kv_dtype in ("bf16", "int8"):
        for use_kernel in (False, True):
            results = {}
            for on in (True, False):
                eng = _engine(config, gen, prefix_cache=on,
                              kv_dtype=kv_dtype,
                              use_decode_kernel=use_kernel)
                outs = []
                for p, m in reqs:           # sequential: real reuse
                    rid = eng.submit(list(p), max_new_tokens=m)
                    outs.append(eng.run_to_completion()[rid])
                results[on] = outs
                if on:
                    assert eng.prefix_hit_tokens > 0, \
                        (kv_dtype, use_kernel)
            assert results[True] == results[False], \
                f"prefix cache changed output ({kv_dtype}, " \
                f"kernel={use_kernel})"
            if kv_dtype == "bf16":
                for (p, m), toks in zip(reqs, results[True]):
                    assert toks == _reference(gen, p, m)


def test_same_round_cold_twins_are_safe(setup):
    """Two identical prompts admitted in ONE admission round are both
    cold (matching sees only blocks whose prefill already dispatched):
    no cross-row aliasing, outputs exact, and the loser of the insert
    race keeps exclusive blocks that free cleanly."""
    config, gen = setup
    rng = np.random.default_rng(8)
    p = list(map(int, rng.integers(1, 250, size=2 * BS + 2)))
    eng = _engine(config, gen, prefix_cache=True)
    r1 = eng.submit(list(p), max_new_tokens=5)
    r2 = eng.submit(list(p), max_new_tokens=5)
    out = eng.run_to_completion()
    assert eng.prefix_hit_tokens == 0      # same-round: both cold
    assert out[r1] == out[r2] == _reference(gen, p, 5)
    first = out[r1]
    # A third request NOW reuses the winner's indexed blocks.
    r3 = eng.submit(list(p), max_new_tokens=5)
    out = eng.run_to_completion()
    assert eng.prefix_hit_tokens == 2 * BS
    assert out[r3] == first


# --------------------------------------------- eviction under pressure

def test_live_shared_blocks_never_reclaimed(setup):
    """Arena pressure must not steal blocks a live slot references:
    the blocked request waits (arena_wait), admits only after the
    sharer finishes, and everyone's output is exact."""
    config, gen = setup
    # 6 usable blocks. r1: 2 blocks live (prompt 17..32 tokens + gen).
    eng = _engine(config, gen, num_blocks=7, prefix_cache=True,
                  num_slots=3)
    p1 = list(range(1, 1 + BS + 4))                      # 2 blocks
    r1 = eng.submit(p1, max_new_tokens=8)
    eng.step()                                           # r1 live
    # r2 wants 5 blocks; only 4 free and r1's 2 are LIVE (refcounted
    # once indexed... r1's full block is indexed and pinned): nothing
    # reclaimable, so r2 must wait.
    p2 = list(range(500, 500 + 3 * BS + 1))
    r2 = eng.submit(p2, max_new_tokens=BS + 8)           # 5 blocks
    eng.step()
    assert eng.active_count >= 1
    stats = eng.kv_block_stats()
    assert stats["shared"] >= 1            # r1's prompt block is pinned
    out = eng.run_to_completion()
    assert len(out[r1]) == 8 and len(out[r2]) == BS + 8
    assert out[r1] == _reference(gen, p1, 8)
    assert out[r2] == _reference(gen, p2, BS + 8)


def test_cached_blocks_reclaimed_before_admission_blocks(setup):
    """A finished prompt's blocks park in the LRU; a new request that
    needs the whole arena must RECLAIM them and admit immediately —
    cached state never wins over admission."""
    config, gen = setup
    eng = _engine(config, gen, num_blocks=7, prefix_cache=True)
    p1 = list(range(1, 1 + 2 * BS + 2))
    r1 = eng.submit(p1, max_new_tokens=4)
    out = eng.run_to_completion()
    assert out[r1] == _reference(gen, p1, 4)
    assert eng.kv_block_stats()["cached"] == 2   # 2 full blocks parked
    # p2 needs 6 blocks = every usable block: only possible by evicting
    # the cached pair. It must admit on the FIRST step, not wait.
    p2 = list(range(900, 900 + 4 * BS))
    r2 = eng.submit(p2, max_new_tokens=2 * BS - 3)
    eng.step()
    assert eng.active_count == 1, "cached blocks blocked admission"
    assert eng.kv_block_stats()["cached"] == 0
    out = eng.run_to_completion()
    assert out[r2] == _reference(gen, p2, 2 * BS - 3)


def test_admission_probe_agrees_with_admission_under_shared_pressure(setup):
    """_head_fits must not count a parked matched block twice —
    once as covering the request's need (via the match) and once as
    evictable capacity (via the LRU): pinning the match revives the
    block WITHOUT freeing anything. An optimistic probe has ``_gather``
    hold slots empty for an admission that then fails."""
    config, gen = setup
    eng = _engine(config, gen, num_blocks=7, prefix_cache=True)
    p1 = list(range(1, 1 + 2 * BS + 2))
    eng.submit(p1, max_new_tokens=BS - 4)               # 3 blocks
    eng.run_to_completion()
    assert eng.kv_block_stats()["cached"] == 2          # p1's prefix
    assert eng.allocator.free_count == 4
    filler = list(range(600, 600 + 2 * BS + 2))
    rf = eng.submit(filler, max_new_tokens=2 * BS - 4)  # 4 blocks
    eng.step()
    assert eng.active_count == 1
    assert eng.allocator.free_count == 0
    # Head shares p1's 2 parked blocks and needs 2 novel ones — but
    # the match revives the parked pair from the LRU, leaving NOTHING
    # evictable for the novel pair: the probe must say no.
    r2 = eng.submit(list(p1), max_new_tokens=2 * BS - 4)
    assert eng._head_fits() is False and not eng._holds_admission()
    eng.step()
    assert eng.active_count == 1, "admission should be arena-blocked"
    out = eng.run_to_completion()
    assert len(out[rf]) == 2 * BS - 4
    assert out[r2] == _reference(gen, p1, 2 * BS - 4)


def test_sibling_eviction_mid_decode_leaves_survivor_bit_identical(setup):
    """Cancel one of two prefix-sharing requests mid-decode: the shared
    blocks stay pinned by the survivor (refcount, not ownership), and
    the survivor's remaining decode is bit-identical to an undisturbed
    run."""
    config, gen = setup
    rng = np.random.default_rng(11)
    shared = list(map(int, rng.integers(1, 250, size=2 * BS + 1)))
    pa, pb = shared + [3, 4], shared + [5]
    # Undisturbed baseline.
    eng = _engine(config, gen, prefix_cache=True)
    rb = eng.submit(list(pa), max_new_tokens=4)
    eng.run_to_completion()
    rb = eng.submit(list(pb), max_new_tokens=20)
    baseline = eng.run_to_completion()[rb]

    eng = _engine(config, gen, prefix_cache=True)
    ra = eng.submit(list(pa), max_new_tokens=4)
    eng.run_to_completion()                      # pa indexed its prefix
    ra = eng.submit(list(pa), max_new_tokens=40)  # sharer A (long)
    rb = eng.submit(list(pb), max_new_tokens=20)  # sharer B (survivor)
    for _ in range(5):
        eng.step()                               # both mid-decode
    assert eng.active_count == 2
    assert eng.cancel(ra)                        # evict the sibling
    out = eng.run_to_completion()
    assert ra not in out
    assert out[rb] == baseline == _reference(gen, pb, 20)


def test_reset_clears_index_and_reuses_cleanly(setup):
    """reset() (engine-error recovery) rebuilds the arena: the radix
    index must restart cold — stale entries would alias zeroed blocks."""
    config, gen = setup
    p = list(range(1, 1 + 2 * BS + 2))
    eng = _engine(config, gen, prefix_cache=True)
    eng.submit(list(p), max_new_tokens=4)
    eng.run_to_completion()
    assert eng.kv_block_stats()["cached"] > 0
    eng.reset()
    assert eng.kv_block_stats()["cached"] == 0
    assert eng.prefix_hit_tokens >= 0
    hit0 = eng.prefix_hit_tokens
    rid = eng.submit(list(p), max_new_tokens=4)
    out = eng.run_to_completion()
    assert eng.prefix_hit_tokens == hit0, "matched a cleared index"
    assert out[rid] == _reference(gen, p, 4)
