"""Paged decode attention + KV arena: kernel parity, quantization
round-trip, and block-allocator lifecycle.

Tier-1 runs on CPU: the ``pallas_interpret`` fixture pins interpret mode
so the real paged kernel (scalar-prefetch block-table gather) executes
without TPU-only skips.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.paged_kv import (GARBAGE_BLOCK, BlockAllocator,
                                     PagedKVCache, quantize_kv,
                                     resolve_kv_dtype)
from ray_tpu.ops.paged_decode_attention import (MAX_VISIT_BLOCKS,
                                                VISIT_BYTES,
                                                decode_attention_reference,
                                                paged_applicable,
                                                paged_attention_reference,
                                                paged_decode_attention,
                                                paged_kv_write, paged_visits,
                                                visit_blocks)


def _paged_inputs(b=3, hq=4, hkv=2, d=16, bs=32, nb_slot=4, seed=0,
                  dtype=jnp.float32, scramble=True):
    """Dense K/V plus an equivalent scattered arena + block tables.

    The arena places each slot's logical blocks at arbitrary physical
    ids (permuted) so a passing test proves the TABLE gather, not a
    lucky identity layout. Returns (q, dense_ck, dense_cv, arena_k,
    arena_v, tables, positions)."""
    s_max = bs * nb_slot
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, hq, d), jnp.float32).astype(dtype)
    ck = jax.random.normal(ks[1], (b, s_max, hkv, d), jnp.float32)
    cv = jax.random.normal(ks[2], (b, s_max, hkv, d), jnp.float32)
    ck, cv = ck.astype(dtype), cv.astype(dtype)
    nb_total = b * nb_slot + 1                    # + garbage block 0
    ids = np.arange(1, nb_total)
    if scramble:
        ids = np.random.default_rng(seed).permutation(ids)
    tables = ids.reshape(b, nb_slot).astype(np.int32)
    arena_k = np.zeros((nb_total, hkv, bs, d), np.asarray(ck).dtype)
    arena_v = np.zeros_like(arena_k)
    for i in range(b):
        for j in range(nb_slot):
            # Heads-major inside a block: [bs, KVH, D] -> [KVH, bs, D].
            arena_k[tables[i, j]] = np.asarray(
                ck[i, j * bs:(j + 1) * bs]).swapaxes(0, 1)
            arena_v[tables[i, j]] = np.asarray(
                cv[i, j * bs:(j + 1) * bs]).swapaxes(0, 1)
    return (q, ck, cv, jnp.asarray(arena_k), jnp.asarray(arena_v),
            jnp.asarray(tables), None)


# --------------------------------------------------- reference vs dense

def test_paged_reference_equals_dense_reference():
    """The paged reference (table gather -> dense attention) is exactly
    the dense reference over the linearized blocks — the parity anchor
    the kernel ships against."""
    q, ck, cv, ak, av, tables, _ = _paged_inputs()
    pos = jnp.asarray([0, 37, 127], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(paged_attention_reference(q, ak, av, tables, pos)),
        np.asarray(decode_attention_reference(q, ck, cv, pos)))


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 2)])
def test_paged_kernel_matches_reference_gqa(pallas_interpret, hq, hkv):
    q, ck, cv, ak, av, tables, _ = _paged_inputs(hq=hq, hkv=hkv)
    # Edge positions included: 0 (one live entry) and s_max-1 (full).
    pos = jnp.asarray([0, 17, 127], jnp.int32)
    ref = decode_attention_reference(q, ck, cv, pos)
    out = paged_decode_attention(q, ak, av, tables, pos, use_kernel=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6)


def test_paged_kernel_ragged_lengths_straddle_blocks(pallas_interpret):
    """Live lengths landing just before/on/after block boundaries: the
    per-block skip guard and the in-block causal mask must agree with
    the dense mask at every straddle."""
    q, ck, cv, ak, av, tables, _ = _paged_inputs(b=5, bs=32, nb_slot=4,
                                                 seed=3)
    # positions: last-in-block, first-in-next-block, mid-block, exactly
    # one full block, and the final position.
    pos = jnp.asarray([31, 32, 45, 63, 127], jnp.int32)
    ref = decode_attention_reference(q, ck, cv, pos)
    out = paged_decode_attention(q, ak, av, tables, pos, use_kernel=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6)


def test_paged_kernel_dead_tail_repeats_last_block(pallas_interpret):
    """Dead table entries repeating the last live block (the no-refetch
    bandwidth trick) must not change the output — they are masked."""
    q, ck, cv, ak, av, tables, _ = _paged_inputs()
    pos = jnp.asarray([5, 40, 70], jnp.int32)
    t = np.asarray(tables).copy()
    for i, p in enumerate([5, 40, 70]):
        last_live = p // 32
        t[i, last_live + 1:] = t[i, last_live]   # repeat last live block
    out_rep = paged_decode_attention(q, ak, av, jnp.asarray(t), pos,
                                     use_kernel=True)
    ref = decode_attention_reference(q, ck, cv, pos)
    np.testing.assert_allclose(np.asarray(out_rep), np.asarray(ref),
                               atol=2e-6)


def test_paged_kernel_bf16_arena(pallas_interpret):
    q, ck, cv, ak, av, tables, _ = _paged_inputs(dtype=jnp.bfloat16)
    pos = jnp.asarray([3, 50, 100], jnp.int32)
    ref = decode_attention_reference(q, ck, cv, pos)
    out = paged_decode_attention(q, ak, av, tables, pos, use_kernel=True)
    np.testing.assert_allclose(
        np.asarray(out, jnp.float32), np.asarray(ref, jnp.float32),
        atol=2e-2)


# ------------------------------------------------------------ int8 arena

def test_int8_quantize_roundtrip_tolerance():
    """Per-token/per-head symmetric int8: worst-case round-trip error is
    bounded by scale/2 = amax/254 per element; zero vectors survive
    exactly."""
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 64)) * 3.0
    x = x.at[1].set(0.0)
    q, scale = quantize_kv(x)
    assert q.dtype == jnp.int8
    back = q.astype(jnp.float32) * scale[..., None]
    amax = np.asarray(jnp.max(jnp.abs(x), axis=-1, keepdims=True))
    np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                               atol=float(amax.max()) / 254 + 1e-7)
    np.testing.assert_array_equal(np.asarray(back[1]),
                                  np.zeros_like(np.asarray(back[1])))


@pytest.mark.parametrize("use_kernel", [False, True])
def test_paged_int8_attention_close_to_fp32(pallas_interpret, use_kernel):
    """int8 arena + per-token scales: attention output stays within
    quantization tolerance of the fp32 dense reference, kernel and
    reference dispatch agreeing with each other much tighter."""
    q, ck, cv, ak, av, tables, _ = _paged_inputs(seed=5)
    pos = jnp.asarray([9, 33, 120], jnp.int32)
    kq, ks = quantize_kv(ak)
    vq, vs = quantize_kv(av)
    out = paged_decode_attention(q, kq, vq, tables, pos, k_scale=ks,
                                 v_scale=vs, use_kernel=use_kernel)
    ref = decode_attention_reference(q, ck, cv, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=0.05, rtol=0.05)
    # Kernel vs reference on identical quantized inputs: tight.
    other = paged_decode_attention(q, kq, vq, tables, pos, k_scale=ks,
                                   v_scale=vs, use_kernel=not use_kernel)
    np.testing.assert_allclose(np.asarray(out), np.asarray(other),
                               atol=2e-6)


# ------------------------------------ live blocks only (the visit schedule)

def _walk_case(hq, hkv, kv_dtype, layered, b=5, seed=11):
    """Kernel arguments for one arena form: (q, args, kwargs). bf16 or
    int8 storage; a slab, or the middle layer of a whole arena whose
    other layers hold other bytes."""
    q, _, _, ak, av, tables, _ = _paged_inputs(
        b=b, hq=hq, hkv=hkv, seed=seed, dtype=jnp.bfloat16)
    kw = {}
    if kv_dtype == "int8":
        ak, kw["k_scale"] = quantize_kv(ak)
        av, kw["v_scale"] = quantize_kv(av)
    if layered:
        ak, av = jnp.stack([av, ak, av]), jnp.stack([ak, av, ak])
        kw = {n: jnp.stack([a * 2, a, a * 3]) for n, a in kw.items()}
        kw["layer"] = jnp.int32(1)
    return q, (ak, av, tables), kw


def _assert_walks_rows(out, walk, per=None):
    """A visit of ONE block is the walk's arithmetic: the same bits. A
    wider visit folds its blocks in one softmax chain, the same float32
    arithmetic in another order of rounding: a bf16 output may land one
    ulp (2^-8 of its size) off the walk's. ``per`` None: the arena's own
    rule, which gives a toy arena the cap."""
    out, walk = np.asarray(out, np.float32), np.asarray(walk, np.float32)
    if per == 1:
        np.testing.assert_array_equal(out, walk)
    else:
        np.testing.assert_allclose(out, walk, rtol=2.0 ** -7, atol=1e-6)


# The served models' head layouts: Mistral's GQA 32/8, OLMoE's MHA 16/16.
@pytest.mark.parametrize("hq,hkv", [(32, 8), (16, 16)])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("layered", [False, True])
def test_live_block_visits_equal_every_entry_walk(pallas_interpret, hq, hkv,
                                                  kv_dtype, layered):
    """The kernel visits only the blocks a query may see; the walk it
    replaced (kept in ``chip_smoke.py``) stepped over every table entry
    and skipped the dead ones. Same blocks, one softmax chain a visit
    where the walk has one a block: the walk's rows within a bf16 ulp,
    and both within tolerance of the XLA reference.
    Positions: a block's last row, the next block's first, the table's
    last row, one past the table (clamped to it), and 0."""
    from chip_smoke import walk_every_entry

    q, args, kw = _walk_case(hq, hkv, kv_dtype, layered)
    bs, nb = 32, 4
    pos = jnp.asarray([bs - 1, bs, nb * bs - 1, nb * bs + 40, 0], jnp.int32)
    out = paged_decode_attention(q, *args, pos, use_kernel=True, **kw)
    _assert_walks_rows(out, walk_every_entry(q, *args, pos, **kw))
    ref = paged_attention_reference(q, *args, pos, **kw)
    np.testing.assert_allclose(np.asarray(out, jnp.float32),
                               np.asarray(ref, jnp.float32), atol=3e-2)


@pytest.mark.parametrize("case", ["all_freed", "all_full", "interleaved",
                                  "omitted"])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_limits_decide_which_slots_are_visited(pallas_interpret, kv_dtype,
                                               case):
    """``limits`` 0 marks a freed slot: never visited, its row exactly
    zero, whatever the garbage block holds (NaN here: nothing may read
    it). Every live row is the walk's (within a bf16 ulp: one chain a
    visit); without ``limits`` every slot is live."""
    from chip_smoke import walk_every_entry

    q, (ak, av, tables), kw = _walk_case(32, 8, kv_dtype, layered=False)
    if kv_dtype == "bf16":
        ak, av = (a.at[GARBAGE_BLOCK].set(jnp.nan) for a in (ak, av))
    else:
        kw = {n: a.at[GARBAGE_BLOCK].set(jnp.nan) for n, a in kw.items()}
    b, s_max = q.shape[0], 128
    pos = jnp.asarray([5, 40, s_max - 1, 64, 100], jnp.int32)
    live = {"all_freed": np.zeros(b, bool), "all_full": np.ones(b, bool),
            "interleaved": np.arange(b) % 2 == 0,
            "omitted": np.ones(b, bool)}[case]
    if case == "all_full":
        pos = jnp.full(b, s_max - 1, jnp.int32)
    # A freed slot as the engine leaves it: every table entry the
    # garbage block, position 0.
    tables = jnp.where(live[:, None], tables, GARBAGE_BLOCK)
    pos = jnp.where(live, pos, 0)
    limits = None if case == "omitted" else jnp.asarray(live * s_max,
                                                        jnp.int32)
    out = np.asarray(paged_decode_attention(
        q, ak, av, tables, pos, limits=limits, use_kernel=True, **kw),
        np.float32)
    want = np.asarray(walk_every_entry(q, ak, av, tables, pos, **kw),
                      np.float32)
    assert np.isfinite(out).all()
    _assert_walks_rows(out[live], want[live])
    np.testing.assert_array_equal(out[~live], 0.0)
    if case == "omitted":
        np.testing.assert_array_equal(out, np.asarray(paged_decode_attention(
            q, ak, av, tables, pos, limits=jnp.full(b, s_max, jnp.int32),
            use_kernel=True, **kw), np.float32))


def _expected_visits(tables, pos, limits, bs, per, window=0):
    """The schedule in plain Python: [(slot, first logical block, [arena
    block of each LIVE sub-block])], runs of ``per`` from each slot's
    first live block."""
    tables, nb = np.asarray(tables), tables.shape[1]
    want = []
    for s, (p, lim) in enumerate(zip(pos, limits)):
        if not lim:
            continue
        first = max(p - window + 1, 0) // bs if window else 0
        last = p // bs if window else min(p // bs, nb - 1)
        for j in range(first, last + 1, per):
            run = range(j, min(j + per, last + 1))
            want.append((s, j, [int(tables[s, b % nb]) for b in run]))
    return want


def _check_schedule(visits, want, tables, per):
    """``visits`` lists ``want`` and nothing else; a dead sub-block names
    the table entry its operand read the step before (nothing to fetch),
    and every entry past the end (one at least, which the pipeline works
    out and never runs) still names a slot and a table entry."""
    slot, block, where, count = (np.asarray(a) for a in visits)
    n = int(count[0])
    assert n == len(want) < slot.size
    assert slot.shape == block.shape == (where.size // per,)
    assert (slot < tables.shape[0]).all() and (slot >= 0).all()
    assert (where < tables.size).all() and (where >= 0).all()
    where = where.reshape(per, -1)
    phys = np.asarray(tables).reshape(-1)[where]
    for v, (s, j, blocks) in enumerate(want):
        assert (slot[v], block[v]) == (s, j)
        assert phys[:len(blocks), v].tolist() == blocks
        if v:
            np.testing.assert_array_equal(where[len(blocks):, v],
                                          where[len(blocks):, v - 1])


@pytest.mark.parametrize("per", [1, 2, 3, 4])
def test_visit_schedule_lists_live_blocks_slot_major(pallas_interpret, per):
    """``paged_visits``: slot i contributes blocks 0..pos // bs (clamped
    to the table) in runs of ``per`` and a freed slot none; a schedule
    made ahead of the call, as the engine makes it before its layer
    loop, gives the same bits as one made inside it, whatever a step
    covers."""
    q, (ak, av, tables), _ = _walk_case(4, 2, "bf16", layered=False)
    pos = [31, 32, 500, 7, 127]
    limits = [128, 128, 128, 0, 128]
    visits = paged_visits(tables, jnp.asarray(pos, jnp.int32),
                          jnp.asarray(limits, jnp.int32), block_size=32,
                          per_visit=per)
    want = _expected_visits(tables, pos, limits, 32, per)
    assert sum(len(blocks) for _, _, blocks in want) == 1 + 2 + 4 + 4
    _check_schedule(visits, want, tables, per)
    pos, limits = jnp.asarray(pos, jnp.int32), jnp.asarray(limits, jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(paged_decode_attention(
            q, ak, av, tables, pos, visits=visits, use_kernel=True)),
        np.asarray(paged_decode_attention(
            q, ak, av, tables, pos, limits=limits, use_kernel=True)))


@pytest.mark.parametrize("per", [2, 4])
def test_visit_schedule_of_a_short_slot_is_one_step_of_one_block(per):
    """A slot with one live block makes one visit whose other
    sub-blocks are dead, whatever its neighbours hold: sixteen such
    slots are sixteen steps, and no operand but the first ever changes
    its block."""
    tables = jnp.arange(1, 16 * 4 + 1, dtype=jnp.int32).reshape(16, 4)
    pos = jnp.arange(16, dtype=jnp.int32)        # every slot in block 0
    slot, block, where, count = (np.asarray(a) for a in paged_visits(
        tables, pos, block_size=32, per_visit=per))
    assert int(count[0]) == 16 and slot[:16].tolist() == list(range(16))
    assert not block[:16].any()
    where = where.reshape(per, -1)[:, :16]
    np.testing.assert_array_equal(where[0], np.arange(16) * 4)
    assert (where[1:] == where[1:, :1]).all()


def _arena(hkv, dtype, bs=64, d=128, layers=None):
    shape = (9, hkv, bs, d) if layers is None else (layers, 9, hkv, bs, d)
    return jax.ShapeDtypeStruct(shape, dtype)


# The seven served arenas, blocks of 64 tokens in bf16: (kv heads, head
# size) -> a block's K and V bytes -> blocks a grid step.
@pytest.mark.parametrize("model,hkv,d,want", [
    ("ZAYA1-8B", 2, 128, 16),               # 64 KB
    ("Qwen3-Next", 2, 256, 8),              # 131 KB
    ("Mistral-7B", 8, 128, 4),              # 262 KB
    ("Granite-4.0-H", 8, 128, 4),
    ("Trinity", 8, 128, 4),
    ("OLMoE", 16, 128, 2),                  # 524 KB
    ("EvaByte", 32, 128, 1),                # 1 MB
])
def test_blocks_a_visit_of_each_served_arena(model, hkv, d, want):
    """``visit_blocks``: about ``VISIT_BYTES`` of ONE slot's K and V a
    step whatever a block weighs, from the arena's shape alone; a slab
    and the whole arena agree."""
    assert visit_blocks(_arena(hkv, jnp.bfloat16, d=d)) == want
    assert visit_blocks(_arena(hkv, jnp.bfloat16, d=d, layers=5)) == want
    block = 2 * hkv * 64 * d * 2
    assert want * block <= VISIT_BYTES < (want + 1) * block


def test_blocks_a_visit_follow_the_blocks_bytes():
    """The rule outside the served shapes: an int8 arena's block weighs
    half and takes twice the blocks, a block over the budget one, and no
    shape more than ``MAX_VISIT_BLOCKS`` (the widest step that was
    timed): a toy arena's 8 KB blocks would ask for 128."""
    assert VISIT_BYTES == 1 << 20 and MAX_VISIT_BLOCKS == 16
    assert visit_blocks(_arena(8, jnp.int8)) == 8
    assert visit_blocks(_arena(16, jnp.bfloat16, layers=12)) == 2
    assert visit_blocks(_arena(16, jnp.float32)) == 1
    assert visit_blocks(_arena(32, jnp.float32, bs=128)) == 1
    assert visit_blocks(_arena(2, jnp.int8)) == MAX_VISIT_BLOCKS
    assert visit_blocks(_arena(2, jnp.float32, bs=32, d=16)) == \
        MAX_VISIT_BLOCKS


# Live blocks a slot: 1, P-1, P, P+1, then an odd and an even count over
# two visits and more; ``block_size`` 32.
def _live_counts(per):
    return (1, max(per - 1, 1), per, per + 1, 2 * per + 1, 2 * per + 2)


def _positions_for(counts, bs=32):
    """A position inside each slot's ``count``-th block: its last row,
    its first, then rows in between."""
    rows = [bs - 1, 0, 5, 17, bs - 2, 9]
    return [(n - 1) * bs + rows[i % len(rows)] for i, n in enumerate(counts)]


def _visits_for(ak, tables, pos, per, limits=None, window=0):
    return paged_visits(tables, pos, limits, block_size=ak.shape[-2],
                        per_visit=per, window=window)


@pytest.mark.parametrize("per", [1, 2, 4])
@pytest.mark.parametrize("hq,hkv", [(32, 8), (16, 16)])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("layered", [False, True])
def test_visits_of_several_blocks_equal_every_entry_walk(
        pallas_interpret, hq, hkv, kv_dtype, layered, per):
    """A grid step covers up to ``per`` consecutive blocks of a slot and
    folds them in one softmax chain: the one-block walk's blocks, its
    rows within a bf16 ulp, whether a slot's live count is under, at or
    over a whole number of visits (a short visit's dead sub-blocks are
    masked by the same body)."""
    from chip_smoke import walk_every_entry

    counts = _live_counts(per)
    q, _, _, ak, av, tables, _ = _paged_inputs(
        b=len(counts), hq=hq, hkv=hkv, nb_slot=max(counts),
        seed=13, dtype=jnp.bfloat16)
    kw = {}
    if kv_dtype == "int8":
        ak, kw["k_scale"] = quantize_kv(ak)
        av, kw["v_scale"] = quantize_kv(av)
    if layered:
        ak, av = jnp.stack([av, ak, av]), jnp.stack([ak, av, ak])
        kw = {n: jnp.stack([a * 2, a, a * 3]) for n, a in kw.items()}
        kw["layer"] = jnp.int32(1)
    pos = jnp.asarray(_positions_for(counts), jnp.int32)
    out = paged_decode_attention(
        q, ak, av, tables, pos, use_kernel=True,
        visits=_visits_for(ak, tables, pos, per), **kw)
    _assert_walks_rows(out, walk_every_entry(q, ak, av, tables, pos, **kw),
                       per)
    ref = paged_attention_reference(q, ak, av, tables, pos, **kw)
    np.testing.assert_allclose(np.asarray(out, jnp.float32),
                               np.asarray(ref, jnp.float32), atol=3e-2)


@pytest.mark.parametrize("per", [2, 4])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("freed", ["odd", "even"])
def test_short_visits_between_freed_slots(pallas_interpret, kv_dtype, freed,
                                          per):
    """Live slots of every count interleaved with freed ones, the
    garbage block NaN: a freed slot has no visit and a zero row, a short
    last visit's dead sub-blocks are never read into the arithmetic, and
    every live row is the walk's within a bf16 ulp."""
    from chip_smoke import walk_every_entry

    counts = [n for n in _live_counts(per) for _ in (0, 1)]
    live = np.arange(len(counts)) % 2 == (freed == "odd")
    q, _, _, ak, av, tables, _ = _paged_inputs(
        b=len(counts), hq=32, hkv=8, nb_slot=max(counts), seed=17,
        dtype=jnp.bfloat16)
    kw = {}
    if kv_dtype == "int8":
        ak, kw["k_scale"] = quantize_kv(ak)
        av, kw["v_scale"] = quantize_kv(av)
        kw = {n: a.at[GARBAGE_BLOCK].set(jnp.nan) for n, a in kw.items()}
    else:
        ak, av = (a.at[GARBAGE_BLOCK].set(jnp.nan) for a in (ak, av))
    # A live slot's dead tail repeats its last live block (`_table_row`);
    # a freed slot's row is the garbage block throughout, position 0.
    t = np.asarray(tables).copy()
    for i, n in enumerate(counts):
        t[i, n:] = t[i, n - 1]
    tables = jnp.where(live[:, None], jnp.asarray(t), GARBAGE_BLOCK)
    pos = jnp.where(live, jnp.asarray(_positions_for(counts), jnp.int32), 0)
    limits = jnp.asarray(live * max(counts) * 32, jnp.int32)
    out = np.asarray(paged_decode_attention(
        q, ak, av, tables, pos, use_kernel=True,
        visits=_visits_for(ak, tables, pos, per, limits), **kw), np.float32)
    want = np.asarray(walk_every_entry(q, ak, av, tables, pos, **kw),
                      np.float32)
    assert np.isfinite(out).all()
    _assert_walks_rows(out[live], want[live], per)
    np.testing.assert_array_equal(out[~live], 0.0)


# A ring of 6 entries of 32 under a window of 4 blocks and 7 keys: a
# query sees 5 blocks, or 6 where the window's first key lies deep
# enough in its block.
_RING_WINDOW, _RING = 4 * 32 + 7, 6


def _ring_positions(per):
    """Query positions over a ring: under the window (first live block
    0); first live block 1, P+1 and 2P+1 (not multiples of P for P > 1)
    before the ring's first wrap, at it and several wraps on; six live
    blocks and five."""
    bs, w = 32, _RING_WINDOW
    # First live block f <=> pos - w + 1 in [f * bs, (f + 1) * bs).
    firsts = [1, per + 1, 2 * per + 1, 7 * _RING + per + 1]
    return [5, w - 1] + [f * bs + w - 1 + r
                         for f, r in zip(firsts, (0, 30, 12, 31))]


@pytest.mark.parametrize("per", [2, 4])
@pytest.mark.parametrize("hq,hkv", [(32, 8), (16, 16)])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("layered", [False, True])
def test_ring_visits_equal_the_walk_from_the_first_live_block(
        pallas_interpret, hq, hkv, kv_dtype, layered, per):
    """Over a ring a slot's visits start at its FIRST live block, which
    no multiple of ``per`` need be, and a visit's blocks lie in ring
    entries ``block % nb``, so a visit may straddle the ring's end:
    still the one-block walk's rows (within a bf16 ulp), before the
    first wrap and after, with a window that is no multiple of the
    block."""
    from chip_smoke import walk_every_entry

    pos = _ring_positions(per)
    q, _, _, ak, av, tables, _ = _paged_inputs(
        b=len(pos), hq=hq, hkv=hkv, nb_slot=_RING, seed=19,
        dtype=jnp.bfloat16)
    kw = {"window": _RING_WINDOW}
    if kv_dtype == "int8":
        ak, kw["k_scale"] = quantize_kv(ak)
        av, kw["v_scale"] = quantize_kv(av)
    if layered:
        ak, av = jnp.stack([av, ak, av]), jnp.stack([ak, av, ak])
        kw.update({n: jnp.stack([kw[n] * 2, kw[n], kw[n] * 3])
                   for n in ("k_scale", "v_scale") if n in kw})
        kw["layer"] = jnp.int32(1)
    firsts = [max(p - _RING_WINDOW + 1, 0) // 32 for p in pos]
    assert any(f % per for f in firsts)
    assert any(p // 32 < _RING for p in pos[2:])        # before a wrap
    assert any(f >= _RING for f in firsts)              # and after one
    pos = jnp.asarray(pos, jnp.int32)
    out = paged_decode_attention(
        q, ak, av, tables, pos, use_kernel=True,
        visits=_visits_for(ak, tables, pos, per, window=_RING_WINDOW), **kw)
    _assert_walks_rows(out, walk_every_entry(q, ak, av, tables, pos, **kw),
                       per)
    ref = paged_attention_reference(q, ak, av, tables, pos, **kw)
    np.testing.assert_allclose(np.asarray(out, jnp.float32),
                               np.asarray(ref, jnp.float32), atol=3e-2)


@pytest.mark.parametrize("per", [1, 2, 3, 4])
def test_ring_visit_schedule_runs_from_the_first_live_block(per):
    pos = _ring_positions(per) + [40]
    limits = [1] * (len(pos) - 1) + [0]                 # the last freed
    tables = jnp.arange(1, len(pos) * _RING + 1, dtype=jnp.int32).reshape(
        len(pos), _RING)
    visits = paged_visits(tables, jnp.asarray(pos, jnp.int32),
                          jnp.asarray(limits, jnp.int32), block_size=32,
                          per_visit=per, window=_RING_WINDOW)
    want = _expected_visits(tables, pos, limits, 32, per, _RING_WINDOW)
    assert {len(b) for _, _, b in want} >= {1, min(per, 2)}
    _check_schedule(visits, want, tables, per)


# ------------------------------------------ 8 and 16 blocks a grid step

# Contexts (keys a slot holds) around a visit of ``per`` blocks of 32: a
# slot whose only visit is short (1, bs - 1, bs keys), one key short of
# a full visit, a full one, one key past it, and two visits and a bit;
# a freed slot stands between two live ones.
def _wide_contexts(per, bs=32):
    return [1, bs - 1, bs, per * bs - 1, 0, per * bs, per * bs + 1,
            2 * per * bs + 5 * bs + 3]


@pytest.mark.parametrize("per", [8, 16])
@pytest.mark.parametrize("hq,hkv,d", [(8, 2, 128), (16, 2, 256)])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_wide_visits_match_the_reference(pallas_interpret, hq, hkv, d,
                                         kv_dtype, per):
    """The two arenas of 2 kv heads (ZAYA1's query group of 4 at head
    size 128, Qwen3-Next's 8 at 256) at the 8 and 16 blocks a step
    their bytes ask for: every context around a visit's boundary
    against the XLA reference, a freed slot's row zero, the garbage
    block NaN (a dead sub-block's operand adds nothing), and the
    one-block walk's rows within a bf16 ulp."""
    from chip_smoke import walk_every_entry

    contexts = _wide_contexts(per)
    nb = -(-max(contexts) // 32)
    q, _, _, ak, av, tables, _ = _paged_inputs(
        b=len(contexts), hq=hq, hkv=hkv, d=d, nb_slot=nb, seed=23,
        dtype=jnp.bfloat16)
    kw = {}
    if kv_dtype == "int8":
        ak, kw["k_scale"] = quantize_kv(ak)
        av, kw["v_scale"] = quantize_kv(av)
        kw = {n: a.at[GARBAGE_BLOCK].set(jnp.nan) for n, a in kw.items()}
    else:
        ak, av = (a.at[GARBAGE_BLOCK].set(jnp.nan) for a in (ak, av))
    live = np.asarray(contexts) > 0
    tables = jnp.where(live[:, None], tables, GARBAGE_BLOCK)
    pos = jnp.asarray([max(n - 1, 0) for n in contexts], jnp.int32)
    limits = jnp.asarray(live * nb * 32, jnp.int32)
    visits = _visits_for(ak, tables, pos, per, limits)
    assert int(visits[3][0]) == sum(-(-n // (per * 32)) for n in contexts)
    out = np.asarray(paged_decode_attention(
        q, ak, av, tables, pos, use_kernel=True, visits=visits, **kw),
        np.float32)
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out[~live], 0.0)
    # The reference attends a freed slot's garbage; nothing reads it.
    clean = {n: a.at[GARBAGE_BLOCK].set(0.0) for n, a in kw.items()}
    ref = np.asarray(paged_attention_reference(
        q, jnp.nan_to_num(ak), jnp.nan_to_num(av), tables, pos, **clean),
        np.float32)
    np.testing.assert_allclose(out[live], ref[live], atol=3e-2)
    walk = np.asarray(walk_every_entry(q, ak, av, tables, pos, **kw),
                      np.float32)
    _assert_walks_rows(out[live], walk[live], per)


@pytest.mark.parametrize("per", [8, 16])
@pytest.mark.parametrize("hq,hkv,d", [(8, 2, 128), (16, 2, 256)])
def test_wide_visits_over_a_ring(pallas_interpret, hq, hkv, d, per):
    """A ring of 21 entries under a window of 19 blocks and 7 keys: a
    query sees 20 or 21 blocks from a first live block that is no
    multiple of ``per``, so a wide visit straddles the ring's end and
    the slot's last is short, before the first wrap and after."""
    ring, window = 21, 19 * 32 + 7
    pos = [5, window - 1, 32 + window - 1, 9 * 32 + window + 11,
           (3 * ring + 5) * 32 + window + 30]
    q, _, _, ak, av, tables, _ = _paged_inputs(
        b=len(pos), hq=hq, hkv=hkv, d=d, nb_slot=ring, seed=29,
        dtype=jnp.bfloat16)
    firsts = [max(p - window + 1, 0) // 32 for p in pos]
    assert any(f % per for f in firsts) and any(f >= ring for f in firsts)
    pos = jnp.asarray(pos, jnp.int32)
    out = paged_decode_attention(
        q, ak, av, tables, pos, use_kernel=True, window=window,
        visits=_visits_for(ak, tables, pos, per, window=window))
    ref = paged_attention_reference(q, ak, av, tables, pos, window=window)
    np.testing.assert_allclose(np.asarray(out, jnp.float32),
                               np.asarray(ref, jnp.float32), atol=3e-2)


@pytest.mark.parametrize("window", [0, 19 * 32 + 7])
@pytest.mark.parametrize("per", [8, 16])
def test_wide_visit_schedule(per, window):
    """``paged_visits`` at 8 and 16 blocks a step over tables of 40
    entries: the count of visits, each visit's live blocks in order,
    every dead sub-block naming the entry its operand read the step
    before (so nothing is fetched for it), a freed slot none."""
    nb = 40
    lives = [1, per - 1, per, per + 1, 0, 2 * per + 1, nb, 1]
    pos = [max(n * 32 - 7, 0) for n in lives]
    limits = [int(n > 0) for n in lives]
    if window:
        pos = [p + 3 * nb * 32 for p in pos]    # some wraps in
    tables = jnp.arange(1, len(pos) * nb + 1, dtype=jnp.int32).reshape(
        len(pos), nb)
    visits = paged_visits(tables, jnp.asarray(pos, jnp.int32),
                          jnp.asarray(limits, jnp.int32), block_size=32,
                          per_visit=per, window=window)
    want = _expected_visits(tables, pos, limits, 32, per, window)
    if not window:
        assert len(want) == sum(-(-n // per) for n in lives)
    # A ring's slots all hold the window's 20 or 21 blocks.
    assert {len(b) for _, _, b in want} >= ({per, 20 % per} if window
                                            else {1, per})
    _check_schedule(visits, want, tables, per)


# ------------------------------------- whole arena: layer index, in-place write

@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("use_kernel", [False, True])
def test_layer_indexed_read_equals_slab_call(pallas_interpret, use_kernel,
                                             kv_dtype, layer):
    """Reading layer ``li`` of the whole arena is bit for bit the call
    on that layer's slab: the layer only steers the block fetch."""
    q, _, _, ak, av, tables, _ = _paged_inputs(seed=7, dtype=jnp.bfloat16)
    pos = jnp.asarray([0, 63, 127], jnp.int32)
    slabs = {"k_scale": None, "v_scale": None}
    if kv_dtype == "int8":
        ak, slabs["k_scale"] = quantize_kv(ak)
        av, slabs["v_scale"] = quantize_kv(av)

    def arena(a, other):
        """``a`` at ``layer``, ``other``'s bytes in the two layers
        beside it (a read of the wrong layer cannot pass)."""
        if a is None:
            return None
        return jnp.stack([a if i == layer else other for i in range(3)])

    whole = paged_decode_attention(
        q, arena(ak, av), arena(av, ak), tables, pos,
        layer=jnp.int32(layer), use_kernel=use_kernel,
        k_scale=arena(slabs["k_scale"], slabs["v_scale"]),
        v_scale=arena(slabs["v_scale"], slabs["k_scale"]))
    slab = paged_decode_attention(q, ak, av, tables, pos,
                                  use_kernel=use_kernel, **slabs)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(slab))


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_kernel_under_layer_scan(pallas_interpret, kv_dtype):
    """The engine's calling convention: inside ``jit(lax.scan)`` over
    layers, the whole arena in the carry, the layer index a traced
    scalar of the carry, the write aliased onto the carry and the read
    after it. Each layer's output is the XLA reference's on that
    layer's slab with the same rows scattered in, and the arena that
    comes out holds them."""
    from ray_tpu.models.continuous_batching import _scatter_arena

    q, _, _, ak, av, tables, _ = _paged_inputs(seed=5, dtype=jnp.bfloat16)
    pos = jnp.asarray([0, 63, 127], jnp.int32)
    bs = ak.shape[2]
    block_idx = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)
    offset = (pos % bs)[:, None]
    layers = 3
    # Layer i holds bytes of its own, so a read or a write of the wrong
    # layer cannot pass.
    arenas = [jnp.stack([jnp.roll(a, i, axis=0) for i in range(layers)])
              for a in (ak, av)]
    rows = jax.random.normal(
        jax.random.PRNGKey(9), (layers, 2, q.shape[0], 1) + ak.shape[1:2]
        + ak.shape[3:], jnp.float32).astype(jnp.bfloat16)
    news = [rows[:, 0], rows[:, 1]]
    if kv_dtype == "int8":
        # (k, v) -> (k, v, k_scale, v_scale), the arena's own order.
        arenas, news = (
            [x[i] for i in (0, 1) for x in map(quantize_kv, pair)]
            for pair in (arenas, news))

    @jax.jit
    def run(arenas, news):
        def body(carry, new):
            arenas, li = carry
            arenas = tuple(paged_kv_write(a, n, li, block_idx, offset)
                           for a, n in zip(arenas, new))
            out = paged_decode_attention(
                q, arenas[0], arenas[1], tables, pos, layer=li,
                use_kernel=True,
                **(dict(k_scale=arenas[2], v_scale=arenas[3])
                   if len(arenas) == 4 else {}))
            return (arenas, li + 1), out
        (arenas, _), outs = jax.lax.scan(
            body, (tuple(arenas), jnp.int32(0)), tuple(news))
        return arenas, outs

    got_arenas, outs = run(arenas, news)
    for li in range(layers):
        slabs = [_scatter_arena(a[li], n[li][:, 0], block_idx[:, 0],
                                offset[:, 0])
                 for a, n in zip(arenas, news)]
        for got, want in zip(got_arenas, slabs):
            np.testing.assert_array_equal(np.asarray(got[li]),
                                          np.asarray(want))
        want = paged_attention_reference(
            q, slabs[0], slabs[1], tables, pos,
            **(dict(k_scale=slabs[2], v_scale=slabs[3])
               if len(slabs) == 4 else {}))
        np.testing.assert_allclose(
            np.asarray(outs[li], np.float32), np.asarray(want, np.float32),
            atol=2e-2)


def test_layer_argument_must_match_arena_rank():
    q, _, _, ak, av, tables, _ = _paged_inputs()
    pos = jnp.asarray([0, 1, 2], jnp.int32)
    with pytest.raises(ValueError, match="layer"):
        paged_decode_attention(q, ak[None], av[None], tables, pos)
    with pytest.raises(ValueError, match="layer"):
        paged_decode_attention(q, ak, av, tables, pos, layer=0)


def _write_tile(kind):
    """Rows of the sublane tile ``paged_kv_write`` moves in an arena of
    this kind when its blocks hold more than one (the scale sidecar
    never takes the tile path; 16 only places its windows)."""
    return 32 if kind == "int8" else 16


def _write_case(hkv, kind, width, bs=32, d=16, slots=9, nb_slot=4, seed=0):
    """A 3-layer arena with live bytes everywhere, and one write: slot
    i's ``width`` consecutive tokens start at ``starts[i]`` of its own
    blocks. Slot 0 starts at row 0 of a block, slot 1 ends on the last
    row of one, slot 2 straddles a block boundary when ``width`` > 1,
    slot 6 a TILE boundary inside a block, slots 7 and 8 start in a
    block's middle tiles (with 0, 1 and 4: a start in every tile of a
    64-row block, four of bf16 and two of int8), and slots 3 and 5 are
    freed (every row aims at the garbage block)."""
    rng = np.random.default_rng(seed)
    nb = slots * nb_slot + 1
    trailing = () if kind == "scale" else (d,)
    shape = (3, nb, hkv, bs) + trailing
    if kind == "int8":
        arena = rng.integers(-127, 128, shape).astype(np.int8)
        new = rng.integers(-127, 128, (slots, width, hkv) + trailing
                           ).astype(np.int8)
    else:
        dtype = jnp.float32 if kind == "scale" else jnp.bfloat16
        arena = jnp.asarray(rng.standard_normal(shape), dtype)
        new = jnp.asarray(
            rng.standard_normal((slots, width, hkv) + trailing), dtype)
    tables = rng.permutation(np.arange(1, nb)).reshape(slots, nb_slot)
    starts = np.array([0, bs - width, bs - 1, 5, bs + 3, 40,
                       bs + _write_tile(kind) - 2, 2 * bs + bs // 2 + 3,
                       bs + 17])
    pos = starts[:, None] + np.arange(width)[None, :]
    block_idx = np.take_along_axis(tables, pos // bs, axis=1)
    block_idx[[3, 5]] = GARBAGE_BLOCK
    return (jnp.asarray(arena), jnp.asarray(new),
            jnp.asarray(block_idx, jnp.int32),
            jnp.asarray(pos % bs, jnp.int32))


# kv heads of the MHA 16/16 and the GQA 32/8 shapes. At ``bs`` 64 a
# block is four tiles of bf16 and two of int8; at 32, two of bf16 and
# one of int8 (the whole block, as the scale rows always). "wide" is the
# narrowest window that can span three tiles: the whole block again.
@pytest.mark.parametrize("hkv", [16, 8])
@pytest.mark.parametrize("kind", ["bf16", "int8", "scale"])
@pytest.mark.parametrize("bs,width", [(32, 1), (32, 4), (64, 1), (64, 4),
                                      (64, "wide")])
def test_write_kernel_equals_xla_scatter(pallas_interpret, hkv, kind, bs,
                                         width):
    """``paged_kv_write`` stores byte for byte what the XLA scatter on
    the layer's slab stores (K/V rows of a bf16 or an int8 arena, and
    the fp32 scale rows through the same kernel): a tick's one token a
    slot and a verify window's four, moved a tile or a block a step;
    every other layer, and every tile a token did not land in,
    untouched. The garbage block is compared nowhere: it holds whichever
    freed row the scatter or the kernel happened to keep, and nothing
    reads it."""
    from ray_tpu.models.continuous_batching import _scatter_arena

    if width == "wide":
        width = _write_tile(kind) + 2
    arena, new, block_idx, offset = _write_case(hkv, kind, width, bs=bs)
    li = 1
    got = paged_kv_write(arena, new, jnp.int32(li), block_idx, offset)
    want = _scatter_arena(arena[li], new.reshape(-1, *new.shape[2:]),
                          block_idx.reshape(-1), offset.reshape(-1))
    assert got.dtype == arena.dtype and got.shape == arena.shape
    live = np.arange(arena.shape[1]) != GARBAGE_BLOCK
    np.testing.assert_array_equal(np.asarray(got[li])[live],
                                  np.asarray(want)[live])
    for other in (0, 2):
        np.testing.assert_array_equal(np.asarray(got[other]),
                                      np.asarray(arena[other]))
    # The rows did land (the comparison above is not two no-ops).
    for b in (2, 6):
        j = width - 1
        np.testing.assert_array_equal(
            np.asarray(got[li, block_idx[b, j], :, offset[b, j]]),
            np.asarray(new[b, j]))


@pytest.mark.parametrize("kind,bs,width,rows", [
    ("bf16", 64, 1, 16), ("bf16", 64, 17, 16), ("bf16", 64, 18, 64),
    ("bf16", 32, 4, 16), ("bf16", 16, 1, 16), ("bf16", 8, 1, 8),
    ("int8", 64, 4, 32), ("int8", 64, 34, 64), ("int8", 32, 1, 32),
    ("scale", 64, 1, 64)])
def test_write_kernel_states_the_bytes_it_moves(kind, bs, width, rows):
    """The traced call's ``cost_estimate`` counts a tile in and out a
    grid step on the tile path (a trailing axis, a block of more than
    one whole tile, a window of at most two) and the block's otherwise."""
    hkv, d, slots = 8, 16, 9
    arena, new, block_idx, offset = _write_case(hkv, kind, width, bs=bs,
                                                slots=slots, nb_slot=8)
    jaxpr = jax.make_jaxpr(paged_kv_write)(arena, new, jnp.int32(0),
                                           block_idx, offset)
    call, = (e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call")
    row_bytes = arena.dtype.itemsize * (1 if kind == "scale" else d)
    assert call.params["cost_estimate"].bytes_accessed == (
        2 * slots * min(width, 2) * hkv * rows * row_bytes)


@pytest.mark.parametrize("bs", [32, 64])
def test_write_kernel_window_second_block_is_garbage(pallas_interpret, bs):
    """A verify window that overruns its slot's reservation: the tokens
    before the boundary land in the slot's last block, the ones past it
    in the garbage block, and no other block changes."""
    arena, new, block_idx, offset = _write_case(8, "bf16", 4, bs=bs)
    block_idx = block_idx.at[2, 1:].set(GARBAGE_BLOCK)  # slot 2 straddles
    got = paged_kv_write(arena, new, jnp.int32(0), block_idx, offset)
    np.testing.assert_array_equal(
        np.asarray(got[0, block_idx[2, 0], :, offset[2, 0]]),
        np.asarray(new[2, 0]))
    touched = np.unique(np.asarray(block_idx))
    rest = np.setdiff1d(np.arange(arena.shape[1]), touched)
    np.testing.assert_array_equal(np.asarray(got[0])[rest],
                                  np.asarray(arena[0])[rest])
    # The slot's last block keeps every byte but the one row.
    kept = np.array(got[0, block_idx[2, 0]])
    kept[:, offset[2, 0]] = np.asarray(arena[0, block_idx[2, 0], :,
                                             offset[2, 0]])
    np.testing.assert_array_equal(kept,
                                  np.asarray(arena[0, block_idx[2, 0]]))
    with pytest.raises(ValueError, match="two blocks"):
        paged_kv_write(arena, jnp.zeros((1, bs + 2, 8, 16), jnp.bfloat16),
                       jnp.int32(0), jnp.zeros((1, bs + 2), jnp.int32),
                       jnp.zeros((1, bs + 2), jnp.int32))


def test_paged_cache_create_dtypes():
    from ray_tpu.models import llama

    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32)
    dense = PagedKVCache.create(cfg, num_blocks=9, block_size=16)
    assert not dense.quantized and dense.k_scale is None
    assert dense.k.shape[1:4] == (9, cfg.num_kv_heads, 16)
    q8 = PagedKVCache.create(cfg, num_blocks=9, block_size=16,
                             kv_dtype="int8")
    assert q8.quantized and q8.k.dtype == jnp.int8
    assert q8.k_scale.shape == q8.k.shape[:-1]
    assert q8.token_bytes() < dense.token_bytes()
    with pytest.raises(ValueError):
        resolve_kv_dtype("fp4")


# ------------------------------------------------------- block allocator

def test_allocator_reuse_after_release():
    """Freed blocks return to the pool and are handed out again;
    all-or-nothing alloc leaves the pool untouched on failure."""
    a = BlockAllocator(num_blocks=8)            # 7 usable (0 reserved)
    first = a.alloc(4)
    assert len(first) == 4 and GARBAGE_BLOCK not in first
    second = a.alloc(3)
    assert a.free_count == 0 and a.used_count == 7
    assert a.alloc(1) is None                    # exhausted: no partial
    a.free(first)
    assert a.free_count == 4
    again = a.alloc(4)
    assert sorted(again) == sorted(first), "freed blocks not reused"
    assert a.alloc(1) is None
    a.free(second)
    a.free(again)
    assert a.free_count == 7 and a.used_count == 0


def test_allocator_zero_and_param_validation():
    a = BlockAllocator(num_blocks=4)
    assert a.alloc(0) == []            # must NOT drain the free list
    assert a.free_count == 3
    from ray_tpu.models.sampling import SamplingParams
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(temperature=0.7, top_p=0.0)
    with pytest.raises(ValueError, match="temperature"):
        SamplingParams(temperature=-1.0)


def test_allocator_rejects_bad_frees():
    a = BlockAllocator(num_blocks=4)
    got = a.alloc(2)
    with pytest.raises(ValueError):
        a.free([GARBAGE_BLOCK])
    with pytest.raises(ValueError):
        a.free([99])
    a.free(got)
    with pytest.raises(ValueError):
        a.free(got)                              # double free


def test_applicability_predicate():
    assert paged_applicable(64, 128, 16, 16)
    assert paged_applicable(32, 128, 32, 8)
    assert not paged_applicable(64, 96, 16, 16)   # d % 128
    assert not paged_applicable(64, 128, 16, 3)   # hq % hkv
    assert not paged_applicable(24, 128, 16, 16)  # block % 32
    # Auto mode on CPU routes to the reference (no kernel, no error).
    q, ck, cv, ak, av, tables, _ = _paged_inputs()
    pos = jnp.asarray([0, 1, 2], jnp.int32)
    out = paged_decode_attention(q, ak, av, tables, pos)  # auto
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(decode_attention_reference(q, ck, cv, pos)))
