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
from ray_tpu.ops.paged_decode_attention import (decode_attention_reference,
                                                paged_applicable,
                                                paged_attention_reference,
                                                paged_decode_attention,
                                                paged_kv_write, paged_visits)


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


# The served models' head layouts: Mistral's GQA 32/8, OLMoE's MHA 16/16.
@pytest.mark.parametrize("hq,hkv", [(32, 8), (16, 16)])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("layered", [False, True])
def test_live_block_visits_equal_every_entry_walk(pallas_interpret, hq, hkv,
                                                  kv_dtype, layered):
    """The kernel visits only the blocks a query may see; the walk it
    replaced (kept in ``chip_smoke.py``) stepped over every table entry
    and skipped the dead ones. Same blocks, same order, same arithmetic:
    the same bits, and both within tolerance of the XLA reference.
    Positions: a block's last row, the next block's first, the table's
    last row, one past the table (clamped to it), and 0."""
    from chip_smoke import walk_every_entry

    q, args, kw = _walk_case(hq, hkv, kv_dtype, layered)
    bs, nb = 32, 4
    pos = jnp.asarray([bs - 1, bs, nb * bs - 1, nb * bs + 40, 0], jnp.int32)
    out = paged_decode_attention(q, *args, pos, use_kernel=True, **kw)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(walk_every_entry(q, *args, pos, **kw)))
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
    it). Every live row is the walk's, bit for bit; without ``limits``
    every slot is live."""
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
    np.testing.assert_array_equal(out[live], want[live])
    np.testing.assert_array_equal(out[~live], 0.0)
    if case == "omitted":
        np.testing.assert_array_equal(out, np.asarray(paged_decode_attention(
            q, ak, av, tables, pos, limits=jnp.full(b, s_max, jnp.int32),
            use_kernel=True, **kw), np.float32))


def test_visit_schedule_lists_live_blocks_slot_major(pallas_interpret):
    """``paged_visits``: slot i contributes blocks 0..pos // bs (clamped
    to the table) and a freed slot none; a schedule made ahead of the
    call, as the engine makes it before its layer loop, gives the same
    bits as one made inside it."""
    q, (ak, av, tables), _ = _walk_case(4, 2, "bf16", layered=False)
    pos = jnp.asarray([31, 32, 500, 7, 127], jnp.int32)
    limits = jnp.asarray([128, 128, 128, 0, 128], jnp.int32)
    slot, block, count = paged_visits(tables, pos, limits, block_size=32)
    want = [(0, 0), (1, 0), (1, 1), (2, 0), (2, 1), (2, 2), (2, 3),
            (4, 0), (4, 1), (4, 2), (4, 3)]
    assert int(count[0]) == len(want)
    assert list(zip(np.asarray(slot)[:len(want)].tolist(),
                    np.asarray(block)[:len(want)].tolist())) == want
    # Past the end the lists still name a slot and a block of the table.
    assert slot.shape == block.shape == (5 * 4,)
    assert (np.asarray(slot) < 5).all() and (np.asarray(block) < 4).all()
    np.testing.assert_array_equal(
        np.asarray(paged_decode_attention(
            q, ak, av, tables, pos, visits=(slot, block, count),
            use_kernel=True)),
        np.asarray(paged_decode_attention(
            q, ak, av, tables, pos, limits=limits, use_kernel=True)))


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


def _write_case(hkv, kind, width, bs=32, d=16, slots=6, nb_slot=3, seed=0):
    """A 3-layer arena with live bytes everywhere, and one write: slot
    i's ``width`` consecutive tokens start at ``starts[i]`` of its own
    blocks. Slot 0 starts at row 0 of a block, slot 1 ends on the last
    row of one, slot 2 straddles a boundary when ``width`` > 1, and
    slots 3 and 5 are freed (every row aims at the garbage block)."""
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
    starts = np.array([0, bs - width, bs - 1, 5, bs + 3, 40])
    pos = starts[:, None] + np.arange(width)[None, :]
    block_idx = np.take_along_axis(tables, pos // bs, axis=1)
    block_idx[[3, 5]] = GARBAGE_BLOCK
    return (jnp.asarray(arena), jnp.asarray(new),
            jnp.asarray(block_idx, jnp.int32),
            jnp.asarray(pos % bs, jnp.int32))


# kv heads of the MHA 16/16 and the GQA 32/8 shapes.
@pytest.mark.parametrize("hkv", [16, 8])
@pytest.mark.parametrize("kind", ["bf16", "int8", "scale"])
@pytest.mark.parametrize("width", [1, 4])
def test_write_kernel_equals_xla_scatter(pallas_interpret, hkv, kind,
                                         width):
    """``paged_kv_write`` stores byte for byte what the XLA scatter on
    the layer's slab stores (K/V rows of a bf16 or an int8 arena, and
    the fp32 scale rows through the same kernel): a tick's one token a
    slot and a verify window's four; every other layer untouched. The
    garbage block is compared nowhere: it holds whichever freed row the
    scatter or the kernel happened to keep, and nothing reads it."""
    from ray_tpu.models.continuous_batching import _scatter_arena

    arena, new, block_idx, offset = _write_case(hkv, kind, width)
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
    b, j = 2, width - 1
    np.testing.assert_array_equal(
        np.asarray(got[li, block_idx[b, j], :, offset[b, j]]),
        np.asarray(new[b, j]))


def test_write_kernel_window_second_block_is_garbage(pallas_interpret):
    """A verify window that overruns its slot's reservation: the tokens
    before the boundary land in the slot's last block, the ones past it
    in the garbage block, and no other block changes."""
    arena, new, block_idx, offset = _write_case(8, "bf16", 4)
    block_idx = block_idx.at[2, 1:].set(GARBAGE_BLOCK)  # slot 2 straddles
    got = paged_kv_write(arena, new, jnp.int32(0), block_idx, offset)
    np.testing.assert_array_equal(
        np.asarray(got[0, block_idx[2, 0], :, offset[2, 0]]),
        np.asarray(new[2, 0]))
    touched = np.unique(np.asarray(block_idx))
    rest = np.setdiff1d(np.arange(arena.shape[1]), touched)
    np.testing.assert_array_equal(np.asarray(got[0])[rest],
                                  np.asarray(arena[0])[rest])
    with pytest.raises(ValueError, match="two blocks"):
        paged_kv_write(arena, jnp.zeros((1, 34, 8, 16), jnp.bfloat16),
                       jnp.int32(0), jnp.zeros((1, 34), jnp.int32),
                       jnp.zeros((1, 34), jnp.int32))


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
