"""Paged decode attention: block-table gather over a shared KV arena.

A decode tick attends ONE query token per slot against that slot's
cached prefix: pure HBM bandwidth. The cache is an ARENA of fixed-size
blocks (``[num_blocks, KVH, block_size, D]``) and each slot owns a small
BLOCK TABLE naming the blocks it has actually filled, so a tick reads
only live prefix blocks — a slot 40 tokens into a 512-token reservation
pays for one block, not 512 positions (vLLM paged-attention, on TPU:
block tables ride scalar prefetch so the BlockSpec ``index_map`` can
gather arena blocks by table lookup before the kernel body runs).
The arena is HEADS-MAJOR inside a block: the TPU lowering takes a block
only in whole trailing (sublane, lane) tiles, and ``(block_size, D)``
tiles exactly for bf16 and int8 at any head count, where a trailing
``(KVH, D)`` would pad small head counts up to a tile in HBM.

Two bandwidth levers stack:

* **Paging** — a 1-D grid over the VISITS a tick needs and no others:
  one step per run of consecutive logical blocks of one slot that hold
  a key the slot's query may see (as many as :func:`visit_blocks` says
  of the arena's shape: about 1 MB of K and V a step), whole blocks
  (every kv head), slot-major so a slot's running softmax state stays in
  VMEM between its steps. The schedule (:func:`paged_visits`) rides
  scalar prefetch and its length is the grid's bound, a traced scalar.
  A freed slot has no visit and its output row stays zero; a dead table
  entry is never stepped over, and a slot's last visit may be short: a
  dead sub-block is not fetched, and the mask gives its keys nothing.
  The kernel copies a visit's K and V blocks out of the arena ITSELF
  (``pl.ANY`` operands, one ``make_async_copy`` a sub-block into a
  double buffer over the visits: a step starts the next visit's copies,
  whichever slot it belongs to, before it waits for its own), because
  the BlockSpec pipeline pays for every operand in scalar work: an
  index map a step, a compare with the step before, the spills of 33
  operands' state. At one operand a sub-block that was 1,790 of a
  16-block step's 2,423 instruction bundles against 633 of arithmetic
  (the compiler's own bundle dump, PR 47), and the step took longer
  than its megabyte; the kernel's own copies cost 53 bundles a
  sub-block and nothing for a dead one. Only an int8 arena's scales
  still arrive through BlockSpecs (one operand a sub-block, a dead one
  naming the entry it read the step before): Mosaic refuses any slice
  of an HBM array whose minor axis is under 128 lanes, and the scale
  sidecar's is ``block_size``.
  A grid step is not free: on the v5e one that ``pl.when`` skips costs
  0.11-0.2 us with no HBM traffic at all, so a ``(batch, table_blocks)``
  grid over 48 slots x 32 entries with 48 of them live spends five
  sixths of its time on entries that hold nothing (224 us against 34,
  PERF.md section 6). A live one of one block costs 0.34 us beyond its
  bytes, most of it the softmax chain's LATENCY (scores, max, exp,
  sum, weighted values: each waits for the one before), so a step
  covers about a megabyte of its slot's K and V whatever a block
  weighs (four 262 KB blocks at 8 kv heads, sixteen 64 KB blocks at 2)
  and folds them in ONE chain (:func:`_fold_blocks`): with no branch
  in the body, a dead sub-block masked and not skipped.
* **int8 KV quantization** — the arena stores K/V as int8 with
  per-token/per-kv-head fp32 scales kept in block-shaped sidecars
  (``[num_blocks, KVH, block_size]``), gathered by the same table;
  dequantization happens in-register after the block is resident, so
  bytes-per-token roughly halve against bf16.

The online-softmax core (:func:`_init_state` / :func:`_block_scores` /
:func:`_fold_blocks` / :func:`_finalize`): K and V stream through VMEM
in their storage dtype with fp32 accumulation and a running max/sum in
VMEM scratch; heads are the batch dim of the two MXU contractions and
GQA keeps each head's query group ``[G, D]`` resident, so a block is
read exactly once.
Per-slot positions arrive via scalar prefetch and set both the schedule
and the in-block causal mask.

The engine hands both kernels the WHOLE arena ``[L, NB, KVH, bs, D]``
with the layer as one more scalar-prefetch operand, and writes the
tick's new token rows through :func:`paged_kv_write`, a second Mosaic
call aliased arena-in -> arena-out. XLA never sees a per-layer slab, so
it has none to slice, relayout for a scatter, and copy back (on the
v5e those four slab moves a layer for K and V each were 72% of a
48-slot tick's device time).

Dispatch: kernel on TPU when shapes tile, interpret mode when forced
(CPU tier-1; ``RAY_TPU_PALLAS_INTERPRET``), the XLA reference
(:func:`paged_attention_reference` over :func:`decode_attention_reference`)
otherwise.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.dispatch import interpret_default

# The reference masks with -1e30 (not -inf: fully-masked garbage rows in
# inactive slots must softmax to finite values, not NaN). Kept identical
# in the kernel so kernel-on/off greedy decode stays token-for-token
# stable.
MASK_VALUE = -1e30


def decode_attention_reference(q, cache_k, cache_v, positions,
                               scale: Optional[float] = None, *,
                               key_positions=None, window: int = 0):
    """Single-token attention with per-slot positions over a dense
    per-slot context: what :func:`paged_attention_reference` attends
    once it has gathered a slot's blocks, and what the external drafter
    attends over its private cache.

    q [B, H, D]; cache [B, S_max, KVH, D]; positions [B] (the absolute
    position each slot's query occupies). ``key_positions`` [B, S_max]:
    the absolute position of each cache entry where that is not its
    index (a ring's; negative = holds nothing); with ``window`` a query
    sees only the last ``window`` keys, itself included.
    """
    b, hq, d = q.shape
    s_max, hkv = cache_k.shape[1], cache_k.shape[2]
    scale = scale if scale is not None else d ** -0.5
    group = hq // hkv
    qg = q.reshape(b, hkv, group, d).astype(jnp.float32)
    logits = jnp.einsum("bhgd,bkhd->bhgk", qg,
                        cache_k.astype(jnp.float32)) * scale
    slots = (jnp.arange(s_max)[None, :] if key_positions is None
             else key_positions)
    mask = positions[:, None] >= slots                      # [B, S_max]
    if key_positions is not None:
        mask &= slots >= 0
    if window:
        mask &= positions[:, None] - slots < window
    logits = jnp.where(mask[:, None, None, :], logits, MASK_VALUE)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", probs,
                     cache_v.astype(jnp.float32))
    return out.reshape(b, hq, d).astype(q.dtype)


def dequantize_block(x, scale):
    """int8 block + per-token/per-head scale -> fp32. ``x`` [..., T, H, D],
    ``scale`` [..., T, H]."""
    return x.astype(jnp.float32) * scale[..., None]


def gather_kv(arena, tables):
    """Linearize a slot's blocks: arena [NB, KVH, bs, ...] gathered
    through tables [B, nb] -> [B, nb*bs, KVH, ...] (the dense-layout view
    the reference path attends over)."""
    b, nb = tables.shape
    hkv, bs = arena.shape[1], arena.shape[2]
    g = jnp.swapaxes(arena[tables], 2, 3)   # [B, nb, bs, KVH, ...]
    return g.reshape(b, nb * bs, hkv, *arena.shape[3:])


def _layer_slab(a, layer):
    """One layer's slab of a whole arena ``[L, NB, ...]``; a slab passes
    through."""
    if layer is None or a is None:
        return a
    return jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)


def ring_key_positions(positions, ring: int, block_size: int):
    """The absolute position each entry of a slot's ring holds when the
    slot's query sits at ``positions`` [B]: ``[B, ring * bs]``. Logical
    block ``b`` lives in ring entry ``b % ring``, so entry ``r`` holds
    the newest block ``b <= pos // bs`` with ``b % ring == r`` (negative:
    not written yet)."""
    last = positions.astype(jnp.int32)[:, None] // block_size    # [B, 1]
    r = jnp.arange(ring, dtype=jnp.int32)[None, :]
    block = last - (last - r) % ring                             # [B, ring]
    return (block[:, :, None] * block_size
            + jnp.arange(block_size, dtype=jnp.int32)).reshape(
                positions.shape[0], ring * block_size)


def paged_attention_reference(q, arena_k, arena_v, tables, positions,
                              scale: Optional[float] = None, *,
                              layer=None, k_scale=None, v_scale=None,
                              window: int = 0):
    """XLA reference: gather blocks into dense layout, dequantize when the
    arena is quantized, then run the positional-mask softmax attention.

    q [B, Hq, D]; arena [NB, KVH, bs, D], or the whole [L, NB, KVH, bs,
    D] with ``layer``; tables [B, nb] (row j = slot's j-th logical
    block; dead entries may repeat blocks — masked out by
    ``positions``); positions [B]. With ``window`` the table is a RING
    (logical block ``b`` in entry ``b % nb``) and a query sees the last
    ``window`` keys.
    """
    arena_k, arena_v, k_scale, v_scale = (
        _layer_slab(a, layer) for a in (arena_k, arena_v, k_scale, v_scale))
    ck = gather_kv(arena_k, tables)
    cv = gather_kv(arena_v, tables)
    if k_scale is not None:
        ck = dequantize_block(ck, gather_kv(k_scale, tables))
        cv = dequantize_block(cv, gather_kv(v_scale, tables))
    ring = ({"key_positions": ring_key_positions(
        positions, tables.shape[1], arena_k.shape[2]), "window": window}
        if window else {})
    return decode_attention_reference(q, ck, cv, positions, scale,
                                      **ring).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _init_state(acc_ref, m_ref, l_ref):
    m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)


def _block_scores(q, k, pos, first_col, *, scale, k_scale=None,
                  window: int = 0):
    """A K block's masked scores, all kv heads at once: [KVH, G, T].

    q [KVH, G, D]; k [KVH, T, D] in storage dtype (upcast here);
    ``pos`` the slot's absolute query position, ``first_col`` the
    absolute position of the block's first key. ``k_scale`` [KVH, T]
    dequantizes an int8 block: it scales the score COLUMNS (keys ride
    the lane axis), which equals scaling K rows without relayouting the
    scales onto sublanes."""
    q = q.astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k.astype(jnp.float32), (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale          # [KVH, G, T]
    if k_scale is not None:
        s = s * k_scale[:, None, :]
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    seen = pos >= first_col + cols
    if window:      # the lower bound: inside the FIRST live block only
        seen &= pos - (first_col + cols) < window
    return jnp.where(seen, s, MASK_VALUE)


def _fold_blocks(scores, m_prev, l_prev, acc, values, v_scales):
    """One online-softmax step over a visit's blocks: fold their scores
    (``[KVH, G, T]`` each) and values (``[KVH, T, D]`` each) into the
    running max and sum [KVH, G, 1] and the accumulator [KVH, G, D];
    returns the three. ONE chain whatever the blocks' count: the
    elementwise maximum of the score tiles, one cross-lane reduce, one
    ``exp`` pass, one rescale of the accumulator, the ``p @ v`` products
    summed; one block folds as it always did. A ``v_scales`` entry
    [KVH, T] scales the probability columns, as ``k_scale`` the scores'.
    A block no key of which is seen adds nothing: its probabilities are
    exp(MASK - m) = 0."""
    tile = functools.reduce(jnp.maximum, scores)
    m_new = jnp.maximum(m_prev, jnp.max(tile, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    probs = [jnp.exp(s - m_new) for s in scores]             # [KVH, G, T]
    l_new = alpha * l_prev + jnp.sum(functools.reduce(jnp.add, probs),
                                     axis=-1, keepdims=True)
    acc = acc * alpha
    for p, v, v_scale in zip(probs, values, v_scales):
        if v_scale is not None:
            p = p * v_scale[:, None, :]
        acc += jax.lax.dot_general(
            p, v.astype(jnp.float32), (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)              # [KVH, G, D]
    return m_new, l_new, acc


def _store_state(state, acc_ref, m_ref, l_ref):
    """The softmax state back into VMEM scratch (the max and the sum
    broadcast along lanes)."""
    m_new, l_new, acc = state
    acc_ref[:] = acc
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)


def _finalize(o_ref, acc_ref, l_ref):
    l = l_ref[:, :, :1]
    # Position 0 is always live, so l > 0 for every real slot; guard
    # anyway so padded grid rows emit zeros rather than NaN.
    safe_l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)


def _scratch(hkv: int, group: int, d: int):
    return [pltpu.VMEM((hkv, group, d), jnp.float32),
            pltpu.VMEM((hkv, group, 128), jnp.float32),
            pltpu.VMEM((hkv, group, 128), jnp.float32)]


def _layer_operand(layer):
    """The layer index as the rank-1 int32 array scalar prefetch takes."""
    return jnp.asarray(layer, jnp.int32).reshape(1)


def _first_live(pos, window: int, block_size: int):
    """The first logical block that holds a key a query at ``pos`` may
    see: 0 without a window."""
    return jnp.maximum(pos - (window - 1), 0) // block_size if window else 0


# K and V bytes of ONE slot a grid step takes, about, whatever a block
# weighs: a step covers as many consecutive blocks of its slot as fit,
# at most ``MAX_VISIT_BLOCKS`` (the widest step that was timed; a toy
# arena's 8 KB blocks would ask for 128 copies a step). What a step
# costs beyond its bytes is paid once a megabyte. Timed alone on the
# v5e at each cell's fill, a call against its live bytes at 819 GB/s
# (`chip_smoke.py kernels` times every width; PERF.md section 6, PR 33
# for the 8- and 16-head rows, PR 47 for all of them with the one-chain
# fold and the kernel's own copies):
#   8 kv heads of 128, a 262 KB block: 65 live blocks a slot, one a
#     step 1.94 ms, two 1.32, three 1.16, four 1.13, eight 1.13, the
#     bytes 1.00;
#   16 kv heads, 524 KB: 4-5 live blocks a slot, two a step 165 us, one
#     192, four 172, the bytes 138;
#   2 kv heads of 128, 64 KB: 33-100 live blocks a slot, four a step
#     1.12 ms (the cap of PR 33, which was set for 8-head arenas: 1.21
#     with BlockSpec operands), eight 0.78, sixteen 0.63 (0.87 with
#     BlockSpec operands), the bytes 0.51;
#   2 kv heads of 256, 131 KB: 17-32 live blocks a slot, four a step
#     1.44 ms, eight 1.20, sixteen 1.18, the bytes 1.00.
VISIT_BYTES = 1 << 20
MAX_VISIT_BLOCKS = 16


def visit_blocks(arena_k) -> int:
    """Blocks a grid step of :func:`paged_decode_attention` covers over
    this arena (a slab or the whole ``[L, NB, KVH, bs, D]``): a rule on
    the block's bytes, which every caller can see."""
    block = 2 * math.prod(arena_k.shape[-3:]) * jnp.dtype(
        arena_k.dtype).itemsize
    return max(1, min(VISIT_BYTES // block, MAX_VISIT_BLOCKS))


def paged_visits(tables, positions, limits=None, *, block_size: int,
                 per_visit: int, window: int = 0):
    """The kernel's schedule: one VISIT per run of up to ``per_visit``
    (:func:`visit_blocks` of the arena the schedule is for)
    consecutive logical blocks of ONE slot that hold a key the slot's
    query may see, slot-major, and nothing else. A slot's query sits at
    absolute position ``pos``, so its blocks ``[0, pos // bs]`` are live
    (clamped to the table); its visits start at its first live block and
    the last may be short; a freed slot (``limits`` 0; every slot is
    live without ``limits``) owns none.
    Returns (slot of visit ``[V]``, first logical block of visit ``[V]``,
    table entry of each of the visit's sub-blocks ``[P * V]`` (sub-block
    ``p`` of visit ``v`` at ``p * V + v``, an index into the flattened
    ``tables``), visits ``[1]``), the lists valid past the end. A DEAD
    sub-block (past the slot's last live block) names the entry its
    operand read at the step before, so a BlockSpec pipeline (an int8
    arena's scales here, the latent kernel's blocks) fetches nothing for
    it; this kernel's own copies skip it, and its arithmetic masks it.

    With ``window`` the table is a slot's RING of ``nb`` entries and a
    query sees the last ``window`` keys: its live blocks are ``[(pos -
    window + 1) // bs, pos // bs]``, LOGICAL blocks that live in ring
    entries ``block % nb``; they number at most ``window // bs + 1``,
    which the ring must exceed.

    Some dozen small XLA fusions and no gather (a gather's index vectors
    pad to 128 lanes: 0.4 MB of scratch each at 768 visits), which the
    compiler leaves inside a layer loop although nothing in them depends
    on the layer: a caller with such a loop makes the schedule once,
    before it, and hands it to :func:`paged_decode_attention` as
    ``visits``."""
    b, nb = tables.shape
    per = per_visit
    positions = positions.astype(jnp.int32)
    first = (_first_live(positions, window, block_size)
             + jnp.zeros_like(positions))
    last = positions // block_size
    if not window:
        last = jnp.minimum(last, nb - 1)
    n_live = last + 1 - first
    if limits is not None:
        n_live = jnp.where(limits > 0, n_live, 0)
    n_visits = -(-n_live // per)
    ends = jnp.cumsum(n_visits)
    # One entry to spare: the pipeline works out the step after the last.
    v = jnp.arange(b * -(-nb // per) + 1, dtype=jnp.int32)
    slots = jnp.arange(b, dtype=jnp.int32)
    # Compare-all, not a binary search: one fusion, no loop on device.
    before = ends[None, :] <= v[:, None]                     # [V, B]
    slot = jnp.minimum(jnp.sum(before, axis=1), b - 1)
    mine = slot[:, None] == slots[None, :]                   # [V, B]

    def of_slot(x):
        """``x[..., slot]`` for ``x`` [..., B], as a masked sum."""
        return jnp.sum(jnp.where(mine, x[..., None, :], 0), axis=-1)

    def entry(s, j):
        """Logical block ``j`` of slot ``s`` in the flattened tables.
        (A ring's entry for logical block j is j % nb.)"""
        return s * nb + (j % nb if window else jnp.minimum(j, nb - 1))

    nth = jnp.maximum(v - of_slot(ends - n_visits), 0)   # visit of its slot
    block = of_slot(first) + nth * per                       # [V]
    p = jnp.arange(per, dtype=jnp.int32)[:, None]
    sub = block[None, :] + p                                 # [P, V]
    live = (sub <= of_slot(last)[None, :]) & (v < ends[-1])[None, :]
    # What operand p read at the step before a dead sub-block: within a
    # slot its sub-block of the visit before, which was live; at a
    # slot's first visit, what the newest earlier slot with more than p
    # live blocks read last (a freed slot hands on what it was handed).
    has = n_live[None, :] > p                                # [P, B]
    left = entry(slots[None, :], first[None, :] + p
                 + (n_live[None, :] - 1 - p) // per * per)
    newest = jax.lax.cummax(jnp.where(has, slots[None, :], -1), axis=1)
    newest = jnp.concatenate(                # of the slots BEFORE each
        [jnp.full((per, 1), -1, jnp.int32), newest[:, :-1]], axis=1)
    handed = jnp.sum(jnp.where(              # (none before: entry 0)
        newest[:, :, None] == slots[None, None, :], left[:, None, :], 0),
        axis=-1)                                             # [P, B]
    where = jnp.where(live, entry(slot[None, :], sub), jnp.where(
        nth[None, :] > 0, entry(slot[None, :], sub - per), of_slot(handed)))
    return (slot.astype(jnp.int32), block.astype(jnp.int32),
            where.astype(jnp.int32).reshape(-1), ends[-1:].astype(jnp.int32))


def _paged_kernel(layer_ref, tables_ref, pos_ref, slot_ref, block_ref,
                  where_ref, count_ref, q_ref, k_hbm, v_hbm, *rest, scale,
                  block_size, num_blocks, per_visit, listed, quantized,
                  window=0):
    n = per_visit
    if quantized:
        ks_refs, vs_refs, rest = rest[:n], rest[n:2 * n], rest[2 * n:]
    _, o_ref, k_buf, v_buf, sems, acc_ref, m_ref, l_ref = rest
    visit = pl.program_id(0)
    half = visit % 2

    def span(u):
        """Visit ``u``'s query position, first block and the last live
        block of its slot. A ring's logical blocks run past its width; a
        table's do not: a query past its table's end sees the whole
        table and no more."""
        pos = pos_ref[slot_ref[u]]
        if not window:
            pos = jnp.minimum(pos, num_blocks * block_size - 1)
        return pos, block_ref[u], pos // block_size

    def copies(into, p, block):
        """Sub-block ``p``'s K and V out of the arena into buffer half
        ``into``."""
        return [pltpu.make_async_copy(hbm.at[layer_ref[0], block],
                                      buf.at[into, p], sems.at[into, c, p])
                for c, (hbm, buf) in enumerate(((k_hbm, k_buf),
                                                (v_hbm, v_buf)))]

    def fetch(u, into):
        """Start the copies of visit ``u``'s live sub-blocks (a slot's
        last visit may be short, and a dead sub-block is not fetched):
        the schedule names the table entry of each, the table the arena
        block. A loop, so a short visit costs its own blocks only."""
        _, first, last = span(u)

        def start(p, carry):
            for copy in copies(into, p,
                               tables_ref[where_ref[p * listed + u]]):
                copy.start()
            return carry

        jax.lax.fori_loop(0, jnp.minimum(last - first + 1, n), start, 0)

    # The kernel's own double buffer over the VISITS: this step starts
    # the next visit's copies (whichever slot it belongs to) before it
    # waits for its own, which the step before started.
    @pl.when(visit == 0)
    def _first():
        fetch(0, 0)

    @pl.when(visit + 1 < count_ref[0])
    def _next():
        fetch(visit + 1, 1 - half)

    pos, j, last = span(visit)
    for p in range(n):
        @pl.when(j + p <= last)
        def _arrived(p=p):
            for copy in copies(half, p, 0):
                copy.wait()

    @pl.when(j == _first_live(pos, window, block_size))
    def _init():
        _init_state(acc_ref, m_ref, l_ref)

    # One body for every visit, one softmax chain for its blocks. A DEAD
    # sub-block (past the slot's last block) was not fetched, its buffer
    # holds whatever an earlier visit left there: every key of it lies
    # past ``pos``, so the causal mask gives them probability 0, and its
    # values are zeroed besides, because 0 x NaN is NaN and nothing says
    # what memory nobody wrote holds (an int8 block holds no NaN; its
    # scales may).
    def live(p, a):
        return a if p == 0 else jnp.where(j + p <= last, a,
                                          jnp.zeros_like(a))

    q = q_ref[0]
    scores = [_block_scores(
        q, k_buf[half, p], pos, (j + p) * block_size, scale=scale,
        k_scale=ks_refs[p][0, 0] if quantized else None, window=window)
        for p in range(n)]
    if quantized:
        values = [v_buf[half, p] for p in range(n)]
        v_scales = [live(p, vs_refs[p][0, 0]) for p in range(n)]
    else:
        values = [live(p, v_buf[half, p]) for p in range(n)]
        v_scales = [None] * n
    _store_state(_fold_blocks(scores, m_ref[:, :, :1], l_ref[:, :, :1],
                              acc_ref[:], values, v_scales),
                 acc_ref, m_ref, l_ref)

    @pl.when(last < j + n)
    def _fin():
        _finalize(o_ref, acc_ref, l_ref)


def _paged_fused(q, arena_k, arena_v, tables, positions, visits, *, layer,
                 k_scale, v_scale, scale, interpret, window=0):
    b, hq, d = q.shape
    _, _, hkv, block_size, _ = arena_k.shape
    nb = tables.shape[1]
    group = hq // hkv
    quantized = k_scale is not None
    slot_of, block_of, where_of, count = visits
    n_visits = slot_of.shape[0]
    per = where_of.shape[0] // n_visits

    # The pipeline also works out block indices for steps it never runs
    # (the one after the last, at least): a step past the lists reads
    # their last entry, which the schedule keeps valid.
    def listed(v):
        return jnp.minimum(v, n_visits - 1)

    qg = q.reshape(b, hkv, group, d)
    q_spec = pl.BlockSpec(
        (1, hkv, group, d),
        lambda v, ly, tab, po, sl, bl, wh, n: (sl[listed(v)], 0, 0, 0))
    # K and V stay in HBM: the kernel copies a visit's blocks itself.
    in_specs = [q_spec] + [pl.BlockSpec(memory_space=pl.ANY)] * 2
    inputs = [qg, arena_k, arena_v]
    if quantized:
        # The scales arrive through BlockSpecs, one operand a sub-block
        # (the scalar-prefetched layer, schedule and tables choose the
        # block; one whose block does not change between two steps is
        # not fetched again): Mosaic refuses a slice of an HBM array
        # whose minor axis is under 128 lanes, and theirs is
        # ``block_size``.
        sc_specs = [pl.BlockSpec(
            (1, 1, hkv, block_size),
            lambda v, ly, tab, po, sl, bl, wh, n, p=p: (
                ly[0], tab[wh[p * n_visits + listed(v)]], 0, 0))
            for p in range(per)]
        in_specs += sc_specs + sc_specs
        inputs += [k_scale] * per + [v_scale] * per
    # The output starts as zeros and only visited slots are written, so
    # a freed slot's row comes back zero at no grid step of its own.
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    inputs.append(jnp.zeros_like(qg))
    buffers = [pltpu.VMEM((2, per, hkv, block_size, d), a.dtype)
               for a in (arena_k, arena_v)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(count[0],),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=buffers + [pltpu.SemaphoreType.DMA((2, 2, per))]
        + _scratch(hkv, group, d),
    )
    kernel = functools.partial(
        _paged_kernel, scale=scale, block_size=block_size, num_blocks=nb,
        per_visit=per, listed=n_visits, quantized=quantized, window=window)
    itemsize = jnp.dtype(arena_k.dtype).itemsize
    kv_bytes = 2 * b * nb * hkv * block_size * d * itemsize
    if quantized:
        kv_bytes += 2 * b * nb * hkv * block_size * 4    # fp32 scales
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
        # Operand index counts the seven scalar-prefetch arrays.
        input_output_aliases={7 + len(inputs) - 1: 0},
        interpret=interpret,
        name="paged_decode_attn",
        cost_estimate=pl.CostEstimate(
            # Static worst case: every table entry live. The engine feeds
            # the monitor a live-token byte estimate for achieved-BW.
            flops=4 * b * hq * nb * block_size * d,
            bytes_accessed=kv_bytes
            + q.size * jnp.dtype(q.dtype).itemsize,
            transcendentals=b * hq * nb * block_size,
        ),
    )(_layer_operand(layer), tables.astype(jnp.int32).reshape(-1),
      positions.astype(jnp.int32), slot_of, block_of, where_of, count,
      *inputs)
    return out.reshape(b, hq, d)


# ---------------------------------------------------------------------------
# In-place token write
# ---------------------------------------------------------------------------

def _write_kernel(layer_ref, blk_ref, off_ref, new_ref, arena_ref, out_ref,
                  *, window, rows):
    """Merge this slot's window rows into one ``rows``-row tile of an
    arena block. Grid step ``t`` of slot ``b`` holds the tile that window
    token ``t * (S-1)`` lands in; every window token aimed at that tile
    of that block replaces its row, all other bytes go back as read. The
    select is exact: bf16 rides through fp32 and int8 through int32, the
    row test is on int32."""
    base = pl.program_id(0) * window
    held = base + pl.program_id(1) * (window - 1)
    target = blk_ref[held]
    first = jax.lax.div(off_ref[held], rows) * rows  # the tile's row 0
    blk = arena_ref[0, 0]                            # [KVH, rows, ...]
    wide = jnp.int32 if blk.dtype == jnp.int8 else jnp.float32
    row = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1)
    merged = blk.astype(wide)
    for j in range(window):   # static: 1 for a tick, k+1 for verify
        # A row of another tile is no row of this one: below 0 or past it.
        hit = ((row == off_ref[base + j] - first)
               & (blk_ref[base + j] == target))
        merged = jnp.where(hit, new_ref[0, j].astype(wide), merged)
    out_ref[0, 0] = merged.astype(out_ref.dtype)


def _write_rows(arena, s: int) -> int:
    """Rows of a block one grid step of :func:`paged_kv_write` moves:
    the arena dtype's native sublane tile (16 rows of bf16, 32 of int8)
    when rows are the second-minor axis, a block is a whole number of
    tiles and more than one, and a window of ``s`` tokens spans at most
    two of them; the whole block otherwise (the fp32 scale sidecar,
    whose minor axis IS the rows; the CPU rehearsals' 8- and 16-row
    blocks; a verify window wider than a tile)."""
    bs = arena.shape[3]
    tile = 32 // jnp.dtype(arena.dtype).itemsize
    tiled = arena.ndim > 4 and bs % tile == 0 and bs > tile and s - 1 <= tile
    return tile if tiled else bs


def paged_kv_write(arena, new, layer, block_idx, offset):
    """Write token rows into the whole arena IN PLACE (aliased in -> out).

    arena [L, NB, KVH, bs, ...] (K/V ``[..., D]``, or the fp32 scale
    sidecar with no trailing axis); ``new`` [B, S, KVH, ...] holds each
    slot's S consecutive tokens, token (b, j) bound for row ``offset[b,
    j]`` of block ``block_idx[b, j]`` of layer ``layer`` (traced).

    Grid ``(B, min(S, 2))``, one tile of :func:`_write_rows` rows of one
    block in and out per step (every head's): a block's other tiles are
    never moved. A window of consecutive positions spans at most two
    tiles when ``S - 1 <= rows``, across a block boundary or inside a
    block alike: the first and the last token's. A window inside one
    tile repeats it in the second step, which pallas neither re-fetches
    nor writes back in between, and the merge is idempotent. DIFFERENT
    slots must not name the same tile of the same block unless its bytes
    are never read: the second slot's step would merge into the copy
    fetched before the first one's write landed. The engine's live slots
    never do (a slot writes only blocks it owns alone; prefix-shared
    blocks are full); freed slots all aim at the garbage block, at
    whatever tile.
    """
    b, s = block_idx.shape
    hkv, bs = arena.shape[2], arena.shape[3]
    rest = arena.shape[4:]
    if s - 1 > bs:
        raise ValueError(f"window of {s} tokens can span more than two "
                         f"blocks of {bs}")
    rows = _write_rows(arena, s)
    zeros = (0,) * len(rest)
    # Rows ride a unit axis where the tile has ``rows``, so the kernel
    # broadcasts along it and never moves heads between tile axes.
    new = new.astype(arena.dtype).reshape(b, s, hkv, 1, *rest)
    new_spec = pl.BlockSpec(
        (1, s, hkv, 1, *rest),
        lambda b_, t, ly, blk, off: (b_, 0, 0, 0, *zeros))

    def held(b_, t, ly, blk, off):
        at = b_ * s + t * (s - 1)
        return (ly[0], blk[at], 0, jax.lax.div(off[at], rows), *zeros)

    arena_spec = pl.BlockSpec((1, 1, hkv, rows, *rest), held)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, min(s, 2)),
        in_specs=[new_spec, arena_spec],
        out_specs=arena_spec,
    )
    tile_bytes = (hkv * rows * math.prod(rest)
                  * jnp.dtype(arena.dtype).itemsize)
    return pl.pallas_call(
        functools.partial(_write_kernel, window=s, rows=rows),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(arena.shape, arena.dtype),
        # Operand 4 counts the three scalar-prefetch arrays and ``new``.
        input_output_aliases={4: 0},
        interpret=interpret_default(),
        name="paged_kv_write",
        cost_estimate=pl.CostEstimate(
            flops=0, transcendentals=0,
            bytes_accessed=2 * b * min(s, 2) * tile_bytes),
    )(_layer_operand(layer), block_idx.astype(jnp.int32).reshape(-1),
      offset.astype(jnp.int32).reshape(-1), new, arena)


def paged_applicable(block_size: int, d: int, hq: int, hkv: int) -> bool:
    """True when auto-dispatch takes the paged fused kernels on TPU for
    these shapes (lane-tiling head_dim, sublane-tiling blocks, whole
    query groups)."""
    return not (hq % hkv or d % 128 or block_size % 32)


def paged_decode_attention(
    q: jnp.ndarray,
    arena_k: jnp.ndarray,
    arena_v: jnp.ndarray,
    tables: jnp.ndarray,
    positions: jnp.ndarray,
    scale: Optional[float] = None,
    *,
    layer=None,
    limits: Optional[jnp.ndarray] = None,
    visits=None,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
    window: int = 0,
) -> jnp.ndarray:
    """Decode-step attention over a paged KV arena.

    q [B, Hq, D]; arena_k/v the whole arena [L, NB, KVH, bs, D] read at
    ``layer`` (a traced int32 scalar), or one slab [NB, KVH, bs, D] with
    ``layer`` None; int8 when ``k_scale`` / ``v_scale`` (the arena's
    shape less D) are given; tables [B, nb] int32 block table (row j =
    the slot's j-th logical block; dead tail entries must name a block,
    which the kernel never visits and the reference masks); positions
    [B].

    ``limits`` [B] int32: a slot whose limit is 0 is freed: the kernel
    does not visit it and its row comes back zero (the reference attends
    whatever its table names; nothing reads that row). Without it every
    slot is live. ``visits``: the kernel's schedule, from
    :func:`paged_visits` on the same tables, positions and limits (and
    :func:`visit_blocks` of this arena), for a caller that makes it once
    for many layers; ``limits`` is then not read.

    ``window``: ``tables`` [B, nb] is each slot's RING (logical block
    ``b`` in entry ``b % nb``; ``nb > window // bs + 1``) and a query
    sees its last ``window`` keys, itself included: the kernel visits
    the blocks that hold one and masks inside the first of them.
    ``visits`` must then come from :func:`paged_visits` with the same
    ``window``.

    ``use_kernel``: None = auto (fused kernel on TPU when the shapes
    tile, XLA reference elsewhere); True forces the kernel (interpret
    mode off-TPU — the CPU tier-1 path); False forces the reference.
    """
    b, hq, d = q.shape
    if (layer is None) != (arena_k.ndim == 4):
        raise ValueError("a whole arena needs `layer`; a slab takes none")
    hkv, block_size = arena_k.shape[-3], arena_k.shape[-2]
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if use_kernel is None:
        use_kernel = (jax.default_backend() == "tpu"
                      and paged_applicable(block_size, d, hq, hkv))
    if not use_kernel:
        return paged_attention_reference(q, arena_k, arena_v, tables,
                                         positions, scale, layer=layer,
                                         k_scale=k_scale, v_scale=v_scale,
                                         window=window)
    if interpret is None:
        interpret = interpret_default()
    if layer is None:
        # A slab is an arena of one layer (a leading unit axis is free).
        layer = 0
        arena_k, arena_v = arena_k[None], arena_v[None]
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
    if visits is None:
        visits = paged_visits(tables, positions, limits,
                              block_size=block_size,
                              per_visit=visit_blocks(arena_k), window=window)
    return _paged_fused(q, arena_k, arena_v, tables, positions, visits,
                        layer=layer, k_scale=k_scale, v_scale=v_scale,
                        scale=scale, interpret=interpret, window=window)
