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
  dead sub-block is neither fetched nor attended.
  Blocks still arrive through BlockSpecs (one operand a sub-block, each
  one whole block chosen by the schedule), which pipeline across slot
  boundaries; a kernel that copies them itself out of ``pl.ANY``
  operands cannot read an int8 arena, because Mosaic refuses any slice
  of an HBM array whose minor axis is under 128 lanes, and the scale
  sidecar's is ``block_size``.
  A grid step is not free: on the v5e one that ``pl.when`` skips costs
  0.11-0.2 us with no HBM traffic at all, so a ``(batch, table_blocks)``
  grid over 48 slots x 32 entries with 48 of them live spends five
  sixths of its time on entries that hold nothing (224 us against 34,
  PERF.md section 6). A live one of one block costs 0.3 us beyond its
  bytes, and that is the softmax chain's LATENCY (scores, max, exp,
  sum, weighted values: each waits for the one before), not its work:
  a step of several blocks whose chains the scheduler may interleave
  (no branch between them, the state in registers) reads four blocks
  in 1.4 us where four steps took 2.5 (PERF.md section 6, PR 33).
* **int8 KV quantization** — the arena stores K/V as int8 with
  per-token/per-kv-head fp32 scales kept in block-shaped sidecars
  (``[num_blocks, KVH, block_size]``), gathered by the same table;
  dequantization happens in-register after the block is resident, so
  bytes-per-token roughly halve against bf16.

The online-softmax core (:func:`_init_state` / :func:`_attend_block` /
:func:`_finalize`): K and V stream through VMEM in their storage dtype
with fp32 accumulation and a running max/sum in VMEM scratch; heads are
the batch dim of the two MXU contractions and GQA keeps each head's
query group ``[G, D]`` resident, so a block is read exactly once.
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


def _fold_block(s, m_prev, l_prev, acc, v, v_scale=None):
    """One online-softmax step: fold a block's scores ``s`` [KVH, G, T]
    and values ``v`` [KVH, T, D] into the running max and sum [KVH, G,
    1] and the accumulator [KVH, G, D]; returns the three. ``v_scale``
    [KVH, T] scales the probability columns, as ``k_scale`` the
    scores'. A block no key of which is seen leaves all three as they
    were: its probabilities are exp(MASK - m) = 0."""
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                                   # [KVH, G, T]
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    if v_scale is not None:
        p = p * v_scale[:, None, :]
    pv = jax.lax.dot_general(
        p, v.astype(jnp.float32), (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)                  # [KVH, G, D]
    return m_new, l_new, acc * alpha + pv


def _attend_block(q, k, v, pos, first_col, acc_ref, m_ref, l_ref, *, scale,
                  k_scale=None, v_scale=None, window: int = 0):
    """:func:`_block_scores` then :func:`_fold_block` on the state in
    VMEM scratch (the max and the sum broadcast along lanes)."""
    s = _block_scores(q, k, pos, first_col, scale=scale, k_scale=k_scale,
                      window=window)
    m_new, l_new, acc = _fold_block(s, m_ref[:, :, :1], l_ref[:, :, :1],
                                    acc_ref[:], v, v_scale)
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


# K and V bytes a grid step takes, about: a step covers as many
# consecutive blocks of its slot as fit, at most ``MAX_VISIT_BLOCKS``
# (nothing above was timed). One block a step spends half its time on
# steps and on the softmax chain's latency at 65-86 live blocks a slot;
# on the v5e four 262 KB blocks a step (8 kv heads, bf16) run at 1.14 ms
# where one a step takes 1.92 and the bytes 1.00, and two 524 KB blocks
# (16 kv heads) at 166 us against 188 and 138, three or four slower
# again (`chip_smoke.py kernels` times 1 to 4; PERF.md section 6, PR 33).
VISIT_BYTES = 1 << 20
MAX_VISIT_BLOCKS = 4


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
    operand read at the step before, so the pipeline fetches nothing for
    it, and the kernel skips it.

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
                  where_ref, q_ref, *rest, scale, block_size, num_blocks,
                  per_visit, quantized, window=0):
    n = per_visit
    k_refs, v_refs, rest = rest[:n], rest[n:2 * n], rest[2 * n:]
    if quantized:
        ks_refs, vs_refs, rest = rest[:n], rest[n:2 * n], rest[2 * n:]
    _, o_ref, acc_ref, m_ref, l_ref = rest
    visit = pl.program_id(0)
    pos = pos_ref[slot_ref[visit]]
    j = block_ref[visit]
    # A ring's logical blocks run past its width; a table's do not.
    last = pos // block_size
    if not window:
        last = jnp.minimum(last, num_blocks - 1)

    @pl.when(j == _first_live(pos, window, block_size))
    def _init():
        _init_state(acc_ref, m_ref, l_ref)

    # The visit's blocks in logical order, one online-softmax step each:
    # the arithmetic of one block a step, whatever a step covers.
    def keys(p):
        return dict(k=k_refs[p][0, 0], first_col=(j + p) * block_size,
                    k_scale=ks_refs[p][0, 0] if quantized else None)

    def values(p):
        return dict(v=v_refs[p][0, 0],
                    v_scale=vs_refs[p][0, 0] if quantized else None)

    @pl.when(j + n - 1 <= last)
    def _full():
        # No branch between the blocks and the state in registers, so
        # the scheduler overlaps one block's scores with the fold of the
        # block before: the softmax chain's latency, not its work, is
        # what a block costs beyond its bytes.
        q = q_ref[0]
        scores = [_block_scores(q, pos=pos, scale=scale, window=window,
                                **keys(p)) for p in range(n)]
        state = (m_ref[:, :, :1], l_ref[:, :, :1], acc_ref[:])
        for p in range(n):
            state = _fold_block(scores[p], *state, **values(p))
        m_new, l_new, acc = state
        acc_ref[:] = acc
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    # A slot's last visit, short: block by block, the dead ones (past
    # the slot's last block) neither read nor attended.
    for p in range(n - 1):
        @pl.when((j + n - 1 > last) & (j + p <= last))
        def _short(p=p):
            _attend_block(q_ref[0], pos=pos, acc_ref=acc_ref, m_ref=m_ref,
                          l_ref=l_ref, scale=scale, window=window,
                          **keys(p), **values(p))

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
        lambda v, ly, tab, po, sl, bl, wh: (sl[listed(v)], 0, 0, 0))

    # The table gather IS the index_map: the scalar-prefetched layer,
    # schedule and block tables choose which arena block each of a
    # visit's operands streams into VMEM. One operand a sub-block, so
    # the blocks still arrive through the BlockSpec pipeline, and one
    # whose block does not change between two steps is not fetched again.
    def specs(shape):
        zeros = (0,) * (len(shape) - 2)
        return [pl.BlockSpec(
            shape, lambda v, ly, tab, po, sl, bl, wh, p=p: (
                ly[0], tab[wh[p * n_visits + listed(v)]], *zeros))
            for p in range(per)]

    kv_specs = specs((1, 1, hkv, block_size, d))
    in_specs = [q_spec] + kv_specs + kv_specs
    inputs = [qg] + [arena_k] * per + [arena_v] * per
    if quantized:
        sc_specs = specs((1, 1, hkv, block_size))
        in_specs += sc_specs + sc_specs
        inputs += [k_scale] * per + [v_scale] * per
    # The output starts as zeros and only visited slots are written, so
    # a freed slot's row comes back zero at no grid step of its own.
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    inputs.append(jnp.zeros_like(qg))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(count[0],),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=_scratch(hkv, group, d),
    )
    kernel = functools.partial(
        _paged_kernel, scale=scale, block_size=block_size, num_blocks=nb,
        per_visit=per, quantized=quantized, window=window)
    itemsize = jnp.dtype(arena_k.dtype).itemsize
    kv_bytes = 2 * b * nb * hkv * block_size * d * itemsize
    if quantized:
        kv_bytes += 2 * b * nb * hkv * block_size * 4    # fp32 scales
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
        # Operand index counts the six scalar-prefetch arrays.
        input_output_aliases={6 + len(inputs) - 1: 0},
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
      positions.astype(jnp.int32), slot_of, block_of, where_of, *inputs)
    return out.reshape(b, hq, d)


# ---------------------------------------------------------------------------
# In-place token write
# ---------------------------------------------------------------------------

def _write_kernel(layer_ref, blk_ref, off_ref, new_ref, arena_ref, out_ref,
                  *, window):
    """Merge this slot's window rows into one arena block. Grid step
    ``t`` of slot ``b`` holds the block that window token ``t * (S-1)``
    lands in; every window token aimed at that block replaces its row,
    all other bytes go back as read. The select is exact: bf16 rides
    through fp32 and int8 through int32, the row test is on int32."""
    base = pl.program_id(0) * window
    target = blk_ref[base + pl.program_id(1) * (window - 1)]
    blk = arena_ref[0, 0]                            # [KVH, bs, ...]
    wide = jnp.int32 if blk.dtype == jnp.int8 else jnp.float32
    rows = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1)
    merged = blk.astype(wide)
    for j in range(window):   # static: 1 for a tick, k+1 for verify
        hit = (rows == off_ref[base + j]) & (blk_ref[base + j] == target)
        merged = jnp.where(hit, new_ref[0, j].astype(wide), merged)
    out_ref[0, 0] = merged.astype(out_ref.dtype)


def paged_kv_write(arena, new, layer, block_idx, offset):
    """Write token rows into the whole arena IN PLACE (aliased in -> out).

    arena [L, NB, KVH, bs, ...] (K/V ``[..., D]``, or the fp32 scale
    sidecar with no trailing axis); ``new`` [B, S, KVH, ...] holds each
    slot's S consecutive tokens, token (b, j) bound for row ``offset[b,
    j]`` of block ``block_idx[b, j]`` of layer ``layer`` (traced).

    Grid ``(B, min(S, 2))``, one whole block in and out per step. A
    window of consecutive positions spans at most two blocks when
    ``S - 1 <= bs``: the first and the last token's. A window inside one
    block repeats it in the second step, which pallas neither re-fetches
    nor writes back in between, and the merge is idempotent. DIFFERENT
    slots must not name the same block unless its bytes are never read:
    the second slot's step would merge into the copy fetched before the
    first one's write landed. The engine's live slots never do (a slot
    writes only blocks it owns alone; prefix-shared blocks are full);
    freed slots all aim at the garbage block.
    """
    b, s = block_idx.shape
    hkv, bs = arena.shape[2], arena.shape[3]
    rest = arena.shape[4:]
    if s - 1 > bs:
        raise ValueError(f"window of {s} tokens can span more than two "
                         f"blocks of {bs}")
    zeros = (0,) * len(rest)
    # Rows ride a unit axis where the block has ``bs``, so the kernel
    # broadcasts along it and never moves heads between tile axes.
    new = new.astype(arena.dtype).reshape(b, s, hkv, 1, *rest)
    new_spec = pl.BlockSpec(
        (1, s, hkv, 1, *rest),
        lambda b_, t, ly, blk, off: (b_, 0, 0, 0, *zeros))
    arena_spec = pl.BlockSpec(
        (1, 1, hkv, bs, *rest),
        lambda b_, t, ly, blk, off: (
            ly[0], blk[b_ * s + t * (s - 1)], 0, 0, *zeros))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, min(s, 2)),
        in_specs=[new_spec, arena_spec],
        out_specs=arena_spec,
    )
    block_bytes = (hkv * bs * math.prod(rest)
                   * jnp.dtype(arena.dtype).itemsize)
    return pl.pallas_call(
        functools.partial(_write_kernel, window=s),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(arena.shape, arena.dtype),
        # Operand 4 counts the three scalar-prefetch arrays and ``new``.
        input_output_aliases={4: 0},
        interpret=interpret_default(),
        name="paged_kv_write",
        cost_estimate=pl.CostEstimate(
            flops=0, transcendentals=0,
            bytes_accessed=2 * b * min(s, 2) * block_bytes),
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
