"""Decode attention over a paged LATENT cache (multi-head latent
attention in absorbed form, ``models/mla.py``).

The cache holds ONE row a token a layer, ``[c_kv | k_rope | 0]`` of ``W``
lanes, shared by every head: ``arena [L, NB, 1, bs, W]``, the K/V
arena's block layout with one "kv head" and no V plane. A slot's H
absorbed queries ``[H, W]`` (``[q_nope W_uk^T | q_rope | 0]``) score a
row by one dot product over all W lanes, and the row's first ``rank``
lanes ARE its value: the block a grid step brought into VMEM is
contracted twice and read from HBM once (handing the arena to
``paged_decode_attn`` as K and again as V would read every byte twice,
and its head dims must tile 128, which 576 does not).

The schedule is ``paged_decode_attention.paged_visits``' to the letter
(runs of live blocks of one slot, slot-major, freed slots not visited),
and the new rows land through ``paged_kv_write``. The kernel copies a
visit's blocks out of the cache ITSELF, as ``paged_decode_attn`` does
since PR 47 (there a BlockSpec operand a sub-block cost more scalar work
than a block's bytes take; here that was a few percent, and what binds
is the copies' own rate: the table at ``VISIT_BYTES``): the cache is one
``pl.ANY`` operand, and one ``make_async_copy`` a live sub-block lands
its rows in their place in a VMEM buffer ``[2, T, W]``, double over the
visits (a step starts the next visit's copies, whichever slot it belongs
to, before it waits for its own). A buffer half IS the visit's
blocks joined along the key axis, folded in ONE online-softmax step: the
H heads are the rows of two MXU products (``[H, W] x [W, T]`` and
``[H, T] x [T, rank]``, bf16 operands, float32 accumulation), so a visit
of sixteen 64-token blocks is 64 x 1024 scores, not sixteen chains of
64 x 64. A dead sub-block of a slot's last visit is neither fetched nor
waited for: its rows of the buffer hold what an earlier visit left, so
they are zeroed in place before either product reads them (as keys the
position mask would do, as values 0 x NaN would not).
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
from ray_tpu.ops.paged_decode_attention import (MASK_VALUE, _finalize,
                                                _init_state, _layer_operand,
                                                _layer_slab, _scratch,
                                                paged_visits)

# Blocks a grid step covers: `paged_decode_attention.visit_blocks`' rule
# (about VISIT_BYTES of ONE slot's rows a step, at most MAX_VISIT_BLOCKS),
# with this kernel's own constants. A row block is 80 KiB (64 x 640
# bf16), and sixteen of them (1.25 MiB) are what the chip times best
# among 8, 12 and 16. On the v5e, alone at the cell's load (96 slots x
# about 7000 rows, 676,702 live: 0.95 ms a call for their 1152 B each at
# 819 GB/s), ms a call at 8 | 12 | 16 blocks a step (my chip runs, PR 50):
#   a BlockSpec operand a sub-block, joined in VMEM (PR 36)  1.61 | 1.50 | 1.39
#   the kernel's own copies, started and awaited in loops    1.66 | 1.52 | 1.38
#   the same, a branch a sub-block (what ships)              1.58 | 1.42 | 1.31
#   the copies alone, no arithmetic                          1.23 | 1.23 | 1.23
#   the arithmetic alone, no copies                          1.16 | 1.04 | 0.92
# so the copies' 1.23 ms (706 GB/s of the padded 1280 B rows) are the
# floor and a wider step only takes arithmetic and scalar work out of
# their way: 1.29 at 24 blocks, 1.28 at 32 (3.9 and 5.2 MB of VMEM for 2.6).
VISIT_BYTES = 5 << 18
MAX_VISIT_BLOCKS = 16


def latent_visit_blocks(arena) -> int:
    """Blocks a grid step of :func:`latent_decode_attention` covers over
    this cache (a slab or the whole ``[L, NB, 1, bs, W]``)."""
    block = math.prod(arena.shape[-2:]) * jnp.dtype(arena.dtype).itemsize
    return max(1, min(VISIT_BYTES // block, MAX_VISIT_BLOCKS))


def latent_attention_reference(q, arena, tables, positions, scale: float, *,
                               rank: int, layer=None):
    """XLA reference: gather each slot's blocks, score every row with
    all W lanes in float32, mask by position, weigh the rows' first
    ``rank`` lanes. q [B, H, W]; arena [NB, 1, bs, W] (or the whole
    [L, ...] with ``layer``); tables [B, nb]; positions [B]. Returns
    [B, H, rank] in q's dtype."""
    arena = _layer_slab(arena, layer)
    b, nb = tables.shape
    bs, w = arena.shape[-2:]
    rows = arena[tables][:, :, 0].reshape(b, nb * bs, w).astype(jnp.float32)
    scores = jnp.einsum("bhw,bkw->bhk", q.astype(jnp.float32), rows,
                        precision=jax.lax.Precision.HIGHEST) * scale
    seen = positions[:, None] >= jnp.arange(nb * bs)[None, :]
    probs = jax.nn.softmax(
        jnp.where(seen[:, None, :], scores, MASK_VALUE), axis=-1)
    out = jnp.einsum("bhk,bkc->bhc", probs, rows[..., :rank],
                     precision=jax.lax.Precision.HIGHEST)
    return out.astype(q.dtype)


def _latent_kernel(layer_ref, tables_ref, pos_ref, slot_ref, block_ref,
                   where_ref, count_ref, q_ref, arena, _, o_ref, buf, sems,
                   acc_ref, m_ref, l_ref, *, scale, block_size, num_blocks,
                   per_visit, listed, rank):
    visit = pl.program_id(0)
    half = visit % 2

    def span(u):
        """Visit ``u``'s query position, first block and the last live
        block of its slot."""
        pos = pos_ref[slot_ref[u]]
        return pos, block_ref[u], jnp.minimum(pos // block_size,
                                              num_blocks - 1)

    def live_blocks(u):
        _, first, last = span(u)
        return jnp.minimum(last - first + 1, per_visit)

    def rows_of(into, p):
        """Sub-block ``p``'s rows of buffer half ``into``: the half IS the
        visit's joined ``[T, W]`` tile."""
        return buf.at[into, pl.ds(pl.multiple_of(p * block_size, block_size),
                                  block_size)]

    def copy(into, p, block):
        return pltpu.make_async_copy(arena.at[layer_ref[0], block, 0],
                                     rows_of(into, p), sems.at[into, p])

    def fetch(u, into):
        """Start the copies of visit ``u``'s live sub-blocks: the schedule
        names the table entry of each, the table the arena block. A
        branch a sub-block, so a slot's short last visit costs its own
        blocks only (a ``fori_loop`` here and over the waits read 5%
        slower at sixteen blocks a step: the table at ``VISIT_BYTES``)."""
        n_live = live_blocks(u)
        for p in range(per_visit):
            @pl.when(p < n_live)
            def _start(p=p):
                copy(into, p, tables_ref[where_ref[p * listed + u]]).start()

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
    n_live = live_blocks(visit)

    for p in range(per_visit):
        @pl.when(p < n_live)
        def _arrived(p=p):
            copy(half, p, 0).wait()

    # A DEAD sub-block (past the slot's last block) was neither fetched
    # nor waited for, and its rows of the buffer hold whatever an earlier
    # visit left there. As keys they would be harmless (the position mask
    # selects their scores away), as VALUES not: a masked column's
    # probability is 0 and 0 x NaN is NaN, and nothing says what memory
    # nobody wrote holds. So they are zeroed, where they lie, before
    # either product reads them: a store a dead sub-block of a slot's
    # last visit, nothing on a full one.
    def blank(p, carry):
        rows_of(half, p)[...] = jnp.zeros((block_size, buf.shape[-1]),
                                          buf.dtype)
        return carry

    jax.lax.fori_loop(n_live, per_visit, blank, 0)

    @pl.when(j == 0)
    def _init():
        _init_state(acc_ref, m_ref, l_ref)

    q = q_ref[0, 0]                                          # [H, W]
    rows = buf[half]                                         # [T, W]
    s = jax.lax.dot_general(
        q, rows, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale          # [H, T]
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    # The visit's first block is live, so each row of s keeps a real
    # score and a masked column's probability is exp(MASK - m) = 0.
    s = jnp.where(pos >= j * block_size + cols, s, MASK_VALUE)
    m_prev, l_prev = m_ref[0, :, :1], l_ref[0, :, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.dot(p.astype(rows.dtype), rows[:, :rank],
                 preferred_element_type=jnp.float32)         # [H, rank]
    acc_ref[0] = acc_ref[0] * alpha + pv
    m_ref[0] = jnp.broadcast_to(m_new, m_ref.shape[1:])
    l_ref[0] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(last < j + per_visit)
    def _fin():
        _finalize(o_ref, acc_ref, l_ref)


def _latent_fused(q, arena, tables, positions, visits, *, layer, scale,
                  rank, interpret):
    b, h, w = q.shape
    block_size = arena.shape[3]
    nb = tables.shape[1]
    slot_of, block_of, where_of, count = visits
    n_visits = slot_of.shape[0]
    per = where_of.shape[0] // n_visits

    def by_slot(width):     # the pipeline also indexes the step after
        return pl.BlockSpec(
            (1, 1, h, width),
            lambda v, ly, tab, po, sl, bl, wh, n: (
                sl[jnp.minimum(v, n_visits - 1)], 0, 0, 0))

    out_shape = jax.ShapeDtypeStruct((b, 1, h, rank), q.dtype)
    # The cache stays in HBM: the kernel copies a visit's rows itself.
    # The output starts as zeros and only visited slots are written.
    inputs = [q[:, None], arena, jnp.zeros(out_shape.shape, q.dtype)]
    itemsize = jnp.dtype(arena.dtype).itemsize
    out = pl.pallas_call(
        functools.partial(_latent_kernel, scale=scale, block_size=block_size,
                          num_blocks=nb, per_visit=per, listed=n_visits,
                          rank=rank),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(count[0],),
            in_specs=[by_slot(w)] + [pl.BlockSpec(memory_space=pl.ANY)] * 2,
            out_specs=by_slot(rank),
            scratch_shapes=[
                pltpu.VMEM((2, per * block_size, w), arena.dtype),
                pltpu.SemaphoreType.DMA((2, per))] + _scratch(1, h, rank)),
        out_shape=out_shape,
        # Operand index counts the seven scalar-prefetch arrays.
        input_output_aliases={7 + len(inputs) - 1: 0},
        interpret=interpret,
        name="latent_decode_attn",
        cost_estimate=pl.CostEstimate(
            # Static worst case: every table entry live.
            flops=2 * b * h * nb * block_size * (w + rank),
            bytes_accessed=b * nb * block_size * w * itemsize
            + (q.size + b * h * rank) * jnp.dtype(q.dtype).itemsize,
            transcendentals=b * h * nb * block_size),
    )(_layer_operand(layer), tables.astype(jnp.int32).reshape(-1),
      positions.astype(jnp.int32), slot_of, block_of, where_of, count,
      *inputs)
    return out[:, 0]


def latent_applicable(block_size: int, width: int, rank: int) -> bool:
    """True when auto-dispatch takes the kernel on the TPU: rows and
    values in whole lane tiles, blocks in whole bf16 sublane tiles."""
    return not (width % 128 or rank % 128 or block_size % 16)


def latent_decode_attention(q, arena, tables, positions, scale: float, *,
                            rank: int, layer=None,
                            limits: Optional[jnp.ndarray] = None,
                            visits=None, use_kernel: Optional[bool] = None,
                            interpret: Optional[bool] = None):
    """One absorbed query a slot over its live cache rows.

    q [B, H, W] (``[q~ | q_rope | 0]``); arena the whole latent cache
    [L, NB, 1, bs, W] read at ``layer`` (a traced int32 scalar), or one
    slab [NB, 1, bs, W] with ``layer`` None; tables [B, nb]; positions
    [B]; ``rank``: a row's first ``rank`` lanes are its value. ``limits``
    and ``visits`` as ``paged_decode_attention`` takes them (the schedule
    is ``paged_visits`` over ``latent_visit_blocks(arena)``). Returns
    [B, H, rank] in q's dtype; a freed slot's row is zero with the
    kernel. ``use_kernel``: None = the kernel on a TPU when the shapes
    tile, the XLA reference elsewhere; True forces it (interpreted off
    the TPU)."""
    if (layer is None) != (arena.ndim == 4):
        raise ValueError("a whole arena needs `layer`; a slab takes none")
    block_size, w = arena.shape[-2:]
    if use_kernel is None:
        use_kernel = (jax.default_backend() == "tpu"
                      and latent_applicable(block_size, w, rank))
    if not use_kernel:
        return latent_attention_reference(q, arena, tables, positions,
                                          scale, rank=rank, layer=layer)
    if interpret is None:
        interpret = interpret_default()
    if layer is None:
        layer, arena = 0, arena[None]
    if visits is None:
        visits = paged_visits(tables, positions, limits,
                              block_size=block_size,
                              per_visit=latent_visit_blocks(arena))
    return _latent_fused(q, arena, tables, positions, visits, layer=layer,
                         scale=scale, rank=rank, interpret=interpret)
