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
(runs of live blocks of one slot, slot-major, dead sub-blocks not
fetched, freed slots not visited), and the new rows land through
``paged_kv_write``. A visit's blocks are joined along the key axis and
folded in ONE online-softmax step: the H heads are the rows of two MXU
products (``[H, W] x [W, T]`` and ``[H, T] x [T, rank]``, bf16 operands,
float32 accumulation), so a visit of eight 64-token blocks is 64 x 512
scores, not eight chains of 64 x 64. A dead sub-block of a slot's last
visit is masked by position, not skipped: its operand still holds the
block it held the step before.
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

# Blocks a grid step covers: a row block is 82 KB (64 x 640 bf16), so a
# step is latency before it is bytes. On the v5e, at the cell's load (96
# slots x about 7000 rows, 0.95 ms of bytes a call): 6.32 ms at one block
# a step, 3.43 at two, 2.26 at four, 1.61 at eight (`chip_smoke.py
# kernels`, PR 36).
VISIT_BYTES = 1 << 20
MAX_VISIT_BLOCKS = 8


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
                   where_ref, q_ref, *rest, scale, block_size, num_blocks,
                   per_visit, rank):
    row_refs, rest = rest[:per_visit], rest[per_visit:]
    _, o_ref, acc_ref, m_ref, l_ref = rest
    visit = pl.program_id(0)
    pos = pos_ref[slot_ref[visit]]
    j = block_ref[visit]
    last = jnp.minimum(pos // block_size, num_blocks - 1)

    @pl.when(j == 0)
    def _init():
        _init_state(acc_ref, m_ref, l_ref)

    q = q_ref[0, 0]                                          # [H, W]
    rows = [ref[0, 0, 0] for ref in row_refs]                # [bs, W] each
    rows = rows[0] if per_visit == 1 else jnp.concatenate(rows, axis=0)
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

    def listed(v):          # the pipeline also indexes the step after
        return jnp.minimum(v, n_visits - 1)

    def by_slot(width):
        return pl.BlockSpec(
            (1, 1, h, width),
            lambda v, ly, tab, po, sl, bl, wh: (sl[listed(v)], 0, 0, 0))

    row_specs = [pl.BlockSpec(
        (1, 1, 1, block_size, w),
        lambda v, ly, tab, po, sl, bl, wh, p=p: (
            ly[0], tab[wh[p * n_visits + listed(v)]], 0, 0, 0))
        for p in range(per)]
    out_shape = jax.ShapeDtypeStruct((b, 1, h, rank), q.dtype)
    # The output starts as zeros and only visited slots are written.
    inputs = [q[:, None]] + [arena] * per + [jnp.zeros(out_shape.shape,
                                                       q.dtype)]
    itemsize = jnp.dtype(arena.dtype).itemsize
    out = pl.pallas_call(
        functools.partial(_latent_kernel, scale=scale, block_size=block_size,
                          num_blocks=nb, per_visit=per, rank=rank),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(count[0],),
            in_specs=[by_slot(w)] + row_specs
            + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=by_slot(rank),
            scratch_shapes=_scratch(1, h, rank)),
        out_shape=out_shape,
        # Operand index counts the six scalar-prefetch arrays.
        input_output_aliases={6 + len(inputs) - 1: 0},
        interpret=interpret,
        name="latent_decode_attn",
        cost_estimate=pl.CostEstimate(
            # Static worst case: every table entry live.
            flops=2 * b * h * nb * block_size * (w + rank),
            bytes_accessed=b * nb * block_size * w * itemsize
            + (q.size + b * h * rank) * jnp.dtype(q.dtype).itemsize,
            transcendentals=b * h * nb * block_size),
    )(_layer_operand(layer), tables.astype(jnp.int32).reshape(-1),
      positions.astype(jnp.int32), slot_of, block_of, where_of, *inputs)
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
