"""Paged decode attention: block-table gather over a shared KV arena.

The dense fused kernel (``ops/decode_attention.py``) still streams each
slot's full ``S_max`` stripe of the pooled cache per tick — a slot 40
tokens into a 512-token cache pays for 512. Here the pooled cache is an
ARENA of fixed-size blocks (``[num_blocks, KVH, block_size, D]``) and
each slot owns a small BLOCK TABLE naming the blocks it has actually
filled, so a tick reads only live prefix blocks (vLLM paged-attention,
on TPU: block tables ride scalar prefetch so the BlockSpec ``index_map``
can gather arena blocks by table lookup before the kernel body runs).
The arena is HEADS-MAJOR inside a block: the TPU lowering takes a block
only in whole trailing (sublane, lane) tiles, and ``(block_size, D)``
tiles exactly for bf16 and int8 at any head count, where a trailing
``(KVH, D)`` would pad small head counts up to a tile in HBM.

Two bandwidth levers stack:

* **Paging** — grid ``(batch, table_blocks)``, one whole block (every
  kv head) per step, with dead table entries repeating the last live
  block: pallas skips the re-fetch when the mapped block index does not
  change between sequential grid steps, so a slot's dead tail costs
  ~zero HBM traffic (and ``pl.when`` skips its compute).
* **int8 KV quantization** — the arena stores K/V as int8 with
  per-token/per-kv-head fp32 scales kept in block-shaped sidecars
  (``[num_blocks, KVH, block_size]``), gathered by the same table;
  dequantization happens in-register after the block is resident, so
  bytes-per-token roughly halve against bf16.

Same online-softmax core as the dense kernel: fp32 accumulation with a
running max/sum in VMEM scratch; per-slot positions arrive via scalar
prefetch and gate both block skip and the in-block causal mask.

Dispatch mirrors ``decode_attention``: kernel on TPU when shapes tile,
interpret mode when forced (CPU tier-1), XLA reference otherwise.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ray_tpu.ops.decode_attention import (_attend_block, _finalize,
                                          _init_state, _interpret_default,
                                          _scratch, pltpu)


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


def paged_attention_reference(q, arena_k, arena_v, tables, positions,
                              scale: Optional[float] = None, *,
                              k_scale=None, v_scale=None):
    """XLA reference: gather blocks into dense layout, dequantize when the
    arena is quantized, then run the positional-mask softmax attention.

    q [B, Hq, D]; arena [NB, KVH, bs, D]; tables [B, nb] (row j = slot's
    j-th logical block; dead entries may repeat blocks — masked out by
    ``positions``); positions [B].
    """
    from ray_tpu.ops.decode_attention import decode_attention_reference

    ck = gather_kv(arena_k, tables)
    cv = gather_kv(arena_v, tables)
    if k_scale is not None:
        ck = dequantize_block(ck, gather_kv(k_scale, tables))
        cv = dequantize_block(cv, gather_kv(v_scale, tables))
    return decode_attention_reference(q, ck, cv, positions,
                                      scale).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _paged_kernel(tables_ref, pos_ref, q_ref, k_ref, v_ref, *rest,
                  scale, block_size, num_blocks, quantized):
    if quantized:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _init_state(acc_ref, m_ref, l_ref)

    # The slot's query sits at absolute position `pos`; logical blocks
    # wholly past it are dead (their table entries repeat the last live
    # block, so the pipeline fetches nothing new for them either).
    pos = pos_ref[pl.program_id(0)]

    @pl.when(j * block_size <= pos)
    def _body():
        _attend_block(q_ref[0], k_ref[0], v_ref[0], pos, j * block_size,
                      acc_ref, m_ref, l_ref, scale=scale,
                      k_scale=ks_ref[0] if quantized else None,
                      v_scale=vs_ref[0] if quantized else None)

    @pl.when(j == num_blocks - 1)
    def _fin():
        _finalize(o_ref, acc_ref, l_ref)


def _paged_fused(q, arena_k, arena_v, tables, positions, *, k_scale,
                 v_scale, scale, interpret):
    b, hq, d = q.shape
    _, hkv, block_size, _ = arena_k.shape
    nb = tables.shape[1]
    group = hq // hkv
    quantized = k_scale is not None

    qg = q.reshape(b, hkv, group, d)
    q_spec = pl.BlockSpec((1, hkv, group, d),
                          lambda b_, j, tab, po: (b_, 0, 0, 0))
    # The table gather IS the index_map: scalar-prefetched block tables
    # choose which arena block each grid step streams into VMEM.
    kv_spec = pl.BlockSpec((1, hkv, block_size, d),
                           lambda b_, j, tab, po: (tab[b_, j], 0, 0, 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    inputs = [qg, arena_k, arena_v]
    if quantized:
        sc_spec = pl.BlockSpec((1, hkv, block_size),
                               lambda b_, j, tab, po: (tab[b_, j], 0, 0))
        in_specs += [sc_spec, sc_spec]
        inputs += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nb),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=_scratch(hkv, group, d),
    )
    kernel = functools.partial(
        _paged_kernel, scale=scale, block_size=block_size, num_blocks=nb,
        quantized=quantized)
    itemsize = jnp.dtype(arena_k.dtype).itemsize
    kv_bytes = 2 * b * nb * hkv * block_size * d * itemsize
    if quantized:
        kv_bytes += 2 * b * nb * hkv * block_size * 4    # fp32 scales
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
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
    )(tables.astype(jnp.int32), positions.astype(jnp.int32), *inputs)
    return out.reshape(b, hq, d)


def paged_applicable(block_size: int, d: int, hq: int, hkv: int) -> bool:
    """True when auto-dispatch takes the paged fused kernel on TPU for
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
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Decode-step attention over a paged KV arena.

    q [B, Hq, D]; arena_k/v [NB, KVH, bs, D] (int8 when ``k_scale`` /
    ``v_scale`` [NB, KVH, bs] are given); tables [B, nb] int32 block
    table (row j = the slot's j-th logical block; dead tail entries
    should repeat the last live block); positions [B].

    ``use_kernel``: None = auto (fused kernel on TPU when the shapes
    tile, XLA reference elsewhere); True forces the kernel (interpret
    mode off-TPU — the CPU tier-1 path); False forces the reference.
    """
    b, hq, d = q.shape
    hkv, block_size = arena_k.shape[1], arena_k.shape[2]
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
                                         positions, scale,
                                         k_scale=k_scale, v_scale=v_scale)
    if interpret is None:
        interpret = _interpret_default()
    return _paged_fused(q, arena_k, arena_v, tables, positions,
                        k_scale=k_scale, v_scale=v_scale, scale=scale,
                        interpret=interpret)
