"""Multi-head LATENT attention (DeepSeek-V2/V3; ``kimi_k2``): the
attention block of a ``"latent_attention"`` layer, SERVING ONLY.

The published layer (``transformers`` ``deepseek_v3``), for ``h =
rms(x)`` and H heads of ``Dn`` unrotated + ``Dr`` rotated dims:

    c_q = rms(h W_qa)                              [Rq]
    [q_nope | q_rope]_head = c_q W_qb              [H, Dn + Dr]
    [c_kv | k_rope] = h W_kva;  c_kv = rms(c_kv)   [Rkv], [Dr]
    [k_nope | v]_head = c_kv W_kvb                 [H, Dn + Dv]
    score = (q_nope . k_nope + rope(q_rope) . rope(k_rope)) * s
    attn = concat_head(softmax(score) v) W_o

``k_rope`` is ONE vector a token, shared by every head, so a token's
keys and values are a function of ``Rkv + Dr`` numbers: what the engine
caches (``paged_kv.LatentKVCache``: ``[c_kv after its norm | k_rope
after rope]``, 576 values for Kimi K2 against 64 x 320 for per-head K
and V). The two forms of the one attention:

* EXPANDED (prefill, :func:`prefill_layer`): K and V per head for the
  chunk's own tokens, and for the earlier chunks' latents block by block
  inside ``ops.attention.paged_chunk_attention``'s loop (896 FLOPs a
  (query, key, head) with the expansion's share, against 2176 absorbed).
* ABSORBED (the tick, :func:`tick_layer`): with ``W_kvb`` split by head
  into ``W_uk`` and ``W_uv``, ``q~ = q_nope W_uk^T [Rkv]``, ``score =
  (q~ . c_kv + q_rope . k_rope) * s``, ``o~ = sum p c_kv``, ``o = o~
  W_uv``: the cache row is key AND value, read once
  (``ops/latent_decode_attention.py``), the H heads of a slot the rows
  of its two matmuls.

THE TREE (a run's, stacked over its layers; :func:`init_attention`) is
held as the tick contracts it, which is the checkpoint's re-laid by
columns, as ``llama.heads_major`` re-lays ``wq``: ``wq_a [E, Rq]``,
``q_a_norm [Rq]``, ``wq_nope [Rq, H * Dn]`` and ``wq_rope [Rq, H * Dr]``
(``q_b_proj``'s columns of each head, split), ``wkv_a [E, Rkv + Dr]``,
``kv_a_norm [Rkv]``, ``w_uk [H, Dn, Rkv]`` and ``w_uv [H, Rkv, Dv]``
(``kv_b_proj``'s rows of each head, split and, for ``w_uk``, transposed),
``wo [H, Dv, E]``. Rope rotates the half-split pairing (``ops/rope.py``);
a checkpoint's interleaved rope columns ``(2i, 2i + 1)`` are
de-interleaved once at load, which ``deepseek_v3`` does at run time.

The cache row is padded to whole 128-lane tiles (576 -> 640: the TPU's
tiled HBM layout pads the minor axis of a 576-wide array to 640 whatever
the program says, so the pad is stated, zeroed, and contracted as zeros).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops.attention import paged_chunk_attention
from ray_tpu.ops.latent_decode_attention import latent_decode_attention
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.paged_decode_attention import paged_kv_write
from ray_tpu.ops.rope import apply_rope

LANES = 128


def latent_width(c) -> int:
    """Values a token keeps a layer: the latent and the shared rope key."""
    return c.kv_lora_rank + c.qk_rope_head_dim


def row_width(c) -> int:
    """:func:`latent_width` padded to whole lane tiles: a cache row."""
    return -(-latent_width(c) // LANES) * LANES


def _yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(c) -> float:
    """``(Dn + Dr) ** -0.5``, times YaRN's ``mscale(factor,
    mscale_all_dim) ** 2`` where the config scales its rope
    (``DeepseekV3Attention.__init__``)."""
    scale = (c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5
    group = dict(c.rope_scaling or ())
    if group.get("mscale_all_dim"):
        scale *= _yarn_mscale(group["factor"], group["mscale_all_dim"]) ** 2
    return scale


def yarn_frequencies(c) -> Tuple[np.ndarray, float]:
    """(inverse frequencies ``[Dr / 2]``, the factor on cos and sin):
    ``transformers``' ``_compute_yarn_parameters`` over the rotated dims.
    The ramp keeps the fast frequencies and divides the slow ones by
    ``factor`` at EVERY position (it is not dynamic). Plain rope without
    ``rope_scaling``."""
    dim, base = c.qk_rope_head_dim, float(c.rope_theta)
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    group = dict(c.rope_scaling or ())
    if not group:
        return (1.0 / pos_freqs).astype(np.float32), 1.0
    kind = group.get("type", group.get("rope_type"))
    if kind != "yarn":
        raise ValueError(f"rope_scaling type {kind!r}: only 'yarn' is run")
    factor = float(group["factor"])
    original = group["original_max_position_embeddings"]
    mscale, all_dim = group.get("mscale"), group.get("mscale_all_dim")
    attention_factor = (
        _yarn_mscale(factor, mscale) / _yarn_mscale(factor, all_dim)
        if mscale and all_dim else _yarn_mscale(factor))

    def correction_dim(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(group.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction_dim(group.get("beta_slow", 1))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    inv = (1.0 / (factor * pos_freqs)) * ramp + (1.0 / pos_freqs) * (1 - ramp)
    return inv.astype(np.float32), attention_factor


def rope_tables(c, positions):
    """cos/sin ``positions.shape + [Dr / 2]`` (float32) of the rotated
    dims at ``positions``."""
    inv, factor = yarn_frequencies(c)
    angles = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return (cos, sin) if factor == 1.0 else (cos * factor, sin * factor)


def init_attention(c, key, n: int) -> Dict[str, Any]:
    """A run of ``n`` latent-attention layers' seeded attention weights
    (the module docstring's tree). The two inner norms are uniform in
    0.5..1.5, not ones, so that dropping one shows."""
    E, H = c.hidden_size, c.num_heads
    rq, rkv = c.q_lora_rank, c.kv_lora_rank
    dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    k = jax.random.split(key, 9)

    def dense(key, fan_in, *shape):
        out = jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5
        return out.astype(c.dtype)

    def norm(key, *shape):
        return jax.random.uniform(key, shape, jnp.float32, 0.5,
                                  1.5).astype(c.dtype)

    return {
        "wq_a": dense(k[0], E, n, E, rq), "q_a_norm": norm(k[1], n, rq),
        "wq_nope": dense(k[2], rq, n, rq, H * dn),
        "wq_rope": dense(k[3], rq, n, rq, H * dr),
        "wkv_a": dense(k[4], E, n, E, rkv + dr),
        "kv_a_norm": norm(k[5], n, rkv),
        "w_uk": dense(k[6], rkv, n, H, dn, rkv),
        "w_uv": dense(k[7], rkv, n, H, rkv, dv),
        "wo": dense(k[8], H * dv, n, H, dv, E),
    }


def _row(parts, c):
    """``parts`` (the latent-wide and the rope-wide part of a cache row
    or of an absorbed query) joined and zero-padded to ``row_width``."""
    pad = row_width(c) - latent_width(c)
    if pad:
        parts = parts + [jnp.zeros(parts[0].shape[:-1] + (pad,),
                                   parts[0].dtype)]
    return jnp.concatenate(parts, axis=-1)


def _queries(h, layer, cos, sin, c):
    """(q_nope [B, S, H, Dn], q_rope [B, S, H, Dr] rotated)."""
    b, s, _ = h.shape
    with jax.named_scope("mla/q_proj"):
        cq = rms_norm(jnp.einsum("bse,er->bsr", h,
                                 layer["wq_a"].astype(c.dtype)),
                      layer["q_a_norm"], c.rms_eps)
        q_nope = jnp.einsum("bsr,rn->bsn", cq,
                            layer["wq_nope"].astype(c.dtype))
        q_rope = jnp.einsum("bsr,rn->bsn", cq,
                            layer["wq_rope"].astype(c.dtype))
        q_nope = q_nope.reshape(b, s, c.num_heads, c.qk_nope_head_dim)
        q_rope = apply_rope(
            q_rope.reshape(b, s, c.num_heads, c.qk_rope_head_dim), cos, sin)
    return q_nope, q_rope


def _latents(h, layer, cos, sin, c):
    """A token's cache row ``[B, S, 1, W]``: ``[rms(c_kv) | rope(k_rope)
    | 0]`` in the model's dtype."""
    rkv = c.kv_lora_rank
    with jax.named_scope("mla/kv_compress"):
        ckv = jnp.einsum("bse,ew->bsw", h, layer["wkv_a"].astype(c.dtype))
        latent = rms_norm(ckv[..., :rkv], layer["kv_a_norm"], c.rms_eps)
        k_rope = apply_rope(ckv[..., None, rkv:], cos, sin)
        return _row([latent[..., None, :], k_rope], c)


def _out(o, layer, c):
    return jnp.einsum("bshd,hde->bse", o, layer["wo"].astype(c.dtype))


def tick_layer(x, layer, c, arena, li, cos, sin, block_idx, offset, tables,
               positions, visits, use_kernel: bool):
    """The attention half of a latent layer in the engine's forward over
    the cache (the tick: S = 1): write each slot's new row into layer
    ``li`` of ``arena [L, NB, 1, bs, W]``, then attend every live row in
    ABSORBED form. x [B, S, E]; cos/sin [B, S, Dr / 2];
    block_idx/offset/positions [B, S]. Returns (attention's output
    [B, S, E], the arena)."""
    rkv = c.kv_lora_rank
    h = rms_norm(x, layer["attn_norm"], c.rms_eps)
    q_nope, q_rope = _queries(h, layer, cos, sin, c)
    row = _latents(h, layer, cos, sin, c)
    with jax.named_scope("mla/absorb"):
        q_lat = jnp.einsum("bshd,hdc->bshc", q_nope,
                           layer["w_uk"].astype(c.dtype))
        q_row = _row([q_lat, q_rope], c)                  # [B, S, H, W]
    with jax.named_scope("mla/attend"):
        if use_kernel:
            arena = view = paged_kv_write(arena, row, li, block_idx, offset)
            at = li
        else:
            slab = jax.lax.dynamic_index_in_dim(arena, li, 0, keepdims=False)
            view = slab.at[block_idx.reshape(-1), :, offset.reshape(-1)].set(
                row.reshape(-1, *row.shape[2:]).astype(slab.dtype))
            arena = jax.lax.dynamic_update_index_in_dim(arena, view, li, 0)
            at = None
        o_lat = jnp.stack([
            latent_decode_attention(
                q_row[:, j], view, tables, positions[:, j], c.attn_scale,
                rank=rkv, layer=at, visits=visits and visits[j],
                use_kernel=use_kernel)
            for j in range(q_row.shape[1])], axis=1)      # [B, S, H, Rkv]
    with jax.named_scope("mla/out_proj"):
        o = jnp.einsum("bshc,hcd->bshd", o_lat.astype(x.dtype),
                       layer["w_uv"].astype(c.dtype))
        return _out(o, layer, c), arena


def expand(rows, layer, c):
    """Cache rows ``[..., T, W]`` as per-head keys ``[..., T, H, Dn +
    Dr]`` and values ``[..., T, H, Dv]``: ``c_kv W_uk``, the shared rope
    key repeated, ``c_kv W_uv``."""
    rkv = c.kv_lora_rank
    latent = rows[..., :rkv].astype(c.dtype)
    k_nope = jnp.einsum("...c,hdc->...hd", latent,
                        layer["w_uk"].astype(c.dtype))
    v = jnp.einsum("...c,hcd->...hd", latent, layer["w_uv"].astype(c.dtype))
    k_rope = jnp.broadcast_to(
        rows[..., None, rkv:latent_width(c)].astype(c.dtype),
        k_nope.shape[:-1] + (c.qk_rope_head_dim,))
    return jnp.concatenate([k_nope, k_rope], axis=-1), v


def _chunk_attention_kernels(q, k, v, arena, li, tables, layer, c):
    """The chunk's attention through ``latent_prefill_attn``: one call a
    run of keys (each step of earlier blocks, expanded; then the chunk's
    own, causal), merged by their log-sum-exps. q/k ``[N, S, H, Dk]``, v
    ``[N, S, H, Dv]``; returns ``[N, S, H, Dv]`` in q's dtype."""
    from ray_tpu.ops.latent_prefill_attention import attend_run, merge

    n, s, h, dk = q.shape
    # Whole lane tiles for the two products' contraction (192 -> 256).
    pad = -dk % LANES

    def heads_first(a, widen=False):
        a = jnp.swapaxes(a, 1, 2)                         # [N, H, S, D]
        return jnp.pad(a, ((0, 0),) * 3 + ((0, pad),)) if widen and pad else a

    qh = heads_first(q, True)
    run = functools.partial(attend_run, qh, scale=c.attn_scale)
    out = run(heads_first(k, True), heads_first(v), causal=True)
    m_blocks = tables.shape[1]
    if m_blocks:
        bs = arena.shape[3]
        # A step's expanded keys and values: at most a chunk's worth.
        g = math.gcd(m_blocks, max(s // bs, 1))

        def step(carry, idx):                             # idx [N, g]
            kb, vb = expand(arena[li, idx].reshape(n, g * bs, -1), layer, c)
            return merge(carry, run(heads_first(kb, True), heads_first(vb),
                                    causal=False)), None

        out, _ = jax.lax.scan(
            step, out, jnp.swapaxes(tables.reshape(n, m_blocks // g, g), 0, 1))
    return jnp.swapaxes(out[0], 1, 2).astype(q.dtype)


def prefill_layer(x, layer, c, arena, li, cos, sin, tables, chunk_pos: int,
                  use_kernel: bool = False):
    """The attention half of a latent layer for ONE CHUNK of a prompt in
    EXPANDED form: x [N, S, E] at positions ``chunk_pos + arange(S)``
    (cos/sin [S, Dr / 2]); the earlier chunks' rows are read out of
    ``arena`` through ``tables [N, m]`` (positions ``0 .. chunk_pos``)
    and expanded a few blocks at a time inside the attention's loop, so
    no per-head K/V of a whole prefix exists: through the
    ``latent_prefill_attn`` kernel with ``use_kernel``, else in
    ``jax.numpy`` (``paged_chunk_attention``'s loop). Returns (attention's
    output [N, S, E], the chunk's rows [N, S, 1, W] for the cache)."""
    n, s, _ = x.shape
    h = rms_norm(x, layer["attn_norm"], c.rms_eps)
    q_nope, q_rope = _queries(h, layer, cos, sin, c)
    row = _latents(h, layer, cos, sin, c)
    with jax.named_scope("mla/attend"):
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k, v = expand(row[:, :, 0], layer, c)

        if use_kernel:
            o = _chunk_attention_kernels(q, k, v, arena, li, tables, layer, c)
        else:
            def from_cache(blocks):
                # [N, g, 1, bs, W] -> keys and values of g * bs positions
                return expand(blocks.reshape(n, -1, blocks.shape[-1]),
                              layer, c)

            o = paged_chunk_attention(
                q, k, v, arena, None, li, tables, 0, chunk_pos, c.attn_scale,
                expand=from_cache, key_blocks=2, key_step=128)
    with jax.named_scope("mla/out_proj"):
        return _out(o, layer, c), row
