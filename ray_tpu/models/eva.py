"""EVA attention (the EvaByte family, ``attention_class`` "eva"): what a
layer keeps of its context, and how the engine's caches hold it.

The sequence is cut into WINDOWS of ``eva_window`` positions (blocks of
the sequence, not a sliding window) and each window into CHUNKS of
``eva_chunk``. A query sees the keys of its own window, causally, and
every earlier window only through one SUMMARY a chunk: the chunk's keys
and values pooled by a softmax of ``k . phi / sqrt(d)`` over the chunk,
``mu`` added to the pooled key (``phi``, ``mu`` ``[KVH, D]`` a layer,
learned). One softmax runs over raw keys and summaries together.

In the engine a slot's context lives in the K/V arena under a
COMPRESSED position (:func:`compressed`): ``S = window / chunk``
summaries for each closed window, then the open window's raw keys. The
causal mask the paged kernels have is then EVA's mask, so
``paged_decode_attn``, ``paged_kv_write`` and
``ops.attention.paged_chunk_attention`` serve unchanged. What is new is
that the cache REWRITES itself: the step that fills a window pools its
``window / block`` blocks into ``S / block`` blocks of summaries, in
place in the window's first blocks (:func:`close_windows`, inside the
decode tick; a prefill chunk is one window and lands summaries and no
raw key at all), and the host gives the other blocks back to the
allocator in the same step (:func:`blocks_held`, :func:`blocks_peak`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

def init_pooling(c, key, n: int):
    """``n`` layers' seeded pooling vectors: ``randn`` clipped to +-1,
    times ``d ** -0.25`` (the published initialiser's scale), float32."""
    shape = (n, c.num_kv_heads, c.head_dim)
    k_phi, k_mu = jax.random.split(key)

    def one(k):
        return (jnp.clip(jax.random.normal(k, shape, jnp.float32), -1.0, 1.0)
                * c.head_dim ** -0.25)

    return {"eva_phi": one(k_phi), "eva_mu": one(k_mu)}


def summaries(c) -> int:
    """Summaries a closed window leaves."""
    return c.eva_window // c.eva_chunk


def check(c, block_size: int) -> None:
    """What the arena asks of the sizes: a window's summaries fill whole
    blocks, so closing one frees whole blocks and nothing is moved; and
    a window is a prefill chunk, whose padded lengths are powers of two."""
    if (c.eva_window % c.eva_chunk or summaries(c) % block_size
            or c.eva_window & (c.eva_window - 1)):
        raise ValueError(
            f"eva attention: the {c.eva_window} / {c.eva_chunk} summaries "
            f"of a window must fill whole blocks of {block_size}, and the "
            f"window be a power of two")


def compressed(positions, c):
    """The arena position of the key written at ``positions`` (ints or an
    array): behind ``S`` summaries for every window before its own."""
    w = c.eva_window
    return positions // w * summaries(c) + positions % w


def blocks_held(tokens: int, c, block_size: int) -> int:
    """Blocks a slot holds with ``tokens`` keys written: its closed
    windows' summaries and the open window as far as it is filled."""
    closed, open_ = divmod(tokens, c.eva_window)
    return (closed * (summaries(c) // block_size)
            + -(-open_ // block_size))


def blocks_peak(tokens: int, c, block_size: int) -> int:
    """The most blocks a slot ever holds on its way to ``tokens`` keys:
    the moment before its last window closes, or the end."""
    closed = tokens // c.eva_window
    if not closed:
        return blocks_held(tokens, c, block_size)
    return max(blocks_held(closed * c.eva_window - 1, c, block_size),
               blocks_held(tokens, c, block_size))


def summarise(k, v, phi, mu, chunk: int):
    """Whole chunks' summaries: ``k``/``v [..., T, KVH, D]`` (rotated
    keys, as the cache holds them), ``phi``/``mu [KVH, D]`` ->
    ``[..., T / chunk, KVH, D]`` in the inputs' dtypes. Pooled in
    float32 by multiply-and-sum, so no matrix unit's precision enters."""
    *lead, t, h, d = k.shape
    kc = k.astype(jnp.float32).reshape(*lead, t // chunk, chunk, h, d)
    vc = v.astype(jnp.float32).reshape(*lead, t // chunk, chunk, h, d)
    logits = jnp.sum(kc * phi, axis=-1) * d ** -0.5      # [..., n, C, KVH]
    a = jax.nn.softmax(logits, axis=-2)[..., None]
    ks = jnp.sum(a * kc, axis=-3) + mu
    vs = jnp.sum(a * vc, axis=-3)
    return ks.astype(k.dtype), vs.astype(v.dtype)


def _tokens_major(blocks):
    """Blocks ``[L, n, KVH, bs, D]`` -> rows ``[L, n * bs, KVH, D]``."""
    lyr, n, h, bs, d = blocks.shape
    return jnp.swapaxes(blocks, 2, 3).reshape(lyr, n * bs, h, d)


def _to_blocks(rows, bs: int):
    """Rows ``[L, T, KVH, D]`` (any leading axis: layers, or a prefill's
    rows) -> blocks ``[L, T / bs, KVH, bs, D]``."""
    lyr, t, h, d = rows.shape
    return jnp.swapaxes(rows.reshape(lyr, t // bs, bs, h, d), 2, 3)


def close_windows(k, v, tables, positions, live, phi, mu, c):
    """The decode tick's compression step, after the layer stack: every
    live row whose new key (at true ``positions [B]``) FILLED its window
    gets that window's blocks, in every layer of the arenas ``k``/``v
    [L, NB, KVH, bs, D]``, pooled into summaries (``phi``/``mu [L, KVH,
    D]``) and written over the window's first ``S / bs`` blocks. This
    tick's queries have attended the raw keys already; the next tick's
    find the summaries, and the host retires the other blocks as it
    dispatches this tick (they are read here before any later program
    can write them). A loop over the rows that close, none on most
    ticks: one program whether or not a row closes. (The masked form,
    every row every tick and the rows that close nothing written to the
    garbage block, cost 127 ms a tick against 5 at 32 slots on the chip:
    PERF.md section 6, PR 43.)"""
    w, bs = c.eva_window, k.shape[3]
    wb, sb = w // bs, summaries(c) // bs
    closing = live & (positions % w == w - 1)
    order = jnp.argsort(~closing)                # rows that close first
    pool = jax.vmap(lambda kk, vv, p, m: summarise(kk, vv, p, m,
                                                   c.eva_chunk))

    def one(i, arenas):
        k, v = arenas
        row = order[i]
        first = positions[row] // w * sb
        blocks = jax.lax.dynamic_slice(tables[row], (first,), (wb,))
        ks, vs = pool(_tokens_major(k[:, blocks]),
                      _tokens_major(v[:, blocks]), phi, mu)
        return (k.at[:, blocks[:sb]].set(_to_blocks(ks, bs)),
                v.at[:, blocks[:sb]].set(_to_blocks(vs, bs)))

    return jax.lax.fori_loop(0, jnp.sum(closing), one, (k, v))


def chunk_blocks(k, v, phi, mu, full, c, bs: int):
    """What a prefill chunk lands in the arena, one layer: ``k``/``v [N,
    S, KVH, D]`` -> blocks ``[N * S / bs, KVH, bs, D]``. A row whose
    chunk is a whole window (``full [N]``; ``S`` is then the window)
    lands its summaries in its first ``S / bs`` blocks, and the host's
    table sends the rest to the garbage block; any other row lands its
    raw keys, the open window the ticks go on to fill."""
    n, s, h, d = k.shape
    kb, vb = _to_blocks(k, bs), _to_blocks(v, bs)
    if s == c.eva_window:
        ks, vs = summarise(k, v, phi, mu, c.eva_chunk)
        sb = summaries(c) // bs
        pick = full[:, None, None, None, None]
        kb = kb.at[:, :sb].set(jnp.where(pick, _to_blocks(ks, bs), kb[:, :sb]))
        vb = vb.at[:, :sb].set(jnp.where(pick, _to_blocks(vs, bs), vb[:, :sb]))
    return (kb.reshape(n * (s // bs), h, bs, d),
            vb.reshape(n * (s // bs), h, bs, d))
