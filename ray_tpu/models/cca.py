"""Compressed Convolutional Attention (the ZAYA1 family, ``model_type``
"zaya"): attention that runs ENTIRELY in a compressed latent.

A token's normed hidden state ``h_t [E]`` is projected DOWN to ``q~ [H
D]`` and ``k~ [KVH D]`` (``H D`` and ``KVH D`` both under ``E``: 1024 and
256 of 2048 as published) and to two value halves; ``u = [q~ | k~]``
goes through two causal convolutions of two taps each (depthwise, then
grouped a head), a mean of q~ and k~ is added back, each head is
L2-normalised (keys times one learned temperature a KV head), rope turns
the first ``rotary_dim`` dims, and plain causal GQA attention runs on
the result; ``wo [H, D, E]`` leaves the latent. The value of token ``t``
is ``[h_t Wv1 | h_{t-1} Wv2]``: its second half is the PREVIOUS token's.

So one layer keeps TWO stores in the engine. Its ``k^`` (after rope) and
``v`` are ordinary K/V: paged, in the arena, read by the kernels every
other family's attention uses. And the next token needs, of the last
one, what no arena holds: the convolutions' last inputs ``u_{t-1}``,
``a_{t-1}`` and the value half ``h_{t-1} Wv2``: the TAIL, one row ``[u |
a | v2]`` a slot a layer in ``paged_kv.TailCache`` beside the arena. A
prefill chunk that is not its prompt's first starts from the slot's
row; a right-padded row leaves the tail of its last REAL token.

Everything here is the layer's first half on ``[rows, S]`` tokens, S = 1
for a tick: :func:`project` (one matmul), :func:`mix` (steps 2-5 of
ISSUE 46 A; rope is the caller's, after it). The router that goes with
the family is ``ops.moe.route_mlp_top1``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops import ssm

F32 = jnp.float32
# Taps of each convolution (the published ``cca_time0``, ``cca_time1``):
# the tail is ONE row because of it.
TAPS = 2


def dims(c):
    """(conv_dim, value half): the channels of ``u = [q~ | k~]`` and of
    each half of a token's value."""
    return ((c.num_heads + c.num_kv_heads) * c.head_dim,
            c.num_kv_heads * c.head_dim // 2)


def tail_width(c) -> int:
    """Values a slot a layer keeps between tokens: ``[u | a | v2]``."""
    conv_dim, half = dims(c)
    return 2 * conv_dim + half


def init_attention(c, key, n: int):
    """A run of ``n`` layers' attention weights, stacked. Seeded so that
    dropping a term shows: ``cca_tau`` uniform in 0.5..1.5, the biases
    of both convolutions uniform in -0.5..0.5, not zeros. ``wo`` at a
    QUARTER of fan-in scale: over a few hundred random tokens any seeded
    softmax is near-flat, so attention's output is nearly the same
    vector at every position; at full scale it swamps what the stream
    keeps of the token itself, every token's router sees the same input
    and one expert of a top-1 layer takes four rows in five (measured on
    the CPU at 10 layers, PR 46); at a quarter the busiest takes about
    twice its share, a trained router's unevenness."""
    E, H, D = c.hidden_size, c.num_heads, c.head_dim
    conv_dim, half = dims(c)
    k = jax.random.split(key, 7)

    def dense(key, fan_in, *shape):
        out = jax.random.normal(key, shape, F32) * fan_in ** -0.5
        return out.astype(c.dtype)

    def uniform(key, lo, hi, *shape):
        return jax.random.uniform(key, shape, F32, lo, hi)

    return {
        # [Wq | Wk | Wv1 | Wv2]: one product a token.
        "cca_in": dense(k[0], E, n, E, conv_dim + 2 * half),
        "cca_conv1_w": dense(k[1], TAPS, n, TAPS, conv_dim),
        "cca_conv1_b": uniform(k[2], -0.5, 0.5, n, conv_dim).astype(c.dtype),
        # A head's own matrix: [heads, tap 0's D inputs | tap 1's, out].
        "cca_conv2_w": dense(k[3], TAPS * D, n, conv_dim // D, TAPS * D, D),
        "cca_conv2_b": uniform(k[4], -0.5, 0.5, n, conv_dim).astype(c.dtype),
        "cca_tau": uniform(k[5], 0.5, 1.5, n, c.num_kv_heads),
        "wo": (0.25 * dense(k[6], H * D, n, H, D, E).astype(F32)
               ).astype(c.dtype),
    }


def project(h, layer, c):
    """``(u [.., conv_dim], v1, v2 [.., half])`` of normed ``h``."""
    conv_dim, half = dims(c)
    proj = jnp.einsum("bse,ef->bsf", h, layer["cca_in"].astype(c.dtype))
    return (proj[..., :conv_dim], proj[..., conv_dim:conv_dim + half],
            proj[..., conv_dim + half:])


def _shifted(x, first):
    """``x [N, S, C]`` one position later, ``first [N, C]`` in front."""
    return jnp.concatenate([first[:, None].astype(x.dtype), x[:, :-1]],
                           axis=1)


def _l2_heads(x, c):
    """``sqrt(D) x / |x|`` a head, float32: x [.., heads, D]."""
    return x * jax.lax.rsqrt(
        jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6) * c.head_dim ** 0.5


def mix(u, v1, v2, tail, layer, c, lengths=None):
    """Steps 2-5 on ``[N, S]`` positions, behind ``tail [N, W]`` (each
    row's ``[u | a | v2]`` of the position before the first; None = an
    empty history, zeros: ``a_{-1}`` is the second convolution's padding,
    not ``b1``). Returns ``(q [N, S, H, D], k [N, S, KVH, D]`` float32,
    normalised and NOT yet rotated, ``v [N, S, KVH, D]``, the next
    tail)``: the row of position ``lengths - 1`` (``lengths [N]``, each
    at least 1: the real tokens of a right-padded row; None = all S).

    The first convolution's output is rounded to the model's dtype
    before the second reads it: that is what the tail carries, so a
    chunk's first position reads the bits its neighbour inside a chunk
    reads."""
    n, s, conv_dim = u.shape
    H, KVH, D = c.num_heads, c.num_kv_heads, c.head_dim
    if tail is None:
        tail = jnp.zeros((n, tail_width(c)), u.dtype)
    u_prev, a_prev, v2_prev = (tail[:, :conv_dim],
                               tail[:, conv_dim:2 * conv_dim],
                               tail[:, 2 * conv_dim:])
    w1 = layer["cca_conv1_w"].astype(F32)
    a = (w1[0] * _shifted(u, u_prev).astype(F32) + w1[1] * u.astype(F32)
         + layer["cca_conv1_b"].astype(F32)).astype(c.dtype)
    # Both taps are one product a head over [a_{t-1} | a_t], rounded
    # once like every projection's (float32 out of bf16 operands inside
    # a layer scan is a dot the CPU runtime does not have).
    taps = jnp.concatenate([_shifted(a, a_prev).reshape(n, s, -1, D),
                            a.reshape(n, s, -1, D)], axis=-1)
    conv = (jnp.einsum("nsgi,gio->nsgo", taps,
                       layer["cca_conv2_w"].astype(c.dtype)).astype(F32)
            + layer["cca_conv2_b"].astype(F32).reshape(-1, D))
    raw = u.astype(F32).reshape(n, s, H + KVH, D)
    q_raw, k_raw = raw[:, :, :H], raw[:, :, H:]
    share = H // KVH
    q = conv[:, :, :H] + 0.5 * (q_raw + jnp.repeat(k_raw, share, axis=2))
    k = conv[:, :, H:] + 0.5 * (
        q_raw.reshape(n, s, KVH, share, D).mean(axis=3) + k_raw)
    q = _l2_heads(q, c)
    k = _l2_heads(k, c) * layer["cca_tau"].astype(F32)[:, None]
    # Head 0.. of the value: the token's own half, then the previous
    # token's.
    v = jnp.concatenate([v1, _shifted(v2, v2_prev)], axis=-1).reshape(
        n, s, KVH, D)
    rows = jnp.concatenate([u, a, v2], axis=-1)
    if lengths is None:
        tail = rows[:, -1]
    else:
        tail = ssm.conv_tail(rows, lengths, TAPS)[:, 0]
    return q, k, v, tail
