"""The plain reference for the EvaByte family (``attention_class`` "eva":
EvaByte 6.5B), its equations in float32 (ISSUE 43, Tentpole 1).

Straightforward ``jax.numpy`` at matmul precision ``highest``: no cache,
no chunking of the prompt, no kernel, no compressed position. Every raw
key and every summary of the whole sequence is made, and a mask says
which of them a query sees; a block of queries at a time, so 6,000
positions fit. It imports nothing from the program.

The equations. Token ids ``t`` (bytes and specials, below 320);
``N(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)`` (``norm_add_unit_offset``):

* ``x0 = embed[t]``; ``h = x + Attn(N(x; w_attn))``; ``y = h + W_d
  (silu(W_g N(h; w_mlp)) * W_u N(h; w_mlp))``; all in float32
  (``fp32_skip_add``). ``logits = N(y_final; w_final) W_head``, ``W_head
  [E, heads x V]`` laid out ``[E, heads, V]`` [ASSUMED]: head ``j``
  predicts byte ``t + 1 + j``; :func:`logits` and :func:`gaps` read head
  0, the next byte's, which the engine samples [ASSUMED: next-byte
  decoding].
* EVA attention, a head of ``d`` dims: ``q, k, v`` projected without
  bias; ``q, k`` rotated (rotate-half over all ``d`` dims, theta
  ``rope_theta``, no scaling). Window ``W = eva_window``, chunk ``C =
  eva_chunk``, learned ``phi, mu`` in R^d a head. Chunk ``c`` holds
  positions ``C c .. C c + C - 1``, window ``w`` positions ``W w .. W w +
  W - 1``.
* Summary of chunk ``c``: ``a_j = softmax over j in c of (k_j . phi) /
  sqrt(d)`` [ASSUMED from the public ``eva.py``, not a config key: the
  ``1 / sqrt(d)`` on the pooling logits]; ``k~_c = sum_j a_j k_j + mu``
  [ASSUMED from the same file: ``+ mu`` on the pooled key]; ``v~_c =
  sum_j a_j v_j``. The keys pooled are the ROTATED keys.
* Query ``i`` in window ``w`` sees ``S_i = {tokens j of window w with j
  <= i} U {summaries of every chunk of windows 0 .. w - 1}``: not its
  own window's summaries, not earlier windows' raw tokens. ``o_i`` is ONE
  softmax over ``S_i`` at ``1 / sqrt(d)``, raw keys and summary keys
  together, in float32 (``mixedp_attn``); ``Attn = W_o concat(o)``.

Two identities follow: with ``C = 1`` and ``phi = mu = 0`` a summary is
its token, so EVA is causal attention; a sequence of at most ``W`` tokens
is causal attention whatever ``phi`` and ``mu``
(``tests/test_evabyte.py``).

It reads the program's parameter tree (``models/llama.py::
_init_windowed_params`` with ``models/eva.py::init_pooling``): ``embed
[V, E]``, ``lm_head [E, heads x V]``, ``final_norm [E]`` and ``runs``:
one tree of stacked layers holding ``attn_norm``, ``mlp_norm [E]``,
``wq [E, H, D]``, ``wk``/``wv [E, KVH, D]``, ``wo [H, D, E]``,
``eva_phi``/``eva_mu [KVH, D]``, ``w_gate``/``w_up [E, M]``, ``w_down
[M, E]``. ``config`` needs ``num_layers``, ``num_heads``,
``num_kv_heads``, ``head_dim``, ``rope_theta``, ``rms_eps``,
``eva_window``, ``eva_chunk``, ``vocab_size``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512


def _norm(x, weight, eps):
    """RMSNorm with a unit offset: scaled by ``1 + weight``."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + weight.astype(F32))


def _rope(x, theta: float):
    """x [S, H, D] at positions 0..S-1, rotate-half: dim j pairs with
    j + D / 2."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def summarise(k, v, phi, mu, chunk: int):
    """``k``/``v [S, H, D]`` (``S`` a multiple of ``chunk``), ``phi``/
    ``mu [H, D]`` -> one pooled pair a chunk, ``[S / chunk, H, D]``."""
    s, h, d = k.shape
    kc = k.reshape(s // chunk, chunk, h, d)
    vc = v.reshape(s // chunk, chunk, h, d)
    a = jax.nn.softmax(jnp.einsum("nchd,hd->nch", kc, phi) / d ** 0.5,
                       axis=1)
    return (jnp.einsum("nch,nchd->nhd", a, kc) + mu,
            jnp.einsum("nch,nchd->nhd", a, vc))


def seen(s: int, window: int, chunk: int):
    """``[S, S + S / chunk]`` bool: which raw keys (first ``S`` columns)
    and which summaries query ``i`` sees."""
    i = jnp.arange(s)
    raw = (i[None, :] <= i[:, None]) & (i[None, :] // window
                                        == i[:, None] // window)
    of_window = jnp.arange(s // chunk) * chunk // window
    return jnp.concatenate(
        [raw, of_window[None, :] < (i // window)[:, None]], axis=1)


def eva_attention(h, layer, config):
    """EVA attention on normed ``h [S, E]`` (``S`` a multiple of the
    chunk)."""
    c = config
    s = h.shape[0]
    heads, kvh, d = int(c.num_heads), int(c.num_kv_heads), int(c.head_dim)
    q = jnp.einsum("se,ehd->shd", h, layer["wq"].astype(F32))
    k = jnp.einsum("se,ehd->shd", h, layer["wk"].astype(F32))
    v = jnp.einsum("se,ehd->shd", h, layer["wv"].astype(F32))
    q, k = _rope(q, float(c.rope_theta)), _rope(k, float(c.rope_theta))
    ks, vs = summarise(k, v, layer["eva_phi"].astype(F32),
                       layer["eva_mu"].astype(F32), int(c.eva_chunk))
    keys = jnp.repeat(jnp.concatenate([k, ks]), heads // kvh, axis=1)
    values = jnp.repeat(jnp.concatenate([v, vs]), heads // kvh, axis=1)
    mask = seen(s, int(c.eva_window), int(c.eva_chunk))
    outs = []
    for at in range(0, s, QUERY_BLOCK):          # a block of queries
        scores = jnp.einsum("qhd,khd->hqk", q[at:at + QUERY_BLOCK], keys
                            ) / d ** 0.5
        probs = jax.nn.softmax(
            jnp.where(mask[None, at:at + QUERY_BLOCK], scores, -jnp.inf), -1)
        outs.append(jnp.einsum("hqk,khd->qhd", probs, values))
    return jnp.einsum("shd,hde->se", jnp.concatenate(outs),
                      layer["wo"].astype(F32))


def _forward(params: Dict[str, Any], tokens, config, rows=None):
    """Head 0's logits at positions ``rows`` = (first, how many) (all
    when None)."""
    c = config
    eps = float(c.rms_eps)
    tokens = jnp.asarray(tokens)
    real = tokens.shape[0]
    # Whole chunks: what is appended follows every real position, and a
    # summary is seen from later windows only.
    tokens = jnp.pad(tokens, (0, -real % int(c.eva_chunk)))

    def block(x, layer):
        x = x + eva_attention(_norm(x, layer["attn_norm"], eps), layer, c)
        h = _norm(x, layer["mlp_norm"], eps)
        return x + (jax.nn.silu(h @ layer["w_gate"].astype(F32))
                    * (h @ layer["w_up"].astype(F32))
                    ) @ layer["w_down"].astype(F32), None

    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(F32)[tokens]
        stacked, = params["runs"]       # one layer after another
        x, _ = jax.lax.scan(block, x, stacked)
        x = (x[:real] if rows is None
             else jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1]))
        x = _norm(x, params["final_norm"], eps)
        return x @ params["lm_head"].astype(F32)[:, :int(c.vocab_size)]


def logits(params: Dict[str, Any], tokens, config) -> jnp.ndarray:
    """Float32 logits ``[S, V]`` of head 0 over one sequence ``[S]``."""
    return _forward(params, tokens, config)


@functools.partial(jax.jit, static_argnames=("config", "count"))
def _gaps(params, seq, chosen, first, config, count: int):
    lg = _forward(params, seq, config, rows=(first, count))
    picked = jnp.take_along_axis(lg, chosen[:, None], axis=-1)[:, 0]
    return (jnp.max(lg, axis=-1) - picked) / jnp.std(lg, axis=-1)


def gaps(params: Dict[str, Any], prompt, chosen, config, pad_to: int = 0):
    """One teacher-forced pass over ``prompt + chosen``: for each chosen
    byte, how far its reference logit lies under the reference maximum
    at that position, in standard deviations of that position's logits
    ``[n]`` (head 0). ``pad_to`` pads at the end (what follows a
    position cannot change it: a query sees nothing behind it, and a
    summary is seen from later windows only), so requests of one length
    of answer share one compiled program."""
    seq = (list(prompt) + list(chosen))[:-1]
    padded = seq + [0] * max(pad_to - len(seq), 0)
    return _gaps(params, jnp.asarray(padded, jnp.int32),
                 jnp.asarray(chosen, jnp.int32), len(prompt) - 1, config,
                 len(chosen))
