"""The plain reference: the Llama-architecture equations in float32.

Straightforward ``jax.numpy`` at matmul precision ``highest``: no kernel,
no cache, no batching, no scan, one layer at a time so that only one
layer's float32 weights exist at once. It follows the published
description of the decoder both configurations share (pre-norm RMSNorm,
rotary embeddings in the half-split convention, grouped-query causal
attention, SwiGLU, untied head). Departures from the published models,
both inert here and listed in the configuration files under ``assumed``:
InternLM2 fuses q, k and v into one ``wqkv`` (the same mathematics as
three projections), and its dynamic-NTK rope scaling acts only past the
trained context, which no cell reaches.

It reads the program's parameter tree (``models/llama.py::init_params``:
``wq [L, E, H, D]``, ``wk``/``wv [L, E, KVH, D]``, ``wo [L, H, D, E]``,
``w_gate``/``w_up [L, E, M]``, ``w_down [L, M, E]``) because the weights
it must reproduce are made by the program from the seed.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight.astype(F32)


def _rope(x, theta):
    """x: [S, heads, D]; position i rotates pair (j, j + D/2) by
    i * theta^(-2j/D)."""
    s, _, d = x.shape
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angles = jnp.arange(s, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("theta", "eps"))
def _layer(x, layer, *, theta: float, eps: float):
    """One decoder layer on one sequence ``x [S, E]``, float32."""
    w = jax.tree.map(lambda a: a.astype(F32), layer)
    s = x.shape[0]
    h = _rms_norm(x, w["attn_norm"], eps)
    q = _rope(jnp.einsum("se,ehd->shd", h, w["wq"]), theta)
    k = _rope(jnp.einsum("se,ehd->shd", h, w["wk"]), theta)
    v = jnp.einsum("se,ehd->shd", h, w["wv"])
    heads, kv_heads, d = q.shape[1], k.shape[1], q.shape[2]
    qg = q.reshape(s, kv_heads, heads // kv_heads, d)
    scores = jnp.einsum("qhgd,khd->hgqk", qg, k) * d ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hgqk,khd->qhgd", probs, v).reshape(s, heads, d)
    x = x + jnp.einsum("shd,hde->se", o, w["wo"])
    h = _rms_norm(x, w["mlp_norm"], eps)
    act = jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])
    return x + act @ w["w_down"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, lm_head, *, eps: float):
    return _rms_norm(x, final_norm, eps) @ lm_head.astype(F32)


def logits(params: Dict[str, Any], tokens, config) -> jnp.ndarray:
    """Float32 logits ``[S, V]`` of one sequence of token ids ``[S]``.
    ``config`` needs ``num_layers``, ``rope_theta`` and ``rms_eps``."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(F32)[jnp.asarray(tokens)]
        for i in range(config.num_layers):
            layer = jax.tree.map(lambda a, i=i: a[i], params["layers"])
            x = _layer(x, layer, theta=float(config.rope_theta),
                       eps=float(config.rms_eps))
        return _head(x, params["final_norm"], params["lm_head"],
                     eps=float(config.rms_eps))


def loss(params: Dict[str, Any], tokens, config) -> jnp.ndarray:
    """Summed next-token negative log-likelihood of one sequence
    ``[S]`` (``S - 1`` targets), float32."""
    lg = logits(params, tokens, config)[:-1]
    targets = jnp.asarray(tokens)[1:]
    picked = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(lg, axis=-1) - picked)


def chosen_gaps(params: Dict[str, Any], prompt, chosen, config,
                pad_to: int = 0):
    """Teacher-force ``prompt + chosen`` and return, for each chosen
    token, how far its reference logit lies under the reference maximum
    at that position, in units of that position's logit standard
    deviation (0 where the engine chose the reference's own argmax).
    ``pad_to`` pads the sequence at its end (causal attention: what
    follows a position cannot change it) so that several checks share
    one compiled length."""
    seq = (list(prompt) + list(chosen))[:-1]
    padded = seq + [0] * max(pad_to - len(seq), 0)
    lg = logits(params, padded, config)[len(prompt) - 1:len(seq)]
    picked = jnp.take_along_axis(
        lg, jnp.asarray(chosen)[:, None], axis=-1)[:, 0]
    return (jnp.max(lg, axis=-1) - picked) / jnp.std(lg, axis=-1)
