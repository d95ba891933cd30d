"""The plain reference for the afmoe family (Trinity): its equations in
float32, for the SHARE of the model one chip holds.

Straightforward ``jax.numpy`` at matmul precision ``highest``: no cache,
no ring, no chunked prefill, no kernel, no sort, no grouped
multiplication, no batching. One layer and one expert at a time, so only
one expert's float32 weights exist at once; attention a block of
queries at a time (every key, a mask), so a 6,000-token sequence fits
beside the bf16 weights. It imports nothing from the program.

The equations (ISSUE 32, section 1; the ``afmoe`` modeling code is not
in the installed ``transformers`` 4.57, so nothing here was held to it:
the configuration file lists under ``assumed`` what the config has no
key for). Token ids ``t``, positions ``i``; ``rms(x; w) = x /
sqrt(mean(x^2) + eps) * w``:

* ``x0 = embed[t] * sqrt(hidden)`` (``mup_enabled``).
* Attention of layer ``l``: ``h = rms(x; w_in)``; ``q = rms_per_head(h
  Wq; q_norm)``, ``k = rms_per_head(h Wk; k_norm)``, ``v = h Wv``; on a
  ``sliding_attention`` layer q and k are rotated (theta ``rope_theta``,
  all of ``head_dim``, the rotate-half pairing) and a query at ``i``
  sees keys ``j <= i`` with ``i - j < sliding_window``; a
  ``full_attention`` layer takes NO positions and sees every ``j <= i``;
  softmax scale ``head_dim ** -0.5``; the output is gated elementwise,
  ``a * sigmoid(h Wg)``; ``x = x + rms(a Wo; w_post_attn)``.
* MLP: ``h2 = rms(x; w_pre_mlp)``; a SwiGLU of the dense width in the
  first ``num_dense_layers`` layers; after them ``shared(h2) + sum_{e in
  top4} p_e expert_e(h2)``; ``x = x + rms(m; w_post_mlp)``.
* Router, float32: ``s = sigmoid(h2 Wr)`` over ALL experts; the top
  ``k`` of ``s + expert_bias`` (the bias selects only); ``p_e =
  route_scale * s_e / (sum_{top k} s + 1e-20)``.
* ``logits = rms(x; w_final) W_head`` (untied).

THE SHARE: the tree holds experts ``[first, first + count)`` of the
router's width (``config.experts_held``) and a slice of the vocabulary.
The router still scores every expert; a chosen expert that is not held
adds nothing here (it is another chip's part of the sum), and the
partial result is what goes on to the next layer. With every expert
held (``experts_held`` None) this is the whole layer, which is what the
share test adds the shares up to.

Departure: ties among router scores break towards the lower expert
index (``jax.lax.top_k``).

It reads the program's parameter tree (``models/llama.py::
_init_windowed_params``): ``embed [V, E]``, ``lm_head [E, V]``,
``final_norm``, ``layers`` = ``moe_gate``/``moe_up [L_moe, held, E,
M]``, ``moe_down [L_moe, held, M, E]`` at the layer's index among
ROUTED layers, and ``runs``: one tree a run of equal layers (a run ends
where the attention kind or the MLP changes), holding ``attn_norm``,
``post_attn_norm``, ``mlp_norm``, ``post_mlp_norm [E]``, ``wq [E, H,
D]``, ``wk``/``wv [E, KVH, D]``, ``wg [E, H, D]``, ``wo [H, D, E]``,
``q_norm``/``k_norm [D]`` and either ``w_gate``/``w_up [E, Md]``,
``w_down [Md, E]`` or ``w_router [E, X]``, ``expert_bias [X]``,
``shared_gate``/``shared_up [E, Ms]``, ``shared_down [Ms, E]``.
``config`` needs ``layer_types``, ``num_dense_layers``,
``sliding_window``, ``rope_theta``, ``rms_eps``, ``head_dim``,
``num_experts_per_tok``, ``route_scale``, ``embedding_multiplier``,
``experts_held``.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512


def _rms_norm(x, weight, eps):
    x = x.astype(F32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight.astype(F32)


def _rope(x, theta):
    """x [S, H, D] at positions 0..S-1, pairing dim j with j + D/2."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(x, layer, kind: str, *, window: int, theta: float,
               eps: float):
    s = x.shape[0]
    h = _rms_norm(x, layer["attn_norm"], eps)
    q = jnp.einsum("se,ehd->shd", h, layer["wq"].astype(F32))
    k = jnp.einsum("se,ehd->shd", h, layer["wk"].astype(F32))
    v = jnp.einsum("se,ehd->shd", h, layer["wv"].astype(F32))
    q = _rms_norm(q, layer["q_norm"], eps)
    k = _rms_norm(k, layer["k_norm"], eps)
    if kind == "sliding_attention":
        q, k = _rope(q, theta), _rope(k, theta)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    j = jnp.arange(s)
    outs = []
    for at in range(0, s, QUERY_BLOCK):          # a block of queries
        i = j[at:at + QUERY_BLOCK, None]
        seen = j[None, :] <= i
        if kind == "sliding_attention":
            seen &= i - j[None, :] < window
        scores = jnp.einsum("qhd,khd->hqk", q[at:at + QUERY_BLOCK], k) \
            * q.shape[-1] ** -0.5
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        outs.append(jnp.einsum("hqk,khd->qhd", probs, v))
    a = jnp.concatenate(outs)
    a = a * jax.nn.sigmoid(jnp.einsum("se,ehd->shd", h,
                                      layer["wg"].astype(F32)))
    out = jnp.einsum("shd,hde->se", a, layer["wo"].astype(F32))
    return _rms_norm(out, layer["post_attn_norm"], eps)


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate.astype(F32)) * (h @ up.astype(F32))
            ) @ down.astype(F32)


def _route(h, w_router, bias, *, top_k: int, scale: float):
    scores = jax.nn.sigmoid(h @ w_router.astype(F32))
    _, chosen = jax.lax.top_k(scores + bias.astype(F32), top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = scale * picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    return weights, chosen


def _layer(x, i: int, kind: str, layer, experts, config
           ) -> Tuple[Any, Any]:
    c = config
    eps = float(c.rms_eps)
    x = x + _attention(x, layer, kind, window=int(c.sliding_window),
                       theta=float(c.rope_theta), eps=eps)
    h = _rms_norm(x, layer["mlp_norm"], eps)
    chosen = None
    if i < int(c.num_dense_layers):
        out = _swiglu(h, layer["w_gate"], layer["w_up"], layer["w_down"])
    else:
        weights, chosen = _route(h, layer["w_router"], layer["expert_bias"],
                                 top_k=int(c.num_experts_per_tok),
                                 scale=float(c.route_scale))
        out = _swiglu(h, layer["shared_gate"], layer["shared_up"],
                      layer["shared_down"])
        first = c.experts_held[0] if c.experts_held else 0
        for e in range(experts["moe_gate"].shape[0]):   # the held experts
            weight = jnp.sum(jnp.where(chosen == first + e, weights, 0.0),
                             axis=-1)
            out = out + weight[:, None] * _swiglu(
                h, experts["moe_gate"][e], experts["moe_up"][e],
                experts["moe_down"][e])
    return x + _rms_norm(out, layer["post_mlp_norm"], eps), chosen


def _forward(params: Dict[str, Any], tokens, config, rows=None):
    """(logits at positions ``rows`` (all when None), router choices
    ``[L_moe, S, k]``)."""
    c = config
    dense = int(c.num_dense_layers)
    with jax.default_matmul_precision("highest"):
        x = (params["embed"].astype(F32)[jnp.asarray(tokens)]
             * float(c.embedding_multiplier))
        choices, run, at = [], -1, 0
        for i, kind in enumerate(c.layer_types):
            if i == 0 or kind != c.layer_types[i - 1] or i == dense:
                run, at = run + 1, 0        # the next run's tree, from 0
            layer = jax.tree.map(lambda a, at=at: a[at],
                                 params["runs"][run])
            experts = (None if i < dense else jax.tree.map(
                lambda a, li=i - dense: a[li], params["layers"]))
            x, chosen = _layer(x, i, kind, layer, experts, c)
            if chosen is not None:
                choices.append(chosen)
            at += 1
        if rows is not None:
            x = x[rows]
        x = _rms_norm(x, params["final_norm"], float(c.rms_eps))
        lg = x @ params["lm_head"].astype(F32)
    return lg, jnp.stack(choices) if choices else None


def logits(params: Dict[str, Any], tokens, config) -> jnp.ndarray:
    """Float32 logits ``[S, V]`` of one sequence of token ids ``[S]``."""
    return _forward(params, tokens, config)[0]


def router_choices(params: Dict[str, Any], tokens, config) -> jnp.ndarray:
    """The experts each position routed to, ``[L_moe, S, k]`` int32 over
    the router's whole width; compare them as SETS."""
    return _forward(params, tokens, config)[1]


def gaps_and_routes(params: Dict[str, Any], prompt, chosen, config,
                    pad_to: int = 0):
    """One teacher-forced pass over ``prompt + chosen``: (for each
    chosen token, how far its reference logit lies under the reference
    maximum at that position, in standard deviations of that position's
    logits ``[n]``; the experts each DECODED position routed to, the
    positions ``chosen[:-1]`` were fed at, ``[n - 1, L_moe, k]``:
    compare them as sets). The head runs on the chosen positions alone.
    ``pad_to`` pads at the end (a causal model: what follows a position
    cannot change it)."""
    seq = (list(prompt) + list(chosen))[:-1]
    padded = seq + [0] * max(pad_to - len(seq), 0)
    lg, choices = _forward(params, padded, config,
                           rows=slice(len(prompt) - 1, len(seq)))
    picked = jnp.take_along_axis(
        lg, jnp.asarray(chosen)[:, None], axis=-1)[:, 0]
    return ((jnp.max(lg, axis=-1) - picked) / jnp.std(lg, axis=-1),
            jnp.swapaxes(choices[:, len(prompt):len(seq)], 0, 1))
