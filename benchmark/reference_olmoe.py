"""The plain reference for the OLMoE family: its equations in float32.

Straightforward ``jax.numpy`` at matmul precision ``highest``: no sort,
no grouped multiplication, no kernel, no cache, no scan. One layer at a
time, so only one layer's float32 weights exist at once, and inside it a
Python loop over the experts, each applied to EVERY position and kept
where a mask says the position routed to it. The layer, as the published
modeling code (``transformers`` ``modeling_olmoe.py``) has it:

* ``h = RMSNorm(x)``; ``q = RMSNorm_q(h Wq)``, ``k = RMSNorm_k(h Wk)``
  (QK-norm: a learned RMSNorm over the WHOLE projected vector, before
  the split into heads), ``v = h Wv``; half-split rope on q and k;
  causal softmax attention; ``x += o Wo``.
* ``h2 = RMSNorm(x)``; ``p = softmax(h2 Wr)`` over all experts; the top
  ``k`` of ``p``, weighted by those entries of ``p`` as they are
  (``norm_topk_prob`` false; true divides them by their sum);
  ``x += sum_e p_e * down_e(silu(gate_e h2) * up_e h2)``. Every routed
  position is computed: no capacity, no drop.
* final RMSNorm, untied head.

Departures from the published model, each listed in the configuration
file under ``assumed``: ``clip_qkv`` is null in the published config and
is not applied; the router's auxiliary loss plays no part in a forward
pass; weights come from the seed. Ties among the router's probabilities
break towards the lower expert index (``jax.lax.top_k``), as
``torch.topk`` does not promise: in float32 on seeded weights none
occurs.

It reads the program's parameter tree (``models/llama.py::init_params``
for a config with experts: ``wq``/``wk``/``wv [L, E, H, D]``, ``wo [L,
H, D, E]``, ``q_norm``/``k_norm [L, H * D]``, ``w_router [L, E, X]``,
``moe_gate``/``moe_up [L, X, E, M]``, ``moe_down [L, X, M, E]``) because
the weights it must reproduce are made by the program from the seed.
``config`` needs ``num_layers``, ``rope_theta``, ``rms_eps``,
``num_experts_per_tok``, ``norm_topk_prob`` and ``qk_norm``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from benchmark.reference import F32, _head, _rms_norm, _rope


@functools.partial(jax.jit, static_argnames=("theta", "eps", "qk_norm"))
def _attention(x, layer, *, theta: float, eps: float, qk_norm: bool):
    """The attention sublayer on one sequence ``x [S, E]``, float32."""
    w = jax.tree.map(lambda a: a.astype(F32), layer)
    s = x.shape[0]
    heads, d = w["wq"].shape[1:]
    kv_heads = w["wk"].shape[1]
    h = _rms_norm(x, w["attn_norm"], eps)
    q = h @ w["wq"].reshape(-1, heads * d)
    k = h @ w["wk"].reshape(-1, kv_heads * d)
    v = h @ w["wv"].reshape(-1, kv_heads * d)
    if qk_norm:
        q, k = _rms_norm(q, w["q_norm"], eps), _rms_norm(k, w["k_norm"], eps)
    q = _rope(q.reshape(s, heads, d), theta)
    k = _rope(k.reshape(s, kv_heads, d), theta)
    v = v.reshape(s, kv_heads, d)
    qg = q.reshape(s, kv_heads, heads // kv_heads, d)
    scores = jnp.einsum("qhgd,khd->hgqk", qg, k) * d ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hgqk,khd->qhgd", probs, v).reshape(s, heads * d)
    return x + o @ w["wo"].reshape(heads * d, -1)


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "renormalise"))
def _route(x, mlp_norm, w_router, *, eps: float, top_k: int,
           renormalise: bool):
    """(h2 [S, E], weights [S, k], experts [S, k])."""
    h = _rms_norm(x, mlp_norm, eps)
    probs = jax.nn.softmax(h @ w_router.astype(F32), axis=-1)
    weights, chosen = jax.lax.top_k(probs, top_k)
    if renormalise:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return h, weights, chosen


@jax.jit
def _expert(h, gate, up, down, weight):
    """One expert on every position, scaled by ``weight [S]`` (0 where
    the position did not route to it)."""
    y = (jax.nn.silu(h @ gate.astype(F32)) * (h @ up.astype(F32))
         ) @ down.astype(F32)
    return weight[:, None] * y


def _layer(x, layer, config) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One decoder layer; returns (x, the chosen experts [S, k])."""
    eps = float(config.rms_eps)
    small = {k: v for k, v in layer.items() if not k.startswith("moe_")}
    x = _attention(x, small, theta=float(config.rope_theta), eps=eps,
                   qk_norm=bool(config.qk_norm))
    h, weights, chosen = _route(
        x, layer["mlp_norm"], layer["w_router"], eps=eps,
        top_k=int(config.num_experts_per_tok),
        renormalise=bool(config.norm_topk_prob))
    for e in range(layer["moe_gate"].shape[0]):
        weight = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        x = x + _expert(h, layer["moe_gate"][e], layer["moe_up"][e],
                        layer["moe_down"][e], weight)
    return x, chosen


def _forward(params: Dict[str, Any], tokens, config):
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(F32)[jnp.asarray(tokens)]
        choices = []
        for i in range(config.num_layers):
            layer = jax.tree.map(lambda a, i=i: a[i], params["layers"])
            x, chosen = _layer(x, layer, config)
            choices.append(chosen)
        lg = _head(x, params["final_norm"], params["lm_head"],
                   eps=float(config.rms_eps))
    return lg, jnp.stack(choices)


def logits(params: Dict[str, Any], tokens, config) -> jnp.ndarray:
    """Float32 logits ``[S, V]`` of one sequence of token ids ``[S]``."""
    return _forward(params, tokens, config)[0]


def router_choices(params: Dict[str, Any], tokens, config) -> jnp.ndarray:
    """The experts each position routed to, ``[L, S, k]`` int32, in the
    order of their probabilities; compare them as SETS."""
    return _forward(params, tokens, config)[1]


def gaps_and_choices(params: Dict[str, Any], prompt, chosen, config,
                      pad_to: int = 0):
    """One teacher-forced pass over ``prompt + chosen``: ``(gaps,
    choices)``. ``gaps``, as ``reference.chosen_gaps``: for each chosen
    token, how far its reference logit lies under the reference maximum
    at that position, in standard deviations of that position's logits.
    ``choices [L, len(prompt + chosen) - 1, k]``: the experts each
    position routed to. ``pad_to`` pads at the end (causal attention,
    and a routed block that treats every position alone: what follows a
    position cannot change it)."""
    seq = (list(prompt) + list(chosen))[:-1]
    padded = seq + [0] * max(pad_to - len(seq), 0)
    lg, choices = _forward(params, padded, config)
    lg = lg[len(prompt) - 1:len(seq)]
    picked = jnp.take_along_axis(
        lg, jnp.asarray(chosen)[:, None], axis=-1)[:, 0]
    gaps = (jnp.max(lg, axis=-1) - picked) / jnp.std(lg, axis=-1)
    return gaps, choices[:, :len(seq)]


def chosen_gaps(params: Dict[str, Any], prompt, chosen, config,
                pad_to: int = 0):
    """``reference.chosen_gaps`` for this family."""
    return gaps_and_choices(params, prompt, chosen, config, pad_to)[0]
