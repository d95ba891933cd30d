"""The plain reference for the DeepSeek-V3 family (``kimi_k2``:
Kimi-K2.7-Code): its equations in float32, for the SHARE of the model one
chip holds.

Straightforward ``jax.numpy`` at matmul precision ``highest``: the
EXPANDED form of latent attention only (per-head keys and values made
from the compressed latent for every position), no cache, no absorbed
products, no chunked prefill, no kernel, no sort, no grouped
multiplication, no batching. One layer and one expert at a time;
attention a block of queries at a time (every key, a mask), so 8,000
positions fit. It imports nothing from the program.
``tests/test_kimi_mla.py`` holds it to ``transformers``' ``deepseek_v3``
modeling code on the CPU with the weights copied across (logits and the
YaRN tables), the whole model uncut.

The equations (ISSUE 36, section 1). Token ids ``t``, positions ``i``;
``rms(x; w) = x / sqrt(mean(x^2) + eps) * w``; H heads:

* ``x0 = embed[t]``.
* Attention: ``h = rms(x; w_in)``; ``c_q = rms(h W_qa; w_qa)``;
  ``[q_nope (Dn) | q_rope (Dr)]_head = c_q W_qb``; ``[c_kv (Rkv) |
  k_rope (Dr)] = h W_kva``; ``c_kv = rms(c_kv; w_kva)``; ``k_rope`` is
  ONE vector a token, shared by every head; ``k_nope_head = c_kv
  W_uk,head``, ``v_head = c_kv W_uv,head`` (``W_kvb`` by head). Rope
  rotates ``q_rope`` of every head and ``k_rope``, nothing else, in the
  rotate-half pairing (a checkpoint's interleaved columns are
  de-interleaved at load). ``score = (q_nope . k_nope + q_rope . k_rope)
  * s``, causal, softmax, ``o_head = sum p v``, ``x = x + concat(o) W_o``.
* ``s = (Dn + Dr) ** -0.5 * m^2``, ``m = 0.1 * mscale_all_dim *
  ln(factor) + 1``.
* Rope: YaRN, ``transformers``' ``_compute_yarn_parameters`` over the
  ``Dr / 2`` frequencies: ``inv_i = theta^(-2i / Dr)``; a linear ramp
  from the dim that makes ``beta_fast`` rotations over the original
  length (floor) to the one that makes ``beta_slow`` (ceil); ``inv_i``
  kept below the ramp, divided by ``factor`` above it, mixed inside; at
  EVERY position. cos and sin times ``mscale(factor, mscale) /
  mscale(factor, mscale_all_dim)``.
* MLP: ``h2 = rms(x; w_mlp)``; a SwiGLU of the dense width in the first
  ``first_k_dense_replace`` layers; after them ``shared(h2) + sum_{e in
  top k} p_e expert_e(h2)``; ``x = x + m``.
* Router, float32: ``s = sigmoid(h2 Wr)`` over ALL experts; the top
  ``k`` of ``s + e_score_correction_bias`` (the bias selects only; one
  group, so the group mask is inert); ``p_e = routed_scaling_factor *
  s_e / (sum_{top k} s + 1e-20)``.
* ``logits = rms(x; w_final) W_head`` (untied).

THE SHARE: the tree holds experts ``[first, first + count)`` of the
router's width (``config.experts_held``) and a slice of the vocabulary.
The router still scores every expert; a chosen expert that is not held
adds nothing here (it is another chip's part of the sum), and the
partial result is what goes on to the next layer. With every expert held
this is the whole layer, which the share test adds the shares up to.

Departure: ties among router scores break towards the lower expert
index (``jax.lax.top_k``).

It reads the program's parameter tree (``models/llama.py::
_init_windowed_params`` with ``models/mla.py::init_attention``):
``embed [V, E]``, ``lm_head [E, V]``, ``final_norm``, ``layers`` =
``moe_gate``/``moe_up [L_moe, held, E, M]``, ``moe_down [L_moe, held, M,
E]`` at the layer's index among ROUTED layers, and ``runs``: one tree a
run of equal layers (the dense layers, then the routed ones), holding
``attn_norm``, ``mlp_norm [E]``, ``wq_a [E, Rq]``, ``q_a_norm [Rq]``,
``wq_nope [Rq, H * Dn]``, ``wq_rope [Rq, H * Dr]``, ``wkv_a [E, Rkv +
Dr]``, ``kv_a_norm [Rkv]``, ``w_uk [H, Dn, Rkv]``, ``w_uv [H, Rkv, Dv]``,
``wo [H, Dv, E]`` and either ``w_gate``/``w_up [E, Md]``, ``w_down [Md,
E]`` or ``w_router [E, X]``, ``expert_bias [X]``, ``shared_gate``/
``shared_up [E, Ms]``, ``shared_down [Ms, E]``. ``config`` needs
``num_layers``, ``num_dense_layers``, ``num_heads``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``rope_theta``,
``rope_scaling`` (pairs or None), ``rms_eps``, ``num_experts_per_tok``,
``route_scale``, ``experts_held``.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 256


def _rms_norm(x, weight, eps):
    x = x.astype(F32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight.astype(F32)


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn(config) -> Tuple[np.ndarray, float, float]:
    """(inverse frequencies ``[Dr / 2]``, the factor on cos and sin, the
    softmax scale)."""
    c = config
    dim, theta = int(c.qk_rope_head_dim), float(c.rope_theta)
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    scale = float(c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5
    group = dict(c.rope_scaling or ())
    if not group:
        return inv, 1.0, scale
    factor, length = float(group["factor"]), group[
        "original_max_position_embeddings"]

    def dim_of(rotations):     # the dim that turns this often over `length`
        return (dim * math.log(length / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(group["beta_fast"])), 0)
    high = min(math.ceil(dim_of(group["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low)
                   / ((high if high != low else high + 0.001) - low), 0, 1)
    inv = inv / factor * ramp + inv * (1 - ramp)
    on_tables = (_mscale(factor, group["mscale"])
                 / _mscale(factor, group["mscale_all_dim"]))
    if group.get("mscale_all_dim"):
        scale *= _mscale(factor, group["mscale_all_dim"]) ** 2
    return inv, on_tables, scale


def _rope(x, inv, factor):
    """x [S, H, D] at positions 0..S-1, pairing dim j with j + D/2."""
    s, _, d = x.shape
    ang = jnp.arange(s, dtype=F32)[:, None] * jnp.asarray(inv, F32)
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(x, layer, config):
    c = config
    s, eps = x.shape[0], float(c.rms_eps)
    heads, dn, dr = int(c.num_heads), int(c.qk_nope_head_dim), int(
        c.qk_rope_head_dim)
    rkv = int(c.kv_lora_rank)
    inv, on_tables, scale = yarn(c)
    h = _rms_norm(x, layer["attn_norm"], eps)
    c_q = _rms_norm(h @ layer["wq_a"].astype(F32), layer["q_a_norm"], eps)
    q_nope = (c_q @ layer["wq_nope"].astype(F32)).reshape(s, heads, dn)
    q_rope = (c_q @ layer["wq_rope"].astype(F32)).reshape(s, heads, dr)
    ckv = h @ layer["wkv_a"].astype(F32)
    c_kv = _rms_norm(ckv[:, :rkv], layer["kv_a_norm"], eps)
    k_rope = _rope(ckv[:, None, rkv:], inv, on_tables)         # [S, 1, Dr]
    q_rope = _rope(q_rope, inv, on_tables)
    k_nope = jnp.einsum("sc,hdc->shd", c_kv, layer["w_uk"].astype(F32))
    v = jnp.einsum("sc,hcd->shd", c_kv, layer["w_uv"].astype(F32))
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([k_nope, jnp.repeat(k_rope, heads, axis=1)], -1)
    j = jnp.arange(s)
    outs = []
    for at in range(0, s, QUERY_BLOCK):          # a block of queries
        seen = j[None, :] <= j[at:at + QUERY_BLOCK, None]
        scores = jnp.einsum("qhd,khd->hqk", q[at:at + QUERY_BLOCK], k) * scale
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        outs.append(jnp.einsum("hqk,khd->qhd", probs, v))
    return jnp.einsum("shd,hde->se", jnp.concatenate(outs),
                      layer["wo"].astype(F32))


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate.astype(F32)) * (h @ up.astype(F32))
            ) @ down.astype(F32)


def _route(h, w_router, bias, *, top_k: int, scale: float):
    scores = jax.nn.sigmoid(h @ w_router.astype(F32))
    _, chosen = jax.lax.top_k(scores + bias.astype(F32), top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = scale * picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    return weights, chosen


def _layer(x, i: int, layer, experts, config) -> Tuple[Any, Any]:
    c = config
    eps = float(c.rms_eps)
    x = x + _attention(x, layer, c)
    h = _rms_norm(x, layer["mlp_norm"], eps)
    if i < int(c.num_dense_layers):
        return x + _swiglu(h, layer["w_gate"], layer["w_up"],
                           layer["w_down"]), None
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
    return x + out, chosen


def _forward(params: Dict[str, Any], tokens, config, rows=None):
    """(logits at positions ``rows`` (all when None), router choices
    ``[L_moe, S, k]``)."""
    c = config
    dense = int(c.num_dense_layers)
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(F32)[jnp.asarray(tokens)]
        choices, run, at = [], -1, 0
        for i in range(int(c.num_layers)):
            if i == 0 or i == dense:
                run, at = run + 1, 0        # the next run's tree, from 0
            layer = jax.tree.map(lambda a, at=at: a[at],
                                 params["runs"][run])
            experts = (None if i < dense else jax.tree.map(
                lambda a, li=i - dense: a[li], params["layers"]))
            x, chosen = _layer(x, i, layer, experts, c)
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
