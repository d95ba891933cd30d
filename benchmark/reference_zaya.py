"""The plain reference for the ZAYA1 family (``model_type`` "zaya":
Zyphra/ZAYA1-8B): ISSUE 46 A's equations in float32, whole sequences.

Straightforward ``jax.numpy`` at matmul precision ``highest``: no cache,
no tail, no chunks, no kernels, no sort, no grouped multiplication, no
batching. One layer and one expert at a time; attention a block of
queries at a time, so 4,000 positions fit. It imports nothing from the
program. ``transformers`` 4.57.6 has no ``zaya``, so nothing here could
be held to modeling code: a line marked (C) follows a key of the
published config, a line marked (P) the CCA paper (arXiv:2510.04476) or
the ZAYA1 report (arXiv:2511.17127) as recalled, and every (P) is
listed in the configuration file's ``assumed``.

The equations. Token ids ``t``; ``rms(x; w) = x / sqrt(mean(x^2) + eps)
* w``; every layer is a CCA attention sublayer, then an MoE sublayer
(C: ``layer_types`` "hybrid" throughout); E hidden, H query heads, KVH
KV heads of D, G = H / KVH, R the router's width, X experts:

* ``x0 = embed[t]``; both sublayers join the stream as ``x = (s_res x +
  t_res) + (s_out f(rms(x)) + t_out)`` (P: residual scaling, four
  learned ``[E]`` vectors a sublayer); ``logits = rms(x; w_final)
  embed^T`` (C: tied).
* Attention, ``h = rms(x; w_attn)``: (1) ``q~ = h Wq [H D]``, ``k~ = h
  Wk [KVH D]``, no bias (C). (2) (P) ``v_t = [h_t Wv1 | h_{t-1} Wv2]``,
  each ``KVH D / 2`` wide, ``h_{-1} = 0``, split into the KVH heads in
  that order. (3) ``u = [q~ | k~]``; first convolution, depthwise, 2
  taps (C ``cca_time0``): ``a_t = w1[0] u_{t-1} + w1[1] u_t + b1``,
  ``u_{-1} = 0``; second, grouped a head, 2 taps (C ``cca_time1``):
  ``c_t[g] = a_{t-1}[g] W2[g, :D] + a_t[g] W2[g, D:] + b2[g]`` with
  ``a_{-1} = 0`` EXACTLY: the padding of the second convolution's
  input, not ``b1`` (P). (4) (P) q-k mean: ``q = c[:H] + (q~[i] +
  k~[i // G]) / 2``, ``k = c[H:] + (mean over group j of q~ + k~[j]) /
  2``. (5) (P) a head: ``q^ = sqrt(D) q / |q|``, ``k^ = tau[j] sqrt(D)
  k / |k|`` (eps 1e-6 inside the root); THEN rotate-half rope on the
  first ``partial_rotary_factor D`` dims (C), theta (C). (6) causal
  softmax of ``q^ . k^ / sqrt(D)``, query head i over KV head ``i //
  G``; ``o Wo``.
* MoE, ``g = rms(x; w_mlp)``: (7) (C ``router_hidden_size``; form P)
  ``r = g Wd + bd``; ``r += gamma * r of the layer before`` (no term in
  layer 0; the carried ``r`` is this sum); ``z = gelu(rms(r; w_r) W1 +
  b1)``, ``z = gelu(z W2 + b2)`` (exact gelu), ``p = softmax(z W3)``;
  the expert is ``argmax(p + beta)``, its weight that ``p`` (C: top 1;
  not renormalised). (8) ``y = p_e Wdown_e(silu(g Wgate_e) * g
  Wup_e)`` (C ``hidden_act`` silu), dropless.

Departures: ties among ``p + beta`` break towards the lower expert
index (``argmax``). No zero-compute ("MoD") expert: the config has no
key for one.

It reads the program's parameter tree (``models/llama.py::
_init_windowed_params`` with ``models/cca.py::init_attention`` and
``ops/moe.py::init_mlp_router``): ``embed [V, E]``, ``final_norm``,
``layers`` = ``moe_gate``/``moe_up [L, X, E, M]``, ``moe_down [L, X, M,
E]``, and ``runs``: ONE tree of all layers, holding ``attn_norm``,
``mlp_norm [E]``, ``cca_in [E, (H + KVH) D + KVH D]`` (columns ``Wq | Wk
| Wv1 | Wv2``), ``cca_conv1_w [2, C]``, ``cca_conv1_b [C]``,
``cca_conv2_w [H + KVH, 2 D, D]``, ``cca_conv2_b [C]``, ``cca_tau
[KVH]``, ``wo [H, D, E]``, ``res_attn``/``res_mlp [4, E]`` (``s_res,
t_res, s_out, t_out``) and ``router`` (``down [E, R]``, ``down_b``,
``gamma``, ``norm [R]``, ``w1``, ``w2 [R, R]``, ``b1``, ``b2 [R]``,
``out [R, X]``, ``beta [X]``). ``config`` needs ``num_layers``,
``num_heads``, ``num_kv_heads``, ``head_dim``, ``partial_rotary_factor``,
``rope_theta``, ``rms_eps``.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512


def _rms(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps
                             ) * w.astype(F32)


def _before(x):
    """``x [S, ...]`` one position later, zeros in front."""
    return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]])


def _rope(x, theta: float, turned: int):
    """Rotate-half over the first ``turned`` dims of x [S, heads, D]."""
    s = x.shape[0]
    inv = 1.0 / theta ** (jnp.arange(0, turned, 2, dtype=F32) / turned)
    angle = jnp.arange(s, dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    x1, x2 = x[..., :turned // 2], x[..., turned // 2:turned]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., turned:]], axis=-1)


def _unit(x, d: int):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6
                             ) * d ** 0.5


def _scores(q, k, d: int):
    """``q . k / sqrt(d)`` a head, float32: ``[heads, queries, keys]``."""
    return jnp.einsum("qhd,khd->hqk", q, k) * d ** -0.5


def _attention(h, layer, config):
    c = config
    s = h.shape[0]
    H, KVH, D = int(c.num_heads), int(c.num_kv_heads), int(c.head_dim)
    G, conv_dim, half = H // KVH, (H + KVH) * D, KVH * D // 2
    proj = h @ layer["cca_in"].astype(F32)                       # (1)
    u = proj[:, :conv_dim]
    v = jnp.concatenate([proj[:, conv_dim:conv_dim + half],      # (2) (P)
                         _before(proj[:, conv_dim + half:])], -1)
    w1 = layer["cca_conv1_w"].astype(F32)
    a = w1[0] * _before(u) + w1[1] * u + layer["cca_conv1_b"].astype(F32)
    w2 = layer["cca_conv2_w"].astype(F32)                        # (3)
    heads = a.reshape(s, H + KVH, D)
    # a_{-1} = 0, NOT b1 (P): _before pads the convolved rows themselves.
    conv = (jnp.einsum("sgi,gio->sgo", _before(heads), w2[:, :D])
            + jnp.einsum("sgi,gio->sgo", heads, w2[:, D:])
            + layer["cca_conv2_b"].astype(F32).reshape(-1, D))
    raw = u.reshape(s, H + KVH, D)
    q_raw, k_raw = raw[:, :H], raw[:, H:]
    q = conv[:, :H] + (q_raw + jnp.repeat(k_raw, G, axis=1)) / 2   # (4) (P)
    k = conv[:, H:] + (q_raw.reshape(s, KVH, G, D).mean(2) + k_raw) / 2
    q = _unit(q, D)                                              # (5) (P)
    k = _unit(k, D) * layer["cca_tau"].astype(F32)[:, None]
    turned = int(D * float(c.partial_rotary_factor))
    q = _rope(q, float(c.rope_theta), turned)       # after the norm (P)
    k = _rope(k, float(c.rope_theta), turned)
    k = jnp.repeat(k, G, axis=1)                                 # (6)
    v = jnp.repeat(v.reshape(s, KVH, D), G, axis=1)
    j = jnp.arange(s)
    outs = []
    for at in range(0, s, QUERY_BLOCK):          # a block of queries
        seen = j[None, :] <= j[at:at + QUERY_BLOCK, None]
        scores = _scores(q[at:at + QUERY_BLOCK], k, D)
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        outs.append(jnp.einsum("hqk,khd->qhd", probs, v))
    return jnp.einsum("shd,hde->se", jnp.concatenate(outs),
                      layer["wo"].astype(F32))


def _route(g, router, before, eps: float):
    """(weight [S], expert [S], this layer's ``r``): step (7) (P)."""
    w = {name: a.astype(F32) for name, a in router.items()}
    r = g @ w["down"] + w["down_b"]
    if before is not None:          # no term in layer 0
        r = r + w["gamma"] * before
    z = jax.nn.gelu(_rms(r, w["norm"], eps) @ w["w1"] + w["b1"],
                    approximate=False)
    z = jax.nn.gelu(z @ w["w2"] + w["b2"], approximate=False)
    p = jax.nn.softmax(z @ w["out"], axis=-1)
    expert = jnp.argmax(p + w["beta"], axis=-1)
    return jnp.take_along_axis(p, expert[:, None], -1)[:, 0], expert, r


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate.astype(F32)) * (h @ up.astype(F32))
            ) @ down.astype(F32)


def _join(x, y, scaling):
    """Step (9) (P): ``(s_res x + t_res) + (s_out y + t_out)``."""
    s_res, t_res, s_out, t_out = scaling.astype(F32)
    return (s_res * x + t_res) + (s_out * y + t_out)


def _layer(x, layer, experts, before, config):
    eps = float(config.rms_eps)
    x = _join(x, _attention(_rms(x, layer["attn_norm"], eps), layer, config),
              layer["res_attn"])
    g = _rms(x, layer["mlp_norm"], eps)
    weight, expert, r = _route(g, layer["router"], before, eps)
    out = jnp.zeros_like(x)
    for e in range(experts["moe_gate"].shape[0]):                # (8)
        out = out + jnp.where(expert == e, weight, 0.0)[:, None] * _swiglu(
            g, experts["moe_gate"][e], experts["moe_up"][e],
            experts["moe_down"][e])
    return _join(x, out, layer["res_mlp"]), expert, r


def _forward(params: Dict[str, Any], tokens, config, rows=None,
             num_layers=None):
    """(logits at positions ``rows`` (all when None), each position's
    expert ``[L, S, 1]``) through the first ``num_layers`` layers (all
    when None)."""
    c = config
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(F32)
        choices, before = [], None
        for i in range(int(num_layers or c.num_layers)):
            layer = jax.tree.map(lambda a, i=i: a[i], params["runs"][0])
            experts = jax.tree.map(lambda a, i=i: a[i], params["layers"])
            x, expert, before = _layer(x, layer, experts, before, c)
            choices.append(expert[:, None])
        if rows is not None:
            x = x[rows]
        x = _rms(x, params["final_norm"], float(c.rms_eps))
        lg = jnp.einsum("se,ve->sv", x, params["embed"].astype(F32))
    return lg, jnp.stack(choices)


def logits(params: Dict[str, Any], tokens, config,
           num_layers=None) -> jnp.ndarray:
    """Float32 logits ``[S, V]`` of one sequence of token ids ``[S]``."""
    return _forward(params, tokens, config, num_layers=num_layers)[0]


def router_choices(params: Dict[str, Any], tokens, config) -> jnp.ndarray:
    """The expert each position routed to, ``[L, S, 1]`` int32."""
    return _forward(params, tokens, config)[1]


def gaps_and_routes(params: Dict[str, Any], prompt, chosen, config,
                    pad_to: int = 0):
    """One teacher-forced pass over ``prompt + chosen``: (for each
    chosen token, how far its reference logit lies under the reference
    maximum at that position, in standard deviations of that position's
    logits ``[n]``; the expert each DECODED position routed to, the
    positions ``chosen[:-1]`` were fed at, ``[n - 1, L, 1]``). The head
    runs on the chosen positions alone. ``pad_to`` pads at the end (a
    causal model: what follows a position cannot change it)."""
    seq = (list(prompt) + list(chosen))[:-1]
    padded = seq + [0] * max(pad_to - len(seq), 0)
    lg, choices = _forward(params, padded, config,
                           rows=slice(len(prompt) - 1, len(seq)))
    picked = jnp.take_along_axis(
        lg, jnp.asarray(chosen)[:, None], axis=-1)[:, 0]
    return ((jnp.max(lg, axis=-1) - picked) / jnp.std(lg, axis=-1),
            jnp.swapaxes(choices[:, len(prompt):len(seq)], 0, 1))
