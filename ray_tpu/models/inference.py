"""Llama inference: KV-cache prefill + single-token decode, jit-compiled.

The reference serves LLMs by hosting vLLM (``python/ray/llm/_internal/serve``
— SURVEY.md §2.4); ray_tpu serves its own models natively. TPU-shaped
decisions:

* the KV cache is a static-shape ring of ``[L, B, S_max, KVH, D]`` arrays —
  no dynamic shapes ever reach XLA; position masking handles partial fill;
* prefill processes the whole (padded) prompt in one batched pass (MXU
  utilization) and decode is one jitted step with donated cache buffers (no
  HBM churn);
* cache layout is shardable with the same logical-axis rules as training
  (batch on data axes, heads on tensor) so a TP-sharded server is a rule
  change, not new code.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu._private import xla_monitor
from ray_tpu.models import llama
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import apply_rope, rope_frequencies


class KVCache(NamedTuple):
    k: jnp.ndarray  # [L, B, S_max, KVH, D]
    v: jnp.ndarray

    @classmethod
    def create(cls, config: llama.LlamaConfig, batch_size: int,
               max_len: int) -> "KVCache":
        shape = (config.num_layers, batch_size, max_len,
                 config.num_kv_heads, config.head_dim)
        return cls(k=jnp.zeros(shape, config.dtype),
                   v=jnp.zeros(shape, config.dtype))


class SelfDrafter:
    """Speculative-decode drafter that IS the target model, truncated: the
    first ``draft_layers`` decoder layers plus the target's own final norm
    and lm_head (:func:`llama.truncated`). Because those layers compute
    bitwise the same K/V the target writes, the drafter reads and writes
    the target's paged arena directly (layers [0:n)) — context K/V is
    already resident, draft writes land where verify will rewrite the
    identical bytes, and no second checkpoint or draft arena exists.

    ``draft_layers=None`` defers to the engine default
    (``RAY_TPU_SPEC_DRAFT_LAYERS``, else num_layers // 4)."""

    external = False

    def __init__(self, draft_layers: Optional[int] = None):
        self.draft_layers = draft_layers


class ExternalLlamaDrafter:
    """Speculative-decode drafter backed by a separate (small) Llama
    checkpoint sharing the target's vocabulary. Keeps its own dense
    per-slot KV cache (``KVCache``), filled by a draft prefill of the full
    prompt at admission and advanced by the spec tick's draft steps; the
    engine's rewind (host-count re-upload) needs no drafter cooperation
    because stale entries past the committed length are overwritten before
    they are ever attended."""

    external = True

    def __init__(self, config: llama.LlamaConfig, params=None,
                 seed: int = 0):
        self.config = _refuse_looped(config, "ExternalLlamaDrafter")
        self.params = params if params is not None else llama.init_params(
            config, jax.random.PRNGKey(seed))


def _attend_cached(q, cache_k, cache_v, q_positions, scale):
    """q: [B, S, H, D] at absolute positions; cache: [B, S_max, KVH, D].

    Causal masking is positional: query at position p sees cache slots
    [0..p]. Unfilled slots are masked out by the same rule.
    """
    b, s, hq, d = q.shape
    s_max, hkv = cache_k.shape[1], cache_k.shape[2]
    group = hq // hkv
    qg = q.reshape(b, s, hkv, group, d).astype(jnp.float32)
    logits = jnp.einsum("bqhgd,bkhd->bqhgk", qg,
                        cache_k.astype(jnp.float32)) * scale
    slots = jnp.arange(s_max)
    mask = q_positions[:, None] >= slots[None, :]           # [S, S_max]
    logits = jnp.where(mask[None, :, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bqhgk,bkhd->bqhgd", probs,
                     cache_v.astype(jnp.float32))
    return out.reshape(b, s, hq, d).astype(q.dtype)


def _block(x, layer, cache_k, cache_v, positions, cos, sin, c,
           experts=None, li=None):
    """One decoder layer over tokens at ``positions``, updating the cache
    (``experts`` / ``li``: :func:`llama.split_layers`' stacked expert
    weights and this layer's index, for a routed model)."""
    scale = c.head_dim ** -0.5
    h = rms_norm(x, layer["attn_norm"], c.rms_eps)
    q, k, v = llama.project_qkv(h, layer, c)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    # Scatter new K/V into the cache at their absolute positions.
    cache_k = jax.lax.dynamic_update_slice(
        cache_k, k.astype(cache_k.dtype), (0, positions[0], 0, 0))
    cache_v = jax.lax.dynamic_update_slice(
        cache_v, v.astype(cache_v.dtype), (0, positions[0], 0, 0))
    o = _attend_cached(q, cache_k, cache_v, positions, scale)
    x = x + jnp.einsum("bshd,hde->bse", o, layer["wo"].astype(c.dtype))
    h = rms_norm(x, layer["mlp_norm"], c.rms_eps)
    x = x + llama.mlp_block(h, layer, c, experts, li)[0]
    return x, cache_k, cache_v


def lm_head_logits(x, params, config: llama.LlamaConfig):
    """Final-norm hidden states [B, S, E] -> fp32 logits [B, S, V].

    The projection runs in the params' storage dtype (bf16 on TPU) with
    fp32 MXU accumulation (``preferred_element_type``) instead of
    materializing an fp32 upcast of the lm_head — at decode batch sizes
    the head read dominates the tick's non-KV bytes, so this halves it.
    Greedy argmax over the result must stay bit-stable vs the fp32 path
    (tests/test_continuous_batching.py::test_bf16_lm_head_argmax_parity).
    """
    c = config
    # A tied head is contracted against the [V, E] embedding where it
    # lies: a transposed copy would be a second embedding in HBM.
    spec, head = (("bse,ve->bsv", params["embed"]) if c.tie_word_embeddings
                  else ("bse,ev->bsv", params["lm_head"]))
    logits = jnp.einsum(spec, x.astype(c.dtype), head.astype(c.dtype),
                        preferred_element_type=jnp.float32)
    if c.num_pred_heads > 1:
        # The head is [E, heads x V], head j for the token j + 1 ahead:
        # head 0, the next token's, is the one a decode step samples.
        logits = logits[..., :c.vocab_size]
    return logits if c.logits_scaling == 1.0 else logits / c.logits_scaling


def _forward_cached(params, tokens, positions, cache: KVCache,
                    config: llama.LlamaConfig):
    """tokens [B, S] at absolute ``positions`` [S]; returns (logits, cache)."""
    c = config
    cos, sin = rope_frequencies(c.head_dim, tokens.shape[1], c.rope_theta,
                                positions=positions)
    x = params["embed"].astype(c.dtype)[tokens]

    scanned, experts = llama.split_layers(params)

    def layer_fn(carry, inputs):
        x, li = carry
        layer, ck, cv = inputs
        x, ck, cv = _block(x, layer, ck, cv, positions, cos, sin, c,
                           experts, li)
        return (x, li + 1), (ck, cv)

    (x, _), (new_k, new_v) = jax.lax.scan(
        layer_fn, (x, jnp.int32(0)), (scanned, cache.k, cache.v))
    x = rms_norm(x, params["final_norm"], c.rms_eps)
    logits = lm_head_logits(x, params, c)
    return logits, KVCache(k=new_k, v=new_v)


class LlamaGenerator:
    """Compiled prefill + decode loops for one model instance."""

    def __init__(self, config: llama.LlamaConfig, params=None,
                 max_len: int = 512, seed: int = 0):
        self.config = _refuse_looped(config, "LlamaGenerator")
        self.max_len = max_len
        self.params = params if params is not None else llama.init_params(
            config, jax.random.PRNGKey(seed))

        cfg = config

        # Whole-prompt prefill legitimately compiles once per distinct
        # prompt length (the batch generate API pads nothing); the
        # production serving path is the bucketed engine, so this one is
        # compile-tracked but exempt from retrace flagging.
        @xla_monitor.instrument(name="llama_prefill", shape_policy="free")
        def prefill(params, tokens, cache):
            positions = jnp.arange(tokens.shape[1])
            return _forward_cached(params, tokens, positions, cache, cfg)

        @xla_monitor.instrument(name="llama_decode", donate_argnums=(2,))
        def decode(params, token, cache, pos):
            positions = jnp.asarray([pos])
            logits, cache = _forward_cached(
                params, token[:, None], positions, cache, cfg)
            return logits[:, -1], cache

        self._prefill = prefill
        self._decode = decode

    def generate(self, prompt_tokens, max_new_tokens: int = 32,
                 temperature: float = 0.0, seed: int = 0):
        """prompt_tokens: [B, P] int32. Returns [B, max_new_tokens]."""
        tokens = jnp.asarray(prompt_tokens, jnp.int32)
        b, p = tokens.shape
        assert p + max_new_tokens <= self.max_len
        cache = KVCache.create(self.config, b, self.max_len)
        logits, cache = self._prefill(self.params, tokens, cache)
        last = logits[:, p - 1]
        key = jax.random.PRNGKey(seed)
        out = []
        pos = p
        for _ in range(max_new_tokens):
            if temperature > 0:
                key, sub = jax.random.split(key)
                nxt = jax.random.categorical(sub, last / temperature, axis=-1)
            else:
                nxt = jnp.argmax(last, axis=-1)
            nxt = nxt.astype(jnp.int32)
            out.append(nxt)
            last, cache = self._decode(self.params, nxt, cache, pos)
            pos += 1
        return jnp.stack(out, axis=1)


def _refuse_looped(config: llama.LlamaConfig, service: str):
    """``config``, unless ``service`` (which runs the layer stack once)
    is handed a looped stack (``loop_steps > 1``): then it raises, naming
    itself (``continuous_batching.refuse_one_pass``)."""
    if config.loop_steps > 1:
        from ray_tpu.models import continuous_batching

        continuous_batching.refuse_one_pass(config, service)
    return config
