"""Continuous batching: iteration-level scheduling for LLM serving.

Reference: the vLLM-style engine behind ``ray.serve.llm``
(``python/ray/llm/_internal/serve``) — instead of batching whole
requests (head-of-line blocking on the longest generation), the engine
owns a fixed pool of KV-cache slots; requests prefill into a free slot
and join the very next decode tick, and finished requests free their
slot immediately for queued work.

TPU-native shape discipline: the decode tick is ONE jitted program over
all ``num_slots`` slots (static shapes; inactive slots compute masked
garbage), per-slot absolute positions drive RoPE/cache scatter/causal
masking, and prompt prefills pad to power-of-two buckets so the number
of compiled programs stays logarithmic. Padded prefill is sound without
length masking because a slot's garbage cache entries live only at
positions strictly greater than its next decode position — every decode
overwrites position ``p`` before attending ``[0..p]``.

The KV data plane is PAGED (``models/paged_kv.py``): slots share one
block arena through per-slot block tables, so a tick's attention
streams only the blocks a slot actually filled — no ``S_max`` padding
traffic — with optional int8 arena storage halving bytes-per-token
again. Sampling (temperature/top-p) runs in-device inside the tick jit;
only token ids cross to the host. One forward over the arena
(:func:`_forward_paged`) is the decode tick (a window of 1), the
speculative verify pass (k+1) and the self-draft (1, the first layers).

CROSS-REQUEST PREFIX CACHING (default on, ``prefix_cache`` /
``RAY_TPU_PREFIX_CACHE``): admission matches each
prompt's longest block-aligned prefix against a radix index of blocks
already resident in the arena (``paged_kv.RadixBlockIndex``), splices
the matched blocks into the slot's table READ-ONLY (decode writes start
at the prompt tail, and speculative overruns redirect to the garbage
block — a shared block is never a write target), and prefills ONLY the
suffix — prefill compute and HBM traffic scale with *novel* tokens, not
total tokens. Released prompt blocks park in an LRU "cached" state that
arena pressure reclaims before admission ever blocks. Greedy outputs
are bit-identical with the prefix cache on or off (bf16 and int8
arenas, paged kernel on or off): int8 prefill quantizes K/V IN-LOOP and
attends the dequantized values, so a later prefix-sharer reading the
arena back attends exactly what the original prefill attended.
"""

from __future__ import annotations

import contextlib
import functools
import heapq
import itertools
import os
import time
import zlib
from collections import deque
from typing import Any, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu._private import xla_monitor
from ray_tpu.models import (cca, eva, gated_delta, llama, looped, mamba1,
                            mamba2)
from ray_tpu.models import paged_kv
from ray_tpu.models.inference import (ExternalLlamaDrafter, KVCache,
                                      SelfDrafter, _attend_cached,
                                      _forward_cached, lm_head_logits)
from ray_tpu.models.llama import rms_norm
from ray_tpu.models.paged_kv import (GARBAGE_BLOCK, BlockAllocator,
                                     LatentKVCache, PagedKVCache,
                                     RadixBlockIndex,
                                     RingKVCache, StateCache, TailCache,
                                     prompt_chunks,
                                     quantize_kv, resolve_kv_dtype)
from ray_tpu.models.sampling import (SPEC_DRAFT_SALT, SamplingParams,
                                     filtered_probs, sample_tokens,
                                     spec_commit, step_key)
from ray_tpu.ops.attention import paged_chunk_attention
from ray_tpu.ops.dispatch import env_flag
from ray_tpu.ops.paged_decode_attention import (decode_attention_reference,
                                                paged_applicable,
                                                paged_decode_attention,
                                                paged_kv_write,
                                                paged_visits, visit_blocks)
from ray_tpu.ops.rope import apply_rope, rope_frequencies
from ray_tpu.util import tracing

# Children of a ``tracing.phase``: names in the profiler's trace only.
_annotation = jax.profiler.TraceAnnotation


def _scatter_slot(cache, new, positions):
    """cache [B, S_max, KVH, D]; new [B, KVH, D] written at per-slot
    ``positions`` [B]."""
    def one(c, n, p):
        return jax.lax.dynamic_update_slice(c, n[None], (p, 0, 0))

    return jax.vmap(one)(cache, new, positions)


def _scatter_arena(arena, new, block_idx, offset):
    """Paged scatter: arena [NB, KVH, bs, ...]; one token per row of
    ``new`` [T, KVH, ...] written at (``block_idx`` [T], ``offset`` [T]).
    Freed slots all target the garbage block — duplicate indices write
    byte-garbage there, which nothing ever attends."""
    return arena.at[block_idx, :, offset].set(new.astype(arena.dtype))


# A prefill program whose queries see at most this many keys (prefix +
# suffix) scores them all at once in float32 (:func:`_attend_cached`:
# every program compiled before chunked prefill existed); one that sees
# more, or whose model has sliding-window layers, runs the blockwise
# softmax over the arena's blocks (``ops.attention.paged_chunk_attention``).
PREFILL_DENSE_KEYS = 1024

# The most padded tokens (rows x padded length) one ``cb_prefill`` call
# takes: what bounds a prefill program's temporaries whatever waits in
# the queue. No batch within one chunk at 8 rows is split by it.
PREFILL_BATCH_TOKENS = 8192


def _window_visits(tables, positions, limits, arena_k, use_kernel: bool,
                   window: int = 0, per_visit=visit_blocks):
    """The attention kernel's schedule over the arena ``arena_k`` (K of
    the table's arena or of the rings) for each of a window's S
    positions (``positions`` [B, S]): made ONCE a program, before the
    layer loop, because XLA leaves it inside the loop otherwise. A freed
    slot (limit 0) is never visited. None without the kernel."""
    if not use_kernel:
        return None
    return [paged_visits(tables, positions[:, j], limits,
                         block_size=arena_k.shape[-2],
                         per_visit=per_visit(arena_k), window=window)
            for j in range(positions.shape[1])]


def _write_then_attend(arenas, li, q, k_new, v_new, block_idx, offset,
                       tables, positions, visits, scale, use_kernel: bool,
                       window: int = 0):
    """The attention half of :func:`_forward_paged`'s layer: write each
    slot's S new tokens' K/V into layer ``li`` (S = 1 for a tick, k+1
    for verify), then attend every window position over the slot's
    blocks. ``arenas`` = (k, v, k_scale, v_scale), each the WHOLE
    ``[L, NB, KVH, bs, ...]`` array as the layer scan carries it (scales
    None for a bf16 arena); q/k_new/v_new [B, S, Hq|KVH, D];
    block_idx/offset/positions [B, S]; ``visits`` from
    :func:`_window_visits`. All S writes land before any
    query attends, which position masking makes safe (query j sees
    [0..p+j] only). With ``window`` the arenas are the RING of the
    sliding-window layers and ``tables`` each slot's ring
    (``paged_kv.RingKVCache``). Returns (o [B, S, Hq, D], arenas').

    With the kernels the write is a Mosaic call aliased onto the carry
    and the read takes the layer as a scalar, so no slab ever exists.
    Without them (shapes that do not tile; every CPU run that does not
    ask for the interpreted kernels) the slab is sliced out, scattered
    by XLA and put back. On the TPU any XLA write into the heads-major
    arena makes the compiler relayout the slab for the scatter and again
    for the reader: that is what this path costs there, and why the
    kernel path never takes it."""
    def each(fn, *columns):
        """``fn`` over (k, v, k_scale, v_scale); an absent scale stays
        None."""
        return tuple(None if col[0] is None else fn(*col)
                     for col in zip(*columns))

    new = (k_new, v_new, None, None)
    if arenas[2] is not None:
        # Per-token/per-head scales reduce over D only, so a
        # window-batched quantize is bitwise the tick's.
        kq, ksc = quantize_kv(k_new)
        vq, vsc = quantize_kv(v_new)
        new = (kq, vq, ksc, vsc)
    if use_kernel:
        arenas = view = each(
            lambda a, n: paged_kv_write(a, n, li, block_idx, offset),
            arenas, new)
        layer = li
    else:
        flat_idx, flat_off = block_idx.reshape(-1), offset.reshape(-1)
        view = each(
            lambda a, n: _scatter_arena(
                jax.lax.dynamic_index_in_dim(a, li, 0, keepdims=False),
                n.reshape(-1, *n.shape[2:]), flat_idx, flat_off),
            arenas, new)
        arenas = each(
            lambda a, slab: jax.lax.dynamic_update_index_in_dim(
                a, slab, li, 0),
            arenas, view)
        layer = None
    ck, cv, ks, vs = view
    outs = [paged_decode_attention(q[:, j], ck, cv, tables,
                                   positions[:, j], scale, layer=layer,
                                   visits=visits and visits[j],
                                   k_scale=ks, v_scale=vs,
                                   use_kernel=use_kernel, window=window)
            for j in range(q.shape[1])]   # unrolled: S = k+1, small
    return jnp.stack(outs, axis=1), arenas


def _blocks_to_ctx(a, n: int):
    """Gathered arena blocks [Lyr, N*m, KVH, bs, ...] -> the dense
    per-row context [Lyr, N, m*bs, KVH, ...] attention reads."""
    lyr, nm, hkv, bs = a.shape[:4]
    m = nm // n                                # 0 when nothing matched
    a = a.reshape(lyr, n, m, hkv, bs, *a.shape[4:])
    a = jnp.swapaxes(a, 3, 4)                  # [Lyr, N, m, bs, KVH, ...]
    return a.reshape(lyr, n, m * bs, hkv, *a.shape[5:])


def _ctx_to_blocks(a, bs: int):
    """Dense per-row K/V [Lyr, N, S, KVH, ...] -> arena blocks
    [Lyr, N*(S//bs), KVH, bs, ...] (inverse of :func:`_blocks_to_ctx`)."""
    lyr, n, s, hkv = a.shape[:4]
    a = a.reshape(lyr, n, s // bs, bs, hkv, *a.shape[4:])
    a = jnp.swapaxes(a, 3, 4)                  # [Lyr, N, npb, KVH, bs, ...]
    return a.reshape(lyr, n * (s // bs), hkv, bs, *a.shape[5:])


def _next_tokens(logits, step, sampling: SamplingParams, salt: int = 0):
    """In-device token selection from tick/prefill logits [B, 1, V]:
    greedy argmax, or temperature/top-p sampling keyed off the
    device-threaded ``step`` counter (deterministic under a fixed
    seed). ``salt``
    separates the prefill and decode key streams — their counters both
    start at 0, and an unsalted collision would correlate prefill
    first-token draws with the first decode tick's."""
    row = logits[:, 0]
    if sampling.greedy:
        return jnp.argmax(row, axis=-1).astype(jnp.int32)
    key = step_key(sampling.seed, step, salt=salt)
    return sample_tokens(row, key, sampling.temperature, sampling.top_p)


_PREFILL_SALT = 1  # prefill sampling stream, distinct from decode's


def init_engine_params(config: llama.LlamaConfig, key):
    """:func:`llama.init_params`' seeded weights in the engine's layout
    (:func:`llama.heads_major`): what ``cb_init`` runs, and the tree
    every engine program is traced against."""
    return llama.heads_major(llama.init_params(config, key))


_relay_heads = xla_monitor.instrument(llama.swap_heads, name="cb_relay",
                                      shape_policy="free")


def _layer_qkv(x, layer, cos, sin, c):
    """Every engine layer's first half: attn-norm, Q/K/V projections,
    RoPE on Q and K (V unrotated). x [B, S, E]; cos/sin [S, D//2] where
    the rows share their positions (prefill) or [B, S, D//2] per (slot,
    position). S is 1 for a tick and k+1 for a verify window: the window
    rides the batch dims and the E-axis accumulation is untouched, so
    position j of a window gets the bits S = 1 gives it, which the
    spec-on/off parity tests pin down. Also returns the output gate
    (``llama.attn_gate``; None for a model without one)."""
    h = llama.norm(x, layer["attn_norm"], c).astype(c.dtype)
    q, k, v = llama.project_qkv(h, layer, c)
    gate = llama.attn_gate(h, layer, c)    # None without an output gate
    if cos is None:     # no positions: "nope", or a layer kind without
        return q, k, v, gate
    return _rotate(q, cos, sin), _rotate(k, cos, sin), v, gate


def _rotate(x, cos, sin):
    """:func:`apply_rope` over the dims the tables cover: all of a head,
    or its first ``2 * cos.shape[-1]`` (``partial_rotary_factor``); the
    rest pass through."""
    turned = 2 * cos.shape[-1]
    if turned == x.shape[-1]:
        return apply_rope(x, cos, sin)
    return jnp.concatenate(
        [apply_rope(x[..., :turned], cos, sin), x[..., turned:]], axis=-1)


def _rope_tables(c, length, positions):
    """cos/sin for ``positions``; None for a model without rope."""
    if not c.rope:
        return None, None
    if c.latent_layers:     # the rotated dims alone, YaRN frequencies
        return _mla().rope_tables(c, positions)
    return rope_frequencies(c.rotary_dim, length, c.rope_theta,
                            positions=positions)


def _mla():
    """``models/mla.py``, imported where a latent-attention layer is met:
    no other family's process loads it or the kernel behind it."""
    from ray_tpu.models import mla

    return mla


def _visit_rule(cache):
    """Blocks a grid step of the attention kernel covers, as a function
    of the cache's ``k``: the latent kernel's rule for a latent cache."""
    if isinstance(cache, LatentKVCache):
        from ray_tpu.ops.latent_decode_attention import latent_visit_blocks

        return latent_visit_blocks
    return visit_blocks


def _embed(params, tokens, c):
    x = params["embed"].astype(c.dtype)[tokens]
    if c.embedding_multiplier != 1.0:
        x = x * c.embedding_multiplier
    # ``fp32_residual``: the stream between sublayers is float32; every
    # sublayer still reads its norm's output in the model's dtype.
    return x.astype(jnp.float32) if c.fp32_residual else x


def _attn_out(o, layer, c, gate=None):
    """Attention's output projection of o [B, S, H, D] -> [B, S, E],
    after the output gate where the model has one."""
    if gate is not None:
        o = o * gate
    return jnp.einsum("bshd,hde->bse", o, layer["wo"].astype(c.dtype))


_KIND_NAMES = {"mamba": "state-space", "mamba1": "selective-scan",
               "linear_attention": "linear-attention",
               "sliding_attention": "sliding-window",
               "latent_attention": "latent-attention",
               "eva_attention": "eva-attention",
               "cca_attention": "cca-attention",
               # Not a layer kind: the whole stack applied ``loop_steps``
               # times (``models/looped.py``).
               "looped": "a looped layer stack"}
# A recurrent layer (Mamba-2, Mamba-1 or Gated DeltaNet) keeps, not K/V, a
# state a slot that only moves FORWARD and belongs to one request.
_RECURRENT_CANNOT = {
    "second_kind": "the engine keeps one cache beside the arena, and the "
                   "state cache holds one mixer's shapes: no second kind of "
                   "recurrent layer, no ring",
    "speculative": "a rejected draft cannot rewind a recurrent state",
    "prefix_cache": "a cached prefix restores K/V blocks, not the state "
                    "after them",
    "handoff": "the KV handoff carries no recurrent state",
}
# What the engine offers that a layer kind's cache cannot have, and why:
# the one place a kind's limits are written. A capability is refused, by
# :func:`_refuse_unsupported`, for the first kind of the stack that names
# it; "second_kind" is asked by the stack itself, the rest by the caller.
_KIND_CANNOT = {
    "mamba": _RECURRENT_CANNOT, "linear_attention": _RECURRENT_CANNOT,
    "mamba1": dict(_RECURRENT_CANNOT, kv_dtype="its prefill lands bf16 K/V"),
    # The last ``sliding_window`` keys of a slot live in a ring beside
    # the arena, which overwrites the rest.
    "sliding_attention": {
        "second_kind": "the engine keeps one cache beside the arena, and "
                       "here that is the ring",
        "kv_dtype": "the ring has no scale sidecar",
        "speculative": "a rejected draft's writes may have overwritten ring "
                       "entries the rewound position still sees",
        "prefix_cache": "a hit restores the arena's blocks, not the window "
                        "layers' keys, which a ring has overwritten",
        "handoff": "the KV handoff carries the arena's blocks, not a ring's",
    },
    # One row a token in ``paged_kv.LatentKVCache``, weights in
    # ``params["runs"]``: nothing written against per-head K/V planes, or
    # that cuts the layer stack out of ``params["layers"]``.
    "latent_attention": {
        "second_kind": "the latent cache takes the arena's place, so every "
                       "layer must keep its keys there",
        "kv_dtype": "8-bit latents are a different model output, not a "
                    "storage option: the cache has no scale sidecar",
        "speculative": "the self-draft cuts params['layers'], which holds "
                       "this family's experts alone, and the verify programs "
                       "take K and V planes",
        "handoff": "the KV handoff gathers and scatters K and V planes, "
                   "and a latent cache has one plane",
        "score_logprobs": "it runs llama.forward, the training forward, "
                          "which this serving-only family has none of",
    },
    # The arena under a compressed position (``models/eva.py``): a closed
    # window's blocks are REWRITTEN as its summaries, so a block no
    # longer stands for the ``block_size`` tokens that filled it.
    "eva_attention": {
        "second_kind": "the compressed position is the whole table's: every "
                       "layer must summarise the windows the others do",
        "kv_dtype": "summaries are pooled from the keys as stored and "
                    "written back over them: an 8-bit arena would quantize "
                    "twice, and the pooling reads no scale sidecar",
        "speculative": "a rejected draft that crossed a window's end has "
                       "already overwritten the window with its summaries",
        "prefix_cache": "the radix index names blocks by the block_size "
                        "tokens that filled them; a closed window's blocks "
                        "hold summaries, and its raw keys are gone",
        "handoff": "the KV handoff counts a prompt's blocks from its "
                   "length, not the compressed table's",
        "score_logprobs": "it runs llama.forward, the training forward, "
                          "which this serving-only family has none of",
    },
    # K/V in the arena like any attention layer's, AND a row a slot in
    # ``paged_kv.TailCache`` (``models/cca.py``): the last token's
    # convolution inputs and shifted value half, which the next token's
    # K/V are made from.
    "cca_attention": {
        "second_kind": "a layer's arena index and its tail's are one "
                       "number, so every layer must keep both",
        "kv_dtype": "the keys are unit vectors times a learned "
                    "temperature, held to the reference as bf16 only: an "
                    "8-bit arena is a different model output until it is",
        "speculative": "a rejected draft cannot rewind the convolution "
                       "tail",
        "prefix_cache": "a hit restores K/V blocks, not the convolution "
                        "tail after them",
        "handoff": "the KV handoff carries the arena's blocks, not the "
                   "convolution tail",
        "score_logprobs": "it runs llama.forward, the training forward, "
                          "which this serving-only family has none of",
    },
    # ``loop_steps > 1`` (``models/looped.py``): a K/V row for every
    # (step, layer) pair. Each capability either runs every step or is
    # refused here; none runs one pass of the stack silently, which would
    # be another model's output under this model's name. The last three
    # are services outside the engine (:func:`refuse_one_pass`).
    "looped": {
        "kv_dtype": "the arena's rows are held to the float32 reference as "
                    "bf16 only: an 8-bit arena under four passes of the "
                    "stack is a different model output until it is measured",
        "speculative": "the self-draft runs the first layers of ONE pass, "
                       "which is no shallow copy of a model that applies its "
                       "stack loop_steps times, and the verify programs run "
                       "one pass",
        "handoff": "the KV handoff sizes and checks its payload by "
                   "num_layers, and a looped arena has loop_steps x "
                   "num_layers rows",
        "score_logprobs": "it runs llama.forward, the training forward, "
                          "which does not run the loop",
        "llama.forward": "the training forward (and loss_fn on it) runs the "
                         "stack once; the loop's objective also needs a "
                         "weight on the exit entropy that the published "
                         "config does not give",
        "LlamaGenerator": "the dense-cache generator keeps num_layers cache "
                          "rows and runs the stack once",
        "ExternalLlamaDrafter": "a drafter's dense cache keeps num_layers "
                                "rows and its forward runs the stack once",
    },
}


def _refuse_unsupported(config, asked: Dict[str, str]) -> None:
    """Raise for the first of ``asked`` ({capability: the caller's name})
    that ``config`` cannot have: :data:`_KIND_CANNOT`."""
    kinds = set(config.layer_types)
    if config.loop_steps > 1:
        kinds.add("looped")
    for kind, cannot in _KIND_CANNOT.items():
        if kind not in kinds:
            continue
        # (The arena's own layers sit beside any; the loop is no layer.)
        beside = kinds - {kind, "looped"}
        if kind not in ("latent_attention", "eva_attention",
                        "cca_attention"):
            beside -= {"attention", "full_attention"}
        wants = dict(asked)
        if beside:
            wants["second_kind"] = "another layer kind in the same stack"
        said = (f"{_KIND_NAMES[kind]} (loop_steps > 1)" if kind == "looped"
                else f"{_KIND_NAMES[kind]} layers (layer_types has {kind!r})")
        for capability, why in cannot.items():
            if capability in wants:
                raise ValueError(f"{wants[capability]} is not supported for "
                                 f"a model with {said}: {why}")


def refuse_one_pass(config: llama.LlamaConfig, service: str) -> None:
    """``service`` (a name of :data:`_KIND_CANNOT`'s "looped" entry) runs
    the layer stack once: raise, naming it, for a config that loops."""
    if config.loop_steps > 1:
        raise NotImplementedError(
            f"{service} does not run a model with a looped layer stack "
            f"(loop_steps = {config.loop_steps}): "
            f"{_KIND_CANNOT['looped'][service]}; the continuous-batching "
            "engine serves it")


_KIND_SCOPES = {"sliding_attention": "attn/window",
                "full_attention": "attn/full"}


def _kind_scope(kind):
    """The profiler scope of the two attention kinds of a windowed
    model; a homogeneous model's layers keep the names they had."""
    name = _KIND_SCOPES.get(kind)
    return jax.named_scope(name) if name else contextlib.nullcontext()


def _kind_rope(c, kind, cos, sin):
    """The rope tables layer ``kind`` takes: none for a full-attention
    layer of a model that gives those no positions."""
    if kind == "full_attention" and not c.rope_full_attention:
        return None, None
    return cos, sin


def _residual(x, y, c, scaling=None):
    """``x + residual_multiplier * y`` (GraniteMoeHybridDecoderLayer);
    with ``scaling [4, E]`` (a ``residual_scaling`` model's ``res_attn``
    or ``res_mlp``) ``(s_res x + t_res) + (s_out y + t_out)``, float32
    inside."""
    if scaling is not None:
        s_res, t_res, s_out, t_out = scaling.astype(jnp.float32)
        return (x.astype(jnp.float32) * s_res + t_res
                + y.astype(jnp.float32) * s_out + t_out).astype(x.dtype)
    return x + y if c.residual_multiplier == 1.0 else (
        x + y * c.residual_multiplier)


def _cca_qkv(x, layer, c, cos, sin, tail, lengths=None):
    """A CCA layer's first half on x [B, S, E] (``models/cca.py``):
    attn-norm, the one down-projection, the two convolutions behind
    ``tail`` (each row's ``[u | a | v2]`` of the position before its
    first; None = an empty history), the q-k mean, the L2 norms and the
    keys' temperature, THEN rope. Returns (q, k, v, the rows' next
    tail)."""
    h = llama.norm(x, layer["attn_norm"], c).astype(c.dtype)
    with jax.named_scope("cca/proj"):
        u, v1, v2 = cca.project(h, layer, c)
    with jax.named_scope("cca/mix"):
        q, k, v, tail = cca.mix(u, v1, v2, tail, layer, c, lengths)
        q = _rotate(q, cos, sin).astype(c.dtype)
        k = _rotate(k, cos, sin).astype(c.dtype)
    return q, k, v, tail


def _layer_finish(x, mixed, layer, c, experts=None, li=None,
                  use_kernel=None, route=None):
    """Every engine layer's second half: ``mixed`` [B, S, E], the token
    mixer's output (attention's :func:`_attn_out`, or the Mamba-2
    mixer's), joins the residual, then ``x + MLP(norm(x))`` through the
    family's one MLP function (:func:`llama.mlp_block`: dense SwiGLU, or
    the routed block reading the stacked ``experts`` at layer ``li``).
    Returns (x, rows, route): the routed block's per-expert assignment
    counts, None for a dense model, and what a router with a state over
    depth (``router_hidden_size``; ``route`` is what the layer before
    left) hands the next layer, None for any other. A HELD SHARE's rows
    carry each token's chosen
    experts ``[T * k]`` behind the counts: its output leaves out what the
    absent experts add, so its routing shows nowhere else
    (:meth:`ContinuousBatcher.take_routes`)."""
    if c.sandwich_norms:
        mixed = llama.norm(mixed, layer["post_attn_norm"], c)
    x = _residual(x, mixed, c, layer.get("res_attn"))
    h = llama.norm(x, layer["mlp_norm"], c).astype(c.dtype)
    down, routed = llama.mlp_block(h, layer, c, experts, li,
                                   use_kernel=use_kernel, route=route)
    if c.sandwich_norms:
        down = llama.norm(down, layer["post_mlp_norm"], c)
    rows = None if routed is None else routed.rows
    if rows is not None and c.experts_held:
        rows = jnp.concatenate([rows, routed.experts.reshape(-1)])
    return (_residual(x, down, c, layer.get("res_mlp")), rows,
            None if routed is None else routed.carry)


def _route_carry(c, tokens):
    """What the first layer's router reads of "the layer before it": a
    zero row a token for a router with a state over depth, None (nothing
    in any program) for every other model."""
    if not c.router_hidden_size:
        return None
    return jnp.zeros((tokens.size, c.router_hidden_size), jnp.float32)


def _concat_runs(parts):
    """Per-run scan outputs (trees stacked over each run's layers)
    joined along the layer axis; one run's come back as they are."""
    parts = [p for p in parts if p is not None]
    if len(parts) <= 1:
        return parts[0] if parts else None
    return jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *parts)


def _split_caches(caches):
    """(arena, second cache) of an engine program's ``caches`` operand:
    the arena alone for a model whose every layer keeps all its K/V (or
    the latent cache in its place), else the pair: with the state cache
    (state-space layers), the ring (sliding-window layers) or the tail
    cache (CCA layers, which keep K/V in the arena as well)."""
    if isinstance(caches, (PagedKVCache, LatentKVCache)):
        return caches, None
    return caches


def _forward_paged(params, tokens, positions, tables, limits,
                   caches, config: llama.LlamaConfig,
                   use_kernel: bool, n_layers: Optional[int] = None):
    """The engine's ONE forward over the paged arena: each slot's window
    of S tokens [B, S] at per-slot absolute ``positions`` [B, S]
    (= p .. p+S-1), reading and writing the arena through the slot's
    block table. The decode tick is S = 1, the speculative verify pass
    S = k+1, and the self-draft S = 1 through the FIRST ``n_layers``
    layers only: the truncated stack computes bitwise the target's
    layer-[0:n) K/V, so its context is already resident and its writes
    are the bytes verify rewrites identically.

    ``tables`` [B, max_blocks] int32 (dead tail entries repeat the last
    live block; freed slots point wholesale at the garbage block);
    ``limits`` [B] is each slot's table-covered token count
    (reserved_blocks * bs), 0 for a freed slot, which the attention
    kernel then never visits.

    Projections and the MLP run batched over the window — a verify pass
    streams the parameters ONCE for all k+1 positions, which is the
    speculative roofline lever — while attention runs per window
    position (:func:`_write_then_attend`).

    The stack is scanned a RUN of equal layers at a time
    (:func:`llama.layer_runs`; a homogeneous model is one run). A
    "mamba" layer (S = 1 only) mixes through
    :func:`mamba2.mixer_step`, a "linear_attention" layer through
    :func:`gated_delta.mixer_step`, a "mamba1" layer through
    :func:`mamba1.mixer_step`; each advances every slot's row of
    the per-slot state cache beside the arena in place; the arena holds the
    attention layers alone, so each kind indexes its own cache by its
    index among layers of its kind. A "cca_attention" layer (S = 1 only)
    uses BOTH stores: its K/V go to the arena like any attention
    layer's, made from the slot's row of the tail cache, which it
    advances. ``caches`` is the arena, or the pair (arena, state cache)
    for a model with state layers.

    A LOOPED stack (``loop_steps > 1``, ``models/looped.py``) runs the
    scans ``loop_steps`` times over the same weights, the final norm
    after every pass; pass ``t`` writes and attends rows ``t x L ..`` of
    the arena.

    Returns (fp32 logits [B, S, V] through the final norm + lm_head,
    the updated ``caches``, and what a plain tick's row carries behind
    its tokens: a routed model's per-layer per-expert row counts [L, X],
    a looped stack's exit gates [T, B, S] (:func:`looped.gate_bits`),
    None for any other model)."""
    c = config
    cache, state = _split_caches(caches)
    bs = cache.block_size
    cos, sin = _rope_tables(c, 0, positions)                  # [B, S, D//2]
    x = _embed(params, tokens, c)                             # [B, S, E]
    scale = c.attn_scale
    true_positions = positions
    if c.eva_window:
        # From here on ``positions`` are the ARENA's: a key lies behind
        # its slot's summaries, not behind every key before it.
        positions = eva.compressed(positions, c)
    # Resolve each position's target block through the slot's table once
    # (shared by every layer's write). The tick in flight can OVERRUN a
    # slot's reservation (the host learns of an end one tick late): past
    # ``limits`` the table tail would alias the write onto the slot's
    # LAST LIVE block. Redirect overrun writes, and a freed slot's, to
    # the garbage block instead.
    gathered = jnp.take_along_axis(tables, positions // bs, axis=1)
    block_idx = jnp.where(positions < limits[:, None], gathered,
                          GARBAGE_BLOCK)                      # [B, S]
    offset = positions % bs
    visits = _window_visits(tables, positions, limits, cache.k, use_kernel,
                            per_visit=_visit_rule(cache))
    if isinstance(state, RingKVCache):
        # Sliding-window layers: every slot's fixed ring of blocks, its
        # table an iota; position p lands in entry (p // bs) % ring.
        ring = paged_kv.ring_blocks(c.sliding_window, bs)
        ring_tables = RingKVCache.tables(jnp.arange(tokens.shape[0]), ring)
        ring_idx = jnp.where(
            positions < limits[:, None],
            jnp.take_along_axis(ring_tables, (positions // bs) % ring,
                                axis=1), GARBAGE_BLOCK)
        ring_visits = _window_visits(ring_tables, positions, limits,
                                     state.k, use_kernel, c.sliding_window)

    runs, experts = llama.layer_runs(c, params, n_layers)

    def layer_fn(carry, layer, kind, shift):
        # The arena (and the state cache) ride the CARRY, updated in
        # place layer by layer, not scan xs/ys: as per-iteration
        # inputs/outputs XLA materializes full cache copies every tick.
        x, arenas, held, route, li = carry
        ki = li + shift if shift else li    # index among layers of its kind
        if kind == "mamba":
            h = rms_norm(x, layer["attn_norm"], c.rms_eps)
            mixed, *held = mamba2.mixer_step(h, layer, c, *held, ki,
                                             use_kernel)
            held = tuple(held)
        elif kind == "linear_attention":
            h = llama.norm(x, layer["attn_norm"], c)
            mixed, *held = gated_delta.mixer_step(h, layer, c, *held, ki,
                                                  use_kernel)
            held = tuple(held)
        elif kind == "mamba1":
            h = llama.norm(x, layer["attn_norm"], c).astype(c.dtype)
            mixed, *held = mamba1.mixer_step(h, layer, c, *held, ki,
                                             use_kernel)
            held = tuple(held)
        elif kind == "latent_attention":
            mixed, latents = _mla().tick_layer(
                x, layer, c, arenas[0], ki, cos, sin, block_idx, offset,
                tables, positions, visits, use_kernel)
            arenas = (latents,)
        elif kind == "eva_attention":
            with jax.named_scope("eva/qkv"):
                q, k, v, _ = _layer_qkv(x, layer, cos, sin, c)
            with jax.named_scope("eva/attend"):
                o, arenas = _write_then_attend(
                    arenas, ki, q, k, v, block_idx, offset, tables,
                    positions, visits, scale, use_kernel)
            with jax.named_scope("eva/out_proj"):
                mixed = _attn_out(o.astype(c.dtype), layer, c)
        elif kind == "cca_attention":
            # One layer, two stores: the slot's tail row makes this
            # token's K/V, which go to the arena.
            q, k, v, tail = _cca_qkv(
                x, layer, c, cos, sin, jax.lax.dynamic_index_in_dim(
                    held[0], ki, 0, keepdims=False))
            held = (jax.lax.dynamic_update_index_in_dim(
                held[0], tail, ki, 0),)
            with jax.named_scope("cca/attend"):
                o, arenas = _write_then_attend(
                    arenas, ki, q, k, v, block_idx, offset, tables,
                    positions, visits, scale, use_kernel)
            with jax.named_scope("cca/out_proj"):
                mixed = _attn_out(o.astype(c.dtype), layer, c)
        else:
            with _kind_scope(kind):
                q, k, v, gate = _layer_qkv(
                    x, layer, *_kind_rope(c, kind, cos, sin), c)
                if kind == "sliding_attention":
                    o, ring_kv = _write_then_attend(
                        held + (None, None), ki, q, k, v, ring_idx, offset,
                        ring_tables, positions, ring_visits, scale,
                        use_kernel, c.sliding_window)
                    held = ring_kv[:2]
                else:
                    o, arenas = _write_then_attend(
                        arenas, ki, q, k, v, block_idx, offset, tables,
                        positions, visits, scale, use_kernel)
                mixed = _attn_out(o.astype(x.dtype), layer, c, gate)
        x, rows, route = _layer_finish(x, mixed, layer, c, experts, li,
                                       use_kernel, route)
        return (x, arenas, held, route, li + 1), rows

    arenas, held = tuple(cache), tuple(state or ())
    route = _route_carry(c, tokens)
    rows, gates = [], []
    for step in range(c.loop_steps):        # one pass, but for a looped stack
        if step:        # between two passes: the final norm, and the gate
            x = looped.step_end(x, params, c)
            gates.append(looped.exit_gate(x, params))
        with looped.step_scope(c, step):
            for (kind, start, _, kind_start), tree in runs:
                (x, arenas, held, route, _), run_rows = jax.lax.scan(
                    functools.partial(
                        layer_fn, kind=kind,
                        shift=kind_start - start + step * c.attn_layers),
                    (x, arenas, held, route, jnp.int32(start)), tree)
                rows.append(run_rows)
    if c.eva_window:
        # The rows whose new key filled its window: pool the window into
        # its summaries, in place (one run: no second kind beside EVA).
        with jax.named_scope("eva/summarise"):
            pooling = runs[0][1]
            arenas = eva.close_windows(
                *arenas[:2], tables, true_positions[:, 0],
                positions[:, 0] < limits, pooling["eva_phi"],
                pooling["eva_mu"], c) + arenas[2:]
    x = looped.step_end(x, params, c)       # the final norm
    if gates:
        gates.append(looped.exit_gate(x, params))
    # lm_head in the params' storage dtype with fp32 accumulation
    # (shared with prefill): bf16 params are never upcast in HBM.
    logits = lm_head_logits(x, params, c)
    cache = type(cache)(*arenas)
    return (logits, (cache, type(state)(*held)) if held else cache,
            looped.gate_bits(gates) if gates else _concat_runs(rows))


def _draft_forward_dense(dparams, tokens, positions, dcache: KVCache,
                         dconfig: llama.LlamaConfig):
    """External-drafter decode step over the drafter's OWN dense
    per-slot cache (another model's cache, not a second plane for the
    target; reference attention — the drafter is small by construction).
    tokens/positions [B]. Returns (logits [B, V], updated cache)."""
    c = dconfig
    cos, sin = _rope_tables(c, 0, positions[:, None])
    x = _embed(dparams, tokens, c)[:, None, :]
    scale = c.attn_scale

    def layer_fn(carry, layer):
        x, ck_all, cv_all, li = carry
        ck = jax.lax.dynamic_index_in_dim(ck_all, li, 0, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(cv_all, li, 0, keepdims=False)
        q, k, v, _ = _layer_qkv(x, layer, cos, sin, c)
        ck = _scatter_slot(ck, k[:, 0].astype(ck.dtype), positions)
        cv = _scatter_slot(cv, v[:, 0].astype(cv.dtype), positions)
        o = decode_attention_reference(q[:, 0], ck, cv, positions, scale)
        ck_all = jax.lax.dynamic_update_index_in_dim(ck_all, ck, li, 0)
        cv_all = jax.lax.dynamic_update_index_in_dim(cv_all, cv, li, 0)
        x, *_ = _layer_finish(x, _attn_out(o[:, None], layer, c), layer, c,
                              experts, li)
        return (x, ck_all, cv_all, li + 1), None

    scanned, experts = llama.split_layers(dparams)
    (x, nk, nv, _), _ = jax.lax.scan(
        layer_fn, (x, dcache.k, dcache.v, jnp.int32(0)), scanned)
    x = rms_norm(x, dparams["final_norm"], c.rms_eps)
    logits = lm_head_logits(x, dparams, c)
    return logits[:, 0], KVCache(k=nk, v=nv)


def _spec_tick_paged(params, tokens, positions, tables, limits,
                     cache: PagedKVCache, step,
                     config: llama.LlamaConfig, k: int, n_draft: int,
                     use_kernel: bool, sampling: SamplingParams,
                     draft_params=None, draft_cache=None,
                     draft_config=None):
    """Speculative decode tick: draft ``k`` tokens per slot, score all
    k+1 window positions in ONE batched verify pass, accept per slot
    in-device (:func:`~ray_tpu.models.sampling.spec_commit`).

    Returns ``(committed [B, k+1], counts [B], next_tokens [B],
    next_positions [B], cache, draft_cache, step + 1)`` — the device
    threads its own next-token/next-position state exactly like the
    plain tick. Rejected draft writes land past each slot's committed
    length inside its (k-lookahead-extended) reservation and are dead on
    arrival: every future decode overwrites a position before attending
    it."""
    external = draft_params is not None
    d_tokens: List[Any] = []
    d_probs: List[Any] = []
    tok = tokens
    pos = positions
    dcache = draft_cache if external else cache
    draft_key = None if sampling.greedy else step_key(
        sampling.seed, step, salt=SPEC_DRAFT_SALT)
    for i in range(k):
        if external:
            logits_d, dcache = _draft_forward_dense(
                draft_params, tok, pos, dcache, draft_config)
        else:
            # The self-draft shares the target's arena (layers [0:n)).
            logits_d, dcache, _ = _forward_paged(
                params, tok[:, None], pos[:, None], tables, limits,
                dcache, config, use_kernel, n_layers=n_draft)
            logits_d = logits_d[:, 0]
        if sampling.greedy:
            nxt = jnp.argmax(logits_d, axis=-1).astype(jnp.int32)
        else:
            # The drafter proposes from its OWN filtered distribution;
            # acceptance needs those q rows, and the proposal stream is
            # salted apart from accept/fix/base-tick draws.
            d_probs.append(filtered_probs(
                logits_d, sampling.temperature, sampling.top_p))
            nxt = sample_tokens(logits_d, jax.random.fold_in(draft_key, i),
                                sampling.temperature, sampling.top_p)
        d_tokens.append(nxt)
        tok = nxt
        pos = pos + 1
    if not external:
        cache = dcache  # self-draft wrote the shared arena layers [0:n)
    window = jnp.stack([tokens] + d_tokens, axis=1)          # [B, k+1]
    window_pos = positions[:, None] + jnp.arange(k + 1)[None, :]
    logits, cache, _ = _forward_paged(params, window, window_pos, tables,
                                      limits, cache, config, use_kernel)
    drafts = jnp.stack(d_tokens, axis=1)
    probs = jnp.stack(d_probs, axis=1) if d_probs else None
    committed, counts = spec_commit(drafts, probs, logits, step, sampling)
    next_tokens = jnp.take_along_axis(
        committed, (counts - 1)[:, None], axis=1)[:, 0]
    next_positions = positions + counts
    return (committed, counts, next_tokens, next_positions, cache,
            dcache if external else None, step + 1)


def _decode_tick_paged(params, tokens, positions, tables, limits,
                       caches, step,
                       config: llama.LlamaConfig, use_kernel: bool = False,
                       sampling: SamplingParams = SamplingParams()):
    """One decode step for every slot, the S = 1 case of
    :func:`_forward_paged`: tokens [B] at per-slot absolute
    ``positions`` [B]. ``caches`` is the K/V arena, or, for a model with
    state layers, the pair (arena, state cache). Returns (next_tokens
    [B], positions+1, caches, step+1) — ``step`` is the device-resident
    sampling counter. Token selection stays ON DEVICE: the host needs 4
    bytes per slot, not the [B, V] logits."""
    logits, caches, rows = _forward_paged(
        params, tokens[:, None], positions[:, None], tables, limits,
        caches, config, use_kernel)
    next_tokens = _next_tokens(logits, step, sampling)
    state = (next_tokens, positions + 1, caches, step + 1)
    if rows is None:
        return state
    # A routed model's tick also reports each layer's per-expert row
    # counts [L, X], a looped stack's every slot's gate of every step
    # [T, B, 1], packed BEHIND the token vector so the host's one
    # fetch a tick brings both (a second array would be a second sync).
    return state + (jnp.concatenate([next_tokens, rows.reshape(-1)]),)


class _PagedPrefix(NamedTuple):
    """Where a prefill chunk's queries find the prompt's EARLIER keys
    without anyone gathering them first: the caches themselves and, for
    each row, the blocks that hold them in order. ``tables [N, m]``
    names arena blocks (positions ``0 .. m * bs``); ``ring_tables [N,
    mw]`` the ring entries of the last ``mw`` of those ``m`` logical
    blocks, the only ones a sliding-window layer's queries still see
    (None without such layers)."""
    cache: PagedKVCache
    tables: jnp.ndarray
    ring: Optional[RingKVCache] = None
    ring_tables: Optional[jnp.ndarray] = None


def _prefill_forward_paged(params, tokens, positions, pk, pv, config,
                           quantized, last_idx, use_kernel=None,
                           state=None, slots=None,
                           paged: Optional[_PagedPrefix] = None,
                           land=None, dense=False):
    """Prefill forward over ``[shared prefix ++ suffix]``.

    ``tokens`` [N, S] are the suffix at absolute ``positions`` [S]
    (= P + arange(S), shared by the group — admission groups rows by
    matched-prefix length); ``pk``/``pv`` [L_attn, N, P, KVH, D] hold the
    prefix K/V exactly as attention must read them (the dequantized
    arena storage); ``last_idx`` [N] is each row's last real position.
    Returns ``(logits [N, 1, V], stored, state)``. The head runs on
    the hidden state at ``last_idx`` ONLY: the one position a prefill
    samples from (all-position float32 logits are N x S x V x 4 bytes,
    2.5 GB for 48 x 128 rows of a 100k vocabulary).
    ``stored`` is the suffix K/V in ARENA form — int8 arenas quantize
    IN-LOOP and attention reads the dequantized values, so what a later
    prefix-sharer gathers back from the arena is bit-identical to what
    this prefill attended: the prefix-cache on/off parity contract.
    ``state`` (None without state layers) is the state cache with, in
    row ``slots[i]`` of every state layer, prompt i's recurrent state
    and conv tail after its last real token: a right-padded row's
    padding must not advance them (K/V past the end are merely never
    attended; a state has no mask), so :func:`mamba2.mixer_prefill`
    takes the rows' lengths. Each layer writes its rows into the cache
    as the layer loop's CARRY (the five layers' states of a 48-row
    batch, stacked as the loop's output, would be a second gigabyte);
    a repeated padding row writes the same bytes twice.

    With ``paged`` (a long prompt's chunk, or a model with
    sliding-window layers; ``pk``/``pv`` are then not read) attention is
    :func:`~ray_tpu.ops.attention.paged_chunk_attention`: blockwise over
    the earlier keys where they lie, in the arena or the ring, then over
    the chunk's own, so no ``[S, P + S]`` float32 score exists; a
    "linear_attention" layer (always under ``paged``) starts from the
    state and conv tail in rows ``slots`` of ``state`` when the chunk has
    earlier ones (``paged.tables`` is not empty), from an empty history
    when it is the prompt's first, and installs what it leaves there (a
    "cca_attention" layer does the same with its row of the tail cache,
    and its K/V are the arena's like a full-attention layer's); and
    ``stored`` comes back as ``{cache kind: K/V of the layers that keep
    theirs there}`` (``"attention"``: the arena; ``"sliding_attention"``:
    the ring). A "mamba1" layer carries its state and conv tail from
    chunk to chunk as a "linear_attention" layer does.

    With ``land [N, S / bs]`` as well (an EVA-attention model, a looped
    stack) the layers land their own blocks in the arena through it as
    they go, the arena riding the layer loop's carry (stacked as the
    loop's output, a 4-row EVA chunk's raw keys of 8 layers would be a
    gigabyte that the arena mostly never takes, and the 192 rows of an
    8 x 256 looped batch 3 GB beside an arena that fills the chip): the
    arena comes back in ``state``'s place. A looped stack runs the scans
    ``loop_steps`` times, the final norm between the passes; in pass
    ``t`` layer ``l`` attends the earlier keys of ROW ``t x L + l`` and
    its own, blockwise where they lie or, ``dense`` (the chunk's queries
    see few enough keys: ``PREFILL_DENSE_KEYS``), all at once in float32
    over the row's gathered blocks."""
    c = config
    cos, sin = _rope_tables(c, tokens.shape[1], positions)
    x = _embed(params, tokens, c)
    scale = c.attn_scale

    runs, experts = llama.layer_runs(c, params)

    def layer_fn(carry, inputs, kind, shift):
        x, arenas, held, route, li = carry
        kept = ()
        if kind == "mamba":
            layer, = inputs
            h = rms_norm(x, layer["attn_norm"], c.rms_eps)
            mixed, *new = mamba2.mixer_prefill(h, layer, c, last_idx + 1)
            held = tuple(a.at[li + shift, slots].set(n.astype(a.dtype))
                         for a, n in zip(held, new))
        elif kind == "linear_attention":
            layer, = inputs
            h = llama.norm(x, layer["attn_norm"], c)
            # A chunk that is not its prompt's first goes on from the
            # rows the chunk before it installed.
            carried = (tuple(a[li + shift, slots] for a in held)
                       if paged.tables.shape[1] else None)
            mixed, *new = gated_delta.mixer_prefill(
                h, layer, c, last_idx + 1, carried)
            held = tuple(a.at[li + shift, slots].set(n.astype(a.dtype))
                         for a, n in zip(held, new))
        elif kind == "mamba1":
            layer, = inputs
            h = llama.norm(x, layer["attn_norm"], c).astype(c.dtype)
            carried = (tuple(a[li + shift, slots] for a in held)
                       if paged.tables.shape[1] else ())
            mixed, *new = mamba1.mixer_prefill(
                h, layer, c, last_idx + 1, *carried,
                use_kernel=use_kernel or None)
            held = tuple(a.at[li + shift, slots].set(n.astype(a.dtype))
                         for a, n in zip(held, new))
        elif kind == "latent_attention":
            layer, = inputs
            # Always the paged chunk form: the chunk's own keys and
            # values expanded per head, the earlier chunks' read as
            # latents where they lie.
            mixed, row = _mla().prefill_layer(
                x, layer, c, paged.cache.k, li + shift, cos, sin,
                paged.tables, paged.tables.shape[1] * paged.cache.block_size,
                bool(use_kernel))
            kept = (row,)
        elif kind == "eva_attention":
            layer, = inputs
            # A chunk is (at most) one window: causal over itself, dense
            # over the earlier windows' summaries, which are all the
            # arena holds of them; and it lands, for a row that fills
            # the window, its own summaries and no raw key.
            bs = paged.cache.block_size
            with jax.named_scope("eva/qkv"):
                q, k, v, _ = _layer_qkv(x, layer, cos, sin, c)
            with jax.named_scope("eva/attend"):
                o = paged_chunk_attention(
                    q, k, v, *arenas, li + shift, paged.tables, 0,
                    paged.tables.shape[1] * bs, scale)
            with jax.named_scope("eva/summarise"):
                blocks = eva.chunk_blocks(
                    k, v, layer["eva_phi"], layer["eva_mu"],
                    last_idx == c.eva_window - 1, c, bs)
                arenas = tuple(
                    a.at[li + shift, land.reshape(-1)].set(b.astype(a.dtype))
                    for a, b in zip(arenas, blocks))
            with jax.named_scope("eva/out_proj"):
                mixed = _attn_out(o.astype(c.dtype), layer, c)
        elif kind == "cca_attention":
            layer, = inputs
            # A chunk that is not its prompt's first goes on from the
            # row the chunk before it installed; each row leaves the
            # tail of its last REAL token.
            q, k, v, tail = _cca_qkv(
                x, layer, c, cos, sin,
                held[0][li + shift, slots] if paged.tables.shape[1]
                else None, last_idx + 1)
            held = (held[0].at[li + shift, slots].set(tail),)
            kept = (k, v)
            with jax.named_scope("cca/attend"):
                o = paged_chunk_attention(
                    q, k, v, paged.cache.k, paged.cache.v, li + shift,
                    paged.tables, 0,
                    paged.tables.shape[1] * paged.cache.block_size, scale)
            with jax.named_scope("cca/out_proj"):
                mixed = _attn_out(o.astype(c.dtype), layer, c)
        elif c.loop_steps > 1:
            # A looped stack's layer: ``shift`` has the pass's first row
            # in it, and both forms read the arena in the carry.
            layer, = inputs
            row, bs = li + shift, paged.cache.block_size
            m = paged.tables.shape[1]
            q, k, v, gate = _layer_qkv(x, layer, cos, sin, c)
            if not dense:
                o = paged_chunk_attention(q, k, v, *arenas, row,
                                          paged.tables, 0, m * bs, scale)
            else:
                ck, cv = k, v
                if m:       # the earlier keys as attention reads them
                    pk_l, pv_l = (_blocks_to_ctx(
                        a[row, paged.tables.reshape(-1)][None],
                        tokens.shape[0])[0].astype(c.dtype) for a in arenas)
                    ck = jnp.concatenate([pk_l, k], axis=1)
                    cv = jnp.concatenate([pv_l, v], axis=1)
                o = _attend_cached(q, ck, cv, positions, scale)
            mixed = _attn_out(o, layer, c, gate)
            arenas = tuple(
                a.at[row, land.reshape(-1)].set(
                    _ctx_to_blocks(new[None].astype(a.dtype), bs)[0])
                for a, new in zip(arenas, (k, v)))
        elif paged is not None:
            layer, = inputs
            with _kind_scope(kind):
                q, k, v, gate = _layer_qkv(
                    x, layer, *_kind_rope(c, kind, cos, sin), c)
                kept = (k, v)
                if kind == "sliding_attention":
                    source, tables = paged.ring, paged.ring_tables
                    window = c.sliding_window
                else:
                    source, tables, window = paged.cache, paged.tables, 0
                chunk_pos = paged.tables.shape[1] * source.k.shape[3]
                o = paged_chunk_attention(
                    q, k, v, source.k, source.v, li + shift, tables,
                    chunk_pos - tables.shape[1] * source.k.shape[3],
                    chunk_pos, scale, window=window)
                mixed = _attn_out(o, layer, c, gate)
        else:
            layer, pk_l, pv_l = inputs
            q, k, v, gate = _layer_qkv(x, layer, cos, sin, c)
            if quantized:
                kq, ksc = quantize_kv(k)
                vq, vsc = quantize_kv(v)
                k_att = (kq.astype(jnp.float32)
                         * ksc[..., None]).astype(c.dtype)
                v_att = (vq.astype(jnp.float32)
                         * vsc[..., None]).astype(c.dtype)
                kept = (kq, vq, ksc, vsc)
            else:
                k_att, v_att = k, v
                kept = (k, v)
            ck = jnp.concatenate([pk_l, k_att], axis=1)   # [N, P+S, KVH, D]
            cv = jnp.concatenate([pv_l, v_att], axis=1)
            mixed = _attn_out(_attend_cached(q, ck, cv, positions, scale),
                              layer, c, gate)
        x, _, route = _layer_finish(x, mixed, layer, c, experts, li,
                                    use_kernel, route)
        return (x, arenas, held, route, li + 1), kept

    stored, held = [], tuple(state or ())
    route = _route_carry(c, tokens)
    # The arena rides the carry only where the layers land as they go.
    arenas = () if land is None else (paged.cache.k, paged.cache.v)
    by_kind: Dict[str, list] = {"attention": [], "sliding_attention": []}
    for step in range(c.loop_steps):        # one pass, but for a looped stack
        if step:
            x = looped.step_end(x, params, c)
        with looped.step_scope(c, step):
            for (kind, start, count, kind_start), tree in runs:
                inputs = (tree,)
                if kind == "attention" and paged is None:
                    whole = count == pk.shape[0]
                    inputs += tuple(
                        a if whole else a[kind_start:kind_start + count]
                        for a in (pk, pv))
                (x, arenas, held, route, _), kept = jax.lax.scan(
                    functools.partial(
                        layer_fn, kind=kind,
                        shift=kind_start - start + step * c.attn_layers),
                    (x, arenas, held, route, jnp.int32(start)), inputs)
                stored.append(kept or None)
                if kind not in llama.STATE_KINDS:
                    by_kind["sliding_attention" if kind == "sliding_attention"
                            else "attention"].append(kept)
    # The head reads one position a row: the (last) norm is taken there
    # alone.
    x = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)  # [N, 1, E]
    x = looped.step_end(x, params, c)
    logits = lm_head_logits(x, params, c)
    if arenas:
        held = PagedKVCache(*arenas)
    else:
        held = type(state)(*held) if held else None
    if paged is not None:
        return logits, {k: _concat_runs(v) for k, v in by_kind.items()}, held
    return logits, _concat_runs(stored), held


def _prefill_chunk_paged(params, tokens, positions, cache, second, ptables,
                         tables_w, last_idx, slots, config, use_kernel,
                         dense=False):
    """One prefill CHUNK through the paged caches (``cb_prefill``'s body
    for a chunk of a long prompt, and for every prefill of a model with
    sliding-window layers): attends the earlier chunks where they lie
    (:class:`_PagedPrefix`), then lands its own K/V: in the arena
    through ``tables_w``, and in each row's ring at the entries of its
    logical blocks. A block that holds PADDING ONLY goes to the garbage
    block instead: in a ring it would overwrite a block the row's first
    decode queries still see. ``second`` is the cache beside the arena:
    the ring, the state cache of a model with linear-attention layers
    (each row's state and conv tail are read from and written to row
    ``slots[i]``), the tail cache of one with CCA layers (likewise), or
    None. An EVA-attention model's layers and a looped stack's land
    their own blocks as they go (``dense``: the looped stack's attention
    form, :func:`_prefill_forward_paged`). Returns (logits [N, 1, V],
    arena, second)."""
    ring = second if isinstance(second, RingKVCache) else None
    state = second if isinstance(second, (StateCache, TailCache)) else None
    bs = cache.block_size
    npb = tokens.shape[1] // bs
    m = ptables.shape[1]
    ring_tables = ring_write = None
    if ring is not None:
        n_ring = paged_kv.ring_blocks(config.sliding_window, bs)
        own = RingKVCache.tables(slots, n_ring)               # [N, ring]
        first = max(m * bs - config.sliding_window + 1, 0) // bs
        ring_tables = jnp.take(own, jnp.arange(first, m) % n_ring, axis=1)
        real = jnp.arange(npb)[None, :] * bs <= last_idx[:, None]
        ring_write = jnp.where(
            real, jnp.take(own, (m + jnp.arange(npb)) % n_ring, axis=1),
            GARBAGE_BLOCK)
    in_loop = bool(config.eva_window or config.loop_steps > 1)
    logits, stored, state = _prefill_forward_paged(
        params, tokens, positions, None, None, config, False, last_idx,
        use_kernel, state, slots,
        paged=_PagedPrefix(cache, ptables, ring, ring_tables),
        land=tables_w if in_loop else None, dense=dense)
    if in_loop:         # landed layer by layer: the arena itself
        return logits, state, None

    def land(into, kv, tables):
        # (k, v) of an arena or a ring; the one plane of a latent cache.
        return type(into)(*(a.at[:, tables.reshape(-1)].set(
            _ctx_to_blocks(new.astype(a.dtype), bs))
            for a, new in zip(into, kv)))

    if stored["attention"] is not None:
        cache = land(cache, stored["attention"], tables_w)
    if ring is not None:
        ring = land(ring, stored["sliding_attention"], ring_write)
    return logits, cache, state if ring is None else ring


def _bucket(n: int, floor: int = 16) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


def _bucket_floor(n: int) -> int:
    """Largest power of two <= n (0 for 0). Matched-prefix block counts
    bucket DOWN through this: compiled prefill programs specialize on
    the prefix-table width m, so exact match lengths would compile one
    program per distinct length seen — a retrace storm under mixed
    system-prompt traffic. Bucketing keeps the program count
    log-bounded; the discarded match tail simply re-prefills with the
    suffix (bit-identical either way, just redundant compute)."""
    return 0 if n <= 0 else 1 << (n.bit_length() - 1)


def _resolve_prefix_cache(prefix_cache: Optional[bool]) -> bool:
    """Cross-request prefix reuse toggle: explicit arg >
    RAY_TPU_PREFIX_CACHE env > on."""
    if prefix_cache is None:
        prefix_cache = env_flag("RAY_TPU_PREFIX_CACHE")
    if prefix_cache is None:
        return True
    return bool(prefix_cache)


# Versioned wire format of an exported KV handoff payload. Bump when the
# staging layout / manifest fields change: import refuses mismatched
# versions instead of scattering misinterpreted bytes into the arena.
HANDOFF_MANIFEST_VERSION = 2

_ROLES = ("prefill", "decode", "both")


def _resolve_role(role: Optional[str]) -> str:
    """Disaggregation role: explicit arg > RAY_TPU_SERVE_ROLE env >
    "both" (the colocated engine). "prefill" engines run admission +
    paged prefill only and park each request's finished arena blocks
    for export at its first token; "decode" engines additionally accept
    imported KV payloads but otherwise behave like "both"."""
    if role is None:
        role = os.environ.get("RAY_TPU_SERVE_ROLE", "").strip() or "both"
    role = str(role).lower()
    if role not in _ROLES:
        raise ValueError(
            f"role must be one of {_ROLES}, got {role!r}")
    return role


def _resolve_decode_kernel(config: llama.LlamaConfig,
                           use_decode_kernel: Optional[bool],
                           block_size: int) -> bool:
    """Whether the engine's programs call the Pallas kernels
    (``ops/paged_decode_attention.py``, ``ops/moe.py``): an explicit
    argument forces either way (CPU tests pass True and run them
    interpreted); None means on a TPU when the shapes tile, the XLA
    reference elsewhere."""
    if use_decode_kernel is None and config.latent_layers:
        from ray_tpu.ops.latent_decode_attention import latent_applicable

        return (jax.default_backend() == "tpu" and latent_applicable(
            block_size, _mla().row_width(config), config.kv_lora_rank))
    if use_decode_kernel is None:
        return (jax.default_backend() == "tpu"
                and paged_applicable(block_size, config.head_dim,
                                     config.num_heads, config.num_kv_heads))
    return bool(use_decode_kernel)


def _env_int(name: str, default: int) -> int:
    raw = os.environ.get(name, "").strip()
    return int(raw) if raw else default


def _resolve_spec_k(spec_k: Optional[int]) -> int:
    """Speculative depth: explicit arg > RAY_TPU_SPEC_K env > 0 (off)."""
    if spec_k is None:
        spec_k = _env_int("RAY_TPU_SPEC_K", 0)
    spec_k = int(spec_k)
    if spec_k < 0:
        raise ValueError(f"spec_k must be >= 0, got {spec_k}")
    return spec_k


def _resolve_spec_draft_layers(arg: Optional[int], num_layers: int) -> int:
    """Self-draft depth: explicit arg > RAY_TPU_SPEC_DRAFT_LAYERS env >
    num_layers // 4 (floor 1 — the EAGLE-style 'shallow slice of the
    target' default)."""
    if arg is None:
        arg = _env_int("RAY_TPU_SPEC_DRAFT_LAYERS",
                       max(1, num_layers // 4))
    arg = int(arg)
    if not 1 <= arg <= num_layers:
        raise ValueError(
            f"spec_draft_layers must be in [1, {num_layers}], got {arg}")
    return arg


def _spec_ladder(spec_k: int) -> List[int]:
    """Adaptive-k steps: powers of two up to spec_k, plus spec_k itself —
    log-bounded, so the compiled spec-tick program count is log-bounded
    too (one program per ladder rung, window dims whitelisted
    prefill_dims-style)."""
    ks = set()
    v = 1
    while v < spec_k:
        ks.add(v)
        v *= 2
    ks.add(spec_k)
    return sorted(ks)


class ContinuousBatcher:
    """Iteration-level scheduler over a fixed pool of KV-cache slots."""

    _engine_ids = itertools.count()  # per-process engine tag suffix

    def __init__(self, config: llama.LlamaConfig, params=None,
                 num_slots: int = 8, max_len: int = 512, seed: int = 0,
                 eos_token: Optional[int] = None, token_callback=None,
                 landing_callback=None,
                 use_decode_kernel: Optional[bool] = None,
                 block_size: int = 64,
                 kv_dtype: Optional[str] = None,
                 num_blocks: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 sampling=None,
                 spec_k: Optional[int] = None,
                 spec_draft_layers: Optional[int] = None,
                 spec_adaptive: Optional[bool] = None,
                 drafter=None,
                 role: Optional[str] = None,
                 device: Optional[jax.Device] = None,
                 prefill_chunk: int = 1024):
        """``landing_callback(tokens, landed_ts)`` fires ONCE a landing
        (a tick's row, a prefill batch's first tokens, an imported
        handoff's first token) with the tokens it booked, ``[(rid,
        token), ...]`` in booking order, and the landing's
        ``time.time()`` stamp: what a serving replica hands to its event
        loop in one call (:meth:`_hand_over`). ``token_callback(rid,
        token)`` is the same hand-over a token at a time, for callers
        that count or collect (benchmarks, tests); either, both or
        neither may be set, and reassigned on a live engine.

        ``device`` is the one chip this engine lives on: parameters, the
        KV arena and every per-tick upload are COMMITTED to it, so each
        compiled program runs there whichever thread dispatches it — a
        process driving four chips holds four engines, one per chip.
        ``None`` leaves placement to JAX's default device.

        A step books one tick's tokens with ONE tick queued behind the
        one that runs: ``step`` dispatches tick n+1 before it fetches
        tick n, so the device never waits for the host between ticks,
        every stream gets one token a tick, and no tick is thrown away
        (see :meth:`step`).

        ``use_decode_kernel`` routes decode attention and the K/V write
        through the paged Pallas kernels; ``None`` is auto (TPU with
        tiling shapes). Outputs are bit-identical kernel on/off.

        PAGED KV plane: the cache is a shared arena of
        ``block_size``-token blocks with per-slot block tables — decode
        reads only live blocks instead of a padded ``S_max`` stripe a
        slot, and admission reserves blocks all-or-nothing so a
        request can also wait on arena space. ``kv_dtype`` ('bf16' |
        'int8', or ``RAY_TPU_KV_DTYPE``) selects arena storage; int8
        halves KV bytes with per-token/per-head scales. ``num_blocks``
        sizes the arena (default: enough for every slot at ``max_len``,
        plus the reserved garbage block).

        ``prefix_cache`` (default on; ``RAY_TPU_PREFIX_CACHE`` env)
        enables CROSS-REQUEST PREFIX
        REUSE: a radix index over block-aligned prompt chunks lets a
        new request splice blocks another request already prefilled
        into its table read-only and prefill only its novel suffix;
        released prompt blocks park in an LRU "cached" state reclaimed
        under arena pressure. Greedy outputs are bit-identical with the
        cache on or off.

        ``sampling`` (:class:`~ray_tpu.models.sampling.SamplingParams`
        or a dict) selects in-device token sampling; the default is
        greedy argmax. Sampled decode is deterministic under a fixed
        ``sampling.seed``.

        SPECULATIVE DECODING (``spec_k`` > 0, or ``RAY_TPU_SPEC_K``):
        each tick a cheap
        drafter proposes up to ``spec_k`` tokens per slot, one batched
        verify pass scores all k+1 positions through the same paged
        attention path, and per-slot acceptance commits a variable
        number of tokens — decode tokens per param-stream instead of
        one. ``drafter`` is a
        :class:`~ray_tpu.models.inference.SelfDrafter` (default: the
        target's first ``spec_draft_layers`` /
        ``RAY_TPU_SPEC_DRAFT_LAYERS`` layers over the target's own
        arena) or an
        :class:`~ray_tpu.models.inference.ExternalLlamaDrafter` (a
        separate small checkpoint with its own dense cache).
        ``spec_adaptive`` (default on; ``RAY_TPU_SPEC_ADAPTIVE``)
        ladders k from the windowed accept rate — down to 0, which
        dispatches the EXACT pre-spec tick program. Greedy outputs are
        bit-identical spec-on/off; sampled acceptance is rejection
        sampling that preserves the target distribution.

        DISAGGREGATED ROLES (``role`` / ``RAY_TPU_SERVE_ROLE``):
        ``"prefill"`` runs admission + prefill and parks
        each request at its FIRST token with its arena blocks retained
        for :meth:`export_kv_payload` — the engine never decode-ticks,
        so a long prefill burst cannot stall anyone's TPOT.
        ``"decode"`` accepts :meth:`import_kv_payload` of an exported
        prefix (scattered into reserved blocks through the same
        table-scatter path prefill uses, indexed into the radix tree on
        arrival) and enters the decode tick directly. ``"both"`` (the
        default) is the colocated engine. Greedy outputs are
        bit-identical split vs colocated: the exported bytes are the
        exact arena blocks (int8 scales included) the colocated decode
        would have attended.

        CHUNKED PREFILL: a prompt (less its matched prefix) longer than
        ``prefill_chunk`` tokens (a power of two) runs as that many
        tokens a ``cb_prefill`` call, each chunk attending the earlier
        ones out of the arena, back to back inside one admission; a
        shorter one is ONE call padded to its power-of-two bucket, as
        ever. ``PREFILL_BATCH_TOKENS`` caps rows x padded tokens of one
        call (a wave of long prompts is split into calls of fewer rows).
        A model with Mamba-2 layers is never chunked (its scan is not
        given a carried state); a linear-attention layer's chunk starts
        from the state and conv tail the chunk before it left in the
        slot's row of the state cache.

        SLIDING-WINDOW LAYERS (``layer_types`` with "sliding_attention"):
        their K/V live in a per-slot ring of ``window / block_size + 2``
        blocks beside the arena (``paged_kv.RingKVCache``), which holds
        the full-attention layers alone. Whatever needs K/V a ring has
        overwritten is refused by name (:data:`_KIND_CANNOT`): the
        prefix cache, speculation, the KV handoff.

        EVA-ATTENTION LAYERS (``layer_types`` all "eva_attention",
        ``models/eva.py``): the arena holds a slot's context under a
        compressed position, ``eva_window / eva_chunk`` summaries for
        each closed window and then the open window's raw keys, so the
        paged kernels serve as they are. ``prefill_chunk`` is the window;
        a chunk lands its summaries, the tick that fills a window pools
        it in place, and the host allocates a slot's blocks as its open
        window fills and frees a closed window's in the step that closed
        it (:meth:`_eva_step_blocks`); admission promises each request
        the most blocks it will ever hold (:func:`eva.blocks_peak`).
        Whatever takes a block for the ``block_size`` tokens that filled
        it is refused by name."""
        self.config = config
        self.device = device
        self.num_slots = num_slots
        self.max_len = max_len
        self.eos_token = eos_token
        self.sampling = SamplingParams.coerce(sampling)
        self.role = _resolve_role(role)
        self.block_size = int(block_size)
        if self.block_size < 8 or self.block_size & (self.block_size - 1):
            # Prompt padding buckets are powers of two; a non-pow2 block
            # would make the padded length a non-multiple of the block
            # and break the prefill block reshape.
            raise ValueError(
                f"block_size must be a power of two >= 8, "
                f"got {self.block_size}")
        self.kv_dtype = resolve_kv_dtype(kv_dtype)
        asked = {}
        if self.kv_dtype != "bf16":
            asked["kv_dtype"] = f"kv_dtype={self.kv_dtype!r}"
        if _resolve_spec_k(spec_k) or drafter is not None:
            asked["speculative"] = "speculative decoding (spec_k > 0)"
        if prefix_cache or (prefix_cache is None
                            and env_flag("RAY_TPU_PREFIX_CACHE")):
            asked["prefix_cache"] = "the prefix cache (prefix_cache=True)"
        if self.role != "both":
            asked["handoff"] = f"role={self.role!r}"
        _refuse_unsupported(config, asked)
        if any("prefix_cache" in _KIND_CANNOT.get(kind, ())
               for kind in config.layer_types):
            prefix_cache = False    # nothing to share: off, not on
        chunk = _bucket_floor(int(prefill_chunk))
        if config.window_layers:
            # A chunk's blocks must be distinct entries of a ring.
            chunk = min(chunk, _bucket_floor(self.block_size * paged_kv.
                        ring_blocks(config.sliding_window, self.block_size)))
        if config.eva_window:
            # A chunk is a window: it attends the earlier ones through
            # their summaries alone and leaves its own behind.
            eva.check(config, self.block_size)
            chunk = config.eva_window
        if chunk < self.block_size:
            raise ValueError(f"prefill_chunk {prefill_chunk} is under one "
                             f"block of {self.block_size}")
        # None: a prompt is never split (Mamba-2's SSD scan is given no
        # carried state: ROADMAP R7). A linear-attention, Mamba-1 or CCA
        # chunk starts from the state or conv tail the one before left.
        self.prefill_chunk = None if "mamba" in config.layer_types else chunk
        self.prefix_cache = _resolve_prefix_cache(prefix_cache)
        self.use_decode_kernel = _resolve_decode_kernel(
            config, use_decode_kernel, self.block_size)
        # Speculative-decode knobs resolve BEFORE the arena is sized:
        # reservations carry spec_k look-ahead tokens (rejected draft
        # writes must land in already-reserved blocks), so max_blocks /
        # the default arena grow accordingly.
        self.spec_k = _resolve_spec_k(spec_k)
        self.drafter = drafter
        if self.spec_k:
            if self.drafter is None:
                self.drafter = SelfDrafter(spec_draft_layers)
            if self.drafter.external:
                if self.drafter.config.vocab_size != config.vocab_size:
                    raise ValueError(
                        "external drafter must share the target's "
                        "vocabulary")
                self.spec_draft_layers = self.drafter.config.num_layers
            else:
                self.spec_draft_layers = _resolve_spec_draft_layers(
                    spec_draft_layers
                    if spec_draft_layers is not None
                    else self.drafter.draft_layers, config.num_layers)
            if spec_adaptive is None:
                spec_adaptive = env_flag("RAY_TPU_SPEC_ADAPTIVE")
            self.spec_adaptive = (True if spec_adaptive is None
                                  else bool(spec_adaptive))
            self._spec_ladder_ks = _spec_ladder(self.spec_k)
        else:
            self.drafter = None
            self.spec_draft_layers = 0
            self.spec_adaptive = False
            self._spec_ladder_ks = []
        self._spec_cur_k = self.spec_k
        self._spec_ticks: Dict[int, Any] = {}   # ladder k -> compiled tick
        self._last_tick_k = 0                   # k the last tick ran with
        # Windowed accept-rate telemetry: (drafted, accepted) per applied
        # fetch — the adaptive-k controller and the accept-rate gauge both
        # read it.
        self._spec_window: deque = deque(
            maxlen=max(1, _env_int("RAY_TPU_SPEC_WINDOW", 128)))
        self._spec_probe_after = max(
            1, _env_int("RAY_TPU_SPEC_PROBE_TICKS", 256))
        self._spec_probe_countdown = self._spec_probe_after
        self.spec_draft_tokens = 0      # cumulative drafted
        self.spec_accepted_tokens = 0   # cumulative accepted by verify
        self.spec_tick_count = 0        # spec-tick dispatches
        self.base_tick_count = 0        # plain-tick dispatches
        self.decoded_tokens = 0         # committed decode tokens (bench)
        # Prefill accounting (bench_serve.py reads these; the metric
        # counters mirror them into the TSDB). With the prefix cache on,
        # ``prefill_tokens`` counts only NOVEL (suffix) tokens; the
        # hit/miss counters below split total prompt traffic.
        self.prefill_requests = 0
        self.prefill_tokens = 0
        self.prefix_hit_tokens = 0      # prompt tokens served from cache
        self.prefix_miss_tokens = 0     # prompt tokens actually prefilled
        self.prefix_hit_requests = 0    # requests with >=1 matched block
        self._prefill_shapes: set = set()   # (N_pad, L_pad) compiled
        if params is None:
            # ONE program, not one per tensor: on the chip each eager op
            # is its own compile, and a replica must finish constructing
            # inside the serve controller's start-up grace. It seeds the
            # weights in the engine's layout, so no second program runs.
            with jax.default_device(device):
                params = xla_monitor.instrument(
                    functools.partial(init_engine_params, config),
                    name="cb_init", shape_policy="free")(
                    jax.random.PRNGKey(seed))
        self.params = self._install(params)
        # What swap_params holds a caller's tree to: the CANONICAL
        # signature of the tree this engine was built with.
        self._canonical_sig = jax.tree_util.tree_flatten(
            jax.eval_shape(llama.canonical_layout, self.params))
        # Weight-sync plane (ray_tpu/rl): monotone version of the live
        # params. 0 = the cold-start weights; every swap_params bumps it
        # and each request records the version that admitted it.
        self._weight_version = 0
        self._score_fn = None  # lazy teacher-forced logprob jit
        self.param_bytes = sum(
            x.nbytes for x in jax.tree_util.tree_leaves(self.params))
        # Split out the non-layer params: a self-draft pass streams only
        # the truncated layer fraction plus the embed/norm/head — the
        # spec-aware tick_bytes_estimate prices drafts from these.
        self._head_param_bytes = sum(
            self.params[k].nbytes
            for k in ("embed", "final_norm", "lm_head")
            if k in self.params)    # a tied head is the embedding
        self._layer_param_bytes = self.param_bytes - self._head_param_bytes
        self._expert_param_bytes = sum(
            self.params["layers"][k].nbytes for k in llama.EXPERT_KEYS
            if k in self.params["layers"])
        self._draft_params = (self._install(self.drafter.params)
                              if self.spec_k and self.drafter.external
                              else None)
        self._draft_param_bytes = sum(
            x.nbytes for x in jax.tree_util.tree_leaves(self._draft_params))
        self._draft_cache = None
        self.token_callback = token_callback
        self.landing_callback = landing_callback
        # Table width covers max_len PLUS the spec look-ahead: a spec
        # tick writes draft/verify K/V up to position p + spec_k, and
        # those writes must stay inside the slot's own reservation
        # (the garbage redirect is for overrun PAST it).
        self.max_blocks = (
            eva.blocks_peak(max_len, config, self.block_size)
            if config.eva_window
            else -(-(max_len + self.spec_k) // self.block_size))
        self.num_blocks = int(
            num_blocks if num_blocks is not None
            else num_slots * self.max_blocks + 1)
        self.cache = self._new_cache()
        # The per-slot state cache of a model with state-space layers
        # (None otherwise): owned, donated and rebuilt with the arena.
        self.state = self._new_state()
        self.state_installs = 0         # prompts whose state was installed
        self.state_carries = 0          # chunks that started from a row
        self.allocator = BlockAllocator(self.num_blocks)
        self._slot_blocks: Dict[int, List[int]] = {}
        # EVA attention: a slot's blocks are allocated as its open window
        # fills and retired as it closes (``_slot_blocks`` is what it
        # holds NOW); admission promises each slot the most it will ever
        # hold (``_slot_peak``, summed in ``_promised``), so the arena
        # never blocks a live slot. ``_tables_stale``: a table changed
        # under unchanged membership.
        self._slot_peak: Dict[int, int] = {}
        self._promised = 0
        self._tables_stale = False
        self.eva_windows_closed = {"prefill": 0, "tick": 0}
        # Radix index over block-aligned prompt chunks -> resident
        # arena blocks (None with the prefix cache off). Slots track
        # their pinned index nodes so release can deref instead of
        # freeing shared blocks.
        self._prefix = RadixBlockIndex() if self.prefix_cache else None
        self._slot_nodes: Dict[int, List[Any]] = {}
        self._d_tables = None
        self._d_limits = None
        self._free: List[int] = list(range(num_slots))
        self._slots: Dict[int, Dict[str, Any]] = {}   # slot -> request
        # Device-resident decode state: last tokens + positions + the
        # sampling step counter live on the chip between ticks (uploaded
        # only when slot membership changes), so a steady decode tick
        # moves 4 bytes/slot host-ward and nothing device-ward.
        self._d_tokens = None
        self._d_positions = None
        self._d_step = None
        self._d_members = None    # [(slot, rid)] the device state stands for
        self._applied_steps = 0   # host mirror of the device step counter
        # Ticks dispatched and not yet booked, oldest first (``step``
        # keeps one queued behind the one that runs), and the clock of
        # the last row that reached the host.
        self._inflight: deque = deque()
        self._row_landed = 0.0
        # This thread's timeline seen from the device. ``_empty_since``:
        # the ``perf_counter`` moment a landing left nothing queued on
        # the device, None while something is;
        # ``_empty_after``: what landed then ("tick" or "prefill");
        # ``_no_work``: no slot was live and nothing waited, so the time
        # up to the next arrival is idle, not starved. The next dispatch
        # books the interval by cause (:meth:`_book_empty`).
        self._empty_since: Optional[float] = time.perf_counter()
        self._empty_after = "tick"
        self._no_work = True
        # Running totals of the prefill batches' booked seconds and their
        # count: a request notes both at its first token and takes the
        # difference when it ends (``stalled_s``, ``stall_count``).
        self._stall_s = 0.0
        self._stall_n = 0
        # What a gathered admission weighs (:meth:`_gather`), all of it
        # measured by this engine on its own clocks. ``_batch_ms``: a
        # prefill batch's shape ``(padded rows, padded length, matched
        # blocks, chunks)`` -> its two newest ``CB_PREFILL_MS`` readings,
        # the first ever left out (an empty list); ``_restart_ms``: the
        # same of the ``CB_STARVED_AFTER_PREFILL_MS`` interval that
        # followed it (owed to the last admission's ``_restart_owed``);
        # ``_tick_ms``: ``CB_TICK_MS``'s clock, smoothed. ``_hold``: the
        # hold in progress, None when the free slots are not being kept.
        self._batch_ms: Dict[tuple, List[float]] = {}
        self._restart_ms: Dict[tuple, List[float]] = {}
        self._restart_owed: List[tuple] = []
        self._tick_ms = 0.0
        self._hold: Optional[Dict[str, Any]] = None
        # ``time.time()`` of the landing whose tokens are being booked:
        # the stamp ``_hand_over`` gives the landing callback.
        self.landed_ts = 0.0
        self._prefill_count = 0   # per-dispatch prefill sampling stream
        self._dirty = True
        self._waiting: deque = deque()
        self._rid = itertools.count()
        self._finished: Dict[int, List[int]] = {}
        self._routes: Dict[int, list] = {}     # take_routes
        # Disaggregation state: prefill-role engines park each request's
        # retained arena blocks here between its first token and the
        # export call; decode-role engines hold pre-reserved import
        # blocks (the router reserves the decode slot BEFORE dispatching
        # prefill so the payload never arrives to a full arena).
        self._handoff_ready: Dict[int, Dict[str, Any]] = {}
        self._import_reservations: Dict[int, Dict[str, Any]] = {}
        self._reservation_ids = itertools.count()
        # Request-path telemetry: one lifecycle record per live request
        # (submit/admit/prefill/first-token/finish timestamps + the
        # caller's trace context). TTFT decomposition histograms are
        # always on (host bookkeeping only); per-window decode spans are
        # recorded only for traced requests, so with tracing disabled
        # the decode loop pays one integer check per fetch.
        self._req_meta: Dict[int, Dict[str, Any]] = {}
        self._traced_live = 0            # live requests carrying a trace
        self.request_breakdowns: deque = deque(maxlen=4096)
        self._MAX_WINDOWS = 64           # per-request span cap (tail merges)
        # Observability: engine label for the slot-occupancy / decode-rate
        # series (continuous-batching is the serving hot loop the decode
        # roofline work tunes — the TSDB needs its history). The instance
        # counter keeps co-resident engines' series from colliding.
        self._mtags = {"engine":
                       f"slots{num_slots}-{next(self._engine_ids)}"}
        cfg = config

        use_kernel = self.use_decode_kernel
        sampling_cfg = self.sampling
        block_size_c = self.block_size
        chunks_state = bool({"linear_attention", "cca_attention", "mamba1"}
                            & set(cfg.layer_types))
        eva_sb = (eva.summaries(cfg) // self.block_size if cfg.eva_window
                  else 0)     # blocks of summaries a closed window keeps

        # The XLA monitor dispatches per signature and audits shape
        # growth: prefill's signatures are pow-2 bucketed in N and L by
        # design (allowed caps included — max_len/num_slots/block counts
        # need not be powers of two), so legitimate bucket growth stays
        # silent while a stray odd shape raises
        # ray_tpu_xla_retraces_total. The tick has exactly ONE legitimate
        # signature.
        # Prefix-aware suffix groups add legitimate non-pow2 dims:
        # suffix buckets clamped to the table capacity left after a
        # matched prefix. Matched-block counts themselves bucket to
        # powers of two in admission (_bucket_floor) — already silent
        # under the bucketed policy — so the clamp takes only log-many
        # values, and this whitelist ENFORCES that bound: an exact-m
        # regression would raise ray_tpu_xla_retraces_total.
        ms = {0}
        m = 1
        while m <= self.max_blocks:
            ms.add(m)
            m *= 2
        prefill_dims = (max_len, num_slots, 0) + tuple(
            self.block_size * (self.max_blocks - v) for v in sorted(ms))
        if self.prefill_chunk:
            # A later chunk's prefix table: its matched blocks plus the
            # whole chunks before it.
            per = eva_sb or self.prefill_chunk // self.block_size
            prefill_dims += tuple(
                v + i * per for v in sorted(ms)
                for i in range(1, self.max_blocks // per + 1)
                if v + i * per <= self.max_blocks)

        @xla_monitor.instrument(name="cb_prefill",
                                shape_policy="bucketed",
                                allowed_dims=prefill_dims,
                                donate_argnums=(2,))
        def prefill(params, tokens, caches, ptables, tables_w,
                    last_idx, pstep, slots=None):
            # BATCHED BUCKETED PREFILL, paged + prefix-aware: tokens
            # [N, S] holds N same-group SUFFIXES (prompt tokens not
            # covered by matched prefix blocks; the whole prompt
            # when nothing matched); ``ptables`` [N, m] names the
            # shared arena blocks holding each row's m-block prefix
            # (READ-ONLY — gathered, dequantized when int8, never
            # written); ``tables_w`` [N, S // bs] names the blocks
            # the suffix K/V land in (overflow entries point at the
            # garbage block). Only N first tokens leave the device.
            # ``caches`` is the arena or, with state layers, (arena,
            # state cache); ``slots`` [N] then names the state-cache row
            # each prompt's final state is installed in.
            cache, held = _split_caches(caches)
            n, s_pad = tokens.shape
            m = ptables.shape[1]
            positions = m * block_size_c + jnp.arange(s_pad)
            if eva_sb:      # true positions: the table's blocks are windows
                positions = m // eva_sb * cfg.eva_window + jnp.arange(s_pad)
            dense = m * block_size_c + s_pad <= PREFILL_DENSE_KEYS
            if not cache.quantized and (
                    isinstance(held, RingKVCache) or chunks_state or eva_sb
                    or isinstance(cache, LatentKVCache)
                    or cfg.loop_steps > 1 or (held is None and not dense)):
                # A long prompt's chunk, sliding-window layers, or
                # linear-attention, Mamba-1 or CCA layers (whose chunks
                # carry a state or a convolution tail):
                # the earlier keys are read where they lie, blockwise.
                # (Not a model with Mamba-2 layers, whose prompt is one
                # piece; nor an int8 arena: both keep the path they had.)
                # A looped stack's layers read and land their own rows
                # of the arena in either form.
                logits, cache, held = _prefill_chunk_paged(
                    params, tokens, positions, cache, held, ptables,
                    tables_w, last_idx, slots, cfg, use_kernel, dense)
                first = _next_tokens(logits, pstep, sampling_cfg,
                                     salt=_PREFILL_SALT)
                return first, (cache if held is None else (cache, held))
            flat_p = ptables.reshape(-1)                 # [N * m]
            pk = cache.k[:, flat_p]
            pv = cache.v[:, flat_p]
            if cache.quantized:
                pk = (pk.astype(jnp.float32)
                      * cache.k_scale[:, flat_p][..., None]
                      ).astype(cfg.dtype)
                pv = (pv.astype(jnp.float32)
                      * cache.v_scale[:, flat_p][..., None]
                      ).astype(cfg.dtype)

            logits, stored, held = _prefill_forward_paged(
                params, tokens, positions,
                _blocks_to_ctx(pk.astype(cfg.dtype), n),
                _blocks_to_ctx(pv.astype(cfg.dtype), n),
                cfg, cache.quantized, last_idx, use_kernel, held, slots)
            flat_tables = tables_w.reshape(-1)           # [N * npb]

            to_blocks = functools.partial(_ctx_to_blocks,
                                          bs=block_size_c)

            if cache.quantized:
                kq, vq, ksc, vsc = stored
                new_cache = PagedKVCache(
                    k=cache.k.at[:, flat_tables].set(to_blocks(kq)),
                    v=cache.v.at[:, flat_tables].set(to_blocks(vq)),
                    k_scale=cache.k_scale.at[:, flat_tables].set(
                        to_blocks(ksc)),
                    v_scale=cache.v_scale.at[:, flat_tables].set(
                        to_blocks(vsc)))
            else:
                k_s, v_s = stored
                dt = cache.k.dtype
                new_cache = PagedKVCache(
                    k=cache.k.at[:, flat_tables].set(
                        to_blocks(k_s.astype(dt))),
                    v=cache.v.at[:, flat_tables].set(
                        to_blocks(v_s.astype(dt))))
            first = _next_tokens(logits, pstep, sampling_cfg,
                                 salt=_PREFILL_SALT)
            return first, (new_cache if held is None
                           else (new_cache, held))

        @xla_monitor.instrument(name="cb_tick", donate_argnums=(5,))
        def tick(params, tokens, positions, tables, limits, caches,
                 step):
            return _decode_tick_paged(params, tokens, positions,
                                      tables, limits, caches, step,
                                      cfg, use_kernel=use_kernel,
                                      sampling=sampling_cfg)

        @xla_monitor.instrument(name="cb_merge_tokens")
        def merge_tokens(fresh, host_tokens, device_tokens):
            # With a tick in flight a running slot's last token is still
            # on the device; only a fresh slot's (a prefill's or an
            # import's first token) comes from the host.
            return jnp.where(fresh, host_tokens, device_tokens)

        self._prefill = prefill
        self._tick = tick
        self._merge_tokens = merge_tokens

        if cfg.loop_steps > 1:
            from ray_tpu._private import metrics_defs as mdefs

            mdefs.CB_LOOP_KV_BYTES.set(
                self.cache.k.nbytes + self.cache.v.nbytes, tags=self._mtags)
        if self.spec_k and self.drafter.external:
            # The drafter's own dense cache: admission prefills the FULL
            # prompt into it, decode advances it inside the spec tick.
            dcfg = self.drafter.config
            self._draft_cache = self._new_draft_cache()

            @xla_monitor.instrument(name="cb_draft_prefill",
                                    shape_policy="bucketed",
                                    allowed_dims=prefill_dims,
                                    donate_argnums=(2,))
            def draft_prefill(dparams, tokens, dcache, slots):
                positions = jnp.arange(tokens.shape[1])
                slot_cache = KVCache(
                    k=jnp.take(dcache.k, slots, axis=1),
                    v=jnp.take(dcache.v, slots, axis=1))
                _, sc = _forward_cached(dparams, tokens, positions,
                                        slot_cache, dcfg)
                return KVCache(k=dcache.k.at[:, slots].set(sc.k),
                               v=dcache.v.at[:, slots].set(sc.v))

            self._draft_prefill = draft_prefill
        else:
            self._draft_prefill = None

    def _place(self, tree):
        """Commit a pytree (host or device values) to this engine's chip;
        with no chip named, host values go to JAX's default device."""
        return jax.device_put(tree, self.device)

    def _install(self, tree):
        """A parameter tree (canonical, or already the engine's) on this
        engine's chip in the ENGINE's layout (:func:`llama.heads_major`):
        every tree the engine takes in comes through here. One program
        transposes the q/k/v projections and nothing else, so the other
        leaves stay the arrays they were and a model never exists twice."""
        return llama.heads_major(self._place(tree), relay=_relay_heads)

    def _new_cache(self):
        with jax.default_device(self.device):
            if self.config.latent_layers:
                return self._place(LatentKVCache.create(
                    self.config, self.num_blocks, self.block_size))
            return self._place(PagedKVCache.create(
                self.config, self.num_blocks, self.block_size,
                self.kv_dtype))

    def _new_state(self):
        """The second cache beside the arena: the state cache of a model
        with state-space layers, the ring of one with sliding-window
        layers, the tail cache of one with CCA layers, None for any
        other."""
        c = self.config
        if not (c.state_layers or c.window_layers or c.cca_layers):
            return None
        with jax.default_device(self.device):
            if c.cca_layers:
                return self._place(TailCache.create(c, self.num_slots))
            if c.window_layers:
                return self._place(RingKVCache.create(
                    c, self.num_slots, self.block_size))
            return self._place(StateCache.create(c, self.num_slots))

    @property
    def _ring(self) -> Optional[RingKVCache]:
        return self.state if isinstance(self.state, RingKVCache) else None

    def _caches(self):
        """The ``caches`` operand of ``cb_prefill`` and ``cb_tick``."""
        return (self.cache if self.state is None
                else (self.cache, self.state))

    def _new_draft_cache(self):
        with jax.default_device(self.device):
            return self._place(KVCache.create(
                self.drafter.config, self.num_slots, self.max_len))

    def _get_spec_tick(self, k: int):
        """Compiled spec-tick program for ladder rung ``k`` (memoized:
        one program per rung, all named cb_spec_tick). The window dims
        k+1 for every rung join the bucketed whitelist so legitimate
        ladder moves never raise ray_tpu_xla_retraces_total — the same
        prefill_dims discipline the admission path uses."""
        tick = self._spec_ticks.get(k)
        if tick is not None:
            return tick
        cfg = self.config
        use_kernel = self.use_decode_kernel
        sampling_cfg = self.sampling
        n_draft = self.spec_draft_layers
        spec_dims = (self.max_len, self.num_slots, self.max_blocks)
        spec_dims += tuple(kk + 1 for kk in self._spec_ladder_ks)
        if self.drafter.external:
            dcfg = self.drafter.config

            @xla_monitor.instrument(name="cb_spec_tick",
                                    shape_policy="bucketed",
                                    allowed_dims=spec_dims,
                                    donate_argnums=(5, 6))
            def spec_tick(params, tokens, positions, tables, limits,
                          cache, dcache, step, dparams):
                return _spec_tick_paged(
                    params, tokens, positions, tables, limits, cache,
                    step, cfg, k, n_draft, use_kernel, sampling_cfg,
                    draft_params=dparams, draft_cache=dcache,
                    draft_config=dcfg)
        else:
            @xla_monitor.instrument(name="cb_spec_tick",
                                    shape_policy="bucketed",
                                    allowed_dims=spec_dims,
                                    donate_argnums=(5,))
            def spec_tick(params, tokens, positions, tables, limits,
                          cache, step):
                return _spec_tick_paged(
                    params, tokens, positions, tables, limits, cache,
                    step, cfg, k, n_draft, use_kernel, sampling_cfg)

        self._spec_ticks[k] = spec_tick
        return spec_tick

    def prefill_cache_misses(self) -> int:
        """Compiled prefill program count (one per (N, bucket) shape) —
        the admission-burst acceptance check reads this. Prefers jax's
        real jit-cache counter (private API); falls back to the shapes
        this engine dispatched if a jax upgrade drops it."""
        cache_size = getattr(self._prefill, "_cache_size", None)
        if cache_size is not None:
            return cache_size()
        return len(self._prefill_shapes)

    # ------------------------------------------ request-path telemetry
    def _req_tags(self, rec: Dict[str, Any]) -> Dict[str, str]:
        t = rec.get("trace") or {}
        return {"deployment": str(t.get("deployment", "")),
                "tenant": str(t.get("tenant", "")),
                "engine": self._mtags["engine"],
                "role": self.role}

    def _span_common(self, rec: Dict[str, Any]) -> Dict[str, Any]:
        t = rec.get("trace") or {}
        return {"trace_id": t.get("trace_id", ""),
                "parent_span_id": t.get("parent_span_id", ""),
                "kind": "engine",
                "request_id": t.get("request_id", ""),
                "rid": rec["rid"]}

    def note_submit_wait(self, rid: int, entered: float,
                         locked: float) -> None:
        """The head of ``rid``'s TTFT chain, which ``submit`` cannot see:
        its caller entered the replica method at ``entered`` and held
        the engine's lock (the one ``step`` runs under) at ``locked``,
        both ``time.time()``. Call it under that lock, right after the
        submit or import that returned ``rid``."""
        rec = self._req_meta.get(rid)
        if rec is None:
            return          # finished inside the submit: nothing to tag
        from ray_tpu._private import metrics_defs as mdefs

        rec["lock_wait_s"] = max(locked - entered, 0.0)
        mdefs.SERVE_REQ_LOCK_WAIT.observe(rec["lock_wait_s"],
                                          tags=self._req_tags(rec))
        if rec["traced"]:
            tracing.emit_span("engine.submit_wait", ts=entered,
                              dur=rec["lock_wait_s"],
                              **self._span_common(rec))

    def _note_first_token(self, rec: Dict[str, Any], prefill_t0: float,
                          first_tok_ts: float) -> None:
        """First token just landed for ``rec``'s request: close the TTFT
        decomposition (queue -> arena-wait -> prefill) and emit the
        component histograms + spans. By construction the components sum
        to TTFT up to the admission loop's group-assembly gap."""
        from ray_tpu._private import metrics_defs as mdefs

        blocked = rec.get("arena_blocked",
                          rec.get("admit", rec["submit"]))
        admit = rec.get("admit", blocked)
        rec["first_token"] = first_tok_ts
        rec["stall0"] = (self._stall_s, self._stall_n)
        rec["queue_s"] = max(blocked - rec["submit"], 0.0)
        rec["arena_wait_s"] = max(admit - blocked, 0.0)
        rec["prefill_s"] = max(first_tok_ts - prefill_t0, 0.0)
        rec["ttft_s"] = max(first_tok_ts - rec["submit"], 0.0)
        tags = self._req_tags(rec)
        mdefs.SERVE_REQ_TTFT.observe(rec["ttft_s"], tags=tags)
        mdefs.SERVE_REQ_QUEUE.observe(rec["queue_s"], tags=tags)
        mdefs.SERVE_REQ_ARENA_WAIT.observe(rec["arena_wait_s"], tags=tags)
        mdefs.SERVE_REQ_PREFILL.observe(rec["prefill_s"], tags=tags)
        if rec["traced"]:
            common = self._span_common(rec)
            tracing.emit_span("engine.queue", ts=rec["submit"],
                              dur=rec["queue_s"], **common)
            if rec["arena_wait_s"] > 0:
                tracing.emit_span("engine.arena_wait", ts=blocked,
                                  dur=rec["arena_wait_s"],
                                  blocks=rec.get("blocks", 0), **common)
            tracing.emit_span("engine.prefill", ts=prefill_t0,
                              dur=rec["prefill_s"],
                              prompt_tokens=rec["prompt_len"], **common)

    def _finish_request(self, rid: int, outcome: str,
                        tokens: int = 0) -> None:
        """Terminal lifecycle edge (finished / evicted / aborted): emit
        TPOT + outcome metrics, the request's decode-window spans, and
        push a breakdown record for bench/CLI consumers."""
        rec = self._req_meta.pop(rid, None)
        if rec is None:
            return
        from ray_tpu._private import metrics_defs as mdefs

        now = time.time()
        if rec["traced"]:
            self._traced_live -= 1
        tags = self._req_tags(rec)
        mdefs.SERVE_REQ_OUTCOMES.inc(tags={**tags, "outcome": outcome})
        tpot = None
        first = rec.get("first_token")
        if first is not None and tokens > 1:
            # ``tokens`` is the COMMITTED count, not the tick count — a
            # spec tick that lands 3 tokens divides the same wall time by
            # 3, so TPOT stays honest under multi-token ticks.
            tpot = max(now - first, 0.0) / (tokens - 1)
            mdefs.SERVE_REQ_TPOT.observe(tpot, tags=tags)
        stalled_s = stall_count = None
        if first is not None:
            # Other requests' prefill batches since this one's first
            # token: its stream stood still through each.
            stalled_s = self._stall_s - rec["stall0"][0]
            stall_count = self._stall_n - rec["stall0"][1]
            mdefs.SERVE_REQ_DECODE_STALL.observe(stalled_s, tags=tags)
        trace = rec.get("trace") or {}
        self.request_breakdowns.append({
            "rid": rid, "outcome": outcome, "tokens": tokens,
            "lock_wait_s": rec.get("lock_wait_s"),
            "queue_s": rec.get("queue_s"),
            "arena_wait_s": rec.get("arena_wait_s"),
            "prefill_s": rec.get("prefill_s"),
            "ttft_s": rec.get("ttft_s"), "tpot_s": tpot,
            "stalled_s": stalled_s, "stall_count": stall_count,
            # Filled in by the replica when the request's stream ends
            # (:meth:`note_stream`); None for a request no stream read.
            "handoff_mean_s": None,
            "prefix_tokens": rec.get("prefix_tokens", 0),
            "prompt_tokens": rec.get("prompt_len", 0),
            "weight_version": rec.get("weight_version"),
            "trace_id": trace.get("trace_id"),
            "request_id": trace.get("request_id"),
            # Disaggregated imports carry the handoff latency split
            # (export_s / channel_s / import_s) — bench_serve's
            # disagg_phase sums these against the handoff wall.
            "handoff": rec.get("handoff")})
        if not rec["traced"]:
            return
        common = self._span_common(rec)
        if first is None:
            # Evicted/aborted before admission completed: the queue span
            # (normally closed at first token) still needs to exist for
            # the trace to show where the request died.
            tracing.emit_span("engine.queue", ts=rec["submit"],
                              dur=max(now - rec["submit"], 0.0),
                              outcome=outcome, **common)
        for i, (w0, w1, n) in enumerate(rec.get("windows", ())):
            tracing.emit_span("engine.decode_window", ts=w0,
                              dur=max(w1 - w0, 0.0), tokens=n,
                              window=i, **common)
        tail = rec.get("window_tail")
        if tail is not None:
            tracing.emit_span("engine.decode_tail", ts=tail[0],
                              dur=max(tail[1] - tail[0], 0.0),
                              tokens=tail[2], windows=tail[3], **common)
        tracing.emit_span(f"engine.{outcome}", ts=now, dur=0.0,
                          tokens=tokens, stalled_s=stalled_s,
                          stall_count=stall_count, **common)

    def note_stream(self, rid: int, handoff_mean_s: float) -> None:
        """The replica's generator read ``rid``'s stream to its end:
        keep the mean lag of its tokens from landing to leaving the
        request's queue beside the request's breakdown record, if the
        request has ended (a stream cut short has no record yet)."""
        for rec in reversed(self.request_breakdowns):
            if rec["rid"] == rid:
                rec["handoff_mean_s"] = handoff_mean_s
                return

    def pressure_snapshot(self) -> Dict[str, Any]:
        """Live engine pressure — the router/autoscaler input: queue
        depth, slot occupancy, free KV arena blocks, and the prefill
        token backlog still waiting for admission."""
        free_blocks = self.allocator.free_count
        cached = (self._prefix.cached_count
                  if self._prefix is not None else 0)
        return {
            "queue_depth": len(self._waiting),
            "active_slots": len(self._slots),
            "num_slots": self.num_slots,
            "kv_blocks_free": free_blocks,
            # Reclaimable-on-demand prefix blocks: admission-available
            # capacity is free + cached, which the router/shedding
            # thresholds should use instead of raw free.
            "kv_blocks_cached": cached,
            "kv_blocks_total": self.num_blocks - 1,
            # Draft look-ahead blocks are RESERVED capacity (the
            # allocator already excludes them from kv_blocks_free — no
            # phantom free arena for the admission gate or the arbiter
            # SLO guard); this reports how much of the reservation is
            # speculative head-room rather than committed tokens.
            "kv_blocks_spec_lookahead": sum(
                st.get("la_blocks", 0) for st in self._slots.values()),
            "inflight_prefill_tokens": sum(
                len(r["prompt"]) for r in self._waiting),
            # Role-aware fields (disaggregated prefill/decode): the
            # router classifier and the autoscaler/arbiter read these so
            # prefill and decode fleets scale independently.
            "role": self.role,
            # Prompt tokens queued for admission PLUS parked exports —
            # a prefill fleet's backlog is both.
            "prefill_queue_tokens": (
                sum(len(r["prompt"]) for r in self._waiting)
                + sum(len(e["prompt"])
                      for e in self._handoff_ready.values())),
            # Arena capacity an import could land in right now: free
            # blocks plus LRU-cached ones _alloc_blocks would reclaim.
            "kv_blocks_importable": free_blocks + cached,
            "handoff_ready": len(self._handoff_ready),
            "import_reservations": len(self._import_reservations),
            # Resident bytes of the per-slot state cache (state-space
            # layers): fixed at construction, whatever the contexts.
            "state_cache_bytes": (self.state.nbytes
                                  if self.config.state_layers else 0),
        }

    # ---------------------------------------------------------------- api
    @property
    def weight_version(self) -> int:
        """Monotone version of the live params (0 = cold-start)."""
        return self._weight_version

    def swap_params(self, params, version: Optional[int] = None) -> int:
        """Replace the live params between ticks — the ONLY sanctioned
        post-init assignment of ``self.params`` (a tick-boundary source
        lint enforces this). The caller must hold the engine's tick
        exclusion (the serve deployment swaps under its engine lock, so
        no ``step`` is running). A tick already dispatched, the one
        ``step`` keeps queued, finishes on the old weights; the next
        ``_run_tick`` dispatch reads the fresh tree. The KV cache and
        every in-flight request's device state are untouched: in-flight
        generations continue un-dropped under the new weights.

        ``params`` is a CANONICAL tree (:func:`llama.init_params`'
        layout: what the trainer, a checkpoint and ``rl/weight_sync``
        hold); the engine re-lays it on the way in (:meth:`_install`),
        as it did the tree it was built with. It must match that tree's
        canonical form structurally (same treedef, same leaf
        shapes/dtypes): the compiled tick programs were traced against
        that signature and a silent mismatch would either retrace per
        swap or miscompute. Returns the new weight version (``version``
        or the previous one + 1)."""
        old_leaves, old_treedef = self._canonical_sig
        new_leaves, new_treedef = jax.tree_util.tree_flatten(params)
        if new_treedef != old_treedef:
            raise ValueError(
                f"swap_params treedef mismatch: engine was built with "
                f"{old_treedef}, swap brought {new_treedef}")
        for i, (old, new) in enumerate(zip(old_leaves, new_leaves)):
            if old.shape != new.shape or old.dtype != new.dtype:
                raise ValueError(
                    f"swap_params leaf {i} mismatch: engine has "
                    f"{old.shape}/{old.dtype}, swap brought "
                    f"{new.shape}/{new.dtype}")
        self.params = self._install(params)
        self._weight_version = (int(version) if version is not None
                                else self._weight_version + 1)
        return self._weight_version

    def score_logprobs(self, prompt_tokens: List[int],
                       out_tokens: List[int]) -> np.ndarray:
        """Per-token behavior logprobs of ``out_tokens`` given
        ``prompt_tokens``, under the CURRENT live params — one
        teacher-forced forward through the same model the decode ticks
        run, so the RL experience path's importance ratios are priced
        against the true generating policy. Returns ``[len(out_tokens)]``
        float32."""
        _refuse_unsupported(self.config,
                            {"score_logprobs": "score_logprobs"})
        if not out_tokens:
            return np.zeros((0,), np.float32)
        if self._score_fn is None:
            cfg = self.config

            @xla_monitor.instrument(name="cb_score",
                                    shape_policy="bucketed",
                                    allowed_dims=(1, self.max_len))
            def score(params, tokens):
                logits = llama.forward(params, tokens, cfg)
                return jax.nn.log_softmax(logits.astype(jnp.float32))

            self._score_fn = score
        full = list(prompt_tokens) + list(out_tokens)
        if len(full) > self.max_len:
            raise ValueError(
                f"score_logprobs sequence ({len(full)} tokens) exceeds "
                f"max_len={self.max_len}")
        pad = min(_bucket(len(full)), self.max_len)
        arr = np.zeros((1, pad), np.int32)
        arr[0, :len(full)] = full
        logp_all = np.asarray(self._score_fn(self.params,
                                             self._place(arr)))[0]
        start = len(prompt_tokens)
        idx = np.arange(start - 1, start - 1 + len(out_tokens))
        return logp_all[idx, np.asarray(out_tokens)].astype(np.float32)

    def submit(self, prompt_tokens: List[int],
               max_new_tokens: int = 32,
               trace: Optional[Dict[str, Any]] = None,
               keep_routes: bool = False) -> int:
        """Queue a request; returns its id. It is admitted as soon as a
        slot is free — no waiting for the current batch to drain — unless
        the engine is saturated (more requests wait than slots are free,
        with streams decoding): then a free slot may stay empty for a few
        ticks so that the slots that free next are refilled by the same
        prefill call, for no longer than the request had already waited
        (:meth:`_holds_admission`).

        ``keep_routes``: keep the experts each decoded position routed
        to, for :meth:`take_routes` (a held expert share alone: only its
        tick rows carry them).

        ``trace`` carries the serve request context
        (``request_id``/``trace_id``/``parent_span_id``/``deployment``/
        ``tenant``): lifecycle spans (queue, arena-wait, prefill, decode
        windows) are emitted into that trace when ``RAY_TPU_TRACING=1``,
        and the TTFT/TPOT histograms are tagged with its
        deployment/tenant either way."""
        assert len(prompt_tokens) + max_new_tokens <= self.max_len
        if keep_routes and not self.config.experts_held:
            raise ValueError(
                "keep_routes: only a held expert share's tick rows carry "
                "each slot's chosen experts (config.experts_held)")
        if max_new_tokens <= 0:
            # Nothing to generate: finish immediately — no slot, no
            # blocks, so arena capacity is irrelevant.
            rid = next(self._rid)
            self._finished[rid] = []
            return rid
        if self._blocks_needed(len(prompt_tokens),
                               max_new_tokens) > self.num_blocks - 1:
            # A reservation larger than the whole arena can NEVER be
            # satisfied: admitting it to the queue would wedge the FIFO
            # head (and every request behind it) forever.
            raise ValueError(
                f"request needs more KV blocks than the arena holds "
                f"({self._blocks_needed(len(prompt_tokens), max_new_tokens)}"
                f" > {self.num_blocks - 1}); raise num_blocks or shorten "
                f"the request")
        rid = next(self._rid)
        traced = trace is not None and tracing.enabled()
        self._req_meta[rid] = {
            "rid": rid, "submit": time.time(),
            "prompt_len": len(prompt_tokens),
            "weight_version": self._weight_version,
            "trace": trace, "traced": traced, "windows": []}
        if traced:
            self._traced_live += 1
        self._waiting.append({"rid": rid,
                              "prompt": list(prompt_tokens),
                              "max_new": max_new_tokens,
                              "routes": [] if keep_routes else None})
        self._work_arrived()
        return rid

    def _work_arrived(self) -> None:
        """A request reached an engine that had none: the device's idle
        time up to now was for want of work, from now on it waits for
        this thread."""
        if not self._no_work:
            return
        self._no_work = False
        if self._empty_since is not None:
            from ray_tpu._private import metrics_defs as mdefs

            now = time.perf_counter()
            self._book_empty(now, mdefs.CB_IDLE_NO_WORK_MS)
            self._empty_since = now

    def _book_empty(self, now: float, hist) -> float:
        """The device had nothing queued from ``_empty_since`` to
        ``now``: one observation in the cause's histogram, and the
        interval once for each live slot, which stood still through it.
        The caller says what follows (``_empty_since``: None when it
        dispatches a program). Returns the interval, in ms."""
        from ray_tpu._private import metrics_defs as mdefs

        ms = max(now - self._empty_since, 0.0) * 1e3
        hist.observe(ms, tags=self._mtags)
        if self._slots:
            mdefs.CB_SLOT_STALLED_MS.inc(ms * len(self._slots),
                                         tags=self._mtags)
        return ms

    def _release_slot(self, slot: int) -> None:
        self._free.append(slot)
        blocks = self._slot_blocks.pop(slot, None)
        self._promised -= self._slot_peak.pop(slot, 0)
        nodes = self._slot_nodes.pop(slot, None)
        if nodes:
            # Indexed (shared/shareable) blocks: deref — refcount 0
            # parks them in the LRU "cached" state instead of the
            # free list, so a later prefix match revives them and
            # arena pressure reclaims them before admission blocks.
            self._prefix.release(nodes)
            shared = {nd.block for nd in nodes}
            blocks = [b for b in (blocks or []) if b not in shared]
        if blocks:
            self.allocator.free(blocks)

    def cancel(self, rid: int) -> bool:
        """Drop a request (client disconnected): frees its slot / queue
        spot so abandoned generations stop burning decode ticks."""
        for i, req in enumerate(self._waiting):
            if req["rid"] == rid:
                del self._waiting[i]
                self._finish_request(rid, "evicted")
                return True
        for slot, st in list(self._slots.items()):
            if st["rid"] == rid:
                del self._slots[slot]
                self._release_slot(slot)
                self._dirty = True
                self._finish_request(rid, "evicted",
                                     tokens=len(st["out"]))
                return True
        # A parked handoff's retained blocks must not outlive the
        # request (the first token already sits in _finished).
        self.abandon_handoff(rid)
        self._routes.pop(rid, None)
        return self._finished.pop(rid, None) is not None

    def reset(self) -> List[int]:
        """Abort everything (recovery after an engine error). Returns the
        request ids that were dropped."""
        dropped = [st["rid"] for st in self._slots.values()]
        dropped += [r["rid"] for r in self._waiting]
        tokens_by_rid = {st["rid"]: len(st["out"])
                         for st in self._slots.values()}
        for rid in dropped:
            self._finish_request(rid, "aborted",
                                 tokens=tokens_by_rid.get(rid, 0))
        self._req_meta.clear()
        self._traced_live = 0
        self._slots.clear()
        self._waiting.clear()
        self._free = list(range(self.num_slots))
        self._finished.clear()
        self._routes.clear()
        # A tick in flight is dropped unfetched, and with it the device's
        # copy of the decode state (it may be the output of the program
        # that failed).
        self._inflight.clear()
        self._device_empty(time.perf_counter(), "tick")
        self._no_work = True
        self._hold = None
        self._d_tokens = self._d_members = None
        # Parked handoffs and import reservations die with the arena
        # (allocator.reset below reclaims their blocks wholesale).
        self._handoff_ready.clear()
        self._import_reservations.clear()
        # The prefill/tick jits donate the pooled cache; after a mid-step
        # failure the old buffers may already be deleted, so rebuild the
        # pool or every later step would raise "Array has been deleted".
        self.cache = self._new_cache()
        self.state = self._new_state()
        self.allocator.reset()
        self._slot_blocks.clear()
        self._slot_peak.clear()
        self._promised = 0
        self._slot_nodes.clear()
        if self._prefix is not None:
            # The rebuilt arena holds zeros: every cached prefix
            # entry would alias garbage, so the index restarts cold.
            self._prefix.clear()
        self._applied_steps = 0
        # Spec state restarts with the engine: the controller re-enters at
        # the configured k and the external drafter's dense cache (donated
        # by the spec tick like the main arena) is rebuilt alongside it.
        self._spec_cur_k = self.spec_k
        self._spec_window.clear()
        self._spec_probe_countdown = self._spec_probe_after
        if self._draft_cache is not None:
            self._draft_cache = self._new_draft_cache()
        self._dirty = True
        return dropped

    @property
    def active_count(self) -> int:
        return len(self._slots)

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of admitted prompt tokens served from cached prefix
        blocks (0.0 with the prefix cache off or before any admission)."""
        total = self.prefix_hit_tokens + self.prefix_miss_tokens
        return self.prefix_hit_tokens / total if total else 0.0

    def has_work(self) -> bool:
        """True while a ``step`` has something to do: a live or waiting
        request, finishes not yet returned, or a tick dispatched whose
        tokens are not booked yet (one is always queued while requests
        decode)."""
        return bool(self._slots or self._waiting or self._finished
                    or self._inflight)

    # --------------------------------------------- disaggregated handoff
    def _park_for_handoff(self, slot: int, req: Dict[str, Any]) -> None:
        """Prefill-role terminal edge: the request just produced its
        first token — free the SLOT (the next admission group can use
        it) but retain the arena blocks until :meth:`export_kv_payload`
        ships them. The first token joins ``_finished`` so the serving
        layer observes it through the normal step() results."""
        st = self._slots.pop(slot, None)
        if st is None:
            return  # finished at the first token: nothing to hand off
        rid = st["rid"]
        self._free.append(slot)
        self._handoff_ready[rid] = {
            "prompt": list(req["prompt"]),
            "first": st["out"][0],
            "max_new": st["max_new"],
            "blocks": self._slot_blocks.pop(slot, []),
            "nodes": self._slot_nodes.pop(slot, []),
        }
        self._finished[rid] = list(st["out"])
        self._finish_request(rid, "prefilled", tokens=len(st["out"]))
        self._dirty = True

    def _release_handoff_blocks(self, entry: Dict[str, Any]) -> None:
        """Return a parked handoff's blocks to the arena. Indexed blocks
        deref into the LRU "cached" state (a resubmitted twin re-matches
        them instead of re-prefilling), exclusives free outright."""
        blocks, nodes = entry["blocks"], entry["nodes"]
        if nodes:
            self._prefix.release(nodes)
            shared = {nd.block for nd in nodes}
            blocks = [b for b in blocks if b not in shared]
        if blocks:
            self.allocator.free(blocks)

    def handoff_ready(self) -> List[int]:
        """Request ids parked with exported-ready KV (prefill role)."""
        return list(self._handoff_ready)

    def abandon_handoff(self, rid: int) -> bool:
        """Drop a parked handoff without exporting (client gone, or the
        decode side never came for it): frees the retained blocks."""
        entry = self._handoff_ready.pop(rid, None)
        if entry is None:
            return False
        self._release_handoff_blocks(entry)
        return True

    def export_kv_payload(self, rid: int) -> Dict[str, Any]:
        """Materialize a parked request's KV handoff: gather its
        prompt-covering arena blocks (K/V plus int8 scale sidecars) to
        host as ZERO-COPY VIEWS of one contiguous staging buffer, with
        a crc32 manifest over the staging bytes. Only the
        ``ceil(prompt/block_size)`` prompt blocks ship — the decode side
        sizes its own reservation for the full generation — and the
        retained blocks release on return (indexed ones park in the
        LRU, so a resubmit after a lost transfer re-matches them).

        Call through ``ray_tpu.serve.kv_transfer`` — the journal-gated
        helper every cross-replica transfer must ride (a source lint
        pins this)."""
        _refuse_unsupported(self.config, {"handoff": "export_kv_payload"})
        if self.role == "decode":
            raise ValueError("decode-role engines do not export KV")
        entry = self._handoff_ready.pop(rid, None)
        if entry is None:
            raise KeyError(
                f"request {rid} has no handoff-ready KV (not prefilled "
                f"by a prefill-role engine, or already exported)")
        prompt = entry["prompt"]
        nb = -(-len(prompt) // self.block_size)
        blocks = list(entry["blocks"][:nb])
        staging, layout = self.cache.gather_blocks(blocks)
        payload = {
            "version": HANDOFF_MANIFEST_VERSION,
            "rid": rid,
            "prompt": prompt,
            "chunks": prompt_chunks(prompt, self.block_size),
            "first_token": int(entry["first"]),
            "max_new": int(entry["max_new"]),
            "block_size": self.block_size,
            "kv_dtype": self.kv_dtype,
            "num_layers": self.config.num_layers,
            "num_kv_heads": self.config.num_kv_heads,
            "head_dim": self.config.head_dim,
            "num_blocks": nb,
            "layout": layout,
            "staging": staging,
            "nbytes": int(staging.nbytes),
            "crc32": zlib.crc32(staging),
        }
        self._release_handoff_blocks(entry)
        return payload

    def reserve_import(self, prompt_len: int,
                       max_new: int) -> Optional[int]:
        """Pre-reserve the arena blocks a future import will need (the
        router reserves the decode slot BEFORE dispatching prefill, so
        the payload never races arena pressure on arrival). Returns a
        reservation id, or None when the arena cannot cover it."""
        _refuse_unsupported(self.config, {"handoff": "reserve_import"})
        if self.role == "prefill":
            raise ValueError("prefill-role engines do not import KV")
        self.sweep_reservations()
        got = self._alloc_blocks(self._blocks_needed(prompt_len, max_new))
        if got is None:
            return None
        res_id = next(self._reservation_ids)
        self._import_reservations[res_id] = {
            "blocks": got, "prompt_len": prompt_len, "max_new": max_new,
            "ts": time.monotonic()}
        return res_id

    def sweep_reservations(self, ttl_s: Optional[float] = None) -> int:
        """Expire import reservations whose handoff never arrived (the
        router's reserve and decode dispatch landed on different
        replicas, or the prefill side died before exporting) — a stale
        ticket must not pin arena blocks forever. TTL from
        ``RAY_TPU_KV_RESERVE_TTL_S`` (default 30s)."""
        if not self._import_reservations:
            return 0
        if ttl_s is None:
            ttl_s = float(os.environ.get("RAY_TPU_KV_RESERVE_TTL_S",
                                         "30"))
        cutoff = time.monotonic() - ttl_s
        stale = [r for r, ent in self._import_reservations.items()
                 if ent.get("ts", 0.0) < cutoff]
        for res_id in stale:
            self.allocator.free(
                self._import_reservations.pop(res_id)["blocks"])
        return len(stale)

    def cancel_reservation(self, res_id: int) -> bool:
        """Release a pre-reservation (prefill died and the request is
        resubmitting elsewhere, or the client disconnected)."""
        ent = self._import_reservations.pop(res_id, None)
        if ent is None:
            return False
        self.allocator.free(ent["blocks"])
        return True

    def import_kv_payload(self, payload: Dict[str, Any],
                          reservation: Optional[int] = None,
                          trace: Optional[Dict[str, Any]] = None,
                          breakdown: Optional[Dict[str, float]] = None
                          ) -> int:
        """Land an exported KV payload in THIS engine's arena and enter
        decode directly: crc-verify the staging bytes, scatter them into
        reserved blocks through the same table-scatter path prefill
        uses, insert the transferred prefix into the radix index
        (shareable immediately, read-only refcounted like any matched
        prefix), and create a live decode slot continuing from the
        prefill's first token. Greedy decode from here is bit-identical
        to the colocated engine: the imported bytes ARE the blocks the
        colocated decode would have attended.

        Returns the LOCAL request id (the import is a fresh request on
        this engine's id stream). Call through
        ``ray_tpu.serve.kv_transfer`` — the journal-gated helper every
        cross-replica transfer must ride (a source lint pins this)."""
        _refuse_unsupported(self.config, {"handoff": "import_kv_payload"})
        if self.role == "prefill":
            raise ValueError("prefill-role engines do not import KV")
        if payload.get("version") != HANDOFF_MANIFEST_VERSION:
            raise ValueError(
                f"KV handoff version mismatch: payload "
                f"v{payload.get('version')}, engine expects "
                f"v{HANDOFF_MANIFEST_VERSION}")
        staging = payload["staging"]
        crc = zlib.crc32(staging)
        if crc != payload["crc32"]:
            raise ValueError(
                f"KV handoff crc mismatch (got {crc:#010x}, manifest "
                f"says {payload['crc32']:#010x}): payload corrupted in "
                f"transit")
        for field, mine in (("block_size", self.block_size),
                            ("kv_dtype", self.kv_dtype),
                            ("num_layers", self.config.num_layers),
                            ("num_kv_heads", self.config.num_kv_heads),
                            ("head_dim", self.config.head_dim)):
            if payload[field] != mine:
                raise ValueError(
                    f"KV handoff geometry mismatch on {field}: payload "
                    f"{payload[field]!r} vs engine {mine!r}")
        t0 = time.time()
        prompt = list(payload["prompt"])
        plen = len(prompt)
        max_new = int(payload["max_new"])
        if plen + max_new > self.max_len:
            raise ValueError(
                f"imported request ({plen}+{max_new} tokens) exceeds "
                f"this engine's max_len={self.max_len}")
        need = self._blocks_needed(plen, max_new)
        blocks: Optional[List[int]] = None
        if reservation is not None:
            ent = self._import_reservations.pop(reservation, None)
            if ent is not None:
                if len(ent["blocks"]) >= need:
                    blocks = ent["blocks"][:need]
                    if ent["blocks"][need:]:
                        self.allocator.free(ent["blocks"][need:])
                else:
                    # Reservation was sized for a different request:
                    # return it and fall through to a fresh grab.
                    self.allocator.free(ent["blocks"])
        if blocks is None:
            blocks = self._alloc_blocks(need)
        if blocks is None:
            raise RuntimeError(
                f"decode arena cannot cover the import ({need} blocks "
                f"needed, {self.allocator.free_count} free); reserve "
                f"ahead with reserve_import")
        if not self._free:
            self.allocator.free(blocks)
            raise RuntimeError("no free decode slot for the import")
        nb = int(payload["num_blocks"])
        self.cache = self.cache.scatter_blocks(
            blocks[:nb], payload["staging"], payload["layout"])
        rid = next(self._rid)
        traced = trace is not None and tracing.enabled()
        meta = {
            "rid": rid, "submit": t0, "prompt_len": plen,
            "weight_version": self._weight_version,
            "trace": trace, "traced": traced, "windows": [],
            "admit": t0, "blocks": len(blocks),
            "prefix_tokens": plen,  # the whole prompt arrived prefilled
        }
        if breakdown:
            meta["handoff"] = dict(breakdown)
        self._req_meta[rid] = meta
        if traced:
            self._traced_live += 1
        slot = self._free.pop()
        self._slot_blocks[slot] = blocks
        if self._prefix is not None and payload["chunks"]:
            created = self._prefix.insert(
                [tuple(c) for c in payload["chunks"]], blocks)
            if created:
                self._slot_nodes[slot] = created
        first = int(payload["first_token"])
        self._work_arrived()
        now = self.landed_ts = time.time()
        self._note_first_token(meta, t0, now)
        if meta.get("handoff") is not None:
            meta["handoff"]["import_s"] = now - t0
        self._hand_over([(rid, first)])
        self._slots[slot] = {
            "rid": rid, "out": [first], "max_new": max_new,
            "pos": plen, "last": first, "routes": None,
            "la_blocks": self._lookahead_blocks(plen, max_new),
        }
        self._maybe_finish(slot)
        if self._draft_prefill is not None and slot in self._slots:
            # The external drafter's dense cache never transferred: it
            # re-prefills the full prompt locally (cheap vs the target).
            self._run_draft_prefill([(slot, prompt)])
        self._dirty = True
        return rid

    # ------------------------------------------------------------ paged kv
    def kv_block_stats(self) -> Dict[str, float]:
        """Arena occupancy: live blocks used/total, LRU-cached and
        refcount-shared prefix blocks, live tokens, and the
        fragmentation ratio (reserved-but-unwritten fraction of used
        blocks)."""
        cached = self._prefix.cached_count if self._prefix is not None \
            else 0
        shared = self._prefix.shared_count if self._prefix is not None \
            else 0
        # Parked (cached) blocks are still on the allocator's books —
        # they hold revivable prefix K/V — but they are not LIVE demand.
        used = self.allocator.used_count - cached
        live = sum(st["pos"] for st in self._slots.values())
        cap = used * self.block_size
        # Prefix sharing lets per-slot live tokens exceed the distinct
        # block capacity (two slots counting one shared prefix), so the
        # fragmentation ratio clamps at 0.
        return {"used": used, "total": self.num_blocks - 1,
                "cached": cached, "shared": shared,
                "live_tokens": live,
                "frag_ratio": max(1.0 - live / cap, 0.0) if cap else 0.0}

    def _attended_blocks(self) -> tuple:
        """One pass over the live slots for the next tick's queries:
        (each slot's block-table entries that hold a key its query may
        see, each slot's blocks that hold one of its last
        ``sliding_window`` keys; the second empty without window
        layers): what ``paged_decode_attn`` reads a full-attention and
        a sliding-window layer."""
        bs, w = self.block_size, self.config.sliding_window
        pos = [st["pos"] for st in self._slots.values()]
        if self.config.eva_window:      # where the arena holds that key
            pos = [eva.compressed(p, self.config) for p in pos]
        table = [p // bs + 1 for p in pos]
        if self._ring is None:
            return table, []
        return table, [n - max(p - w + 1, 0) // bs
                       for n, p in zip(table, pos)]

    def _live_blocks(self) -> int:
        """Block-table entries that hold a key the next tick's queries
        may see: the blocks ``paged_decode_attn`` reads a layer."""
        return sum(self._attended_blocks()[0])

    def _window_blocks(self) -> tuple:
        """(blocks a sliding-window layer's kernel reads for the next
        tick's queries, blocks a table of every position would have it
        read): over the live slots."""
        table, ring = self._attended_blocks()
        return sum(ring), sum(table)

    def _account_tick(self, tick_fn, tick: Dict[str, Any],
                      spec_k: int) -> None:
        """Feed one landed tick to the XLA monitor, with the live-byte hint,
        because the compiled cost prices every table entry as live, and
        book the share of entries
        that were, and how full the kernel's grid steps ran (a step
        covers up to ``visit_blocks`` blocks of one slot; every
        attention layer, rings and table by their layer counts): all
        from one pass over the slots."""
        from ray_tpu._private import metrics_defs as mdefs

        table, ring = self._attended_blocks()
        live = sum(table)
        mdefs.CB_PAGED_LIVE_BLOCK_SHARE.observe(
            live / (self.num_slots * self.max_blocks), tags=self._mtags)
        if ring and live:
            mdefs.CB_WINDOW_LIVE_BLOCK_SHARE.observe(
                sum(ring) / live, tags=self._mtags)
        if self.config.state_layers:
            mdefs.CB_STATE_LIVE_SLOTS.observe(len(self._slots),
                                              tags=self._mtags)
        if self.config.eva_window:
            # The keys the tick's queries attended, summed over the
            # slots, by what they are: summaries of closed windows, raw
            # keys of the open one (the query's own included).
            c = self.config
            closed = sum(st["pos"] // c.eva_window
                         for st in self._slots.values())
            mdefs.CB_EVA_SUMMARY_KEYS.observe(
                closed * eva.summaries(c), tags=self._mtags)
            mdefs.CB_EVA_WINDOW_KEYS.observe(
                sum(st["pos"] % c.eva_window + 1
                    for st in self._slots.values()), tags=self._mtags)
        if self.config.loop_steps > 1:
            # The tick's live rows and the steps the program ran for
            # them, read off the ROW (the gates it carries).
            rows = len(tick["members"])
            steps = (len(tick["row"]) - self.num_slots) // self.num_slots
            mdefs.CB_LOOP_ROWS.inc(rows, tags=self._mtags)
            mdefs.CB_LOOP_STEPS.inc(rows * steps, tags=self._mtags)
        if self.config.latent_layers:
            # The positions the tick's queries attended, summed over the
            # slots: what the latent kernel must read a layer, in tokens.
            mdefs.CB_MLA_LIVE_TOKENS.observe(
                sum(st["pos"] + 1 for st in self._slots.values()),
                tags=self._mtags)
        runs = [(self.cache.k, table)]
        if ring:
            runs.append((self._ring.k, ring))
        read = held = 0
        rule = _visit_rule(self.cache)
        for arena_k, blocks in runs:        # each by its layer count
            per, layers = rule(arena_k), arena_k.shape[0]
            read += layers * sum(blocks)
            held += layers * per * sum(-(-n // per) for n in blocks)
        if held:
            mdefs.CB_PAGED_VISIT_FILL_SHARE.observe(read / held,
                                                    tags=self._mtags)
        tick_fn.note_execution(
            tick["wall"], bytes_hint=self.tick_bytes_estimate(
                spec_k=spec_k, live_blocks=live),
            shape=len(tick["members"]), call=tick["call"])

    def tick_bytes_estimate(self, spec_k: Optional[int] = None,
                            live_blocks: Optional[int] = None) -> int:
        """HBM bytes one decode tick actually streams: the full parameter
        set plus the LIVE tokens' arena traffic. This is the
        live-traffic figure the achieved-bandwidth gauges and
        bench_serve report — the compiled program's static cost analysis
        can only ever price the worst case.

        ``spec_k`` prices a SPECULATIVE tick (defaults to the k the
        engine currently dispatches): each of the k draft passes streams
        the truncated layer slice (or the external drafter's params +
        cache) plus its share of the live arena, and the batched verify
        streams the full params ONCE plus k+1 per-position arena passes
        — live bytes actually read, so multi-token ticks don't inflate
        the achieved-bandwidth gauges."""
        if spec_k is None:
            spec_k = self._spec_cur_k if self.spec_k else 0
        # The kernel streams WHOLE blocks, so each slot's live
        # prefix counts rounded up to block granularity — otherwise
        # the figure would be block-size-invariant and the
        # block_size sweep meaningless.
        if live_blocks is None:
            live_blocks = self._live_blocks()
        live_bytes = (live_blocks * self.block_size
                      * self.cache.token_bytes())
        # A routed model streams only the experts its rows touch: at
        # most rows x top-k of each layer's X (every slot routes,
        # live or not), over each position of a spec window.
        c = self.config
        # (A held share gets its share of the assignments, so the same
        # ratio holds for the experts held here.)
        idle_experts = (max(1.0 - self.num_slots * (1 + spec_k)
                            * c.num_experts_per_tok / c.num_experts,
                            0.0) if c.num_experts else 0.0)
        # (A looped stack streams its layers' weights once a step.)
        total = (self.param_bytes + live_bytes
                 + (c.loop_steps - 1) * self._layer_param_bytes
                 - int(self._expert_param_bytes * idle_experts))
        if c.state_layers or c.cca_layers:
            # Every slot's state and conv tail, live or not, read and
            # written once a tick.
            total += 2 * self.state.nbytes
        if self._ring is not None:
            total += (self._window_blocks()[0] * self.block_size
                      * self._ring.token_bytes())
        if spec_k:
            if self._draft_cache is not None:
                dcfg = self.drafter.config
                ditem = jnp.dtype(self._draft_cache.k.dtype).itemsize
                dstripes = (2 * dcfg.num_layers * self.num_slots
                            * self.max_len * dcfg.num_kv_heads
                            * dcfg.head_dim * ditem)
                draft_pass = self._draft_param_bytes + dstripes
            else:
                frac = self.spec_draft_layers / self.config.num_layers
                draft_pass = (self._layer_param_bytes * frac
                              + self._head_param_bytes
                              + live_bytes * frac)
            # k draft passes + k EXTRA verify query positions (the
            # base figure already counts one arena pass).
            total += spec_k * (draft_pass + live_bytes)
        return total

    def _blocks_needed(self, prompt_len: int, max_new: int) -> int:
        # Spec decode needs spec_k look-ahead tokens past the committed
        # length: rejected draft/verify writes must land inside the
        # slot's own reservation, never a neighbor's block — reserved at
        # admission, all-or-nothing, so free counts stay honest.
        if self.config.eva_window:
            # The COMPRESSED worst case: closed windows are summaries.
            return eva.blocks_peak(prompt_len + max_new, self.config,
                                   self.block_size)
        return -(-(prompt_len + max_new + self.spec_k)
                 // self.block_size)

    def _lookahead_blocks(self, prompt_len: int, max_new: int) -> int:
        """Blocks of a reservation attributable to spec look-ahead."""
        if not self.spec_k:
            return 0
        return (self._blocks_needed(prompt_len, max_new)
                - -(-(prompt_len + max_new) // self.block_size))

    def _head_fits(self) -> bool:
        """True when the FIFO head could admit RIGHT NOW (free slot and
        enough free arena blocks — counting LRU-cached blocks the
        allocator can reclaim and prefix blocks a radix match would
        cover)."""
        if not (self._waiting and self._free):
            return False
        req = self._waiting[0]
        need = self._blocks_needed(len(req["prompt"]), req["max_new"])
        avail = self.allocator.free_count
        if self.config.eva_window:
            # Against what is not promised yet, not what is free now: a
            # live slot's open window has blocks still to come.
            avail = self.num_blocks - 1 - self._promised
        if self._prefix is not None:
            nodes = self._prefix.match_nodes(
                self._req_chunks(req)[:self._match_cap(req)])
            m = _bucket_floor(len(nodes))   # admission buckets the same
            need -= m
            # A parked matched block must not count twice: the match
            # will revive it from the LRU (covering part of ``need``)
            # WITHOUT freeing anything, so it is no longer evictable
            # for the novel blocks — an optimistic probe here has
            # ``_gather`` hold slots empty for an admission that fails.
            parked = sum(1 for nd in nodes[:m] if nd.refs == 0)
            avail += self._prefix.cached_count - parked
        return need <= avail

    def _match_cap(self, req: Dict[str, Any]) -> int:
        """Blocks a prefix MATCH may cover: full prompt blocks, capped
        so at least one prompt token remains to prefill (the first
        generated token samples from the last prompt position's logits,
        which the KV cache does not store)."""
        return (len(req["prompt"]) - 1) // self.block_size

    def _req_chunks(self, req: Dict[str, Any]) -> List[tuple]:
        """Block-aligned chunk keys for a queued request, memoized on
        the request: the admission probe (:meth:`_head_fits`) and the
        eventual admission itself would otherwise re-tuple the whole
        prompt each time a request waits on the arena."""
        chunks = req.get("chunks")
        if chunks is None:
            chunks = req["chunks"] = prompt_chunks(req["prompt"],
                                                   self.block_size)
        return chunks

    def _alloc_blocks(self, n: int) -> Optional[List[int]]:
        """All-or-nothing reservation with LRU reclaim: when the free
        list can't cover ``n``, refcount-0 cached prefix blocks are
        evicted (leaf-first, oldest-first) before the request is left
        blocking on the arena — cached state never wins over
        admission. Live (refcounted) shared blocks are untouchable."""
        if self._prefix is not None and n > self.allocator.free_count:
            evicted = self._prefix.evict(n - self.allocator.free_count)
            if evicted:
                self.allocator.free(evicted)
        return self.allocator.alloc(n)

    def _table_row(self, blocks: List[int]) -> List[int]:
        # Dead tail entries repeat the last live block. The attention
        # kernel never visits them (its schedule ends at the slot's
        # position: `paged_visits`); the XLA reference gathers and
        # masks them, so they must name a block.
        tail = blocks[-1] if blocks else GARBAGE_BLOCK
        return blocks + [tail] * (self.max_blocks - len(blocks))

    def _prefill_batches(self, groups):
        """``_admit_waiting``'s groups, each cut into batches of at most
        ``PREFILL_BATCH_TOKENS`` padded tokens a program call (a power
        of two of rows, at least one): what bounds a prefill program's
        temporaries whatever waits in the queue."""
        for key, group in groups.items():
            rows = max(_bucket_floor(PREFILL_BATCH_TOKENS // key[0]), 1)
            for at in range(0, len(group), rows):
                yield key, group[at:at + rows]

    def _admit(self) -> None:
        if self._import_reservations:
            # Stale import tickets (handoff never arrived) must not
            # starve local admission out of the same arena.
            self.sweep_reservations()
        if not (self._waiting and self._free):
            self._hold = None
            return
        if self._holds_admission():
            return
        from ray_tpu._private import metrics_defs as mdefs

        with tracing.phase("engine.admit", mdefs.CB_STEP_ADMIT_MS,
                           self._mtags) as admit:
            self._admit_waiting(admit)
        self._hold = None

    def _group(self, prompt_len: int, m: int) -> tuple:
        """The key under which :meth:`_admit_waiting` batches a prompt of
        ``prompt_len`` tokens behind ``m`` matched blocks: (padded
        length, ``m``, program calls). A prompt past the chunk length is
        whole chunks, the last one padded; a shorter one is one call at
        its power-of-two bucket, at least one block and never beyond the
        table. Requests of one key share their prefill calls."""
        chunk, bs = self.prefill_chunk, self.block_size
        suffix_len = prompt_len - m * bs
        if chunk and suffix_len > chunk:
            return chunk, m, -(-suffix_len // chunk)
        padded_len = min(_bucket(suffix_len), (self.max_blocks - m) * bs)
        return max(padded_len, bs), m, 1

    def _group_of(self, req: Dict[str, Any]) -> tuple:
        """:meth:`_group` of a request still in the queue: what its
        admission would find in the prefix index now, with nothing
        pinned."""
        m = 0
        if self._prefix is not None:
            m = _bucket_floor(len(self._prefix.match_nodes(
                self._req_chunks(req)[:self._match_cap(req)])))
        return self._group(len(req["prompt"]), m)

    def _batch_cost(self, shape: tuple) -> Optional[float]:
        """Device ms an admission of one prefill batch of ``shape``
        costs, by this engine's own readings: the batch's time plus the
        restart that followed it, each the LOWER of its two newest
        readings (so one slow reading, a host stall inside the fetch,
        prices nothing until the next confirms it). None for a shape not
        measured yet."""
        seen = self._batch_ms.get(shape)
        if not seen:
            return None
        return min(seen) + min(self._restart_ms.get(shape) or (0.0,))

    @staticmethod
    def _note_reading(table: Dict[tuple, List[float]], shape: tuple,
                      ms: float) -> None:
        """Keep ``ms`` among ``shape``'s two newest readings, but not
        its first in this engine: that one holds a program's compilation
        or its load from the cache, and a shape priced too high is never
        chosen, so never measured again."""
        seen = table.get(shape)
        if seen is None:
            table[shape] = []
        else:
            seen.append(ms)
            del seen[:-2]

    def _note_tick_ms(self, ms: float) -> None:
        """Keep the tick's cadence (``CB_TICK_MS``'s clock) for
        :meth:`_gather`: what a row of a tick is worth."""
        self._tick_ms += (ms - self._tick_ms) / 8 if self._tick_ms else ms

    @staticmethod
    def _hold_room_s(meta: Dict[str, Any], now: float) -> float:
        """Seconds a waiting request may still be held: no longer than
        it had waited when its hold began, so a hold at most doubles a
        wait that saturation already caused."""
        since = meta.get("held", now)
        return (since - meta["submit"]) - (now - since)

    def _gather(self) -> Optional[Dict[str, Any]]:
        """The admission that costs a request the least device time, if
        it is larger than the one the free slots allow now; None when
        admitting now is best, or when nothing may be held at all.

        Only a saturated engine gathers: requests would still wait after
        this admission, a stream is decoding (its ticks are what frees
        the next slots), and the head fits the arena. It weighs, for
        each number of slots ``n`` one admission could fill (up to the
        rows ``_prefill_batches`` gives one call of the head's group),

            (batch + restart) / rows  +  forgone decode / n

        ``batch + restart``: :meth:`_batch_cost` of the head's group at
        the padded row count, ``rows`` the requests of that group among
        the first ``n`` waiting (another group is another call). A
        bucket never measured is not waited for. ``forgone decode``: a
        slot that stands empty through a tick forgoes one row of it,
        ``_tick_ms / num_slots`` of device time, and how long each
        stands empty is KNOWN: a live slot ends after at most ``max_new
        - len(out)`` more booked ticks (an EOS, or a speculative tick's
        extra tokens, only bring that forward, so this is an upper bound
        and a hold on it ends early, never late). A wait the head has
        no room for (:meth:`_hold_room_s`) is not considered."""
        free, live = len(self._free), len(self._slots)
        if not (live and self._tick_ms and len(self._waiting) > free
                and self._head_fits()):
            return None
        head = self._waiting[0]
        key = self._group_of(head)
        most = min(max(_bucket_floor(PREFILL_BATCH_TOKENS // key[0]), 1),
                   len(self._waiting), free + live)
        if most <= free:
            return None
        ends = heapq.nsmallest(most - free, (
            st["max_new"] - len(st["out"]) for st in self._slots.values()))
        meta = self._req_meta.get(head["rid"])
        room_ticks = (self._hold_room_s(meta, time.time()) * 1e3
                      / self._tick_ms if meta is not None else 0.0)
        slot_tick_ms = self._tick_ms / self.num_slots
        rows, ended, now_row_ms, best = 0, 0, None, None
        for n, req in enumerate(itertools.islice(self._waiting, most), 1):
            rows += req is head or self._group_of(req) == key
            if n < free:
                continue
            wait = 0
            if n > free:
                wait = ends[n - free - 1]
                ended += wait
                if wait > room_ticks:
                    break
            cost = self._batch_cost(
                (min(_bucket(rows, floor=1), self.num_slots),) + key)
            if cost is None:
                if n == free:
                    return None   # admitting now has no price to beat
                continue
            # Slot-ticks left empty by then: the free slots through all
            # of the wait, each later one from its own end.
            empty = n * wait - ended
            row_ms = cost / rows
            if n == free:
                now_row_ms = row_ms
            total = row_ms + empty * slot_tick_ms / n
            if best is None or total < best[0]:
                best = (total, n, rows, row_ms)
        if best[1] == free:
            return None
        return {"slots": best[1], "rows": best[2], "row_ms": best[3],
                "now_row_ms": now_row_ms}

    def _holds_admission(self) -> bool:
        """True while :meth:`_admit` leaves the free slots empty so that
        the slots that free next join the same prefill call
        (:meth:`_gather`). Two bounds end a hold whatever the readings
        say, and it then stays ended until the admission has run: the
        slot-time it has left empty, as device time, reaches what the
        gathered batch saves over admitting as the hold began (a wrong
        reading costs at most what a right one gains), and no request is
        held longer than it had already waited (:meth:`_hold_room_s`)."""
        hold = self._hold
        if hold is not None and hold["over"]:
            return False
        plan = self._gather()
        if plan is None:
            self._hold = None
            return False
        if hold is None:
            hold = self._hold = {"row_ms": plan["now_row_ms"],
                                 "slot_ms": 0.0, "over": False}
        saving_ms = plan["rows"] * (hold["row_ms"] - plan["row_ms"])
        over = hold["slot_ms"] / self.num_slots >= saving_ms
        now = time.time()
        for req in itertools.islice(self._waiting, len(self._free)):
            meta = self._req_meta.get(req["rid"])
            if meta is not None:
                meta.setdefault("held", now)
                over = over or self._hold_room_s(meta, now) <= 0
        hold["over"] = over
        return not over

    def _book_held(self, tick_ms: float, hold: Optional[Dict[str, Any]],
                   slots: int) -> None:
        """A tick of ``tick_ms`` ran while ``hold`` kept ``slots`` free
        slots empty: neither stalled nor advancing, the third part of
        the slots' time."""
        if hold is None:
            return
        from ray_tpu._private import metrics_defs as mdefs

        hold["slot_ms"] += tick_ms * slots
        mdefs.CB_ADMIT_HELD_SLOT_MS.inc(tick_ms * slots, tags=self._mtags)
        mdefs.CB_ADMIT_HELD_TICKS.inc(tags=self._mtags)

    def _held(self) -> Optional[Dict[str, Any]]:
        """The hold that keeps the free slots empty now, or None."""
        hold = self._hold
        return hold if hold is not None and not hold["over"] else None

    def _admit_waiting(self, admit: "tracing.phase") -> None:
        """``_admit``'s work, inside its ``engine.admit`` phase: each
        prefill program below is an ``engine.prefill`` phase that takes
        its time out of ``admit``'s, so ``admit`` books host work only."""
        from ray_tpu._private import metrics_defs as mdefs

        # Drain every admissible request FIRST, grouped by (pow-2 suffix
        # bucket, matched-prefix blocks) — compile reuse, never beyond
        # the cache length — so an admission burst costs one prefill
        # dispatch per group instead of one per request. Slots are
        # independent, so batched admission is bit-identical to the old
        # one-at-a-time loop. Each request's NOVEL blocks are reserved
        # all-or-nothing (FIFO: when the head of the queue
        # doesn't fit the arena even after LRU reclaim, admission
        # stops); matched prefix blocks are pinned read-only instead of
        # allocated, so prefill cost and arena demand both scale with
        # novel tokens.
        bs = self.block_size
        eva_c = self.config if self.config.eva_window else None
        self._restart_owed = []   # by the batches of THIS admission
        groups: Dict[tuple, List] = {}
        draft_pending: List = []   # (slot, prompt) for the ext. drafter
        while self._waiting and self._free:
            req = self._waiting[0]
            matched: List[Any] = []
            chunks: List[tuple] = []
            m = 0
            meta = self._req_meta.get(req["rid"])
            if self._prefix is not None:
                chunks = self._req_chunks(req)
                matched = self._prefix.match(
                    chunks[:self._match_cap(req)])
                # Bucket the match DOWN to a power of two so the
                # compiled prefill program count stays log-bounded
                # in m (see _bucket_floor); the released tail
                # parks young in the LRU, still resident for the
                # next matcher and evictable by _alloc_blocks.
                m = _bucket_floor(len(matched))
                if m < len(matched):
                    self._prefix.release(matched[m:])
                    matched = matched[:m]
            need = self._blocks_needed(len(req["prompt"]),
                                       req["max_new"]) - m
            if eva_c:
                # Promise the peak, take what the prompt leaves behind.
                peak, need = need, eva.blocks_held(len(req["prompt"]),
                                                   eva_c, bs)
                got = (self._alloc_blocks(need) if peak <= self.num_blocks
                       - 1 - self._promised else None)
            else:
                got = self._alloc_blocks(need)
            if got is None:
                # Head blocked on arena space with a slot free: from
                # here until admission the wait is ARENA wait, not
                # queue wait — the TTFT decomposition splits there.
                if matched:
                    self._prefix.release(matched)
                if meta is not None and "arena_blocked" not in meta:
                    meta["arena_blocked"] = time.time()
                break
            blocks = [nd.block for nd in matched] + got
            suffix = req["prompt"][m * bs:]
            if self._prefix is not None:
                self.prefix_hit_tokens += m * bs
                self.prefix_miss_tokens += len(suffix)
                if m:
                    self.prefix_hit_requests += 1
                    mdefs.CB_PREFIX_HIT_TOKENS.inc(m * bs,
                                                   tags=self._mtags)
                mdefs.CB_PREFIX_MISS_TOKENS.inc(len(suffix),
                                                tags=self._mtags)
            self._waiting.popleft()
            if meta is not None:
                meta["admit"] = time.time()
                meta["blocks"] = len(blocks)
                meta["prefix_tokens"] = m * bs
            slot = self._free.pop()
            self._slot_blocks[slot] = blocks
            if eva_c:
                self._slot_peak[slot] = peak
                self._promised += peak
            groups.setdefault(
                self._group(len(req["prompt"]), m), []).append(
                (req, slot, blocks, matched, suffix, chunks))
        for (padded_len, m, n_chunks), group in self._prefill_batches(
                groups):
            n = len(group)
            # The batch dim buckets to a power of two as well, so the
            # compiled prefill program count stays log(N) x log(L).
            # Padding rows REPEAT the last request: a duplicate slot
            # index in the scatter writes byte-identical KV twice, which
            # is well-defined; the duplicate's first token is dropped.
            # (Duplicated prefix gathers are reads — trivially safe.)
            n_pad = min(_bucket(n, floor=1), self.num_slots)
            npb_w = padded_len // bs
            # Table entries a chunk leaves behind it: its blocks, or, of
            # an EVA window, its summaries'.
            npb_c = eva.summaries(eva_c) // bs if eva_c else npb_w
            rows = [group[min(i, n - 1)] for i in range(n_pad)]
            live_before = len(self._slots)  # streams that stand still now
            call = xla_monitor.Dispatched()
            pt0 = call.ts  # wall-clock anchor for the prefill span
            with tracing.phase("engine.prefill", mdefs.CB_PREFILL_MS,
                               self._mtags, outer=admit) as prefill:
                with _annotation("engine.prefill.dispatch", seq=call.seq):
                    slots = (None if self.state is None else self._place(
                        np.asarray([row[1] for row in rows], np.int32)))
                    firsts = []
                    # One program call a chunk (a prompt within the chunk
                    # length is one), back to back: chunk i reads what
                    # the chunks before it wrote through the arena.
                    for ci in range(n_chunks):
                        mc = m + ci * npb_c
                        tokens = np.zeros((n_pad, padded_len), np.int32)
                        last_idx = np.zeros(n_pad, np.int32)
                        tables_w = np.full((n_pad, npb_w), GARBAGE_BLOCK,
                                           np.int32)
                        ptables = np.full((n_pad, mc), GARBAGE_BLOCK,
                                          np.int32)
                        for i, (_, _, blocks, _, suffix, _) in enumerate(rows):
                            part = suffix[ci * padded_len:
                                          (ci + 1) * padded_len]
                            tokens[i, :len(part)] = part
                            last_idx[i] = len(part) - 1
                            # Suffix K/V land in the slot's NEW blocks
                            # (the matched prefix is read-only);
                            # bucket-padding overflow past the
                            # reservation writes masked garbage to block 0.
                            new_blocks = blocks[mc:]
                            k = min(len(new_blocks), npb_w)
                            if eva_c and len(part) == eva_c.eva_window:
                                k = npb_c       # a whole window: summaries
                            tables_w[i, :k] = new_blocks[:k]
                            ptables[i, :mc] = blocks[:mc]
                        pstep = self._place(np.int32(self._prefill_count))
                        self._prefill_count += 1
                        args = (self._place(tokens), self._caches(),
                                self._place(ptables), self._place(tables_w),
                                self._place(last_idx), pstep, slots)
                        if ci == 0:
                            # The arguments are on the device: from here
                            # it has a program to run.
                            dispatched = time.perf_counter()
                        first, caches = self._prefill(self.params, *args)
                        self.cache, self.state = _split_caches(caches)
                        firsts.append(first)
                # The programs queue behind the tick in flight, whose
                # row reaches the host first: land it here, on its own
                # clock. The device starts the prefill at that moment,
                # so the prefill's clock does too. With nothing in
                # flight the device stood empty until the first program
                # was dispatched: that wait has a clock of its own.
                queued = [t for t in self._inflight if t["wall"] is None]
                for tick in queued:
                    self._land(tick, prefill_behind=True)
                behind = 0.0
                if queued:
                    behind = prefill.elapsed_ms()
                elif self._empty_since is not None:
                    self._book_empty(dispatched,
                                     mdefs.CB_STARVED_BEFORE_PREFILL_MS)
                    self._empty_since = None
                    behind = (dispatched - prefill.t0) * 1e3
                prefill.exclude(behind)
                with _annotation("engine.prefill.fetch", seq=call.seq):
                    # Only the last chunk's N ints are first tokens and
                    # fetched; the chunks before it are waited for, not
                    # fetched. Each is ready when its program ends, so
                    # the time between two is a chunk's device time (and
                    # the last one's answers for all of them).
                    call.fetching(firsts[-1:])
                    for first in firsts:
                        with tracing.phase("engine.prefill.chunk",
                                           mdefs.CB_PREFILL_CHUNK_MS,
                                           self._mtags):
                            if first is firsts[-1]:
                                first = np.asarray(first)
                            else:
                                first.block_until_ready()
            # The fetch syncs the dispatch, so this interval (what
            # ``CB_PREFILL_MS`` just booked) is the programs' device
            # time: the XLA monitor turns it into achieved-FLOPs and
            # bandwidth gauges against this bucket's cost analysis, and
            # every stream that was live stood still through it.
            call.landed()
            self._device_empty(prefill.t1, "prefill")
            prefill_ms = prefill.ms - behind
            self._stall_s += prefill_ms / 1e3
            self._stall_n += 1
            shape = (n_pad, padded_len, m, n_chunks)
            self._note_reading(self._batch_ms, shape, prefill_ms)
            self._restart_owed.append(shape)
            if live_before:
                mdefs.CB_SLOT_STALLED_MS.inc(prefill_ms * live_before,
                                             tags=self._mtags)
            with tracing.phase("engine.account", mdefs.CB_STEP_ACCOUNT_MS,
                               self._mtags, outer=admit):
                self._prefill.note_execution(prefill_ms / 1e3, calls=n_chunks,
                                             shape=shape, call=call)
            self._prefill_shapes.add((n_pad, padded_len))
            true_tokens = sum(len(row[4]) for row in group)
            self.prefill_requests += n
            self.prefill_tokens += true_tokens
            mdefs.CB_PREFILL_REQUESTS.inc(n, tags=self._mtags)
            mdefs.CB_PREFILL_TOKENS.inc(true_tokens, tags=self._mtags)
            # What the programs ran beside those: rows padded to a power
            # of two, lengths to the bucket or the chunk.
            mdefs.CB_PREFILL_PADDED_ROWS.inc(n_pad, tags=self._mtags)
            mdefs.CB_PREFILL_PADDED_TOKENS.inc(
                n_pad * padded_len * n_chunks, tags=self._mtags)
            if eva_c:
                closed = sum(len(row[4]) // eva_c.eva_window for row in group)
                self.eva_windows_closed["prefill"] += closed
                mdefs.CB_EVA_WINDOWS_CLOSED.inc(
                    closed, tags=dict(self._mtags, phase="prefill"))
            if self.config.state_layers:
                self.state_installs += n
                mdefs.CB_STATE_INSTALLS.inc(n, tags=self._mtags)
                if n_chunks > 1:
                    self.state_carries += n * (n_chunks - 1)
                    mdefs.CB_PREFILL_STATE_CARRIES.inc(
                        n * (n_chunks - 1), tags=self._mtags)
            # The fetch above synced the device: the first tokens landed.
            first_ts = self.landed_ts = time.time()
            landed = []
            for (req, slot, blocks, matched, _sfx, chunks), tok in \
                    zip(group, first):
                tok = int(tok)
                meta = self._req_meta.get(req["rid"])
                if meta is not None:
                    self._note_first_token(meta, pt0, first_ts)
                if self._prefix is not None and chunks:
                    # Index this prompt's full blocks now that the
                    # dispatch above ordered their arena writes (the
                    # donated-cache dependency chain sequences any later
                    # prefill's gather after them). A chunk already
                    # indexed under another block — a cold twin admitted
                    # this same round — stops the walk and leaves the
                    # remaining blocks exclusive.
                    created = self._prefix.insert(chunks, blocks,
                                                  start=len(matched))
                    if matched or created:
                        self._slot_nodes[slot] = matched + created
                landed.append((req["rid"], tok))
                self._slots[slot] = {
                    "rid": req["rid"], "out": [tok],
                    "max_new": req["max_new"],
                    "pos": len(req["prompt"]),   # next decode writes here
                    "last": tok, "routes": req["routes"],
                    # Reserved-but-speculative block head-room, reported
                    # by pressure_snapshot (router congestion must see
                    # it as occupied, not free).
                    "la_blocks": self._lookahead_blocks(
                        len(req["prompt"]), req["max_new"]),
                }
                self._maybe_finish(slot)
                if self.role == "prefill":
                    # Prefill-role engines stop at the first token: park
                    # the slot's blocks for export instead of entering
                    # the decode tick (a request _maybe_finish already
                    # completed — max_new=1 / immediate EOS — has
                    # nothing to hand off and stays finished).
                    self._park_for_handoff(slot, req)
                if (self._draft_prefill is not None
                        and slot in self._slots):
                    draft_pending.append((slot, req["prompt"]))
            # The batch's first tokens leave now, not behind the fetch
            # of the tick that follows them.
            self._hand_over(landed)
        if self._draft_prefill is not None and draft_pending:
            self._run_draft_prefill(draft_pending)
        self._dirty = True  # device tokens/positions need re-upload

    def _run_draft_prefill(self, admitted) -> None:
        """Prefill the external drafter's dense cache for freshly
        admitted slots — FULL prompts (the target's prefix cache only
        shortens the target's prefill), grouped into the same pow-2
        buckets as the main prefill so the program count stays
        log-bounded. Padding garbage past each prompt is dead: the
        drafter's first decode write at position p overwrites before
        position p is ever attended."""
        by_bucket: Dict[int, List] = {}
        for slot, prompt in admitted:
            blen = min(_bucket(len(prompt)), self.max_len)
            by_bucket.setdefault(blen, []).append((slot, prompt))
        for blen, grp in by_bucket.items():
            n = len(grp)
            n_pad = min(_bucket(n, floor=1), self.num_slots)
            toks = np.zeros((n_pad, blen), np.int32)
            slots_arr = np.zeros(n_pad, np.int32)
            for i in range(n_pad):
                slot, prompt = grp[min(i, n - 1)]
                toks[i, :len(prompt)] = prompt
                slots_arr[i] = slot
            self._draft_cache = self._draft_prefill(
                self._draft_params, self._place(toks),
                self._draft_cache, self._place(slots_arr))

    def _maybe_finish(self, slot: int) -> None:
        st = self._slots.get(slot)
        if st is None:
            return
        done = len(st["out"]) >= st["max_new"] or (
            self.eos_token is not None and st["out"][-1] == self.eos_token)
        if done:
            self._finished[st["rid"]] = st["out"]
            if st["routes"] is not None:
                self._routes[st["rid"]] = st["routes"]
            del self._slots[slot]
            self._release_slot(slot)
            self._finish_request(st["rid"], "finished",
                                 tokens=len(st["out"]))

    def _ahead(self) -> Dict[tuple, int]:
        """(slot, rid) -> ticks in flight that decode a token for it:
        how far the device runs ahead of ``st["out"]`` and ``st["pos"]``."""
        ahead: Dict[tuple, int] = {}
        for tick in self._inflight:
            for member in tick["members"]:
                ahead[member] = ahead.get(member, 0) + 1
        return ahead

    def _next_members(self) -> List[tuple]:
        """The (slot, rid) pairs the next tick decodes a token for: every
        live request but those whose answer the ticks in flight complete.
        ``max_new`` is host-known at dispatch, so such a finish costs no
        overrun row; an EOS is not, and leaves one (see :meth:`step`)."""
        ahead = self._ahead()
        return [(slot, st["rid"]) for slot, st in self._slots.items()
                if len(st["out"]) + ahead.get((slot, st["rid"]), 0)
                < st["max_new"]]

    def _upload_state(self, members: List[tuple]) -> None:
        """Bring the device's decode state up to date for a tick over
        ``members``: whatever the host knows
        a tick ahead is rebuilt from its books. Blocks are reserved at
        admission, so tables and limits change only with membership (an
        EVA-attention model's also as windows fill and close:
        :meth:`_upload_tables`); a
        slot's position is ``pos`` plus the ticks in flight that advance
        it; a row left out (freed, or finishing in flight) gets limit 0
        and the garbage table row, so the tick neither visits nor writes
        it. The LAST TOKEN of a slot with a tick in flight is that
        tick's output, still on the device: only a fresh slot's comes
        from the host, merged in on the device."""
        from ray_tpu._private import metrics_defs as mdefs

        with tracing.phase("engine.upload", mdefs.CB_STEP_UPLOAD_MS,
                           self._mtags):
            ahead = self._ahead()
            tokens = np.zeros(self.num_slots, np.int32)
            fresh = np.zeros(self.num_slots, bool)
            positions = np.zeros(self.num_slots, np.int32)
            for slot, rid in members:
                st = self._slots[slot]
                running = ahead.get((slot, rid), 0)
                fresh[slot] = not running
                tokens[slot] = st["last"]
                positions[slot] = st["pos"] + running
            tokens = self._place(tokens)
            self._d_tokens = self._merge_tokens(
                self._place(fresh), tokens,
                tokens if self._d_tokens is None else self._d_tokens)
            self._d_positions = self._place(positions)
            # The device sampling-step counter is the host-applied count
            # plus the ticks in flight.
            self._d_step = self._place(
                np.int32(self._applied_steps + len(self._inflight)))
            self._upload_tables(members)
            self._d_members = members
            self._dirty = False

    def _upload_tables(self, members: List[tuple]) -> None:
        """The device's block tables and limits from the host's books:
        with the membership and, for a model whose slots' blocks come
        and go while they decode (EVA attention), whenever one did."""
        tables = np.zeros((self.num_slots, self.max_blocks), np.int32)
        limits = np.zeros(self.num_slots, np.int32)
        for slot, _ in members:
            blocks = self._slot_blocks[slot]
            tables[slot] = self._table_row(blocks)
            limits[slot] = len(blocks) * self.block_size
        self._d_tables = self._place(tables)
        self._d_limits = self._place(limits)
        self._tables_stale = False

    def _eva_step_blocks(self, members: List[tuple], before: bool) -> None:
        """The host's half of the tick about to be dispatched over
        ``members`` (``before``) or just dispatched: a slot's next key
        lies at a position the host knows (its booked position plus the
        ticks in flight), so where that key opens a block the block is
        allocated first, out of what admission promised the slot, and
        where it fills the slot's window the tick pools the window and
        its blocks, all but the summaries', go back to the allocator
        here, in the same step (the device runs programs in order: no
        later one can write them before this tick has read them)."""
        from ray_tpu._private import metrics_defs as mdefs

        c, bs = self.config, self.block_size
        ahead = self._ahead()
        closed = retired = 0
        for slot, rid in members:
            # ``before``: this tick is not in flight yet; after: it is.
            written = (self._slots[slot]["pos"] + ahead.get((slot, rid), 0)
                       + before)
            blocks = self._slot_blocks[slot]
            held = eva.blocks_held(written, c, bs)
            if before and held > len(blocks):
                blocks += self.allocator.alloc(held - len(blocks))
                self._tables_stale = True
            elif not before and written % c.eva_window == 0:
                self.allocator.free(blocks[held:])
                closed += 1
                retired += len(blocks) - held
                del blocks[held:]
                self._tables_stale = True
        if closed:
            self.eva_windows_closed["tick"] += closed
            mdefs.CB_EVA_WINDOWS_CLOSED.inc(
                closed, tags=dict(self._mtags, phase="tick"))
            mdefs.CB_EVA_BLOCKS_RETIRED.inc(retired, tags=self._mtags)

    def _run_tick(self):
        """Dispatch one decode tick. Returns the device row to fetch:
        a [B] token vector from the plain tick, or a
        ``(committed [B, k+1], counts [B])`` pair from a spec tick. At
        k = 0 — spec off, or the accept-rate controller parked at the
        bottom rung — this dispatches the EXACT pre-spec ``cb_tick``
        program: same jit, same arguments, same device sequence."""
        k = self._spec_cur_k if self.spec_k else 0
        if k > 0:
            tick = self._get_spec_tick(k)
            if self._draft_cache is not None:
                (committed, counts, self._d_tokens, self._d_positions,
                 self.cache, self._draft_cache, self._d_step) = tick(
                    self.params, self._d_tokens, self._d_positions,
                    self._d_tables, self._d_limits, self.cache,
                    self._draft_cache, self._d_step, self._draft_params)
            else:
                (committed, counts, self._d_tokens, self._d_positions,
                 self.cache, _, self._d_step) = tick(
                    self.params, self._d_tokens, self._d_positions,
                    self._d_tables, self._d_limits, self.cache,
                    self._d_step)
            self.spec_tick_count += 1
            self._last_tick_k = k
            return (committed, counts)
        # A routed model's tick has a fifth output: the row to fetch
        # (tokens with the expert row counts packed behind them).
        (self._d_tokens, self._d_positions, caches,
         self._d_step, *fetch) = self._tick(
            self.params, self._d_tokens, self._d_positions,
            self._d_tables, self._d_limits, self._caches(), self._d_step)
        self.cache, self.state = _split_caches(caches)
        self.base_tick_count += 1
        self._last_tick_k = 0
        return fetch[0] if fetch else self._d_tokens

    def _record_window_token(self, rid: int, entries: Dict[int, list],
                             w0: float, w1: float) -> None:
        """Attribute one applied token to the current sync window of a
        TRACED request (span emission is deferred to finish). Past the
        per-request window cap the tail merges into one aggregate so a
        long generation can't flood the span buffer."""
        ent = entries.get(rid)
        if ent is not None:
            ent[2] += 1
            return
        rec = self._req_meta.get(rid)
        if rec is None or not rec["traced"]:
            return
        wins = rec["windows"]
        if len(wins) < self._MAX_WINDOWS:
            ent = [w0, w1, 1]
            wins.append(ent)
        else:
            ent = rec.get("window_tail")
            if ent is None:
                ent = rec["window_tail"] = [w0, w1, 0, 0]
            ent[1] = w1
            ent[3] += 1
            ent[2] += 1
            entries[rid] = ent
            return
        entries[rid] = ent

    def _hand_over(self, tokens: List[tuple]) -> None:
        """One landing's booked tokens, ``[(rid, token), ...]`` in
        booking order, to whoever streams them: the landing callback
        once, with ``landed_ts``; the per-token callback for each."""
        if not tokens:
            return
        if self.landing_callback is not None:
            self.landing_callback(tokens, self.landed_ts)
        if self.token_callback is not None:
            for rid, tok in tokens:
                self.token_callback(rid, tok)

    def _apply_tokens(self, nxt_rows, membership, window=None) -> None:
        """Book one or more fetched tick rows. ``window`` is the
        (wall_start, wall_end) of the sync window these rows cover —
        recorded per traced request for the decode-window spans (windows
        must attach BEFORE ``_maybe_finish`` pops the record, so this
        rides the apply loop, not a post-pass). What was booked is
        handed over in ONE call when the rows are done
        (:meth:`_hand_over`): the per-tick step has the next tick queued
        on the device by then, so the streams it feeds run while the
        device computes, and a request the step goes on to report as
        finished has all its tokens delivered."""
        from ray_tpu._private import metrics_defs as mdefs

        landed: List[tuple] = []
        with tracing.phase("engine.apply", mdefs.CB_STEP_APPLY_MS,
                           self._mtags):
            applied = 0
            drafted = 0
            accepted = 0
            track = window is not None and self._traced_live > 0
            if track:
                w0, w1 = window
                entries: Dict[int, list] = {}
            # One device tick == one sampling step regardless of how many
            # tokens it committed (spec windows burn exactly one step number),
            # so the step counter advances per ROW, not per token.
            self._applied_steps += len(nxt_rows)
            for row in nxt_rows:
                if isinstance(row, tuple):
                    toks, counts = row   # spec tick: ([B, k+1], [B]) committed
                else:
                    toks, counts = row, None
                routes = None
                for slot, rid in membership:
                    st = self._slots.get(slot)
                    if st is None or st["rid"] != rid:
                        continue  # finished earlier in this batch: skip tail
                    n = 1 if counts is None else int(counts[slot])
                    if st["routes"] is not None and counts is None:
                        if routes is None:
                            routes = self._split_row(row)[1]
                        st["routes"].append(routes[:, slot].tolist())
                    if counts is not None:
                        drafted += toks.shape[1] - 1
                        accepted += n - 1
                    for j in range(n):
                        tok = int(toks[slot]) if counts is None else int(
                            toks[slot, j])
                        landed.append((rid, tok))
                        st["out"].append(tok)
                        st["last"] = tok
                        st["pos"] += 1
                        applied += 1
                        if track:
                            self._record_window_token(rid, entries, w0, w1)
                        self._maybe_finish(slot)
                        if slot not in self._slots:
                            # EOS / max_new mid-window: the rest of the
                            # committed window is past the request's end —
                            # drop it (the finish forces a re-upload).
                            break
            self._hand_over(landed)
            self.decoded_tokens += applied
            if applied:
                mdefs.CB_DECODE_TOKENS.inc(applied, tags=self._mtags)
            if drafted:
                self.spec_draft_tokens += drafted
                self.spec_accepted_tokens += accepted
                self._spec_window.append((drafted, accepted))
                mdefs.CB_SPEC_DRAFT_TOKENS.inc(drafted, tags=self._mtags)
                mdefs.CB_SPEC_ACCEPTED_TOKENS.inc(accepted,
                                                  tags=self._mtags)

    # Accept-rate controller thresholds: shrink k below LOW (drafts are
    # wasting verify bandwidth), grow above HIGH (more look-ahead pays),
    # hold in between. MIN_SAMPLE drafted tokens gate any move so one
    # unlucky window can't thrash the rung.
    _SPEC_RATE_LOW = 0.3
    _SPEC_RATE_HIGH = 0.6
    _SPEC_MIN_SAMPLE = 16

    @property
    def spec_accept_rate(self) -> float:
        """Windowed draft accept rate: accepted / drafted over the last
        ``RAY_TPU_SPEC_WINDOW`` spec rows (0.0 when no drafts yet)."""
        drafted = sum(d for d, _ in self._spec_window)
        if not drafted:
            return 0.0
        return sum(a for _, a in self._spec_window) / drafted

    def _adapt_spec_k(self) -> None:
        """Move the live draft depth along the rung ladder from the
        windowed accept rate, once a step. At rung 0
        the engine runs the exact pre-spec tick program; a probe
        re-enters the bottom rung after ``RAY_TPU_SPEC_PROBE_TICKS``
        base ticks so a workload whose accept rate recovers isn't parked
        at 0 forever."""
        if not (self.spec_k and self.spec_adaptive):
            return
        if self._spec_cur_k == 0:
            self._spec_probe_countdown -= 1
            if self._spec_probe_countdown <= 0:
                self._spec_cur_k = self._spec_ladder_ks[0]
                self._spec_window.clear()
                self._spec_probe_countdown = self._spec_probe_after
            return
        drafted = sum(d for d, _ in self._spec_window)
        if drafted < self._SPEC_MIN_SAMPLE:
            return
        rate = self.spec_accept_rate
        idx = self._spec_ladder_ks.index(self._spec_cur_k)
        if rate < self._SPEC_RATE_LOW:
            self._spec_cur_k = (
                self._spec_ladder_ks[idx - 1] if idx > 0 else 0)
            self._spec_window.clear()
            self._spec_probe_countdown = self._spec_probe_after
        elif rate > self._SPEC_RATE_HIGH and (
                idx + 1 < len(self._spec_ladder_ks)):
            self._spec_cur_k = self._spec_ladder_ks[idx + 1]
            self._spec_window.clear()

    def _split_row(self, row):
        """A plain tick row's routed part: (assignment counts ``[L, X]``
        over the experts held here, each slot's chosen experts ``[L,
        num_slots, k]`` over the router's whole width; None but for a
        held share)."""
        c = self.config
        ids = self.num_slots * c.num_experts_per_tok if c.experts_held else 0
        per_layer = row[self.num_slots:].reshape(-1, c.experts_here + ids)
        if not ids:
            return per_layer, None
        return (per_layer[:, :c.experts_here],
                per_layer[:, c.experts_here:].reshape(
                    len(per_layer), self.num_slots, -1))

    def take_routes(self, rid: int) -> Optional[List[List[List[int]]]]:
        """The experts a finished request's DECODED positions routed to,
        ``[position][routed layer][k]`` over the router's whole width,
        if it was submitted with ``keep_routes``; else None. Position j
        is the one generated token j was fed at (the tick that chose
        token j + 1), so there is one entry fewer than tokens. Held
        until taken."""
        return self._routes.pop(rid, None)

    def _note_expert_rows(self, rows) -> None:
        """Feed the routed block's registry metrics from fetched plain
        tick rows: behind its ``num_slots`` tokens each carries the
        tick's per-layer, per-expert assignment counts ``[L, X]`` (every
        slot routes, live or not: the device computed them all). A dense
        model's rows and a spec tick's carry none."""
        c = self.config
        if not c.num_experts:
            return
        from ray_tpu._private import metrics_defs as mdefs

        for row in rows:
            if isinstance(row, tuple):
                continue
            counts = self._split_row(row)[0]
            if c.experts_held:
                # Every slot routes top-k in every routed layer; the rows
                # count what fell on the experts held here.
                mdefs.CB_MOE_ASSIGNMENTS.inc(
                    self.num_slots * c.num_experts_per_tok * len(counts),
                    tags=self._mtags)
                mdefs.CB_MOE_LOCAL_ASSIGNMENTS.inc(int(counts.sum()),
                                                   tags=self._mtags)
            else:
                mdefs.CB_MOE_ASSIGNMENTS.inc(int(counts.sum()),
                                             tags=self._mtags)
            mdefs.CB_MOE_TOUCHED_SHARE.observe(
                float(np.count_nonzero(counts)) / counts.size,
                tags=self._mtags)
            # (A layer none of whose held experts got a row has no mean
            # to be uneven about; without a held share there is none.)
            busy = counts[counts.any(axis=1)]
            if len(busy):
                mdefs.CB_MOE_LOAD_IMBALANCE.observe(
                    float(np.mean(busy.max(axis=1) / busy.mean(axis=1))),
                    tags=self._mtags)

    def _emit_gauges(self) -> None:
        from ray_tpu._private import metrics_defs as mdefs

        active = len(self._slots)
        mdefs.CB_ACTIVE_SLOTS.set(active, tags=self._mtags)
        mdefs.CB_WAITING_REQUESTS.set(len(self._waiting), tags=self._mtags)
        mdefs.CB_SLOT_OCCUPANCY.set(active / max(self.num_slots, 1),
                                    tags=self._mtags)
        kv = self.kv_block_stats()
        mdefs.CB_KV_BLOCKS_USED.set(kv["used"], tags=self._mtags)
        mdefs.CB_KV_BLOCKS_TOTAL.set(kv["total"], tags=self._mtags)
        mdefs.CB_KV_FRAG_RATIO.set(kv["frag_ratio"], tags=self._mtags)
        if self._prefix is not None:
            mdefs.CB_KV_BLOCKS_CACHED.set(kv["cached"], tags=self._mtags)
            mdefs.CB_KV_BLOCKS_SHARED.set(kv["shared"], tags=self._mtags)
        if self.config.state_layers:
            mdefs.CB_STATE_CACHE_BYTES.set(self.state.nbytes,
                                           tags=self._mtags)
        if self.config.eva_window:
            # What the live slots hold, and what their contexts would
            # hold with every key kept.
            block_bytes = self.block_size * self.cache.token_bytes()
            mdefs.CB_EVA_CACHE_BYTES.set(
                block_bytes * sum(map(len, self._slot_blocks.values())),
                tags=self._mtags)
            mdefs.CB_EVA_UNCOMPRESSED_BYTES.set(
                block_bytes * sum(-(-st["pos"] // self.block_size)
                                  for st in self._slots.values()),
                tags=self._mtags)
        if self.config.cca_layers:
            mdefs.CB_CCA_KV_BYTES.set(
                self.cache.k.nbytes + self.cache.v.nbytes, tags=self._mtags)
            mdefs.CB_CCA_TAIL_BYTES.set(self.state.nbytes, tags=self._mtags)
        if self.config.latent_layers:
            mdefs.CB_LATENT_KV_BYTES.set(self.cache.nbytes, tags=self._mtags)
        if self._ring is not None:
            mdefs.CB_WINDOW_KV_BYTES.set(self._ring.nbytes, tags=self._mtags)
            mdefs.CB_FULL_KV_BYTES.set(
                self.cache.k.nbytes + self.cache.v.nbytes, tags=self._mtags)
        if self.spec_k:
            mdefs.CB_SPEC_ACCEPT_RATE.set(self.spec_accept_rate,
                                          tags=self._mtags)
            mdefs.CB_SPEC_K.set(self._spec_cur_k, tags=self._mtags)

    def _dispatch_tick(self, members: List[tuple]) -> None:
        """Queue one decode tick over ``members`` on the device and start
        its row's copy to the host; it joins the ticks in flight."""
        from ray_tpu._private import metrics_defs as mdefs

        if self.config.eva_window:
            self._eva_step_blocks(members, before=True)
        if self._dirty or members != self._d_members:
            self._upload_state(members)
        elif self._tables_stale:
            self._upload_tables(members)
        call = xla_monitor.Dispatched()     # the tick carries it to its landing
        w0 = call.ts if self._traced_live else None
        t0 = call.pc
        if self._empty_since is not None:
            # The device had nothing queued: it waited for this thread,
            # through the restart after a prefill or after its last tick.
            if self._empty_after == "prefill":
                restart_ms = self._book_empty(
                    t0, mdefs.CB_STARVED_AFTER_PREFILL_MS)
                # The restart the admission's batches brought with them.
                for shape in self._restart_owed:
                    self._note_reading(self._restart_ms, shape, restart_ms)
            else:
                self._book_empty(t0, mdefs.CB_STARVED_TICK_LATE_MS)
            self._empty_since = None
        with _annotation("engine.tick.dispatch", seq=call.seq):
            row = self._run_tick()
            for part in (row if isinstance(row, tuple) else (row,)):
                part.copy_to_host_async()
        if any(tick["wall"] is None for tick in self._inflight):
            # Queued behind a tick whose row has not landed: the device
            # goes into this one without waiting for the host. (After an
            # admission the tick ahead has landed, before the prefill.)
            mdefs.CB_TICK_OVERLAPPED.inc(tags=self._mtags)
        self._inflight.append({"row": row, "members": members,
                               "k": self._last_tick_k, "t0": t0,
                               "w0": w0, "wall": None, "call": call,
                               "hold": self._held(),
                               "held": len(self._free)})
        if self.config.eva_window:
            self._eva_step_blocks(members, before=False)

    def _device_empty(self, now: float, after: str) -> None:
        """A landing at ``now`` (``after``: "tick" or "prefill") left
        nothing queued on the device: until the next dispatch it waits
        for this thread."""
        self._empty_since, self._empty_after = now, after

    def _land(self, tick: Dict[str, Any],
              prefill_behind: bool = False) -> None:
        """Wait for ``tick``'s row to reach the host, once: 4 bytes a
        slot (a routed model's expert row counts behind them; a spec
        tick's committed window and counts). ``CB_TICK_MS`` gets the
        time between two consecutive rows landing, which in steady state
        is the device's tick (the next one is already queued behind it);
        the clock starts at the dispatch instead where the device was
        idle before it, or ran a prefill. Every member advanced through
        that time. ``prefill_behind``: a prefill's programs are queued
        behind this tick, so the device does not run dry when it ends."""
        from ray_tpu._private import metrics_defs as mdefs

        if tick["wall"] is not None:
            return
        call = tick["call"]
        with _annotation("engine.tick.fetch", seq=call.seq):
            row = tick["row"]
            # Was the row there before this thread came for it? Then the
            # host is the slower side of this tick.
            call.fetching(row if isinstance(row, tuple) else (row,))
            tick["row"] = (tuple(np.asarray(part) for part in row)
                           if isinstance(row, tuple) else np.asarray(row))
        now = call.landed()
        tick["landed_ts"] = call.landed_ts
        tick["wall"] = now - max(self._row_landed, tick["t0"])
        self._row_landed = now
        wall_ms = tick["wall"] * 1e3
        mdefs.CB_TICK_MS.observe(wall_ms, tags=self._mtags)
        mdefs.CB_SLOT_ADVANCING_MS.inc(wall_ms * len(tick["members"]),
                                       tags=self._mtags)
        self._book_held(wall_ms, tick["hold"], tick["held"])
        self._note_tick_ms(wall_ms)
        if not prefill_behind and all(
                t["wall"] is not None for t in self._inflight):
            self._device_empty(now, "tick")

    def _book_tick(self, tick: Dict[str, Any]) -> None:
        """Apply a landed tick's tokens to the requests that were its
        members when it was dispatched; a member that ended since (EOS,
        cancel) drops its row."""
        from ray_tpu._private import metrics_defs as mdefs

        self._land(tick)
        k = tick["k"]
        # Spec ticks report against THEIR program (per-k instrumented
        # jit) with the bytes hint priced for k draft passes + the wider
        # verify window.
        tick_fn = self._spec_ticks[k] if k else self._tick
        with tracing.phase("engine.account", mdefs.CB_STEP_ACCOUNT_MS,
                           self._mtags):
            self._note_expert_rows([tick["row"]])
            self._account_tick(tick_fn, tick, k)
        self.landed_ts = tick["landed_ts"]
        self._apply_tokens(
            [tick["row"]], tick["members"],
            window=(tick["w0"], time.time())
            if tick["w0"] is not None else None)

    def step(self) -> Dict[int, List[int]]:
        """Admit waiting requests, book ONE decode tick's tokens, and
        return the requests that finished.

        The step keeps one tick queued behind the one that runs: it
        dispatches tick n+1 BEFORE it fetches tick n, so the device goes
        from one tick straight into the next while this thread books
        tokens, hands them over, admits and uploads (the first step after
        an idle engine dispatches two). The device carries tokens,
        positions, step counter and arena from tick to tick; what the
        host re-uploads on a membership change it knows a tick ahead
        (:meth:`_upload_state`). An admission's prefill queues behind
        the tick in flight, so a slot freed by tick n is refilled for
        tick n+2 at the earliest, one tick later than a step that waited
        would. A saturated engine refills it later still, on purpose:
        :meth:`_admit` leaves it empty while the slots that free over
        the next few ticks are worth waiting for, and then refills them
        all with one prefill batch (:meth:`_gather`, which weighs the
        batch's measured time a row against the decode rows the empty
        slots forgo; the ticks go on through a hold).

        Running ahead costs at most one OVERRUN ROW, for an end the host
        cannot foresee (EOS, ``cancel``): tick n+1 has run for a request
        tick n ended. Its token is dropped (the row's rid no longer owns
        the slot); its K/V write lands inside the slot's own reservation
        or, past it, in the garbage block, never in a block the prefix
        index holds (those are full PROMPT blocks); and the freed blocks
        can only be refilled by a prefill the device runs after tick
        n+1. An end by ``max_new`` is foreseen (:meth:`_next_members`).

        A speculative tick (``spec_k`` rung above 0) advances each slot
        by a count the device decides, so positions are not host-known a
        tick ahead: it is fetched right after its dispatch, a depth of 1
        in the same loop."""
        from ray_tpu._private import chaos
        from ray_tpu._private import metrics_defs as mdefs

        if chaos.enabled():
            # Delayed-engine-tick chaos site (``delay_tick``): decode
            # stutters — a slow device, a co-tenant hog — with every
            # request still alive. Drains under load and streaming
            # timeouts must ride it out.
            chaos.inject("serve_tick", engine=self._mtags["engine"])
        with tracing.phase("engine.account", mdefs.CB_STEP_ACCOUNT_MS,
                           self._mtags):
            self._emit_gauges()
            self._adapt_spec_k()
        self._admit()
        depth = 1 if self.spec_k and self._spec_cur_k else 2
        while len(self._inflight) >= depth:
            # Only when the rung has just left 0 with a plain tick queued.
            self._book_tick(self._inflight.popleft())
        while len(self._inflight) < depth:
            members = self._next_members()
            if not members:
                break
            self._dispatch_tick(members)
        if self._inflight:
            self._book_tick(self._inflight.popleft())
        if self._empty_since is not None and not (
                self._slots or self._waiting):
            self._no_work = True
        out, self._finished = self._finished, {}
        return out

    def run_to_completion(self) -> Dict[int, List[int]]:
        """Drive ticks until every submitted request finished."""
        results: Dict[int, List[int]] = {}
        while self.has_work():
            results.update(self.step())
        return results
