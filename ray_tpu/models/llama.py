"""Flagship model: Llama-family decoder, pure JAX, GSPMD-sharded.

This is the BASELINE.md north-star workload (Llama-2-7B fine-tune on a TPU
pod). Where the reference framework hosts external engines for the model
itself (SURVEY.md §2.3 — TP/PP arrive via vLLM / HF integrations), ray_tpu
ships the model natively, TPU-first:

* parameters are plain pytrees with a parallel pytree of *logical axis
  names*; :mod:`ray_tpu.parallel.sharding` rules map them onto any mesh
  (DP / FSDP / TP / SP hybrids are rule-table changes, not model changes);
* the layer stack is a ``jax.lax.scan`` over stacked layer params (one
  compiled layer body regardless of depth) with optional ``jax.checkpoint``
  rematerialization;
* attention auto-selects: pallas flash attention on a local sequence, ring
  attention over the ``seq`` mesh axis when the sequence is context-parallel;
* activations/params default to bfloat16 with fp32 logits/loss.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops.attention import (FLASH_RESIDUAL_NAMES, flash_applicable,
                                   flash_attention, mha_reference)
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.ops.rope import apply_rope, rope_frequencies
from ray_tpu.parallel import constrain, mesh_shape
from ray_tpu.util import compile_cache

# Every process that builds a model imports this module before its first
# compile (trainers, engines, replicas, workers): place the persistent
# compilation cache here, once, for all of them.
compile_cache.ensure()

Params = Dict[str, Any]

# Layer kinds whose mixer keeps a recurrent state and a conv tail a slot.
STATE_KINDS = ("mamba", "linear_attention", "mamba1")


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: int = 128
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # remat_policy: what the backward of a checkpointed layer finds saved.
    # "full" keeps every matmul output (some 350 MB a layer at the train
    # cells' shapes) and recomputes the elementwise work between them: the
    # most memory, the least recompute, the default of every cell;
    # "attn_out" keeps the attention block's output alone and recomputes
    # the rest; "mlp_only" keeps q/k/v beside it and recomputes the MLP.
    # All three keep the flash kernel's output and log-sum-exp (B x S x H x
    # (2 D + 4) bytes a layer, 34 MB at 4 x 2048 x 16 x 128), so no policy's
    # backward runs the flash forward a second time. See forward().
    remat_policy: str = "full"
    # attention: "auto" | "flash" | "ring" | "reference"
    attention: str = "auto"
    # The OLMoE family (published keys ``num_experts``,
    # ``num_experts_per_tok``, ``norm_topk_prob``): with experts, every
    # layer's MLP is the dropless routed block of ops/moe.py and
    # ``intermediate_size`` is ONE expert's width. 0 = dense SwiGLU.
    num_experts: int = 0
    num_experts_per_tok: int = 0
    norm_topk_prob: bool = False
    # A learned RMSNorm over the WHOLE projected q and k vectors, before
    # the split into heads and before rope (OLMoE's modeling code).
    qk_norm: bool = False
    # The Granite 4.0-H family (``transformers`` ``granitemoehybrid``;
    # SERVING ONLY: the engine runs it, :func:`forward` refuses it).
    # ``layer_types`` names each layer's token mixer, "mamba" (a Mamba-2
    # state-space mixer, ``models/mamba2.py``) or "attention"; empty =
    # attention everywhere. Its parameters live in ``params["runs"]``,
    # one stacked tree a run of equal layers (:func:`layer_runs`).
    layer_types: Tuple[str, ...] = ()
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    # The Jamba family (``model_type`` "jamba" with ``num_experts`` 1;
    # SERVING ONLY): ``layer_types`` names "mamba1", a Mamba-1 mixer
    # (``models/mamba1.py``; ``transformers`` calls the layer "mamba",
    # which here is Mamba-2), or "attention", where the published
    # ``i % attn_layer_period == attn_layer_offset``. Mamba-1 has a
    # decay for EVERY (channel, state) pair (``A_log [d_inner, N]``), a
    # time step a channel through a bottleneck of ``mamba_dt_rank``,
    # RMSNorms on dt, B and C, a convolution over x alone and no gated
    # norm; it has no heads, so ``d_inner`` (the published
    # ``mamba_expand`` x hidden) is held as ONE head: ``mamba_n_heads``
    # 1 x ``mamba_d_head``, with ``mamba_d_state`` N and
    # ``mamba_d_conv`` as above. A slot keeps ``[N, d_inner]`` float32
    # and the conv's last inputs a layer (``paged_kv.StateCache``), and
    # a long prompt's chunk goes on from them. 0 = every other model.
    mamba_dt_rank: int = 0
    # ``position_embedding_type`` "nope": no rotation of q and k.
    rope: bool = True
    # Softmax scale of attention; None = head_dim ** -0.5.
    attention_multiplier: Optional[float] = None
    embedding_multiplier: float = 1.0    # x = embed[tokens] * this
    residual_multiplier: float = 1.0     # x += this * sublayer(norm(x))
    logits_scaling: float = 1.0          # logits = x @ head / this
    # A dense SwiGLU every token takes beside the routed experts; 0 =
    # none. Added unweighted, or, with ``shared_expert_gate``, times
    # ``sigmoid(h w_sg)``, one scalar a token (``shared_gate_w [E]``).
    shared_intermediate_size: int = 0
    shared_expert_gate: bool = False
    # The head is the embedding: contracted against ``embed [V, E]`` in
    # place, no ``lm_head`` in the tree.
    tie_word_embeddings: bool = False
    # The afmoe family (Trinity; SERVING ONLY like the hybrids above).
    # ``layer_types`` may name "sliding_attention" (a query sees the last
    # ``sliding_window`` keys, itself included; the engine keeps them in
    # a per-slot ring, ``paged_kv.RingKVCache``) and "full_attention"
    # (the growing arena; without positions when ``rope_full_attention``
    # is off). Every field below defaults to the program without it.
    sliding_window: int = 0
    rope_full_attention: bool = True
    # RMSNorm over each head's ``head_dim`` of q and k, weight ``[D]``.
    qk_norm_per_head: bool = False
    # ``a * sigmoid(h Wg)`` on attention's output, ``Wg`` shaped as ``wq``.
    attn_gate: bool = False
    # Four norms a layer: each sublayer's OUTPUT is normed as well
    # (``post_attn_norm``, ``post_mlp_norm``) before it joins the residual.
    sandwich_norms: bool = False
    # The first layers' MLP is a dense SwiGLU of ``dense_intermediate_size``;
    # the routed block starts after them.
    num_dense_layers: int = 0
    dense_intermediate_size: int = 0
    # "softmax" (OLMoE, Granite) or "sigmoid": scores ``sigmoid(h Wr)``,
    # the top k of ``score + expert_bias``, weights the chosen scores
    # over their sum, times ``route_scale``.
    router_score: str = "softmax"
    route_scale: float = 1.0
    # ``(first, count)``: of the router's ``num_experts`` this chip holds
    # ``[first, first + count)`` and computes their part of the result;
    # None = all of them.
    experts_held: Optional[Tuple[int, int]] = None
    # The DeepSeek-V3 family (``kimi_k2``; SERVING ONLY like the two
    # above): ``layer_types`` names "latent_attention", multi-head LATENT
    # attention (``models/mla.py``). q is projected through a normed
    # bottleneck of ``q_lora_rank``; keys and values through one of
    # ``kv_lora_rank`` beside ``qk_rope_head_dim`` rotated dims that all
    # heads share; a head's q and k have ``qk_nope_head_dim +
    # qk_rope_head_dim`` dims, its v ``v_head_dim``. The engine keeps the
    # ``kv_lora_rank + qk_rope_head_dim`` values a token in
    # ``paged_kv.LatentKVCache``, no per-head K/V. ``rope_scaling``: the
    # published YaRN group as sorted ``(key, value)`` pairs
    # (:func:`scaling_pairs`), None = plain rope.
    # ``first_k_dense_replace`` is ``num_dense_layers``,
    # ``routed_scaling_factor`` ``route_scale``.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_scaling: Optional[Tuple[Tuple[str, Any], ...]] = None
    # The Qwen3-Next family (``qwen3_next``; SERVING ONLY like the three
    # above): ``layer_types`` names "linear_attention", a Gated DeltaNet
    # mixer (``models/gated_delta.py``: ``linear_num_key_heads`` heads of
    # ``linear_key_head_dim`` shared by ``linear_num_value_heads`` of
    # ``linear_value_head_dim``, behind a causal depthwise conv of
    # ``linear_conv_kernel_dim`` taps), whose recurrent state and conv
    # tail a slot keeps in ``paged_kv.StateCache``, beside
    # "full_attention" layers with ``attn_gate`` and ``qk_norm_per_head``.
    # ``partial_rotary_factor``: rope turns the first ``rotary_dim`` dims
    # of a head only. ``zero_centered_norms``: every RMSNorm but the
    # linear mixer's own scales by ``1 + w`` (``w`` initialised at 0).
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    partial_rotary_factor: float = 1.0
    zero_centered_norms: bool = False
    # The EvaByte family (``attention_class`` "eva"; SERVING ONLY): every
    # layer is "eva_attention" (``models/eva.py``): a query sees the raw
    # keys of its own block of ``eva_window`` positions, causally, and
    # every EARLIER window only through one learned summary for each
    # ``eva_chunk`` of its keys (``eva_phi``, ``eva_mu`` ``[KVH, D]`` a
    # layer, float32). The arena holds both, under a compressed position
    # (:func:`eva.compressed`): a closed window's blocks are rewritten as
    # its summaries. ``num_pred_heads``: the head is ``[E, heads x V]``,
    # head j for the token j + 1 ahead; the engine samples from head 0.
    # ``fp32_residual`` (``fp32_skip_add``): the
    # engine keeps the stream between sublayers in float32.
    eva_window: int = 0
    eva_chunk: int = 0
    num_pred_heads: int = 1
    fp32_residual: bool = False
    # The ZAYA1 family (``model_type`` "zaya"; SERVING ONLY): every layer
    # is "cca_attention", Compressed Convolutional Attention
    # (``models/cca.py``): q and k live in latents of ``num_heads x
    # head_dim`` and ``num_kv_heads x head_dim`` (both under
    # ``hidden_size``) behind two causal convolutions, the value's second
    # half is the previous token's; K/V go to the arena like any GQA
    # layer's, and the convolutions' last inputs a slot to
    # ``paged_kv.TailCache`` beside it. ``router_hidden_size``: the
    # router is an MLP of that width with a state carried from layer to
    # layer (``ops.moe.route_mlp_top1``; the tree's ``router``), top 1.
    # ``residual_scaling``: each sublayer joins the stream as ``(s_res x
    # + t_res) + (s_out f(norm(x)) + t_out)``, four learned ``[E]``
    # vectors a sublayer (``res_attn``, ``res_mlp`` ``[4, E]``, float32).
    router_hidden_size: int = 0
    residual_scaling: bool = False
    # The Ouro family (``model_type`` "ouro"; SERVING ONLY): the whole
    # stack, a LLaMA block with ``sandwich_norms`` and no ``layer_types``,
    # is applied ``loop_steps`` times with the SAME weights, the final
    # norm between the steps and one exit-gate scalar a token a step
    # (``exit_gate_w [E]``, ``exit_gate_b`` in the tree). A token keeps
    # K/V for every (step, layer) pair, ``loop_steps x attn_layers`` rows
    # of the arena: ``models/looped.py``. 1 = no loop, every other model.
    loop_steps: int = 1

    @property
    def rotary_dim(self) -> int:
        """Dims of a head that rope turns (the first ones)."""
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def latent_layers(self) -> int:
        """Layers whose keys and values are one latent row a token."""
        return sum(t == "latent_attention" for t in self.layer_types)

    @property
    def state_layers(self) -> int:
        """Layers that keep a recurrent state a request (Mamba-2 or
        Gated DeltaNet: ``paged_kv.StateCache`` holds either's)."""
        return sum(t in STATE_KINDS for t in self.layer_types)

    @property
    def window_layers(self) -> int:
        """Layers that keep the last ``sliding_window`` keys only."""
        return sum(t == "sliding_attention" for t in self.layer_types)

    @property
    def cca_layers(self) -> int:
        """Layers that keep a convolution tail a slot beside their K/V."""
        return sum(t == "cca_attention" for t in self.layer_types)

    @property
    def attn_layers(self) -> int:
        """Layers that keep ALL their K/V: what the arena holds."""
        return (self.num_layers - self.state_layers - self.window_layers
                - self.latent_layers)

    @property
    def moe_layers(self) -> int:
        """Layers whose MLP is the routed block (0 for a dense model)."""
        return (self.num_layers - self.num_dense_layers
                if self.num_experts else 0)

    @property
    def experts_here(self) -> int:
        """Experts whose weights the tree holds."""
        return self.experts_held[1] if self.experts_held else self.num_experts

    @property
    def mamba_dims(self) -> Tuple[int, int]:
        """(d_inner, conv_dim) of a Mamba-2 mixer: the channels of x, and
        of x, B and C together, which the convolution runs over."""
        inner = self.mamba_n_heads * self.mamba_d_head
        return inner, inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def attn_scale(self) -> float:
        if self.latent_layers and self.attention_multiplier is None:
            from ray_tpu.models import mla

            return mla.softmax_scale(self)
        return (self.head_dim ** -0.5 if self.attention_multiplier is None
                else self.attention_multiplier)

    @staticmethod
    def llama2_7b(**kw) -> "LlamaConfig":
        return LlamaConfig(**kw)

    @staticmethod
    def llama2_13b(**kw) -> "LlamaConfig":
        return LlamaConfig(hidden_size=5120, intermediate_size=13824,
                           num_layers=40, num_heads=40, num_kv_heads=40, **kw)

    @staticmethod
    def llama3_8b(**kw) -> "LlamaConfig":
        return LlamaConfig(vocab_size=128256, hidden_size=4096,
                           intermediate_size=14336, num_layers=32,
                           num_heads=32, num_kv_heads=8,
                           rope_theta=500000.0, max_seq_len=8192, **kw)

    @staticmethod
    def olmoe_1b_7b(**kw) -> "LlamaConfig":
        """allenai/OLMoE-1B-7B-0125: 64 experts of 1024, top 8 of a
        softmax over all 64, not renormalised; MHA; QK-norm."""
        return LlamaConfig(**{**dict(
            vocab_size=50304, hidden_size=2048, intermediate_size=1024,
            num_layers=16, num_heads=16, num_kv_heads=16, head_dim=128,
            max_seq_len=4096, rope_theta=10000.0, rms_eps=1e-5,
            num_experts=64, num_experts_per_tok=8, norm_topk_prob=False,
            qk_norm=True), **kw})

    @staticmethod
    def granite_4_0_h_small(**kw) -> "LlamaConfig":
        """ibm-granite/granite-4.0-h-small (32B-A9B): 40 layers, 36
        Mamba-2 mixers (128 heads x 64, state 128) and 4 GQA 32/8
        attention layers without positions; every MLP 72 experts of 768,
        top 10, beside a shared SwiGLU of 1536; tied 100k embedding."""
        pattern = ("mamba",) * 5 + ("attention",) + (
            ("mamba",) * 9 + ("attention",)) * 3 + ("mamba",) * 4
        return LlamaConfig(**{**dict(
            vocab_size=100352, hidden_size=4096, intermediate_size=768,
            num_layers=40, num_heads=32, num_kv_heads=8, head_dim=128,
            max_seq_len=131072, rms_eps=1e-5, num_experts=72,
            num_experts_per_tok=10, norm_topk_prob=True,
            layer_types=pattern, mamba_n_heads=128, mamba_d_head=64,
            mamba_d_state=128, mamba_n_groups=1, mamba_d_conv=4,
            rope=False, attention_multiplier=0.0078125,
            embedding_multiplier=12.0, residual_multiplier=0.22,
            logits_scaling=16.0, shared_intermediate_size=1536,
            tie_word_embeddings=True), **kw})

    @staticmethod
    def trinity_large_preview(**kw) -> "LlamaConfig":
        """arcee-ai/Trinity-Large-Preview (``afmoe``, 400B-A13B): 60
        layers, (sliding x3, full) x15 with a window of 4096 and no
        positions on the full layers; GQA 48/8 of 128 with per-head
        QK-norm and a gated output; four norms a layer; 6 dense layers of
        12288, then 256 experts of 3072, top 4 of a sigmoid score, beside
        one shared expert; untied 200k vocabulary; muP embedding scale."""
        pattern = ("sliding_attention",) * 3 + ("full_attention",)
        return LlamaConfig(**{**dict(
            vocab_size=200192, hidden_size=3072, intermediate_size=3072,
            num_layers=60, num_heads=48, num_kv_heads=8, head_dim=128,
            max_seq_len=262144, rope_theta=10000.0, rms_eps=1e-5,
            layer_types=pattern * 15, sliding_window=4096,
            rope_full_attention=False, qk_norm_per_head=True,
            attn_gate=True, sandwich_norms=True, num_dense_layers=6,
            dense_intermediate_size=12288, num_experts=256,
            num_experts_per_tok=4, norm_topk_prob=True,
            router_score="sigmoid", route_scale=2.448,
            shared_intermediate_size=3072,
            embedding_multiplier=3072 ** 0.5), **kw})

    @staticmethod
    def kimi_k2_7_code(**kw) -> "LlamaConfig":
        """moonshotai/Kimi-K2.7-Code (``kimi_k2``: the DeepSeek-V3
        decoder): 61 layers of latent attention, 64 heads of 128 + 64
        rotated dims (values of 128) through ranks 1536 (q) and 512
        (k/v), YaRN x64 over 4096; one dense layer of 18432, then 384
        experts of 2048, top 8 of a sigmoid score times 2.827, beside one
        shared expert; untied 164k vocabulary."""
        return LlamaConfig(**{**dict(
            vocab_size=163840, hidden_size=7168, intermediate_size=2048,
            num_layers=61, num_heads=64, num_kv_heads=64, head_dim=192,
            max_seq_len=262144, rope_theta=50000.0, rms_eps=1e-5,
            layer_types=("latent_attention",) * 61,
            q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128,
            rope_scaling=scaling_pairs(dict(
                type="yarn", factor=64.0,
                original_max_position_embeddings=4096, beta_fast=32.0,
                beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0)),
            num_dense_layers=1, dense_intermediate_size=18432,
            num_experts=384, num_experts_per_tok=8, norm_topk_prob=True,
            router_score="sigmoid", route_scale=2.827,
            shared_intermediate_size=2048), **kw})

    @staticmethod
    def qwen3_next_80b_a3b(**kw) -> "LlamaConfig":
        """Qwen/Qwen3-Next-80B-A3B-Instruct (``qwen3_next``): 48 layers,
        (Gated DeltaNet x3, full attention) x12; the linear mixer 16 key
        heads and 32 value heads of 128 behind a conv of 4; attention GQA
        16/2 of 256 with a gated output, zero-centred per-head QK-norm and
        rope on the first 64 dims; every MLP 512 experts of 512, top 10 of
        a softmax, renormalised, beside a sigmoid-gated shared expert of
        512; zero-centred norms; untied 152k vocabulary."""
        pattern = ("linear_attention",) * 3 + ("full_attention",)
        return LlamaConfig(**{**dict(
            vocab_size=151936, hidden_size=2048, intermediate_size=512,
            num_layers=48, num_heads=16, num_kv_heads=2, head_dim=256,
            max_seq_len=262144, rope_theta=1e7, rms_eps=1e-6,
            layer_types=pattern * 12, linear_num_key_heads=16,
            linear_num_value_heads=32, linear_key_head_dim=128,
            linear_value_head_dim=128, linear_conv_kernel_dim=4,
            partial_rotary_factor=0.25, zero_centered_norms=True,
            qk_norm_per_head=True, attn_gate=True, num_experts=512,
            num_experts_per_tok=10, norm_topk_prob=True,
            shared_intermediate_size=512, shared_expert_gate=True), **kw})

    @staticmethod
    def evabyte_6_5b(**kw) -> "LlamaConfig":
        """EvaByte/EvaByte (6.5B, tokenizer-free): 32 LLaMA layers of
        hidden 4096, MHA 32/32 of 128, SwiGLU 11008, RMSNorm with a unit
        offset, rope theta 1e5; every layer EVA attention over windows of
        2048 bytes with one summary for 16; 320 byte ids, 8 prediction
        heads."""
        return LlamaConfig(**{**dict(
            vocab_size=320, hidden_size=4096, intermediate_size=11008,
            num_layers=32, num_heads=32, num_kv_heads=32, head_dim=128,
            max_seq_len=32768, rope_theta=1e5, rms_eps=1e-5,
            layer_types=("eva_attention",) * kw.get("num_layers", 32),
            zero_centered_norms=True, eva_window=2048, eva_chunk=16,
            num_pred_heads=8, fp32_residual=True), **kw})

    @staticmethod
    def zaya1_8b(**kw) -> "LlamaConfig":
        """Zyphra/ZAYA1-8B (``zaya``, 8.4B-A0.76B): 40 layers of hidden
        2048, each CCA attention (8 query heads and 2 KV heads of 128 in
        latents of 1024 and 256, two 2-tap convolutions, rope on half a
        head, theta 5e6) then 16 SwiGLU experts of 2048, top 1 by an MLP
        router of 256 with a carry over depth; scaled residuals; tied
        262k vocabulary."""
        return LlamaConfig(**{**dict(
            vocab_size=262272, hidden_size=2048, intermediate_size=2048,
            num_layers=40, num_heads=8, num_kv_heads=2, head_dim=128,
            max_seq_len=131072, rope_theta=5e6, rms_eps=1e-5,
            layer_types=("cca_attention",) * kw.get("num_layers", 40),
            partial_rotary_factor=0.5, num_experts=16,
            num_experts_per_tok=1, router_hidden_size=256,
            residual_scaling=True, tie_word_embeddings=True), **kw})

    @staticmethod
    def ouro_2_6b(**kw) -> "LlamaConfig":
        """ByteDance/Ouro-2.6B (``ouro``, a looped language model): 48
        LLaMA layers of hidden 2048, MHA 16/16 of 128, SwiGLU 5632, four
        norms a layer, applied 4 times with shared weights, the final
        norm between the steps; untied 49k vocabulary."""
        return LlamaConfig(**{**dict(
            vocab_size=49152, hidden_size=2048, intermediate_size=5632,
            num_layers=48, num_heads=16, num_kv_heads=16, head_dim=128,
            max_seq_len=65536, rope_theta=1e6, rms_eps=1e-6,
            sandwich_norms=True, loop_steps=4), **kw})

    @staticmethod
    def jamba2_3b(**kw) -> "LlamaConfig":
        """ai21labs/AI21-Jamba2-3B (``jamba``, ``num_experts`` 1): 28
        layers of hidden 2560, 26 Mamba-1 mixers (5120 channels x 16
        states, dt rank 160, 4 conv taps) and MQA 20/1 attention of 128
        without positions at layers 7 and 21 (``i % 14 == 7``); a dense
        SwiGLU of 8192 on every layer; tied 65k embedding; 3,029.3M
        parameters, which one chip holds whole. The published
        ``max_position_embeddings`` is 262144."""
        pattern = ("mamba1",) * 7 + ("attention",) + ("mamba1",) * 6
        return LlamaConfig(**{**dict(
            vocab_size=65536, hidden_size=2560, intermediate_size=8192,
            num_layers=28, num_heads=20, num_kv_heads=1, head_dim=128,
            max_seq_len=262144, rms_eps=1e-6, layer_types=pattern * 2,
            mamba_n_heads=1, mamba_d_head=5120, mamba_d_state=16,
            mamba_d_conv=4, mamba_dt_rank=160, rope=False,
            tie_word_embeddings=True), **kw})

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        """CPU-runnable config for tests (BASELINE.md config #1 analog)."""
        kw.setdefault("vocab_size", 256)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("intermediate_size", 128)
        kw.setdefault("num_layers", 2)
        kw.setdefault("num_heads", 4)
        kw.setdefault("num_kv_heads", 2)
        kw.setdefault("head_dim", 16)
        kw.setdefault("max_seq_len", 128)
        kw.setdefault("remat", False)
        return LlamaConfig(**kw)


def scaling_pairs(group: Optional[Dict[str, Any]]):
    """A published ``rope_scaling`` group as ``LlamaConfig.rope_scaling``
    holds it (hashable: sorted pairs); None stays None."""
    return None if group is None else tuple(sorted(group.items()))


def logical_axes(config: LlamaConfig) -> Params:
    """Pytree of logical-axis tuples matching :func:`init_params`."""
    layer = {
        "attn_norm": ("layers", "norm"),
        "wq": ("layers", "embed", "heads", "head_dim"),
        "wk": ("layers", "embed", "kv_heads", "head_dim"),
        "wv": ("layers", "embed", "kv_heads", "head_dim"),
        "wo": ("layers", "heads", "head_dim", "embed"),
        "mlp_norm": ("layers", "norm"),
        "w_gate": ("layers", "embed", "mlp"),
        "w_up": ("layers", "embed", "mlp"),
        "w_down": ("layers", "mlp", "embed"),
    }
    if "eva_attention" in config.layer_types:
        layer["eva_phi"] = ("layers", "kv_heads", "head_dim")
        layer["eva_mu"] = ("layers", "kv_heads", "head_dim")
    if config.qk_norm:
        layer["q_norm"] = ("layers", None)
        layer["k_norm"] = ("layers", None)
    if config.num_experts:
        for name in ("w_gate", "w_up", "w_down"):
            del layer[name]
        layer.update({
            "w_router": ("layers", "embed", None),
            "moe_gate": ("layers", "experts", "embed", "mlp"),
            "moe_up": ("layers", "experts", "embed", "mlp"),
            "moe_down": ("layers", "experts", "mlp", "embed"),
        })
    return {
        "embed": ("vocab", "embed"),
        "layers": layer,
        "final_norm": ("norm",),
        "lm_head": ("embed", "vocab"),
    }


def init_params(config: LlamaConfig, key: jax.Array) -> Params:
    """Random init (normal / scaled), stacked over layers for lax.scan."""
    c = config
    if c.loop_steps > 1:
        return _init_looped_params(c, key)
    if c.mamba_dt_rank:
        return _init_jamba_params(c, key)
    if c.layer_types:
        return (_init_hybrid_params(c, key) if "mamba" in c.layer_types
                else _init_windowed_params(c, key))
    k_embed, k_head, k_layers = jax.random.split(key, 3)

    def norm_init(*shape):
        return jnp.ones(shape, c.dtype)

    def dense_init(key, *shape, scale=None):
        fan_in = shape[0] if len(shape) == 2 else int(jnp.prod(jnp.array(shape[:-1])))
        scale = scale if scale is not None else fan_in ** -0.5
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(c.dtype)

    keys = jax.random.split(k_layers, 7)
    L, E, M = c.num_layers, c.hidden_size, c.intermediate_size
    H, KV, D = c.num_heads, c.num_kv_heads, c.head_dim

    def stacked(key, fan_in, *shape):
        scale = fan_in ** -0.5
        out = jax.random.normal(key, (L,) + shape, jnp.float32) * scale
        return out.astype(c.dtype)

    layers = {
        "attn_norm": jnp.ones((L, E), c.dtype),
        "wq": stacked(keys[0], E, E, H, D),
        "wk": stacked(keys[1], E, E, KV, D),
        "wv": stacked(keys[2], E, E, KV, D),
        "wo": stacked(keys[3], H * D, H, D, E),
        "mlp_norm": jnp.ones((L, E), c.dtype),
    }
    if c.qk_norm:
        # Not ones: rms(q) is already about 1 under this init, so a unit
        # weight would make the norm a near-identity no check could see.
        k_q, k_k = jax.random.split(jax.random.fold_in(k_layers, 8))
        layers["q_norm"] = jax.random.uniform(
            k_q, (L, H * D), jnp.float32, 0.5, 1.5).astype(c.dtype)
        layers["k_norm"] = jax.random.uniform(
            k_k, (L, KV * D), jnp.float32, 0.5, 1.5).astype(c.dtype)
    if c.num_experts:
        # mixtral.py's names and layout; the router stays float32.
        X = c.num_experts
        layers["w_router"] = jax.random.normal(
            jax.random.fold_in(k_layers, 7), (L, E, X),
            jnp.float32) * E ** -0.5
        layers["moe_gate"] = stacked(keys[4], E, X, E, M)
        layers["moe_up"] = stacked(keys[5], E, X, E, M)
        layers["moe_down"] = stacked(keys[6], M, X, M, E)
    else:
        layers["w_gate"] = stacked(keys[4], E, E, M)
        layers["w_up"] = stacked(keys[5], E, E, M)
        layers["w_down"] = stacked(keys[6], M, M, E)
    return {
        "embed": dense_init(k_embed, c.vocab_size, E, scale=1.0),
        "layers": layers,
        "final_norm": jnp.ones((E,), c.dtype),
        "lm_head": dense_init(k_head, E, c.vocab_size),
    }


def _init_hybrid_params(c: LlamaConfig, key: jax.Array) -> Params:
    """The Granite 4.0-H family's tree: ``embed`` (also the head),
    ``final_norm``, ``layers`` = the stacked experts ``[L, X, ...]``
    alone (read in place at a GLOBAL layer index), and ``runs``: for
    each run of equal layers (:func:`layer_runs`) one tree stacked over
    the run's layers, holding everything else a layer has. A scan takes
    a run's tree as it is: nothing is sliced, at any depth.

    Seeded so that dropping a term shows: ``a_log`` gives decays
    ``-exp(a_log)`` in -1..-16, ``dt_bias`` time steps of 0.001..0.1
    (the ranges of ``mamba_ssm``'s own initialiser), ``ssm_d`` and the
    gate norm's weight are uniform in 0.5..1.5, not ones."""
    L, E, M, X = c.num_layers, c.hidden_size, c.intermediate_size, c.num_experts
    H, KV, D = c.num_heads, c.num_kv_heads, c.head_dim
    inner, conv_dim = c.mamba_dims
    k_embed, k_experts, k_runs = jax.random.split(key, 3)

    def dense(key, fan_in, *shape):
        out = jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5
        return out.astype(c.dtype)

    def uniform(key, lo, hi, *shape):
        return jax.random.uniform(key, shape, jnp.float32, lo, hi)

    ke = jax.random.split(k_experts, 3)
    runs = []
    for r, (kind, _, n, _) in enumerate(layer_runs(c)):
        k = jax.random.split(jax.random.fold_in(k_runs, r), 12)
        tree = {
            "attn_norm": jnp.ones((n, E), c.dtype),
            "mlp_norm": jnp.ones((n, E), c.dtype),
            "w_router": jax.random.normal(k[0], (n, E, X), jnp.float32)
            * E ** -0.5,
            "shared_gate": dense(k[1], E, n, E, c.shared_intermediate_size),
            "shared_up": dense(k[2], E, n, E, c.shared_intermediate_size),
            "shared_down": dense(k[3], c.shared_intermediate_size, n,
                                 c.shared_intermediate_size, E),
        }
        if kind == "mamba":
            dt = jnp.exp(uniform(k[7], jnp.log(1e-3), jnp.log(1e-1),
                                 n, c.mamba_n_heads))
            tree.update({
                "ssm_in": dense(k[4], E, n, E,
                                inner + conv_dim + c.mamba_n_heads),
                "conv_w": dense(k[5], c.mamba_d_conv, n, c.mamba_d_conv,
                                conv_dim),
                "conv_b": uniform(k[6], -0.5, 0.5, n, conv_dim).astype(c.dtype),
                # softplus(dt_bias) = dt
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "a_log": jnp.log(uniform(k[8], 1.0, 16.0, n, c.mamba_n_heads)),
                "ssm_d": uniform(k[9], 0.5, 1.5, n, c.mamba_n_heads),
                "ssm_norm": uniform(k[10], 0.5, 1.5, n, inner).astype(c.dtype),
                "ssm_out": dense(k[11], inner, n, inner, E),
            })
        else:
            tree.update({
                "wq": dense(k[4], E, n, E, H, D),
                "wk": dense(k[5], E, n, E, KV, D),
                "wv": dense(k[6], E, n, E, KV, D),
                "wo": dense(k[7], H * D, n, H, D, E),
            })
        runs.append(tree)
    return {
        # The head is this matrix too: at unit scale a token's own
        # embedding, still the largest part of x after six scaled
        # residuals, would win every argmax and no check on chosen
        # tokens could see a fault in a layer. At this scale its logit
        # is one standard deviation of the others.
        "embed": (jax.random.normal(k_embed, (c.vocab_size, E), jnp.float32)
                  / (c.embedding_multiplier * E ** 0.5)).astype(c.dtype),
        "final_norm": jnp.ones((E,), c.dtype),
        "layers": {
            "moe_gate": dense(ke[0], E, L, X, E, M),
            "moe_up": dense(ke[1], E, L, X, E, M),
            "moe_down": dense(ke[2], M, L, X, M, E),
        },
        "runs": runs,
    }


def _init_windowed_params(c: LlamaConfig, key: jax.Array) -> Params:
    """The afmoe family's tree (and the latent-attention family's, whose
    attention weights are :func:`mla.init_attention`'s), laid out as the
    hybrids' is: ``embed``,
    ``lm_head``, ``final_norm``, ``layers`` = the stacked experts
    ``[L_moe, held, ...]`` alone (read in place at the layer's index
    among ROUTED layers: the leading dense layers have none), and
    ``runs``: one stacked tree a run of equal layers
    (:func:`layer_runs`: a run ends where the attention kind or the MLP
    changes).

    Seeded so that dropping a term shows: the four norms and the
    per-head q/k norms are uniform in 0.5..1.5, not ones; ``expert_bias``
    is normal at 0.01, the spacing of the TOP scores of 256 (which a
    sigmoid packs under 1): without it half the tokens pick another
    fourth expert, and with it the busiest expert gets about twice the
    mean load, a trained router's unevenness (at 0.2, the spread of all
    the scores, a dozen experts would take every token); ``wg`` is a
    projection like ``wq``, so its sigmoid ranges over (0, 1)."""
    E, M, X = c.hidden_size, c.intermediate_size, c.num_experts
    H, KV, D = c.num_heads, c.num_kv_heads, c.head_dim
    k_embed, k_head, k_experts, k_runs = jax.random.split(key, 4)

    def dense(key, fan_in, *shape):
        out = jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5
        return out.astype(c.dtype)

    def norm(key, *shape):
        # A zero-centred norm scales by 1 + w: the same 0.5..1.5.
        low = -0.5 if c.zero_centered_norms else 0.5
        return jax.random.uniform(key, shape, jnp.float32, low,
                                  low + 1.0).astype(c.dtype)

    runs = []
    for r, (kind, start, n, _) in enumerate(layer_runs(c)):
        k = jax.random.split(jax.random.fold_in(k_runs, r), 20)
        tree = {"attn_norm": norm(k[0], n, E), "mlp_norm": norm(k[1], n, E)}
        attention = kind != "linear_attention"
        if kind == "eva_attention":
            from ray_tpu.models import eva

            tree.update(eva.init_pooling(c, jax.random.fold_in(k[2], 7), n))
        if c.latent_layers:
            from ray_tpu.models import mla

            tree.update(mla.init_attention(c, k[2], n))
        elif kind == "cca_attention":
            from ray_tpu.models import cca

            tree.update(cca.init_attention(c, k[2], n))
        elif attention:
            tree.update({"wq": dense(k[2], E, n, E, H, D),
                         "wk": dense(k[3], E, n, E, KV, D),
                         "wv": dense(k[4], E, n, E, KV, D),
                         "wo": dense(k[5], H * D, n, H, D, E)})
        else:
            from ray_tpu.models import gated_delta

            tree.update(gated_delta.init_mixer(c, k[2], n))
        if c.sandwich_norms:
            tree["post_attn_norm"] = norm(k[6], n, E)
            tree["post_mlp_norm"] = norm(k[7], n, E)
        if c.residual_scaling:
            # (s_res, t_res, s_out, t_out): scales in 0.5..1.5, shifts
            # small, so that dropping any one shows.
            for name, kr in (("res_attn", k[6]), ("res_mlp", k[7])):
                ks, kt = jax.random.split(kr)
                scale = jax.random.uniform(ks, (n, 2, E), jnp.float32,
                                           0.5, 1.5)
                shift = 0.02 * jax.random.normal(kt, (n, 2, E), jnp.float32)
                tree[name] = jnp.stack([scale[:, 0], shift[:, 0],
                                        scale[:, 1], shift[:, 1]], axis=1)
        if c.qk_norm_per_head and attention:
            tree["q_norm"] = norm(k[8], n, D)
            tree["k_norm"] = norm(k[9], n, D)
        if c.attn_gate and attention:
            tree["wg"] = dense(k[10], E, n, E, H, D)
        if start < c.num_dense_layers or not X:
            Md = c.dense_intermediate_size or M
            tree.update({"w_gate": dense(k[11], E, n, E, Md),
                         "w_up": dense(k[12], E, n, E, Md),
                         "w_down": dense(k[13], Md, n, Md, E)})
        else:
            Ms = c.shared_intermediate_size
            if c.router_hidden_size:
                from ray_tpu.ops import moe

                tree["router"] = moe.init_mlp_router(
                    k[14], n, E, c.router_hidden_size, X)
            else:
                tree["w_router"] = jax.random.normal(
                    k[14], (n, E, X), jnp.float32) * E ** -0.5
            if c.router_score == "sigmoid":
                tree["expert_bias"] = 0.01 * jax.random.normal(
                    k[15], (n, X), jnp.float32)
            if Ms:
                tree.update({"shared_gate": dense(k[16], E, n, E, Ms),
                             "shared_up": dense(k[17], E, n, E, Ms),
                             "shared_down": dense(k[18], Ms, n, Ms, E)})
            if c.shared_expert_gate:
                tree["shared_gate_w"] = dense(k[19], E, n, E)
        runs.append(tree)
    out = {
        # x0 = embed[t] * embedding_multiplier has unit entries.
        "embed": (jax.random.normal(k_embed, (c.vocab_size, E), jnp.float32)
                  / c.embedding_multiplier).astype(c.dtype),
        "final_norm": norm(jax.random.fold_in(k_head, 1), E),
        "lm_head": dense(k_head, E, E, c.num_pred_heads * c.vocab_size),
        "layers": {},
        "runs": runs,
    }
    if c.tie_word_embeddings:
        # The head is the embedding, at a head's scale (see
        # :func:`_init_hybrid_params`): a logit is one standard deviation.
        del out["lm_head"]
        out["embed"] = (out["embed"].astype(jnp.float32)
                        * E ** -0.5).astype(c.dtype)
    if X:
        Lm, Xh = c.moe_layers, c.experts_here
        ke = jax.random.split(k_experts, 3)
        out["layers"] = {"moe_gate": dense(ke[0], E, Lm, Xh, E, M),
                         "moe_up": dense(ke[1], E, Lm, Xh, E, M),
                         "moe_down": dense(ke[2], M, Lm, Xh, M, E)}
    return out


EXPERT_KEYS = ("moe_gate", "moe_up", "moe_down")


def _init_looped_params(c: LlamaConfig, key: jax.Array) -> Params:
    """The Ouro family's tree (``loop_steps > 1``): :func:`init_params`'
    homogeneous tree, ``layers`` stacked ``[L, ...]`` ONCE however many
    times the stack is applied, with the two output norms a layer
    (``post_attn_norm``, ``post_mlp_norm``) and, at the top, the exit
    gate ``exit_gate_w [E]`` (normal at ``E ** -0.5``) and ``exit_gate_b``
    (0). Seeded so that dropping a term shows: the four norms a layer and
    the final norm, which stands between the steps too, are uniform in
    0.5..1.5, not ones."""
    if c.layer_types or c.num_experts or not c.sandwich_norms:
        raise ValueError(
            "loop_steps > 1 is the Ouro family's: a dense stack with "
            "sandwich_norms and no layer_types")
    out = init_params(dataclasses.replace(c, loop_steps=1), key)
    L, E = c.num_layers, c.hidden_size
    k = jax.random.split(jax.random.fold_in(key, 0x100b), 6)

    def norm(key, *shape):
        return jax.random.uniform(key, shape, jnp.float32, 0.5,
                                  1.5).astype(c.dtype)

    out["layers"].update(
        attn_norm=norm(k[0], L, E), post_attn_norm=norm(k[1], L, E),
        mlp_norm=norm(k[2], L, E), post_mlp_norm=norm(k[3], L, E))
    out["final_norm"] = norm(k[4], E)
    out["exit_gate_w"] = (jax.random.normal(k[5], (E,), jnp.float32)
                          * E ** -0.5).astype(c.dtype)
    out["exit_gate_b"] = jnp.zeros((), jnp.float32)
    return out


def _init_jamba_params(c: LlamaConfig, key: jax.Array) -> Params:
    """The Jamba family's tree (``mamba_dt_rank > 0``), laid out as the
    hybrids' is: ``embed`` (also the head), ``final_norm``, ``layers``
    empty (a dense SwiGLU on every layer: no stacked experts) and
    ``runs``: for each run of equal layers (:func:`layer_runs`) one tree
    stacked over the run's layers, the two norms and the SwiGLU beside a
    Mamba-1 mixer's weights (``mamba1.init_mixer``) or MQA attention's.
    Seeded so that dropping a term shows: every norm's weight is uniform
    in 0.5..1.5, not ones; the embedding is at ``E ** -0.5``, as
    :func:`_init_hybrid_params` has it and for its reason."""
    from ray_tpu.models import mamba1

    if (c.num_experts or c.rope or not c.tie_word_embeddings
            or set(c.layer_types) - {"mamba1", "attention"}):
        raise ValueError(
            "mamba_dt_rank > 0 is the Jamba family's: mamba1 and attention "
            "layers, no positions, a dense MLP, a tied head")
    E, M = c.hidden_size, c.intermediate_size
    H, KV, D = c.num_heads, c.num_kv_heads, c.head_dim
    k_embed, k_final, k_runs = jax.random.split(key, 3)

    def dense(key, fan_in, *shape):
        out = jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5
        return out.astype(c.dtype)

    def norm(key, *shape):
        return jax.random.uniform(key, shape, jnp.float32, 0.5,
                                  1.5).astype(c.dtype)

    runs = []
    for r, (kind, _, n, _) in enumerate(layer_runs(c)):
        k = jax.random.split(jax.random.fold_in(k_runs, r), 10)
        tree = {
            "attn_norm": norm(k[0], n, E), "mlp_norm": norm(k[1], n, E),
            "w_gate": dense(k[2], E, n, E, M), "w_up": dense(k[3], E, n, E, M),
            "w_down": dense(k[4], M, n, M, E),
        }
        if kind == "mamba1":
            tree.update(mamba1.init_mixer(c, k[5], n))
        else:
            tree.update(wq=dense(k[6], E, n, E, H, D),
                        wk=dense(k[7], E, n, E, KV, D),
                        wv=dense(k[8], E, n, E, KV, D),
                        wo=dense(k[9], H * D, n, H, D, E))
        runs.append(tree)
    return {
        "embed": (jax.random.normal(k_embed, (c.vocab_size, E), jnp.float32)
                  * E ** -0.5).astype(c.dtype),
        "final_norm": norm(k_final, E), "layers": {}, "runs": runs,
    }


def truncated(config: LlamaConfig, params: Params,
              num_layers: int) -> Tuple[LlamaConfig, Params]:
    """First-``num_layers`` view of a model: (config, params) where the
    layer stack is sliced to the leading ``num_layers`` and the embedding,
    final norm, and lm_head are shared (same arrays, zero copies).

    This is the speculative-decode self-drafter (EAGLE/Medusa-style
    truncated-depth draft): because the sliced stack computes bitwise the
    SAME layer-0..n-1 activations and K/V as the full model, the drafter
    can read and write the target's own paged KV arena for those layers —
    no second checkpoint, no separate draft arena."""
    if not 1 <= num_layers <= config.num_layers:
        raise ValueError(
            f"truncated depth must be in [1, {config.num_layers}], "
            f"got {num_layers}")
    cfg = dataclasses.replace(config, num_layers=num_layers)
    sliced = dict(params)
    sliced["layers"] = jax.tree.map(lambda a: a[:num_layers],
                                    params["layers"])
    return cfg, sliced


def split_layers(params: Params, num_layers: Optional[int] = None):
    """``(scanned, experts)``: the per-layer tree a layer scan slices
    (its first ``num_layers`` layers), and the stacked expert weights
    ``[L, X, ...]``, which it must NOT slice: a scan's slice of a
    custom call's operand is a copy (805 MB a layer at OLMoE's sizes),
    so the routed block reads them in place at a layer index. ``experts``
    is None for a dense model.

    ``scanned`` carries the q/k/v projections in whichever layout
    ``params`` holds them: canonical ``wq``/``wk``/``wv`` ``[L, E, H, D]``
    (:func:`init_params`' tree: the trainer's, a checkpoint's) or the
    engine's ``wq_heads``/... ``[L, H, E, D]`` (:func:`heads_major`).
    Layers stay axis 0 in both, so the cut and the scan's slice are the
    same; :func:`project_qkv` contracts whichever it is handed."""
    layers = params["layers"]
    experts = {k: layers[k] for k in EXPERT_KEYS if k in layers} or None
    scanned = {k: v for k, v in layers.items() if k not in EXPERT_KEYS}
    if num_layers is not None:
        scanned = jax.tree.map(lambda a: a[:num_layers], scanned)
    return scanned, experts


def layer_runs(config: LlamaConfig, params: Optional[Params] = None,
               num_layers: Optional[int] = None):
    """The layer stack as RUNS of equal layers, in order: a list of
    ``(kind, start, count, kind_start)`` where ``kind`` is "attention",
    "mamba", "mamba1", "linear_attention", "sliding_attention", "full_attention",
    "latent_attention", "eva_attention" or "cca_attention", ``start`` the
    run's first GLOBAL layer and ``kind_start`` its first index among
    layers that share its cache (the K/V arena's layer for attention and full
    attention, the state cache's for mamba, mamba1 and linear attention, the
    ring's for sliding attention, the latent cache's for latent
    attention, the arena's again for EVA attention, which no other kind
    shares it with, and for CCA attention the arena's and the tail
    cache's both, which are one index because every layer is such a
    one). A model without ``layer_types`` is one attention run.

    With ``params``: ``(runs, experts)``, each run followed by the tree
    a ``lax.scan`` over its layers takes (``params["runs"][i]``; for a
    homogeneous model :func:`split_layers`' ``scanned``, cut to the first
    ``num_layers``)."""
    c = config
    types = c.layer_types or ("attention",) * c.num_layers
    if len(types) != c.num_layers:
        raise ValueError(f"layer_types names {len(types)} layers, "
                         f"num_layers is {c.num_layers}")
    runs, seen = [], {"attention": 0, "mamba": 0, "sliding_attention": 0,
                      "latent_attention": 0, "linear_attention": 0,
                      "eva_attention": 0, "cca_attention": 0, "mamba1": 0}
    for i, kind in enumerate(types):
        # "full_attention" keeps all its K/V in the arena, as "attention"
        # does: they count as one kind of cache.
        cache = "attention" if kind == "full_attention" else kind
        if cache not in seen:
            raise ValueError(f"layer {i}: unknown layer type {kind!r}")
        # A run also ends where the MLP turns from dense to routed.
        if (runs and runs[-1][0] == kind
                and not (c.num_experts and i == c.num_dense_layers)):
            runs[-1][2] += 1
        else:
            runs.append([kind, i, 1, seen[cache]])
        seen[cache] += 1
    runs = [tuple(r) for r in runs]
    if params is None:
        return runs
    scanned, experts = split_layers(params, num_layers)
    if not c.layer_types:
        return [(("attention", 0, num_layers or c.num_layers, 0), scanned)
                ], experts
    return list(zip(runs, params["runs"])), experts


# Canonical name -> the name the same projection has in the engine's
# heads-major tree (:func:`heads_major`).
HEADS_MAJOR = {"wq": "wq_heads", "wk": "wk_heads", "wv": "wv_heads"}
# ... and with the output gate of a model that has one (``attn_gate``),
# a projection of ``wq``'s shape: every leaf the engine re-lays.
_ENGINE_LAYOUT = {**HEADS_MAJOR, "wg": "wg_heads"}


def swap_heads(leaves):
    """Each stacked projection ``[L, E, H, D]`` as ``[L, H, E, D]``, or
    back: one transpose, its own inverse."""
    return [jnp.swapaxes(a, 1, 2) for a in leaves]


def _relaid(params: Params, names: Dict[str, str], relay) -> Params:
    def one(tree):
        found = [k for k in names if k in tree]
        if not found:
            return tree
        out = {k: v for k, v in tree.items() if k not in names}
        out.update(zip((names[k] for k in found),
                       relay([tree[k] for k in found])))
        return out

    out = dict(params, layers=one(params["layers"]))
    if "runs" in params:
        out["runs"] = [one(run) for run in params["runs"]]
    return out


def heads_major(params: Params, relay=swap_heads) -> Params:
    """``params`` in the ENGINE's layout: every attention layer's
    ``wq``/``wk``/``wv`` ``[L, E, H, D]`` held as ``wq_heads``/
    ``wk_heads``/``wv_heads`` ``[L, H, E, D]`` (``KVH`` for k and v), in
    both homes of the weights: ``params["layers"]`` and each attention
    run of ``params["runs"]``. The same numbers transposed; every other
    leaf is the same array. A tree already re-laid comes back as it is.

    Why the engine holds them so: a decode tick's projection wants, per
    head, an ``[E, D]`` panel with ``E`` on a tiled axis. Canonical tiles
    span ``(H, D)``, so XLA first copied each layer's three slices out
    of the stacked weights (33.5 + 8.4 + 8.4 MB a layer at Mistral's
    widths, 9% of the tick); heads-major, the matmul reads the stacked
    weight in place at the layer index, as ``wo`` and the MLP do.

    The CANONICAL layout is a contract and does not change:
    :func:`init_params`, :func:`logical_axes`, ``ShardedTrainer``,
    checkpoints, weight sync and the benchmark's references all hold
    ``[L, E, H, D]``. ``relay`` maps a list of projection leaves to
    their transposes; the engine passes one jitted program, so a tree
    already on the chip copies its three projections and nothing else."""
    return _relaid(params, _ENGINE_LAYOUT, relay)


def canonical_layout(params: Params) -> Params:
    """:func:`heads_major`'s inverse: an engine's tree as
    :func:`init_params` lays it out (a canonical tree comes back as it
    is). What ``swap_params`` validates against, and what to hand to
    anything that reads ``wq`` by name."""
    return _relaid(params, {v: k for k, v in _ENGINE_LAYOUT.items()},
                   swap_heads)


def norm(x, weight, c: LlamaConfig):
    """The family's RMSNorm of ``x`` over its last axis: scaled by
    ``weight``, or by ``1 + weight`` for ``zero_centered_norms``."""
    if c.zero_centered_norms:
        weight = 1.0 + weight.astype(jnp.float32)
    return rms_norm(x, weight, c.rms_eps)


def _project_heads(h, layer, c: LlamaConfig, name: str):
    """``h [B, S, E]`` through the layer's projection ``name`` in
    whichever layout the tree holds it: ``[B, S, H, D]``."""
    if _ENGINE_LAYOUT[name] in layer:
        return jnp.einsum("bse,hed->bshd", h,
                          layer[_ENGINE_LAYOUT[name]].astype(c.dtype))
    return jnp.einsum("bse,ehd->bshd", h, layer[name].astype(c.dtype))


def attn_gate(h, layer, c: LlamaConfig):
    """``sigmoid(h Wg) [B, S, H, D]``, which multiplies attention's
    output before ``wo`` (``attn_gate`` configs); None otherwise."""
    if not c.attn_gate:
        return None
    return jax.nn.sigmoid(_project_heads(h, layer, c, "wg"))


def project_qkv(h, layer, c: LlamaConfig):
    """The layer's q, k, v projections of normed ``h [B, S, E]``, before
    rope: ``q [B, S, H, D]``, ``k``/``v [B, S, KVH, D]``. Every forward
    of the family (training, the dense-cache generator, each engine
    program) projects here, so QK-norm reaches all of them at once.

    It contracts the layout it is given, read from the tree itself:
    canonical ``wq [E, H, D]`` (the trainer's and ``init_params``' tree)
    or the engine's ``wq_heads [H, E, D]`` (:func:`heads_major`). The
    same products summed over the same ``E``; no flag selects it, so
    ``forward`` on an engine's tree (``score_logprobs``), the self-draft,
    verify and prefill all follow."""
    project = functools.partial(_project_heads, h, layer, c)
    q, k, v = project("wq"), project("wk"), project("wv")
    if c.qk_norm_per_head:
        q = norm(q, layer["q_norm"], c)
        k = norm(k, layer["k_norm"], c)
    if c.qk_norm:
        def whole(x, w):
            flat = x.reshape(*x.shape[:2], -1)
            return rms_norm(flat, w, c.rms_eps).reshape(x.shape)

        q, k = whole(q, layer["q_norm"]), whole(k, layer["k_norm"])
    return q, k, v


def _swiglu(h, w_gate, w_up, w_down, c: LlamaConfig,
            mesh: Optional[Mesh] = None):
    """``down(silu(gate h) * up h)`` on h [B, S, E]."""
    gate = jnp.einsum("bse,em->bsm", h, w_gate.astype(c.dtype))
    up = jnp.einsum("bse,em->bsm", h, w_up.astype(c.dtype))
    act = jax.nn.silu(gate) * up
    if mesh is not None:
        act = constrain(act, mesh, "batch", "seq", "act_mlp")
    return jnp.einsum("bsm,me->bse", act, w_down.astype(c.dtype))


# The most bytes the routed block's sorted rows ``[T * k, E]`` may take
# for a HELD SHARE, whose rows are mostly assignments to absent experts
# that are gathered, never multiplied, and thrown away: a longer batch
# runs the block a piece of its tokens at a time. (8 x 1024 tokens of
# Kimi K2's 7168 at top 8 are 940 MB, beside as much again for the
# results and again for their copy in top-k order, which the weighted
# sum reads; Trinity's 201 MB stay one piece.)
ROUTED_SORT_BYTES = 256 << 20


def _routed_pieces(c: LlamaConfig, tokens: int, width: int, dtype) -> int:
    pieces = 1
    while (c.experts_held and tokens % (2 * pieces) == 0
           and tokens // pieces * c.num_experts_per_tok * width
           * jnp.dtype(dtype).itemsize > ROUTED_SORT_BYTES):
        pieces *= 2
    return pieces


def mlp_block(h, layer, c: LlamaConfig, experts=None, li=None,
              mesh: Optional[Mesh] = None, use_kernel=None, route=None):
    """The layer's MLP sublayer on normed ``h [B, S, E]``: ``(out,
    routed)``. Dense SwiGLU (``routed`` None), or, for a config with
    experts, the dropless routed block over ``experts`` (the stacked
    ``[L, X, ...]`` tree of :func:`split_layers`) read at layer ``li``;
    ``routed`` is its :class:`~ray_tpu.ops.moe.Routed`: per-expert
    assignment counts ``[X]`` and each token's experts ``[B * S, k]``.
    A layer whose tree has no router (the leading dense layers of a
    config with ``num_dense_layers``) takes the dense branch. A layer
    whose router is the MLP with a state over depth (the tree's
    ``router``; ``router_hidden_size``) routes from ``route [B * S, R]``,
    what the layer before it left (zeros into the first), and leaves its
    own in ``routed.carry``."""
    if c.num_experts and ("w_router" in layer or "router" in layer):
        from ray_tpu.ops import moe

        b, s, e = h.shape
        extra = {}
        carry = None
        if "router" in layer:
            with jax.named_scope("moe/router"):
                weights, idx, carry = moe.route_mlp_top1(
                    h.reshape(b * s, e), layer["router"], route, c.rms_eps)
            extra["routing"] = (weights, idx)
        if c.router_score == "sigmoid":
            extra["route"] = functools.partial(
                moe.route_sigmoid_topk, bias=layer["expert_bias"],
                scale=c.route_scale)
        if c.experts_held:
            extra["held"] = c.experts_held
        if c.num_dense_layers:
            li = li - c.num_dense_layers    # its index among routed layers
        block = functools.partial(
            moe.routed_block, w_router=layer.get("w_router"), experts=experts,
            layer=li, top_k=c.num_experts_per_tok,
            norm_topk=c.norm_topk_prob, use_kernel=use_kernel, **extra)
        # (Routed already, the rows cannot be cut into pieces here.)
        pieces = 1 if carry is not None else _routed_pieces(
            c, b * s, e, h.dtype)
        if pieces == 1:
            out, routed = block(h.reshape(b * s, e))
            routed = routed._replace(carry=carry)
        else:
            # No token's result depends on the other rows, so the pieces'
            # results are the whole batch's.
            out, routed = jax.lax.map(block, h.reshape(pieces, -1, e))
            routed = moe.Routed(routed.rows.sum(axis=0),
                                routed.experts.reshape(b * s, -1))
        if c.shared_intermediate_size:
            # The routed sum stands as an array of its own: left free,
            # XLA fuses it into the epilogue of the shared down
            # projection, where its gather runs at the matmul's tiling
            # (PERF.md, PR 58).
            out = jax.lax.optimization_barrier(out)
        out = out.reshape(b, s, e)
        if c.shared_intermediate_size:
            # GraniteMoeHybridDecoderLayer.forward: moe(h) + shared_mlp(h),
            # one norm output feeding both, the shared one unweighted;
            # Qwen3NextSparseMoeBlock's times sigmoid(h w_sg).
            with jax.named_scope("shared_mlp"):
                shared = _swiglu(h, layer["shared_gate"], layer["shared_up"],
                                 layer["shared_down"], c)
                if c.shared_expert_gate:
                    shared = shared * jax.nn.sigmoid(jnp.einsum(
                        "bse,e->bs", h, layer["shared_gate_w"].astype(c.dtype)
                    ))[..., None]
                out = out + shared
        return out, routed
    return _swiglu(h, layer["w_gate"], layer["w_up"], layer["w_down"], c,
                   mesh), None


def _select_attention(config: LlamaConfig, mesh: Optional[Mesh]):
    mode = config.attention
    if mode == "auto":
        if mesh is not None and not mesh.empty and mesh_shape(mesh).get("seq", 1) > 1:
            mode = "ring"
        else:
            mode = "flash"
    return mode


def _attend(q, k, v, config: LlamaConfig, mesh: Optional[Mesh]):
    mode = _select_attention(config, mesh)
    if mode == "reference":
        return mha_reference(q, k, v, causal=True)
    if mode == "ring":
        fn = functools.partial(ring_attention, axis_name="seq", causal=True)
        spec = P(("data", "fsdp"), "seq", "tensor", None)
    elif (mesh is None or mesh.size == 1
          or not flash_applicable(q.shape[1], k.shape[1], q.shape[3])):
        # One device, or shapes the dispatcher hands to mha_reference
        # anyway (plain HLO, which GSPMD partitions by itself).
        return flash_attention(q, k, v, causal=True)
    else:
        # A Mosaic kernel cannot be partitioned by GSPMD: each device
        # runs it on its own batch rows and heads.
        shape = mesh_shape(mesh)
        rows = shape.get("data", 1) * shape.get("fsdp", 1)
        if (q.shape[0] % rows or q.shape[2] % shape.get("tensor", 1)
                or k.shape[2] % shape.get("tensor", 1)):
            raise ValueError(
                f"flash attention on mesh {shape}: batch {q.shape[0]} must "
                f"divide over data*fsdp={rows}, and heads {q.shape[2]}/"
                f"{k.shape[2]} over tensor={shape.get('tensor', 1)}")
        fn = functools.partial(flash_attention, causal=True)
        spec = P(("data", "fsdp"), None, "tensor", None)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def forward(
    params: Params,
    tokens: jnp.ndarray,
    config: LlamaConfig,
    mesh: Optional[Mesh] = None,
    return_hidden: bool = False,
    mlp_fn=None,
    return_routes: bool = False,
):
    """Compute logits [B, S, V] (fp32) for int32 tokens [B, S].

    For a config with experts (the OLMoE family) every layer's MLP is
    the dropless routed block, chosen by the config alone;
    ``return_routes=True`` then returns ``(logits, routes)`` with
    ``routes [L, B * S, k]`` the experts each position chose, best
    first (what a check against a reference compares).

    ``mlp_fn(h, layer) -> (out, aux_scalar)`` swaps the dense SwiGLU block
    for another token-mixing-free sublayer — the MoE family
    (:mod:`ray_tpu.models.mixtral`) routes through here so the attention
    backbone, remat policy, and sharding constraints are shared, not
    copied. With ``return_hidden=True`` the return value is the tuple
    ``(hidden [B, S, E], aux_total)`` where ``aux_total`` is the per-layer
    auxiliary scalar (router load-balancing loss) summed over layers;
    otherwise just the logits array.
    """
    c = config
    if c.layer_types or c.loop_steps > 1:
        raise NotImplementedError(
            "a config with layer_types (state-space, linear-attention, "
            "sliding-window, latent-attention, eva-attention or "
            "cca-attention layers) or with loop_steps > 1 (a looped stack) "
            "is served by the continuous-batching engine only: "
            "llama.forward, loss_fn and LlamaGenerator do not run it")
    seq_len = tokens.shape[1]
    cos, sin = rope_frequencies(c.head_dim, seq_len, c.rope_theta)

    x = params["embed"].astype(c.dtype)[tokens]
    x = constrain(x, mesh, "batch", "seq", "act_embed") if mesh is not None else x

    from jax.ad_checkpoint import checkpoint_name

    scanned, experts = split_layers(params)

    def config_mlp(h, layer, li):
        down, routed = mlp_block(h, layer, c, experts, li, mesh=mesh)
        return (down, jnp.zeros((), jnp.float32),
                None if routed is None else routed.experts)

    mlp = ((lambda h, layer, li: mlp_fn(h, layer) + (None,)) if mlp_fn
           else config_mlp)

    def layer_fn(carry, layer):
        x, aux_sum, li = carry
        # Scope names ride each instruction's metadata into the compiled
        # program and the profiler's trace; they change no arithmetic.
        with jax.named_scope("attention"):
            h = rms_norm(x, layer["attn_norm"], c.rms_eps)
            q, k, v = project_qkv(h, layer, c)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
            if mesh is not None:
                q = constrain(q, mesh, "batch", "seq", "act_heads", None)
                k = constrain(k, mesh, "batch", "seq", "act_kv_heads", None)
                v = constrain(v, mesh, "batch", "seq", "act_kv_heads", None)
            q = checkpoint_name(q, "q")
            k = checkpoint_name(k, "k")
            v = checkpoint_name(v, "v")
            o = _attend(q, k, v, c, mesh)
            o = checkpoint_name(o, "attn_out")
            o = jnp.einsum("bshd,hde->bse", o, layer["wo"].astype(c.dtype))
            x = x + o
            if mesh is not None:
                x = constrain(x, mesh, "batch", "seq", "act_embed")

        with jax.named_scope("mlp"):
            h = rms_norm(x, layer["mlp_norm"], c.rms_eps)
            down, aux, routes = mlp(h, layer, li)
            x = x + down
            if mesh is not None:
                x = constrain(x, mesh, "batch", "seq", "act_embed")
        return (x, aux_sum + aux, li + 1), routes

    body = layer_fn
    if c.remat:
        # flash_attention's backward needs (q, k, v, out, lse). out and
        # lse leave a pallas_call, which no dots policy saves, so every
        # policy keeps them by name: q, k, v come back from the saved
        # projections through the rope, and the backward holds the two
        # backward kernels and no second flash forward.
        policies = jax.checkpoint_policies
        if c.remat_policy == "mlp_only":
            policy = policies.save_only_these_names(
                "q", "k", "v", "attn_out", *FLASH_RESIDUAL_NAMES)
        elif c.remat_policy == "attn_out":
            # The attention block's output and nothing of the matmuls:
            # the backward recomputes norms, projections, rope and the
            # whole MLP. Not timed on the chip.
            policy = policies.save_only_these_names(
                "attn_out", *FLASH_RESIDUAL_NAMES)
        elif c.remat_policy == "full":
            policy = policies.save_from_both_policies(
                policies.dots_with_no_batch_dims_saveable,
                policies.save_only_these_names(*FLASH_RESIDUAL_NAMES))
        else:
            raise ValueError(
                f"unknown remat_policy {c.remat_policy!r}; "
                "expected 'full', 'attn_out', or 'mlp_only'"
            )
        body = jax.checkpoint(layer_fn, policy=policy)
    (x, aux_total, _), routes = jax.lax.scan(
        lambda carry, lp: body(carry, lp),
        (x, jnp.zeros((), jnp.float32), jnp.int32(0)),
        params["layers"] if mlp_fn else scanned)

    x = rms_norm(x, params["final_norm"], c.rms_eps)
    if return_hidden:
        return x, aux_total
    # bf16 operands with fp32 accumulation: the params are STORED bf16, so
    # upcasting inputs to fp32 buys no precision on the products — it only
    # runs the MXU at its fp32 rate (~4x slower on v5e). fp32 accumulate +
    # fp32 logits keep the softmax math exact.
    logits = jnp.einsum(
        "bse,ev->bsv", x, params["lm_head"].astype(c.dtype),
        preferred_element_type=jnp.float32,
    )
    if mesh is not None:
        logits = constrain(logits, mesh, "batch", "seq", "act_vocab")
    if return_routes:
        return logits, routes
    return logits


def hidden_states(
    params: Params,
    tokens: jnp.ndarray,
    config: LlamaConfig,
    mesh: Optional[Mesh] = None,
    mlp_fn=None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(final-norm hidden states [B, S, E], summed aux scalar)."""
    return forward(params, tokens, config, mesh, return_hidden=True,
                   mlp_fn=mlp_fn)


def loss_fn(
    params: Params,
    batch: Dict[str, jnp.ndarray],
    config: LlamaConfig,
    mesh: Optional[Mesh] = None,
    vocab_chunks: int = 8,
    mlp_fn=None,
    aux_coeff: float = 0.0,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Next-token cross-entropy. batch: {"tokens": [B,S] int32, "mask": [B,S]}.

    The LM-head matmul + softmax run over *sequence chunks* so the fp32
    [B, S, V] logits tensor is never materialized (V=32k dominates HBM at
    long seq) — the standard memory-side optimization for LLM training on
    16GB-HBM chips; remat recomputes each chunk's logits in the backward.

    ``mlp_fn``/``aux_coeff`` support MoE variants: the per-layer auxiliary
    scalar (router load balancing) is summed by the backbone and added to
    the loss with weight ``aux_coeff``.
    """
    tokens = batch["tokens"]
    mask = batch.get("mask")
    x, aux = hidden_states(params, tokens, config, mesh,
                           mlp_fn=mlp_fn)                # [B, S, E]
    targets = tokens[:, 1:]
    x = x[:, :-1]
    m = (mask[:, 1:] if mask is not None else
         jnp.ones_like(targets)).astype(jnp.float32)
    # Keep the head in the params' storage dtype: the chunk matmul runs
    # bf16 x bf16 -> fp32-accumulated logits (see forward()).
    head = params["lm_head"].astype(config.dtype)
    if mesh is not None:
        # Every chunk of the scan below needs the whole head. Closed over
        # as the parameter's FSDP shard, its all-gather and its gradient's
        # float32 all-reduce stand in the scan's body and run once a
        # chunk; made whole here, the head is gathered once a step and
        # each chip's partial gradient is summed once, after the backward
        # scan. "vocab" keeps its tensor axis.
        head = constrain(head, mesh, "act_embed", "vocab")

    s = x.shape[1]
    n_chunks = vocab_chunks
    while s % n_chunks:
        n_chunks -= 1
    xs = x.reshape(x.shape[0], n_chunks, s // n_chunks, x.shape[2])
    ts = targets.reshape(targets.shape[0], n_chunks, s // n_chunks)
    ms = m.reshape(m.shape[0], n_chunks, s // n_chunks)

    @functools.partial(jax.checkpoint, policy=jax.checkpoint_policies.nothing_saveable)
    def chunk_stats(xc, tc, mc):
        logits = jnp.einsum("bse,ev->bsv", xc, head,
                            preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        nll = (lse - picked) * mc
        correct = (jnp.argmax(logits, -1) == tc) * mc
        return jnp.sum(nll), jnp.sum(correct)

    def scan_body(carry, inp):
        xc, tc, mc = inp
        nll, correct = chunk_stats(xc, tc, mc)
        return (carry[0] + nll, carry[1] + correct), None

    with jax.named_scope("loss_head"):
        (nll_sum, correct_sum), _ = jax.lax.scan(
            scan_body, (jnp.zeros(()), jnp.zeros(())),
            (xs.transpose(1, 0, 2, 3), ts.transpose(1, 0, 2),
             ms.transpose(1, 0, 2)))
    total = jnp.maximum(jnp.sum(m), 1.0)
    loss = nll_sum / total
    acc = correct_sum / total
    metrics = {"loss": loss, "accuracy": acc, "tokens": total}
    if aux_coeff:
        metrics["aux_loss"] = aux
        loss = loss + aux_coeff * aux
        metrics["total_loss"] = loss
    return loss, metrics


def num_params(config: LlamaConfig) -> int:
    c = config
    if c.layer_types and "mamba" not in c.layer_types:
        shapes = jax.eval_shape(functools.partial(init_params, c),
                                jax.random.PRNGKey(0))
        return sum(a.size for a in jax.tree_util.tree_leaves(shapes))
    if c.layer_types:
        inner, conv_dim = c.mamba_dims
        common = (2 * c.hidden_size + c.hidden_size * c.num_experts
                  + 3 * c.hidden_size * (c.intermediate_size * c.num_experts
                                         + c.shared_intermediate_size))
        attention = c.hidden_size * c.head_dim * 2 * (
            c.num_heads + c.num_kv_heads)
        mamba = (c.hidden_size * (inner + conv_dim + c.mamba_n_heads)
                 + conv_dim * (c.mamba_d_conv + 1) + 3 * c.mamba_n_heads
                 + inner + inner * c.hidden_size)
        return (c.vocab_size * c.hidden_size + c.hidden_size
                + c.num_layers * common + c.attn_layers * attention
                + c.state_layers * mamba)
    per_layer = (
        (4 if c.sandwich_norms else 2) * c.hidden_size
        + c.hidden_size * c.num_heads * c.head_dim * 2
        + c.hidden_size * c.num_kv_heads * c.head_dim * 2
        + 3 * c.hidden_size * c.intermediate_size * max(c.num_experts, 1)
        + c.hidden_size * c.num_experts
        + c.qk_norm * (c.num_heads + c.num_kv_heads) * c.head_dim
    )
    return (
        c.vocab_size * c.hidden_size * 2
        + c.hidden_size
        + c.num_layers * per_layer
        + (c.loop_steps > 1) * (c.hidden_size + 1)      # the exit gate
    )
