"""The DeepSeek-V3 family (Kimi K2: multi-head LATENT attention over a
compressed cache, low-rank q, YaRN rope on 8 of 24 head dims here, a
leading dense layer, a sigmoid router over 16 experts of which this
"chip" holds 4, a shared expert) on the CPU at small sizes, on seeded
weights: the float32 reference against ``transformers``' ``deepseek_v3``
(the whole model, uncut); the engine (chunked prefill in expanded form,
the latent cache, absorbed ticks, the prefix cache, refusals) against the
reference; and the pieces (the latent kernel, the two forms of the one
attention, the shares) against plain formulas.

Tolerances. float32 against float32: both sides hold the same numbers
and differ in operation order (the absorbed form contracts q with W_uk
before the keys, not after), so logits within 2e-4 of their standard
deviation, and the engine's tokens are the reference's ARGMAX at every
generated position. Against ``transformers`` (torch on the CPU, float32):
5e-5 of the logits' standard deviation, the same arithmetic in another
library's order.
"""

import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import reference_kimi_k2 as reference  # noqa: E402
from ray_tpu.models import continuous_batching as cb  # noqa: E402
from ray_tpu.models import llama, mla  # noqa: E402
from ray_tpu.models.continuous_batching import ContinuousBatcher  # noqa: E402
from ray_tpu.models.paged_kv import LatentKVCache  # noqa: E402
from ray_tpu.ops.latent_decode_attention import (  # noqa: E402
    latent_attention_reference, latent_decode_attention)

BS, CHUNK = 8, 16
LAYERS = 3


def tiny(**kw):
    return llama.LlamaConfig.kimi_k2_7_code(**{**dict(
        vocab_size=256, hidden_size=64, intermediate_size=32,
        num_layers=LAYERS, layer_types=("latent_attention",) * LAYERS,
        num_heads=4, num_kv_heads=4, head_dim=24, q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, dense_intermediate_size=96, num_experts=16,
        num_experts_per_tok=4, experts_held=(4, 4),
        shared_intermediate_size=32, max_seq_len=256,
        rope_scaling=llama.scaling_pairs(dict(
            type="yarn", factor=8.0, original_max_position_embeddings=16,
            beta_fast=4.0, beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0)),
        dtype=jnp.float32), **kw})


@pytest.fixture(scope="module")
def model():
    config = tiny()
    return config, llama.init_params(config, jax.random.PRNGKey(1))


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, n).tolist() for n in lengths]


def _serve(config, params, prompts, max_new=6, **engine):
    engine = {**dict(num_slots=4, max_len=160, block_size=BS,
                     prefill_chunk=CHUNK), **engine}
    eng = ContinuousBatcher(config, params=params, **engine)
    rids = [eng.submit(p, max_new) for p in prompts]
    out = eng.run_to_completion()
    return [out[r] for r in rids], eng


def _reference_tokens(params, config, prompt, out):
    seq = list(prompt) + list(out)
    lg = reference.logits(params, seq[:-1], config)[len(prompt) - 1:]
    return [int(t) for t in jnp.argmax(lg, axis=-1)]


# ----------------------------------------------------------- the model

def test_runs_cache_kind_and_param_count():
    c = tiny()
    assert llama.layer_runs(c) == [("latent_attention", 0, 1, 0),
                                   ("latent_attention", 1, 2, 1)]
    assert (c.latent_layers, c.attn_layers, c.moe_layers) == (3, 0, 2)
    # The share ISSUE 36 sizes: 1 dense + 4 routed layers holding 12 of
    # 384 experts and an eighth of the vocabulary.
    share = llama.LlamaConfig.kimi_k2_7_code(
        num_layers=5, layer_types=("latent_attention",) * 5,
        experts_held=(0, 12), vocab_size=20480)
    assert abs(llama.num_params(share) / 1e9 - 3.497) < 0.001
    assert (mla.latent_width(share), mla.row_width(share)) == (576, 640)
    assert abs(share.attn_scale - 0.14468) < 1e-5
    whole = llama.LlamaConfig.kimi_k2_7_code()
    assert whole.latent_layers == 61 and whole.moe_layers == 60


def test_training_forward_refuses_the_family(model):
    config, params = model
    with pytest.raises(NotImplementedError, match="latent-attention"):
        llama.forward(params, jnp.zeros((1, 8), jnp.int32), config)
    with pytest.raises(NotImplementedError, match="latent-attention"):
        llama.loss_fn(params, {"tokens": jnp.zeros((1, 8), jnp.int32)},
                      config)


# ------------------------------------------- against transformers

def _to_deepseek_v3(params, c):
    """The seeded tree as ``DeepseekV3ForCausalLM``'s state dict: the
    checkpoint's layout, rope columns re-interleaved ``(2i, 2i + 1)``."""
    import torch

    def t(a):
        return torch.tensor(np.asarray(a, np.float32))

    dr = c.qk_rope_head_dim
    # Checkpoint rope column r holds the program's column inv[r].
    inv = np.empty(dr, np.int64)
    inv[0::2], inv[1::2] = np.arange(dr // 2), np.arange(dr // 2) + dr // 2
    h, dn, dv, rkv = (c.num_heads, c.qk_nope_head_dim, c.v_head_dim,
                      c.kv_lora_rank)
    out = {"model.embed_tokens.weight": t(params["embed"]),
           "model.norm.weight": t(params["final_norm"]),
           "lm_head.weight": t(params["lm_head"]).T}
    layer = 0
    for run in params["runs"]:
        for i in range(run["attn_norm"].shape[0]):
            w = jax.tree.map(lambda a, i=i: np.asarray(a[i], np.float32), run)
            pre = f"model.layers.{layer}."
            q_b = np.concatenate(
                [w["wq_nope"].reshape(-1, h, dn),
                 w["wq_rope"].reshape(-1, h, dr)[..., inv]], -1)
            kv_a = np.concatenate(
                [w["wkv_a"][:, :rkv], w["wkv_a"][:, rkv:][:, inv]], -1)
            kv_b = np.concatenate(               # [H, Dn + Dv, Rkv]
                [w["w_uk"], np.swapaxes(w["w_uv"], 1, 2)], 1)
            out.update({
                pre + "input_layernorm.weight": t(w["attn_norm"]),
                pre + "post_attention_layernorm.weight": t(w["mlp_norm"]),
                pre + "self_attn.q_a_proj.weight": t(w["wq_a"]).T,
                pre + "self_attn.q_a_layernorm.weight": t(w["q_a_norm"]),
                pre + "self_attn.q_b_proj.weight":
                    t(q_b.reshape(q_b.shape[0], -1)).T,
                pre + "self_attn.kv_a_proj_with_mqa.weight": t(kv_a).T,
                pre + "self_attn.kv_a_layernorm.weight": t(w["kv_a_norm"]),
                pre + "self_attn.kv_b_proj.weight":
                    t(kv_b.reshape(-1, rkv)),
                pre + "self_attn.o_proj.weight":
                    t(w["wo"].reshape(h * dv, -1)).T,
            })
            if "w_router" not in w:
                for ours, theirs in (("w_gate", "gate_proj"),
                                     ("w_up", "up_proj"),
                                     ("w_down", "down_proj")):
                    out[pre + f"mlp.{theirs}.weight"] = t(w[ours]).T
            else:
                li = layer - c.num_dense_layers
                out[pre + "mlp.gate.weight"] = t(w["w_router"]).T
                out[pre + "mlp.gate.e_score_correction_bias"] = t(
                    w["expert_bias"])
                for ours, theirs in (("gate", "gate_proj"), ("up", "up_proj"),
                                     ("down", "down_proj")):
                    out[pre + f"mlp.shared_experts.{theirs}.weight"] = t(
                        w["shared_" + ours]).T
                    for e in range(c.num_experts):
                        out[pre + f"mlp.experts.{e}.{theirs}.weight"] = t(
                            params["layers"]["moe_" + ours][li, e]).T
            layer += 1
    return out


def test_reference_is_transformers_deepseek_v3():
    """(a) The whole model, uncut (every expert held): the reference's
    logits are ``DeepseekV3ForCausalLM``'s on the same weights, and the
    YaRN frequencies are ``_compute_yarn_parameters``'."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from transformers.modeling_rope_utils import _compute_yarn_parameters
    from transformers.models.deepseek_v3 import (DeepseekV3Config,
                                                 DeepseekV3ForCausalLM)

    c = tiny(experts_held=None)
    params = llama.init_params(c, jax.random.PRNGKey(2))
    hf_config = DeepseekV3Config(
        vocab_size=c.vocab_size, hidden_size=c.hidden_size,
        intermediate_size=c.dense_intermediate_size,
        moe_intermediate_size=c.intermediate_size,
        num_hidden_layers=c.num_layers, num_attention_heads=c.num_heads,
        num_key_value_heads=c.num_heads, n_shared_experts=1,
        n_routed_experts=c.num_experts,
        routed_scaling_factor=c.route_scale, kv_lora_rank=c.kv_lora_rank,
        q_lora_rank=c.q_lora_rank, qk_rope_head_dim=c.qk_rope_head_dim,
        v_head_dim=c.v_head_dim, qk_nope_head_dim=c.qk_nope_head_dim,
        n_group=1, topk_group=1, num_experts_per_tok=c.num_experts_per_tok,
        first_k_dense_replace=c.num_dense_layers, norm_topk_prob=True,
        max_position_embeddings=c.max_seq_len, rms_norm_eps=c.rms_eps,
        rope_theta=c.rope_theta, rope_scaling=dict(c.rope_scaling),
        rope_interleave=True, attention_bias=False,
        tie_word_embeddings=False, attn_implementation="eager")
    inv, factor = _compute_yarn_parameters(hf_config, "cpu")
    ours, ours_factor = mla.yarn_frequencies(c)
    np.testing.assert_allclose(ours, inv.numpy(), rtol=1e-6)
    np.testing.assert_allclose(reference.yarn(c)[0], inv.numpy(), rtol=1e-6)
    assert abs(ours_factor - factor) < 1e-7
    assert abs(reference.yarn(c)[2] - mla.softmax_scale(c)) < 1e-9
    # ... and at the published numbers (32 frequencies, factor 64).
    pub = llama.LlamaConfig.kimi_k2_7_code()
    pub_hf = DeepseekV3Config(
        qk_rope_head_dim=64, rope_theta=50000.0,
        max_position_embeddings=262144, rope_scaling=dict(pub.rope_scaling))
    np.testing.assert_allclose(mla.yarn_frequencies(pub)[0],
                               _compute_yarn_parameters(pub_hf, "cpu")[0],
                               rtol=1e-6)

    net = DeepseekV3ForCausalLM(hf_config).eval()
    missing, unexpected = net.load_state_dict(_to_deepseek_v3(params, c),
                                              strict=False)
    assert not unexpected and all("rotary" in k for k in missing), (
        missing, unexpected)
    assert abs(net.model.layers[0].self_attn.scaling
               - mla.softmax_scale(c)) < 1e-7
    tokens = _prompts((40,), seed=5)[0]
    with torch.no_grad():
        want = net(torch.tensor([tokens])).logits[0].numpy()
    got = np.asarray(reference.logits(params, tokens, c))
    assert np.max(np.abs(got - want)) < 5e-5 * want.std()
    del transformers


# ----------------------------------------------------------- the kernel

def _latent_case(positions, nb, limits=None, seed=0, h=4, w=128, rank=32,
                 layers=2):
    rng = np.random.default_rng(seed)
    b = len(positions)
    arena = jnp.asarray(rng.normal(size=(layers, 1 + b * nb, 1, BS, w)),
                        jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, h, w)), jnp.float32)
    tables = 1 + jnp.arange(b * nb, dtype=jnp.int32).reshape(b, nb)
    return (q, arena, tables, jnp.asarray(positions, jnp.int32),
            None if limits is None else jnp.asarray(limits, jnp.int32), rank)


@pytest.mark.parametrize("positions,limits,nb,per,poison", [
    ([0, 7, 8, 30, 39], None, 5, None, False),     # ragged, a full table
    ([3, 0, 17, 0, 33], [40, 0, 40, 0, 40], 5, None, False),  # freed between
    # A short last visit: 5, 2 and 2 live blocks of the rule's step.
    ([32, 15, 8], None, 5, None, False),
    # A last visit with ONE live sub-block of four: 4 + 1, 4 + 4 + 1, 4 + 1.
    ([39, 71, 33], None, 9, 4, False),
    # Consecutive visits of different slots, each with another count of
    # live sub-blocks, a freed slot between: the copies of visit v + 1 are
    # started from a step of another slot.
    ([7, 20, 3, 39, 11], [40, 40, 0, 40, 40], 5, 2, False),
    ([5], None, 5, None, False),        # one slot of one block: one visit
    ([0, 7, 3], None, 5, 2, False),     # every slot's context is one block
    # The blocks NO live table entry names hold NaN (the garbage block,
    # the dead tails' blocks): nothing of them may reach the output.
    ([39, 12, 71, 0], [72, 72, 72, 0], 9, 4, True),
])
def test_latent_kernel_is_the_gather(positions, limits, nb, per, poison,
                                     pallas_interpret):
    """(d) ``latent_decode_attn`` (interpreted) against the ``jax.numpy``
    gather: every row scored over all its lanes, weighed by its first
    ``rank``; a freed slot's row is zero. ``per``: blocks a grid step, the
    cache's own rule when None. The interpreter starts the kernel's row
    buffer as NaN, so a dead sub-block that was neither fetched nor
    blanked shows in every case with a short visit."""
    from ray_tpu.ops.paged_decode_attention import paged_visits

    q, arena, tables, pos, lim, rank = _latent_case(positions, nb, limits)
    want = latent_attention_reference(q, arena, tables, pos, 0.3,
                                      rank=rank, layer=1)
    live = np.ones(len(positions), bool) if limits is None else (
        np.asarray(limits) > 0)
    if poison:
        named = np.zeros(arena.shape[1], bool)
        for s, p in enumerate(positions):
            if live[s]:
                named[np.asarray(tables)[s, :p // BS + 1]] = True
        assert not named[0] and named.sum() < named.size - 1
        arena = arena.at[:, ~named].set(jnp.nan)
    visits = None if per is None else paged_visits(
        tables, pos, lim, block_size=BS, per_visit=per)
    got = latent_decode_attention(q, arena, tables, pos, 0.3, rank=rank,
                                  layer=jnp.int32(1), limits=lim,
                                  visits=visits, use_kernel=True)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-5)
    assert not np.asarray(got)[~live].any()
    # A slab with no layer axis is an arena of one layer.
    np.testing.assert_allclose(
        latent_decode_attention(q, arena[1], tables, pos, 0.3, rank=rank,
                                limits=lim, visits=visits,
                                use_kernel=True)[live],
        np.asarray(want)[live], atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_prefill_kernel_is_the_softmax_and_runs_merge(causal,
                                                      pallas_interpret):
    """``latent_prefill_attn`` (interpreted): keys 24 wide, values 16
    wide, against the dense softmax, with its log-sum-exp; and two runs'
    results merged are the one softmax over both runs' keys."""
    from ray_tpu.ops.latent_prefill_attention import attend_run, merge

    rng = np.random.default_rng(7)

    def r(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    q, k, v = r(2, 3, 16, 24), r(2, 3, 16, 24), r(2, 3, 16, 16)
    out, lse = attend_run(q, k, v, 0.2, causal=causal)
    scores = jnp.einsum("nhqd,nhkd->nhqk", q, k) * 0.2
    if causal:
        scores = jnp.where(jnp.tril(jnp.ones((16, 16), bool)), scores,
                           -jnp.inf)
    want = jnp.einsum("nhqk,nhkd->nhqd", jax.nn.softmax(scores, -1), v)
    np.testing.assert_allclose(out, want, atol=2e-5)
    np.testing.assert_allclose(lse, jax.nn.logsumexp(scores, -1), atol=2e-5)
    k2, v2 = r(2, 3, 32, 24), r(2, 3, 32, 16)
    both, _ = merge((out, lse), attend_run(q, k2, v2, 0.2, causal=False))
    scores2 = jnp.einsum("nhqd,nhkd->nhqk", q, k2) * 0.2
    joined = jax.nn.softmax(jnp.concatenate([scores, scores2], -1), -1)
    np.testing.assert_allclose(
        both, jnp.einsum("nhqk,nhkd->nhqd", joined,
                         jnp.concatenate([v, v2], 2)), atol=2e-5)


def test_a_latent_grid_step_covers_about_a_megabyte():
    """``latent_visit_blocks``: ``visit_blocks``' rule with the latent
    kernel's own constants: about ``VISIT_BYTES`` of ONE slot's rows a
    grid step, at most ``MAX_VISIT_BLOCKS``. Kimi's cache (blocks of 64
    rows x 640 lanes, 80 KiB) gets the sixteen the chip timed best."""
    from ray_tpu.ops.latent_decode_attention import (MAX_VISIT_BLOCKS,
                                                     VISIT_BYTES,
                                                     latent_visit_blocks)

    assert VISIT_BYTES == 5 << 18 and MAX_VISIT_BLOCKS == 16
    for arena, want in (
            (jnp.zeros((5, 3, 1, 64, 640), jnp.bfloat16), 16),   # the cell's
            (jnp.zeros((3, 1, 128, 640), jnp.bfloat16), 8),      # a slab
            (jnp.zeros((3, 1, 256, 640), jnp.float32), 2),
            # A block over the rule's bytes is a step by itself.
            (jnp.zeros((3, 1, 2048, 640), jnp.bfloat16), 1)):
        block = math.prod(arena.shape[-2:]) * arena.dtype.itemsize
        assert latent_visit_blocks(arena) == want
        if want > 1:
            assert want * block <= VISIT_BYTES < (want + 1) * block
    # Small blocks meet the cap, not the bytes.
    assert latent_visit_blocks(jnp.zeros((3, 1, 8, 128),
                                         jnp.float32)) == MAX_VISIT_BLOCKS


def test_absorbed_form_is_the_expanded_form(model):
    """(c) One attention, two forms: a 24-token prompt prefilled whole
    (EXPANDED: per-head K and V from the latents) gives at its last
    position the logits that 23 tokens prefilled and one tick (ABSORBED:
    q through W_uk, the cache row as key and value, the output through
    W_uv) give."""
    config, params = model
    seq = _prompts((24,), seed=3)[0]
    blocks = jnp.arange(1, 5, dtype=jnp.int32)[None]

    def prefill(n):
        cache = LatentKVCache.create(config, 8, BS)
        tokens = jnp.zeros((1, 32), jnp.int32).at[0, :n].set(
            jnp.asarray(seq[:n]))
        logits, cache, _ = cb._prefill_chunk_paged(
            params, tokens, jnp.arange(32), cache, None, blocks[:, :0],
            blocks, jnp.asarray([n - 1]), None, config, False)
        return logits[0, 0], cache

    expanded, _ = prefill(24)
    _, cache = prefill(23)
    logits, _, _ = cb._forward_paged(
        params, jnp.asarray([[seq[23]]]), jnp.asarray([[23]]), blocks,
        jnp.asarray([32]), cache, config, False)
    want = reference.logits(params, seq, config)[-1]
    for got in (expanded, logits[0, 0]):
        assert np.max(np.abs(np.asarray(got) - np.asarray(want))) < (
            2e-4 * float(jnp.std(want)))


# ----------------------------------------------------------- the engine

LENGTHS = (5, 16, 37, 40)   # one call; ends on a block AND a chunk
# boundary; crosses two chunk boundaries; ends on a block boundary


@pytest.mark.parametrize("engine", [
    dict(use_decode_kernel=False), dict(use_decode_kernel=True)],
    ids=["kernels-off", "kernels-interpreted"])
def test_engine_tokens_are_the_references_argmax(model, engine,
                                                 pallas_interpret):
    """(b) Prefill (one call, and three chunks reading latents back out
    of the cache) then decode through the cache across block boundaries:
    every generated token is the reference's argmax."""
    config, params = model
    prompts = _prompts(LENGTHS)
    outs, eng = _serve(config, params, prompts, max_new=12,
                       prefix_cache=False, **engine)
    for prompt, out in zip(prompts, outs):
        assert out == _reference_tokens(params, config, prompt, out)
    assert isinstance(eng.cache, LatentKVCache) and eng.state is None
    assert eng.cache.k.shape == (LAYERS, 4 * 20 + 1, 1, BS, 128)


def test_chunked_prefill_then_ticks_give_the_references_logits(model):
    """(b) The engine's two forwards by hand: a 37-token prompt as three
    chunks of 16, then 12 teacher-forced ticks: the logits at the
    prompt's end and at every decoded position within 2e-4 of the
    reference's standard deviation."""
    config, params = model
    seq = _prompts((50,), seed=4)[0]
    n_prompt, slot, per = 37, 1, CHUNK // BS
    cache = LatentKVCache.create(config, 16, BS)
    blocks = jnp.arange(1, 9, dtype=jnp.int32)[None]         # 64 tokens
    for ci in range(3):
        part = seq[ci * CHUNK:min((ci + 1) * CHUNK, n_prompt)]
        tokens = jnp.zeros((1, CHUNK), jnp.int32).at[0, :len(part)].set(
            jnp.asarray(part))
        logits, cache, _ = cb._prefill_chunk_paged(
            params, tokens, ci * CHUNK + jnp.arange(CHUNK), cache, None,
            blocks[:, :ci * per], blocks[:, ci * per:(ci + 1) * per],
            jnp.asarray([len(part) - 1]), None, config, False)
    got = [np.asarray(logits[0, 0])]
    tables = jnp.zeros((2, 8), jnp.int32).at[slot].set(blocks[0])
    limits = jnp.asarray([0, 64], jnp.int32)
    for p in range(n_prompt, len(seq) - 1):
        tokens = jnp.zeros((2, 1), jnp.int32).at[slot, 0].set(seq[p])
        positions = jnp.zeros((2, 1), jnp.int32).at[slot, 0].set(p)
        logits, cache, rows = cb._forward_paged(
            params, tokens, positions, tables, limits, cache, config, False)
        got.append(np.asarray(logits[slot, 0]))
    # Routed layers x (held experts' counts + 2 rows' top 4 of 16).
    assert rows.shape == (2, 4 + 2 * 4)
    want = reference.logits(params, seq[:-1], config)[n_prompt - 1:]
    assert np.max(np.abs(np.stack(got) - np.asarray(want))) < 2e-4 * float(
        jnp.std(want))


def test_prefix_hit_is_the_cold_prefill(model):
    """(f) One table serves every layer, so the radix index works
    unchanged: a prompt whose first 32 tokens another request left in the
    cache prefills its tail alone (reading the shared rows as latents)
    and decodes the tokens the cold engine decodes."""
    config, params = model
    first, second = _prompts((45, 45), seed=6)
    second[:32] = first[:32]
    cold, _ = _serve(config, params, [second], max_new=8,
                     prefix_cache=False)
    eng = ContinuousBatcher(config, params=params, num_slots=4, max_len=160,
                            block_size=BS, prefill_chunk=CHUNK)
    assert eng.prefix_cache
    eng.submit(first, 4)
    eng.run_to_completion()
    rid = eng.submit(second, 8)
    warm = eng.run_to_completion()[rid]
    assert eng.prefix_hit_tokens == 32
    assert warm == cold[0]
    assert warm == _reference_tokens(params, config, second, warm)


def test_a_long_batch_routes_in_pieces_and_gives_the_same(model,
                                                          monkeypatch):
    """A held share's routed block over a batch whose sorted rows would
    pass ``ROUTED_SORT_BYTES`` runs a piece of the tokens at a time: the
    same outputs, counts and routes."""
    config, params = model
    run = jax.tree.map(lambda a: a[0], params["runs"][1])
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 64), jnp.float32)
    whole, routed = llama.mlp_block(h, run, config, params["layers"],
                                    jnp.int32(1))
    monkeypatch.setattr(llama, "ROUTED_SORT_BYTES", 16 * 4 * 64 * 4 // 2)
    assert llama._routed_pieces(config, 32, 64, jnp.float32) == 4
    pieces, routed4 = llama.mlp_block(h, run, config, params["layers"],
                                      jnp.int32(1))
    np.testing.assert_allclose(pieces, whole, atol=1e-6)
    assert np.array_equal(routed4.rows, routed.rows)
    assert np.array_equal(routed4.experts, routed.experts)
    # Every expert held: one piece, whatever the size.
    assert llama._routed_pieces(dataclasses.replace(
        config, experts_held=None), 1 << 20, 64, jnp.float32) == 1


def test_the_shares_and_the_shared_expert_once_are_the_layer():
    """(e) THE SHARE TEST: a routed layer's 16 experts held one each by
    16 "chips"; the routed parts the program's block computes for the
    shares, added up, and the shared expert counted ONCE, are the uncut
    reference's MLP for that layer."""
    c = tiny(experts_held=None)
    params = llama.init_params(c, jax.random.PRNGKey(4))
    run = jax.tree.map(lambda a: a[0], params["runs"][1])
    experts = jax.tree.map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(5), (24, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        h = reference._rms_norm(x, run["mlp_norm"], c.rms_eps)
        shared = reference._swiglu(h, run["shared_gate"], run["shared_up"],
                                   run["shared_down"])
        # The uncut reference layer, less its attention and residual.
        weights, chosen = reference._route(
            h, run["w_router"], run["expert_bias"], top_k=4,
            scale=c.route_scale)
        whole = shared
        for e in range(16):
            whole = whole + jnp.sum(jnp.where(chosen == e, weights, 0.0),
                                    -1)[:, None] * reference._swiglu(
                h, experts["moe_gate"][e], experts["moe_up"][e],
                experts["moe_down"][e])
        parts, local = 0.0, 0
        for first in range(16):
            share = dataclasses.replace(c, experts_held=(first, 1))
            held = {k: v[None, first:first + 1] for k, v in experts.items()}
            out, routed = llama.mlp_block(
                h[None], run, share, held, jnp.int32(c.num_dense_layers))
            parts = parts + (out[0] - shared)      # its routed part alone
            local += int(routed.rows.sum())
    assert local == 24 * 4          # every assignment on exactly one chip
    np.testing.assert_allclose(parts + shared, whole, atol=2e-5)


# ------------------------------------------------------------ refusals

@pytest.mark.parametrize("kwargs,named", [
    (dict(spec_k=2), "speculative"),
    (dict(role="prefill"), "role='prefill'"),
    (dict(role="decode"), "role='decode'"),
    (dict(kv_dtype="int8"), "kv_dtype='int8'"),
])
def test_refused_by_name_for_latent_layers(model, kwargs, named):
    """(g)"""
    config, params = model
    with pytest.raises(ValueError, match="latent-attention") as err:
        ContinuousBatcher(config, params=params, num_slots=2, max_len=64,
                          block_size=BS, **kwargs)
    assert named in str(err.value)


def test_a_mixed_stack_is_refused(model):
    config, _ = model
    mixed = dataclasses.replace(config, layer_types=(
        "latent_attention", "full_attention", "latent_attention"))
    with pytest.raises(ValueError, match="another layer kind"):
        ContinuousBatcher(mixed, num_slots=2, max_len=64, block_size=BS)


@pytest.mark.parametrize("call,arg", [
    ("export_kv_payload", 0), ("import_kv_payload", {}),
    ("score_logprobs", ([1, 2], [3]))])
def test_methods_are_refused_for_latent_layers(model, call, arg):
    config, params = model
    eng = ContinuousBatcher(config, params=params, num_slots=2, max_len=64,
                            block_size=BS)
    with pytest.raises(ValueError, match="latent-attention"):
        getattr(eng, call)(*(arg if isinstance(arg, tuple) else (arg,)))


def test_latent_and_expert_metrics_are_booked(model):
    from ray_tpu._private import metrics_defs as mdefs

    def total(metric, suffix=""):
        return sum(v for n, _, v in metric.samples()
                   if n == metric.name + suffix)

    config, params = model
    before = {end: total(mdefs.CB_MLA_LIVE_TOKENS, end)
              for end in ("_sum", "_count")}
    local = total(mdefs.CB_MOE_LOCAL_ASSIGNMENTS)
    outs, eng = _serve(config, params, _prompts((37,)), max_new=10)
    ticks = eng.base_tick_count
    assert total(mdefs.CB_MLA_LIVE_TOKENS, "_count") - before["_count"] \
        == ticks
    # One live slot: positions 37 .. 45 attended, each its position + 1.
    assert total(mdefs.CB_MLA_LIVE_TOKENS, "_sum") - before["_sum"] == sum(
        range(38, 38 + ticks))
    assert total(mdefs.CB_MOE_LOCAL_ASSIGNMENTS) > local
    assert eng.cache.nbytes == LAYERS * (4 * 20 + 1) * BS * 128 * 4
    assert eng.cache.nbytes in [
        v for _, _, v in mdefs.CB_LATENT_KV_BYTES.samples()]
