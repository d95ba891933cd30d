"""The Qwen3-Next family (Gated DeltaNet linear-attention layers beside
gated full attention with a quarter-rotary rope and zero-centred norms,
a softmax router over 8 experts with a sigmoid-gated shared expert) on
the CPU at small sizes, on seeded weights: the float32 reference against
``transformers``' ``qwen3_next`` (the whole model, uncut); the engine
(chunked prefill that carries state and conv tail, the state cache, the
in-place tick, refusals) against the reference; and the pieces (the
chunked scan, the tick kernel, padding, the shares) against plain
formulas.

Tolerances. float32 against float32: both sides hold the same numbers
and differ in operation order (the chunked scan solves a triangular
system where the reference loops over tokens), so logits within 2e-4 of
their standard deviation, and the engine's tokens are the reference's
ARGMAX at every generated position. Against ``transformers`` (torch on
the CPU, float32): 5e-5 of the logits' standard deviation.
"""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import reference_qwen3_next as reference  # noqa: E402
from ray_tpu.models import continuous_batching as cb  # noqa: E402
from ray_tpu.models import gated_delta, llama  # noqa: E402
from ray_tpu.models.continuous_batching import ContinuousBatcher  # noqa: E402
from ray_tpu.models.paged_kv import PagedKVCache, StateCache  # noqa: E402
from ray_tpu.ops import gated_delta as gdn  # noqa: E402

BS, CHUNK = 8, 16
PATTERN = ("linear_attention",) * 3 + ("full_attention",)
F32 = jnp.float32


def tiny(**kw):
    return llama.LlamaConfig.qwen3_next_80b_a3b(**{**dict(
        vocab_size=256, hidden_size=64, intermediate_size=32, num_layers=8,
        layer_types=PATTERN * 2, num_heads=4, num_kv_heads=2, head_dim=32,
        linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=16, linear_value_head_dim=24, num_experts=8,
        num_experts_per_tok=2, shared_intermediate_size=48, max_seq_len=256,
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


def _close(got, want, rel=2e-4):
    want = np.asarray(want)
    assert np.max(np.abs(np.asarray(got) - want)) < rel * float(want.std())


# ----------------------------------------------------------- the model

def test_runs_cache_kinds_and_param_count():
    c = tiny()
    assert llama.layer_runs(c) == [
        ("linear_attention", 0, 3, 0), ("full_attention", 3, 1, 0),
        ("linear_attention", 4, 3, 3), ("full_attention", 7, 1, 1)]
    assert (c.state_layers, c.attn_layers, c.moe_layers) == (6, 2, 8)
    assert c.rotary_dim == 8
    whole = llama.LlamaConfig.qwen3_next_80b_a3b()
    assert whole.layer_types[:8] == PATTERN * 2
    assert (whole.state_layers, whole.attn_layers) == (36, 12)
    assert (whole.rotary_dim, whole.attn_scale) == (64, 256 ** -0.5)
    assert gated_delta.dims(whole) == (2048, 4096, 8192)
    assert gated_delta.state_shapes(whole) == ((32, 128, 128), (3, 8192))
    # The share ISSUE 38 sizes: two periods holding 64 of 512 experts and
    # an eighth of the vocabulary: 1,978.8M parameters.
    share = dataclasses.replace(
        whole, num_layers=8, layer_types=whole.layer_types[:8],
        experts_held=(0, 64), vocab_size=18992)
    assert abs(llama.num_params(share) / 1e6 - 1978.8) < 0.1
    with pytest.raises(ValueError, match="unknown layer type"):
        llama.layer_runs(dataclasses.replace(
            c, layer_types=("linear",) * 8))


def test_training_forward_refuses_the_family(model):
    config, params = model
    with pytest.raises(NotImplementedError, match="linear-attention"):
        llama.forward(params, jnp.zeros((1, 8), jnp.int32), config)
    with pytest.raises(NotImplementedError, match="linear-attention"):
        llama.loss_fn(params, {"tokens": jnp.zeros((1, 8), jnp.int32)},
                      config)


def test_zero_centred_norm_and_partial_rope():
    c = tiny()
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 64), F32)
    w = jax.random.uniform(jax.random.PRNGKey(1), (64,), F32, -0.5, 0.5)
    np.testing.assert_allclose(llama.norm(x, w, c),
                               reference._rms0(x, w, c.rms_eps), atol=1e-6)
    q = jax.random.normal(jax.random.PRNGKey(2), (1, 5, 4, 32), F32)
    cos, sin = cb._rope_tables(c, 0, jnp.arange(5)[None])
    assert cos.shape == (1, 5, 4)
    got = cb._rotate(q, cos, sin)
    np.testing.assert_allclose(
        got[0], reference._rope(q[0], c.rope_theta, 8), atol=1e-5)
    assert np.array_equal(got[..., 8:], q[..., 8:])


# ------------------------------------------- against transformers

def _to_qwen3_next(params, c):
    """The seeded tree as ``Qwen3NextForCausalLM``'s state dict: the
    checkpoint's layout, the linear mixer's in-projections interleaved a
    key head (q, k, v x share, z x share; b x share, a x share) and the
    attention's query and gate interleaved a head."""
    import torch

    def t(a):
        return torch.tensor(np.asarray(a, np.float32))

    hk, hv = c.linear_num_key_heads, c.linear_num_value_heads
    dk, dv = c.linear_key_head_dim, c.linear_value_head_dim
    share, kd, vd = hv // hk, hk * dk, hv * dv
    out = {"model.embed_tokens.weight": t(params["embed"]),
           "model.norm.weight": t(params["final_norm"]),
           "lm_head.weight": t(params["lm_head"]).T}
    layer = 0
    for run in params["runs"]:
        for i in range(run["attn_norm"].shape[0]):
            w = jax.tree.map(lambda a, i=i: np.asarray(a[i], np.float32), run)
            pre = f"model.layers.{layer}."
            out[pre + "input_layernorm.weight"] = t(w["attn_norm"])
            out[pre + "post_attention_layernorm.weight"] = t(w["mlp_norm"])
            if "gdn_in" in w:
                e = w["gdn_in"].shape[0]
                qkvz = np.concatenate([
                    w["gdn_in"][:, :kd].reshape(e, hk, dk),
                    w["gdn_in"][:, kd:2 * kd].reshape(e, hk, dk),
                    w["gdn_in"][:, 2 * kd:2 * kd + vd].reshape(
                        e, hk, share * dv),
                    w["gdn_in"][:, 2 * kd + vd:].reshape(e, hk, share * dv),
                ], -1).reshape(e, -1)
                ba = np.concatenate([
                    w["gdn_ba"][:, :hv].reshape(e, hk, share),
                    w["gdn_ba"][:, hv:].reshape(e, hk, share)],
                    -1).reshape(e, -1)
                p = pre + "linear_attn."
                out[p + "in_proj_qkvz.weight"] = t(qkvz).T
                out[p + "in_proj_ba.weight"] = t(ba).T
                out[p + "conv1d.weight"] = t(w["conv_w"]).T[:, None, :]
                out[p + "dt_bias"] = t(w["dt_bias"])
                out[p + "A_log"] = t(w["a_log"])
                out[p + "norm.weight"] = t(w["gdn_norm"])
                out[p + "out_proj.weight"] = t(w["gdn_out"]).T
            else:
                e, h, d = w["wq"].shape
                p = pre + "self_attn."
                out[p + "q_proj.weight"] = t(np.concatenate(
                    [w["wq"], w["wg"]], -1).reshape(e, h * 2 * d)).T
                out[p + "k_proj.weight"] = t(w["wk"].reshape(e, -1)).T
                out[p + "v_proj.weight"] = t(w["wv"].reshape(e, -1)).T
                out[p + "o_proj.weight"] = t(w["wo"].reshape(-1, e)).T
                out[p + "q_norm.weight"] = t(w["q_norm"])
                out[p + "k_norm.weight"] = t(w["k_norm"])
            p = pre + "mlp."
            out[p + "gate.weight"] = t(w["w_router"]).T
            for ours, theirs in (("shared_gate", "gate_proj"),
                                 ("shared_up", "up_proj"),
                                 ("shared_down", "down_proj")):
                out[p + f"shared_expert.{theirs}.weight"] = t(w[ours]).T
            out[p + "shared_expert_gate.weight"] = t(w["shared_gate_w"])[None]
            for x in range(c.num_experts):
                for ours, theirs in (("moe_gate", "gate_proj"),
                                     ("moe_up", "up_proj"),
                                     ("moe_down", "down_proj")):
                    out[p + f"experts.{x}.{theirs}.weight"] = t(
                        params["layers"][ours][layer, x]).T
            layer += 1
    return out


def test_reference_is_transformers_qwen3_next(model):
    """(a) The plain reference, on seeded weights copied across, gives
    ``Qwen3NextForCausalLM``'s logits (pure-torch path; the WHOLE small
    model: both mixers, the conv, the gates, the router, the shared
    expert's gate, zero-centred norms, partial rope)."""
    torch = pytest.importorskip("torch")
    from transformers import Qwen3NextConfig, Qwen3NextForCausalLM

    c, params = model
    net = Qwen3NextForCausalLM(Qwen3NextConfig(
        vocab_size=c.vocab_size, hidden_size=c.hidden_size,
        intermediate_size=4 * c.hidden_size,
        num_hidden_layers=c.num_layers, num_attention_heads=c.num_heads,
        num_key_value_heads=c.num_kv_heads, head_dim=c.head_dim,
        hidden_act="silu", max_position_embeddings=c.max_seq_len,
        rms_norm_eps=c.rms_eps, tie_word_embeddings=False,
        rope_theta=c.rope_theta, rope_scaling=None,
        partial_rotary_factor=c.partial_rotary_factor, attention_bias=False,
        linear_conv_kernel_dim=c.linear_conv_kernel_dim,
        linear_key_head_dim=c.linear_key_head_dim,
        linear_value_head_dim=c.linear_value_head_dim,
        linear_num_key_heads=c.linear_num_key_heads,
        linear_num_value_heads=c.linear_num_value_heads,
        decoder_sparse_step=1, moe_intermediate_size=c.intermediate_size,
        shared_expert_intermediate_size=c.shared_intermediate_size,
        num_experts_per_tok=c.num_experts_per_tok,
        num_experts=c.num_experts, norm_topk_prob=True, mlp_only_layers=[],
        layer_types=list(c.layer_types), attn_implementation="eager"))
    net = net.float().eval()
    net.load_state_dict(_to_qwen3_next(params, c), strict=True)
    tokens = _prompts((75,), seed=2)[0]     # past one torch chunk of 64
    with torch.no_grad():
        want = net(torch.tensor([tokens])).logits[0].numpy()
    got = np.asarray(reference.logits(params, tokens, c))
    assert np.max(np.abs(got - want)) < 5e-5 * want.std()


# -------------------------------------------------------------- pieces

def _rule_inputs(bsz, s, h, dk, dv, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = gated_delta._l2norm(jax.random.normal(k[0], (bsz, s, h, dk)))
    kk = gated_delta._l2norm(jax.random.normal(k[1], (bsz, s, h, dk)))
    v = jax.random.normal(k[2], (bsz, s, h, dv))
    g = -0.3 * jax.nn.softplus(jax.random.normal(k[3], (bsz, s, h)))
    beta = jax.nn.sigmoid(jax.random.normal(k[4], (bsz, s, h)))
    state = jax.random.normal(k[5], (bsz, h, dk, dv))
    return q * dk ** -0.5, kk, v, g, beta, state


def _token_loop(q, k, v, g, beta, state):
    outs = []
    for t in range(q.shape[1]):
        o, state = gdn.gdn_step_reference(state, q[:, t], k[:, t], v[:, t],
                                          g[:, t], beta[:, t])
        outs.append(o)
    return jnp.stack(outs, 1), state


@pytest.mark.parametrize("real,chunk", [(48, 16), (40, 16), (37, 64),
                                        (48, 48)])
def test_chunked_scan_is_the_token_recurrence(real, chunk):
    """(c) The chunked scan from a carried state, over 48 positions of
    which ``real`` are real (the rest ``g = beta = 0``), at chunk
    lengths that do and do not divide the real length: the per-token
    recurrence's outputs and final state."""
    q, k, v, g, beta, state = _rule_inputs(2, 48, 3, 16, 24)
    live = (jnp.arange(48) < real)[None, :, None]
    g, beta = jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0)
    with jax.default_matmul_precision("highest"):
        want_o, want_s = _token_loop(*(a[:, :real] for a in (q, k, v, g,
                                                            beta)), state)
        o, s = gdn.gdn_chunked_scan(q, k, v, g, beta, state, chunk=chunk)
    np.testing.assert_allclose(o[:, :real], want_o, atol=2e-5)
    np.testing.assert_allclose(s, want_s, atol=2e-5)
    with pytest.raises(ValueError, match="multiple of chunk"):
        gdn.gdn_chunked_scan(q, k, v, g, beta, state, chunk=36)


def _scan_inputs(rows, s, real, carried, seed=7):
    """The kernel's shapes: 4 value heads over 2 key heads at 128 x 128,
    ``real`` positions of ``s`` live (the rest ``g = beta = 0``)."""
    q, k, v, g, beta, state = _rule_inputs(rows, s, 4, 128, 128, seed=seed)
    live = (jnp.arange(s) < real)[None, :, None]
    return (q[:, :, ::2], k[:, :, ::2], v, jnp.where(live, g, 0.0),
            jnp.where(live, beta, 0.0), state if carried else None)


# (positions, real): every position real; a right-padded row whose last
# chunk is all padding; a row of ONE real token.
@pytest.mark.parametrize("s,real", [(128, 128), (192, 100), (128, 1)])
@pytest.mark.parametrize("carried", [True, False], ids=["carried", "fresh"])
@pytest.mark.parametrize("rows", [1, 3])
def test_chunk_scan_kernel_is_the_scan(rows, carried, s, real,
                                       pallas_interpret):
    """The prefill's kernel (interpreted) at the published head shape,
    value heads reading their KEY head's q and k: the ``jax.numpy``
    body's outputs and final state, and the per-token recurrence's."""
    q, k, v, g, beta, state = _scan_inputs(rows, s, real, carried)
    with jax.default_matmul_precision("highest"):
        o, new = gdn.gdn_chunked_scan(q, k, v, g, beta, state, chunk=64,
                                      use_kernel=True)
        want_o, want = gdn.gdn_chunked_scan(q, k, v, g, beta, state,
                                            chunk=64, use_kernel=False)
        zeros = jnp.zeros((rows, 4, 128, 128))
        loop_o, loop = _token_loop(*(
            a[:, :real] for a in (jnp.repeat(q, 2, 2), jnp.repeat(k, 2, 2),
                                  v, g, beta)), zeros if state is None
            else state)
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    np.testing.assert_allclose(new, want, atol=2e-5)
    np.testing.assert_allclose(o[:, :real], loop_o, atol=2e-5)
    np.testing.assert_allclose(new, loop, atol=2e-5)


def test_chunk_scan_kernel_rounds_what_the_scan_rounds(pallas_interpret):
    """At the model's dtype: kernel and ``jax.numpy`` body round the same
    operands to bfloat16, so they part by no more than one bfloat16 ulp
    of the products' operands (a float32 last bit that the two solves'
    orders of addition leave different can flip one rounding)."""
    q, k, v, g, beta, state = _scan_inputs(2, 128, 128, True, seed=8)
    v = v.astype(jnp.bfloat16)
    got = gdn.gdn_chunked_scan(q, k, v, g, beta, state, chunk=64,
                               dtype=jnp.bfloat16, use_kernel=True)
    want = gdn.gdn_chunked_scan(q, k, v, g, beta, state, chunk=64,
                                dtype=jnp.bfloat16, use_kernel=False)
    for a, b in zip(got, want):
        a, b = np.asarray(a), np.asarray(b)
        assert np.max(np.abs(a - b)) <= 2.0 ** -8 * np.max(np.abs(b))
        assert np.mean(np.abs(a - b) <= 1e-5) > 0.9


def test_chunk_scan_kernel_is_taken_where_the_shapes_tile(pallas_interpret):
    """The shape rule beside ``gdn_applicable``: the published 32 value
    heads over 16 key heads at 128 x 128 in chunks of 64 take the kernel,
    four value heads a grid step; the rehearsal's 16-wide heads, a
    chunk that is no whole diagonal block, and value heads that do not
    share key heads evenly do not, and are served by the ``jax.numpy``
    body whatever ``use_kernel`` says."""
    assert gdn.gdn_scan_applicable(32, 16, 128, 128, 64)
    assert gdn._scan_head_block(32, 16) == 4
    assert gdn._scan_head_block(4, 2) == 4 and gdn._scan_head_block(6, 2) == 3
    assert not gdn.gdn_scan_applicable(4, 2, 16, 16, 16)     # the rehearsal
    assert not gdn.gdn_scan_applicable(32, 16, 128, 128, 8)
    assert not gdn.gdn_scan_applicable(32, 12, 128, 128, 64)
    q, k, v, g, beta, state = _rule_inputs(2, 32, 4, 16, 16, seed=9)
    q, k = q[:, :, ::2], k[:, :, ::2]
    got = gdn.gdn_chunked_scan(q, k, v, g, beta, state, chunk=16,
                               use_kernel=True)
    want = gdn.gdn_chunked_scan(jnp.repeat(q, 2, 2), jnp.repeat(k, 2, 2), v,
                                g, beta, state, chunk=16, use_kernel=False)
    for a, b in zip(got, want):
        assert np.array_equal(a, b)


@pytest.mark.parametrize("layer", [0, 2])
def test_gdn_step_kernel_is_the_step_in_place(layer, pallas_interpret):
    """(d) The tick's kernel (interpreted) at the published head shape:
    the ``jax.numpy`` step's outputs and state, written at ``layer`` of
    the whole cache, every other layer untouched."""
    q, k, v, g, beta, _ = _rule_inputs(3, 1, 16, 128, 128, seed=1)
    q, k, v, g, beta = (a[:, 0] for a in (q, k, v, g, beta))
    cache = jax.random.normal(jax.random.PRNGKey(2), (3, 3, 16, 128, 128))
    assert gdn.gdn_applicable(32, 128, 128) and gdn._head_block(32) == 16
    assert not gdn.gdn_applicable(4, 16, 24)
    step = jax.jit(lambda c, ly, kern: gdn.gdn_step(
        c, ly, q, k, v, g, beta, use_kernel=kern), static_argnums=2)
    o, new = step(cache, jnp.int32(layer), True)
    want_o, want = step(cache, jnp.int32(layer), False)
    np.testing.assert_allclose(o, want_o, atol=1e-5)
    np.testing.assert_allclose(new, want, atol=1e-5)
    for other in set(range(3)) - {layer}:
        assert np.array_equal(new[other], cache[other])
    assert not np.array_equal(new[layer], cache[layer])


@pytest.fixture(scope="module")
def wide_mixer():
    """One linear layer's mixer at a 128-wide head: the shape the
    prefill's kernel takes."""
    config = tiny(linear_key_head_dim=128, linear_value_head_dim=128)
    return config, jax.tree.map(
        lambda a: a[0], gated_delta.init_mixer(config, jax.random.PRNGKey(6),
                                               1))


@pytest.fixture(params=["jax.numpy", "kernel"])
def scan_path(request, model, wide_mixer, pallas_interpret, monkeypatch):
    """(config, layer): the tiny model's mixer through the ``jax.numpy``
    scan, and a 128-wide one through the interpreted ``gdn_chunk_scan``
    kernel (what the TPU's dispatch takes at that width)."""
    if request.param == "kernel":
        monkeypatch.setattr(gdn, "gdn_chunked_scan", functools.partial(
            gdn.gdn_chunked_scan, use_kernel=True))
        return wide_mixer
    config, params = model
    return config, jax.tree.map(lambda a: a[1], params["runs"][0])


def test_a_padded_row_keeps_its_last_real_tokens_state_and_tail(scan_path):
    """(f) The mixer over a right-padded row: the state and the conv
    tail it returns are the unpadded row's, after the last REAL token,
    and a row shorter than the tail is zeros in front."""
    config, layer = scan_path
    h = jax.random.normal(jax.random.PRNGKey(3), (3, 32, 64), F32)
    lengths = jnp.asarray([32, 21, 2])
    with jax.default_matmul_precision("highest"):
        out, state, tail = gated_delta.mixer_prefill(
            h, layer, config, lengths)
        for row, n in enumerate((32, 21, 2)):
            want = gated_delta.mixer_prefill(
                h[row:row + 1, :n], layer, config, jnp.asarray([n]))
            np.testing.assert_allclose(out[row, :n], want[0][0], atol=2e-5)
            np.testing.assert_allclose(state[row], want[1][0], atol=2e-5)
            np.testing.assert_allclose(tail[row], want[2][0], atol=1e-6)
    assert not np.asarray(tail[2, 0]).any() and np.asarray(tail[2, 1]).any()


def test_a_chunk_goes_on_from_the_carried_state_and_tail(scan_path):
    """The mixer over a row in two and three pieces, each handed the
    state and conv tail the piece before it returned (the last piece
    padded), is the mixer over the row in one piece: the hand-off
    between ``cb_prefill`` calls, on both scan paths."""
    config, layer = scan_path
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 48, 64), F32)
    with jax.default_matmul_precision("highest"):
        want = gated_delta.mixer_prefill(h[:, :41], layer, config,
                                         jnp.asarray([41, 41]))
        for piece in (16, 32):
            carried, outs = None, []
            for at in range(0, 48, piece):
                n = min(41 - at, piece)
                out, *carried = gated_delta.mixer_prefill(
                    h[:, at:at + piece], layer, config, jnp.asarray([n, n]),
                    carried)
                outs.append(out[:, :n])
            np.testing.assert_allclose(jnp.concatenate(outs, 1), want[0],
                                       atol=2e-5)
            np.testing.assert_allclose(carried[0], want[1], atol=2e-5)
            np.testing.assert_allclose(carried[1], want[2], atol=1e-6)


def test_the_step_is_one_more_token_of_the_prefill(model, pallas_interpret):
    """Prefill 20 tokens, then one tick through the state cache at a
    layer index (kernel interpreted and not): the prefill of 21."""
    config, params = model
    layer = jax.tree.map(lambda a: a[2], params["runs"][0])
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 21, 64), F32)
    with jax.default_matmul_precision("highest"):
        want = gated_delta.mixer_prefill(h, layer, config,
                                         jnp.asarray([21, 21]))
        _, state, tail = gated_delta.mixer_prefill(
            h[:, :20], layer, config, jnp.asarray([20, 20]))
        cache = StateCache.create(config, 2)
        held = (cache.ssm.at[4].set(state), cache.conv.at[4].set(tail))
        for kernel in (False, True):
            out, ssm, conv = gated_delta.mixer_step(
                h[:, 20:], layer, config, *held, jnp.int32(4), kernel)
            np.testing.assert_allclose(out[:, 0], want[0][:, 20], atol=2e-5)
            np.testing.assert_allclose(ssm[4], want[1], atol=2e-5)
            np.testing.assert_allclose(conv[4], want[2], atol=1e-6)
            assert not np.asarray(ssm[3]).any()


# ----------------------------------------------------------- the engine

LENGTHS = (5, 16, 37, 40)   # one call; ends on a block AND a chunk
# boundary; crosses two chunk boundaries; ends on a block boundary


@pytest.mark.parametrize("engine", [
    dict(use_decode_kernel=False), dict(use_decode_kernel=True)],
    ids=["kernels-off", "kernels-interpreted"])
def test_engine_tokens_are_the_references_argmax(model, engine,
                                                 pallas_interpret):
    """(b) Prefill (one call, and three chunks that carry state and conv
    tail through the state cache) then decode through both caches across
    block boundaries: every generated token is the reference's argmax."""
    config, params = model
    prompts = _prompts(LENGTHS)
    outs, eng = _serve(config, params, prompts, max_new=12, **engine)
    for prompt, out in zip(prompts, outs):
        assert out == _reference_tokens(params, config, prompt, out)
    assert isinstance(eng.cache, PagedKVCache)
    assert isinstance(eng.state, StateCache) and not eng.prefix_cache
    assert eng.state.ssm.shape == (6, 4, 4, 16, 24)
    assert eng.state.conv.shape == (6, 4, 3, 2 * 32 + 96)
    assert eng.cache.k.shape[0] == 2           # the full layers alone
    # 37 and 40 tokens are three chunks each: two carries a prompt.
    assert (eng.state_installs, eng.state_carries) == (4, 4)
    assert eng.prefill_chunk == CHUNK


@pytest.mark.parametrize("chunk", [16, 32])
def test_a_chunked_prefill_is_the_one_piece_prefill(model, chunk):
    """(c) A 2- and a 3-chunk prefill give the tokens the one-piece
    prefill gives (``prefill_chunk`` 64 holds every prompt)."""
    config, params = model
    prompts = _prompts((47, 33, 64, 20), seed=5)
    want, whole = _serve(config, params, prompts, prefill_chunk=64)
    got, eng = _serve(config, params, prompts, prefill_chunk=chunk)
    assert got == want and whole.state_carries == 0
    assert eng.state_carries == sum(-(-len(p) // chunk) - 1
                                    for p in prompts)


def test_chunked_prefill_then_ticks_give_the_references_logits(model):
    """(b) The engine's two forwards by hand: a 37-token prompt as three
    chunks of 16 into slot 1 (state and conv tail carried through the
    state cache), then 12 teacher-forced ticks: the logits at the
    prompt's end and at every decoded position within 2e-4 of the
    reference's standard deviation."""
    config, params = model
    params = llama.heads_major(params)
    seq = _prompts((50,), seed=4)[0]
    n_prompt, slot, per = 37, 1, CHUNK // BS
    cache = PagedKVCache.create(config, 16, BS, "bf16")
    state = StateCache.create(config, 2)
    blocks = jnp.arange(1, 9, dtype=jnp.int32)[None]         # 64 tokens
    for ci in range(3):
        part = seq[ci * CHUNK:min((ci + 1) * CHUNK, n_prompt)]
        tokens = jnp.zeros((1, CHUNK), jnp.int32).at[0, :len(part)].set(
            jnp.asarray(part))
        logits, cache, state = cb._prefill_chunk_paged(
            params, tokens, ci * CHUNK + jnp.arange(CHUNK), cache, state,
            blocks[:, :ci * per], blocks[:, ci * per:(ci + 1) * per],
            jnp.asarray([len(part) - 1]), jnp.asarray([slot]), config, False)
    assert not np.asarray(state.ssm[:, 0]).any()
    got = [np.asarray(logits[0, 0])]
    tables = jnp.zeros((2, 8), jnp.int32).at[slot].set(blocks[0])
    limits = jnp.asarray([0, 64], jnp.int32)
    for p in range(n_prompt, len(seq) - 1):
        tokens = jnp.zeros((2, 1), jnp.int32).at[slot, 0].set(seq[p])
        positions = jnp.zeros((2, 1), jnp.int32).at[slot, 0].set(p)
        logits, (cache, state), rows = cb._forward_paged(
            params, tokens, positions, tables, limits, (cache, state),
            config, False)
        got.append(np.asarray(logits[slot, 0]))
    assert rows.shape == (8, 8)             # layers x experts' row counts
    want = reference.logits(params_canonical(params), seq[:-1],
                            config)[n_prompt - 1:]
    assert len(got) == len(want) == 13
    for g, w in zip(got, want):
        _close(g, w)


def params_canonical(params):
    return llama.canonical_layout(params)


def test_reset_clears_the_state_cache(model):
    config, params = model
    prompt = _prompts((20,))[0]
    eng = ContinuousBatcher(config, params=params, num_slots=2, max_len=64,
                            block_size=BS, prefill_chunk=CHUNK)
    first = eng.submit(prompt, 4)
    want = eng.run_to_completion()[first]
    eng.submit(prompt, 4)
    eng.step()
    eng.reset()
    assert not np.asarray(eng.state.ssm).any()
    again = eng.submit(prompt, 4)
    assert eng.run_to_completion()[again] == want


# ------------------------------------------------------------ the share

def test_the_shares_and_the_gated_shared_expert_once_are_the_layer():
    """(e) THE SHARE TEST: a layer's 8 experts held one each by 8
    "chips"; the routed parts the program's block computes for the
    shares, added up, and the GATED shared expert counted ONCE, are the
    uncut reference's MLP for that layer."""
    c = tiny()
    params = llama.init_params(c, jax.random.PRNGKey(4))
    run = jax.tree.map(lambda a: a[0], params["runs"][1])
    experts = jax.tree.map(lambda a: a[3], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(5), (24, 64), F32)
    with jax.default_matmul_precision("highest"):
        h = reference._rms0(x, run["mlp_norm"], c.rms_eps)
        shared = jax.nn.sigmoid(h @ run["shared_gate_w"])[:, None] * (
            reference._swiglu(h, run["shared_gate"], run["shared_up"],
                              run["shared_down"]))
        # The uncut reference layer, less its mixer and residual.
        weights, chosen = reference._route(h, run["w_router"], top_k=2)
        whole = shared
        for e in range(8):
            whole = whole + jnp.sum(jnp.where(chosen == e, weights, 0.0),
                                    -1)[:, None] * reference._swiglu(
                h, experts["moe_gate"][e], experts["moe_up"][e],
                experts["moe_down"][e])
        ungated, _ = llama.mlp_block(
            h[None], run, dataclasses.replace(c, shared_expert_gate=False),
            jax.tree.map(lambda a: a[None], experts), jnp.int32(0))
        parts, local = 0.0, 0
        for first in range(8):
            share = dataclasses.replace(c, experts_held=(first, 1))
            held = {k: v[None, first:first + 1] for k, v in experts.items()}
            out, routed = llama.mlp_block(h[None], run, share, held,
                                          jnp.int32(0))
            parts = parts + (out[0] - shared)      # its routed part alone
            local += int(routed.rows.sum())
    assert local == 24 * 2          # every assignment on exactly one chip
    np.testing.assert_allclose(parts + shared, whole, atol=2e-5)
    assert np.max(np.abs(ungated[0] - whole)) > 1e-2    # the gate shows


# ------------------------------------------------------------ refusals

@pytest.mark.parametrize("kwargs,named", [
    (dict(spec_k=2), "speculative"),
    (dict(prefix_cache=True), "prefix cache"),
    (dict(role="prefill"), "role='prefill'"),
    (dict(role="decode"), "role='decode'"),
])
def test_refused_by_name_for_linear_attention_layers(model, kwargs, named):
    """(g) What rests on rewinding, sharing or shipping K/V alone is
    refused, and the refusal names the kind it met."""
    config, params = model
    with pytest.raises(ValueError, match="linear-attention") as err:
        ContinuousBatcher(config, params=params, num_slots=2, max_len=64,
                          block_size=BS, **kwargs)
    assert named in str(err.value)
    assert "'linear_attention'" in str(err.value)


def test_a_second_recurrent_kind_is_refused(model):
    config, _ = model
    mixed = dataclasses.replace(config, layer_types=(
        "mamba",) + config.layer_types[1:])
    with pytest.raises(ValueError, match="second kind of recurrent"):
        ContinuousBatcher(mixed, num_slots=2, max_len=64, block_size=BS)


@pytest.mark.parametrize("call", ["export_kv_payload", "import_kv_payload"])
def test_kv_handoff_is_refused_for_linear_attention_layers(model, call):
    config, params = model
    eng = ContinuousBatcher(config, params=params, num_slots=2, max_len=64,
                            block_size=BS)
    with pytest.raises(ValueError, match="no recurrent state"):
        getattr(eng, call)(0 if call.startswith("export") else {})


# ------------------------------------------------------------ the series

def test_state_series_are_booked(model):
    from ray_tpu._private import metrics_defs as mdefs

    def total(metric, suffix=""):
        return sum(v for n, _, v in metric.samples()
                   if n == metric.name + suffix)

    config, params = model
    before = {end: total(mdefs.CB_STATE_LIVE_SLOTS, end)
              for end in ("_sum", "_count")}
    carries = total(mdefs.CB_PREFILL_STATE_CARRIES)
    installs = total(mdefs.CB_STATE_INSTALLS)
    chunks = total(mdefs.CB_PREFILL_CHUNK_MS, "_count")
    local = total(mdefs.CB_MOE_ASSIGNMENTS)
    outs, eng = _serve(config, params, _prompts((37, 12)), max_new=10)
    ticks = eng.base_tick_count
    assert total(mdefs.CB_PREFILL_STATE_CARRIES) - carries == 2
    assert total(mdefs.CB_STATE_INSTALLS) - installs == 2
    # Two batches (a 3-chunk prompt, a 1-call prompt): 4 program calls.
    assert total(mdefs.CB_PREFILL_CHUNK_MS, "_count") - chunks == 4
    assert total(mdefs.CB_STATE_LIVE_SLOTS, "_count") - before["_count"] \
        == ticks
    assert total(mdefs.CB_STATE_LIVE_SLOTS, "_sum") - before["_sum"] \
        == 2 * ticks
    assert total(mdefs.CB_MOE_ASSIGNMENTS) > local
    want = 6 * 4 * (4 * 16 * 24 + 3 * 160) * 4
    assert eng.state.nbytes == want
    assert want in [v for _, _, v in mdefs.CB_STATE_CACHE_BYTES.samples()]
    assert eng.pressure_snapshot()["state_cache_bytes"] == want


# ------------------------------------------------- the solve inside a chunk

@pytest.mark.parametrize("q", [8, 16, 32, 64, 48])
def test_blocked_unit_lower_solve_is_the_triangular_solve(q):
    """Forward substitution inside 16-row diagonal blocks, merged
    pairwise (48 = three blocks, padded to four with identity rows): the
    triangular solve, also where a chunk's keys are ALIKE and a product
    of powers of the strict part would cancel catastrophically."""
    from ray_tpu.ops import gated_delta as ops

    k = jax.random.split(jax.random.PRNGKey(q), 2)
    strict = jnp.tril(jax.random.normal(k[0], (2, 3, q, q)), -1)
    alike = jnp.broadcast_to(0.9 * jnp.tril(jnp.ones((q, q)), -1),
                             (1, 3, q, q))
    rhs = jax.random.normal(k[1], (3, 3, q, 24))
    system = jnp.concatenate([strict, alike]) + jnp.eye(q)
    want = np.linalg.solve(np.asarray(system, np.float64),
                           np.asarray(rhs, np.float64))
    got = np.asarray(ops._solve_unit_lower(system, rhs))
    assert np.max(np.abs(got - want)) <= 2e-5 * np.max(np.abs(want))


# ------------------------------------------- places for a wide engine's streams

@pytest.mark.parametrize("slots,places", [(8, 100), (48, 100), (96, 100),
                                          (97, 194), (256, 512)])
def test_a_wide_engines_replica_has_places_for_its_streams(slots, places):
    """A stream holds one of the replica's ``max_ongoing_requests``
    places while it holds or waits for a slot: the default of 100 up to
    96 slots (those engines keep what they had), twice the slots
    beyond."""
    from ray_tpu import llm

    assert llm._ongoing_for(slots) == places
    app = llm.build_continuous_llama_app(num_slots=slots)
    assert app.deployment.max_ongoing_requests == places
