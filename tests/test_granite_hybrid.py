"""The Granite 4.0-H family (Mamba-2 layers beside attention layers in
one stack, 8 experts top 2 beside a shared SwiGLU, tied scaled head) on
the CPU at small sizes: the plain reference against ``transformers``'
own implementation, the scan and the tick's kernel against the plain
recurrence, and the engine (prefill, state cache, ticks, refusals)
against the reference, on seeded weights.

Tolerances. float32 against float32: both sides hold the same numbers
and differ only in operation order, so 1e-4 of the logits' standard
deviation for the reference against ``transformers``, 1e-4 absolute on
O(1) states and outputs for the scan and the kernel. The engine is held
to the reference's ARGMAX at every generated position: the seeded head
is the embedding at a scale where a token's own logit does not win by
itself (``llama._init_hybrid_params``), so the chosen tokens move when a
layer is wrong.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import reference_granite_hybrid as reference  # noqa: E402
from ray_tpu.models import llama, mamba2  # noqa: E402
from ray_tpu.models.continuous_batching import ContinuousBatcher  # noqa: E402
from ray_tpu.models.paged_kv import StateCache  # noqa: E402
from ray_tpu.ops import ssm  # noqa: E402

TYPES = ("mamba", "mamba", "attention", "mamba")


def tiny(**kw):
    return llama.LlamaConfig.granite_4_0_h_small(**{**dict(
        vocab_size=256, hidden_size=64, intermediate_size=32, num_layers=4,
        layer_types=TYPES, num_heads=4, num_kv_heads=2, head_dim=16,
        attention_multiplier=1 / 16, num_experts=8, num_experts_per_tok=2,
        shared_intermediate_size=48, mamba_n_heads=8, mamba_d_head=8,
        mamba_d_state=16, max_seq_len=128, dtype=jnp.float32), **kw})


@pytest.fixture(autouse=True)
def _chunks_of_eight(monkeypatch):
    """Prefill scans in chunks of 8, so prompts of 16 to 128 tokens
    cross 2 to 16 chunk boundaries."""
    monkeypatch.setattr(mamba2, "CHUNK", 8)


@pytest.fixture(scope="module")
def model():
    config = tiny()
    return config, llama.init_params(config, jax.random.PRNGKey(1))


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, n).tolist() for n in lengths]


def _serve(config, params, prompts, max_new=6, **engine):
    engine = {**dict(num_slots=4, max_len=128, block_size=16), **engine}
    eng = ContinuousBatcher(config, params=params, **engine)
    rids = [eng.submit(p, max_new) for p in prompts]
    out = eng.run_to_completion()
    return [out[r] for r in rids], eng


def _reference_tokens(params, config, prompt, n):
    """Greedy decoding by the reference's full forward, a token at a
    time."""
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(jnp.argmax(reference.logits(params, seq, config)[-1])))
    return seq[len(prompt):]


# ------------------------------------------------ the reference itself

def test_reference_matches_transformers_in_float32(model):
    """Both kinds of layer, a prompt longer than one chunk (29 > 8):
    every logit within 1e-4 of the logits' standard deviation of
    ``GraniteMoeHybridForCausalLM`` holding the same weights."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "GraniteMoeHybridForCausalLM"):
        pytest.skip("this transformers has no granitemoehybrid")
    config, params = model
    c = config
    hf_config = transformers.GraniteMoeHybridConfig(
        vocab_size=c.vocab_size, hidden_size=c.hidden_size,
        intermediate_size=c.intermediate_size, num_hidden_layers=c.num_layers,
        num_attention_heads=c.num_heads, num_key_value_heads=c.num_kv_heads,
        layer_types=list(c.layer_types),
        attention_multiplier=c.attention_multiplier,
        embedding_multiplier=c.embedding_multiplier,
        residual_multiplier=c.residual_multiplier,
        logits_scaling=c.logits_scaling, num_local_experts=c.num_experts,
        num_experts_per_tok=c.num_experts_per_tok,
        shared_intermediate_size=c.shared_intermediate_size,
        mamba_n_heads=c.mamba_n_heads, mamba_d_head=c.mamba_d_head,
        mamba_d_state=c.mamba_d_state, mamba_n_groups=c.mamba_n_groups,
        mamba_d_conv=c.mamba_d_conv, mamba_expand=1, mamba_chunk_size=8,
        mamba_conv_bias=True, mamba_proj_bias=False,
        position_embedding_type="nope", tie_word_embeddings=True,
        rms_norm_eps=c.rms_eps, attention_bias=False,
        max_position_embeddings=128)
    hf = transformers.GraniteMoeHybridForCausalLM(hf_config).float().eval()

    def t(a):
        return torch.tensor(np.asarray(a, np.float32))

    e = c.hidden_size
    sd = {"model.embed_tokens.weight": t(params["embed"]),
          "lm_head.weight": t(params["embed"]),
          "model.norm.weight": t(params["final_norm"])}
    li = 0
    for (kind, _, count, _), tree in zip(llama.layer_runs(c), params["runs"]):
        for j in range(count):
            w = {k: np.asarray(v[j], np.float32) for k, v in tree.items()}
            x = {k: np.asarray(v[li], np.float32)
                 for k, v in params["layers"].items()}
            pre = f"model.layers.{li}."
            sd.update({
                pre + "input_layernorm.weight": t(w["attn_norm"]),
                pre + "post_attention_layernorm.weight": t(w["mlp_norm"]),
                pre + "block_sparse_moe.router.layer.weight":
                    t(w["w_router"].T),
                # input_linear's rows: gate then up (it chunks in two).
                pre + "block_sparse_moe.input_linear.weight": t(
                    np.concatenate([x["moe_gate"].transpose(0, 2, 1),
                                    x["moe_up"].transpose(0, 2, 1)], 1)),
                pre + "block_sparse_moe.output_linear.weight":
                    t(x["moe_down"].transpose(0, 2, 1)),
                pre + "shared_mlp.input_linear.weight": t(np.concatenate(
                    [w["shared_gate"].T, w["shared_up"].T], 0)),
                pre + "shared_mlp.output_linear.weight":
                    t(w["shared_down"].T)})
            if kind == "mamba":
                sd.update({
                    pre + "mamba.in_proj.weight": t(w["ssm_in"].T),
                    pre + "mamba.conv1d.weight":
                        t(w["conv_w"].T[:, None, :]),
                    pre + "mamba.conv1d.bias": t(w["conv_b"]),
                    pre + "mamba.dt_bias": t(w["dt_bias"]),
                    pre + "mamba.A_log": t(w["a_log"]),
                    pre + "mamba.D": t(w["ssm_d"]),
                    pre + "mamba.norm.weight": t(w["ssm_norm"]),
                    pre + "mamba.out_proj.weight": t(w["ssm_out"].T)})
            else:
                sd.update({
                    pre + "self_attn.q_proj.weight":
                        t(w["wq"].reshape(e, -1).T),
                    pre + "self_attn.k_proj.weight":
                        t(w["wk"].reshape(e, -1).T),
                    pre + "self_attn.v_proj.weight":
                        t(w["wv"].reshape(e, -1).T),
                    pre + "self_attn.o_proj.weight":
                        t(w["wo"].reshape(-1, e).T)})
            li += 1
    missing, unexpected = hf.load_state_dict(sd, strict=False)
    assert not missing and not unexpected
    tokens = _prompts([29], seed=3)[0]
    with torch.no_grad():
        want = hf(torch.tensor(tokens)[None]).logits[0].numpy()
    got = np.asarray(reference.logits(params, tokens, config))
    assert np.abs(got - want).max() <= 1e-4 * want.std()


def test_reference_choices_are_the_top_k_router_logits(model):
    config, params = model
    tokens = _prompts([11])[0]
    choices = np.asarray(reference.router_choices(params, tokens, config))
    assert choices.shape == (4, 11, 2)
    assert (choices[..., 0] != choices[..., 1]).all()
    gaps, again = reference.gaps_and_choices(
        params, tokens[:8], tokens[8:], config, pad_to=16)
    assert np.asarray(gaps).shape == (3,)
    assert (np.asarray(again) == choices[:, :10]).all()


# ----------------------------------------- the recurrence, three ways

def _scan_inputs(batch=2, s=48, h=4, p=8, n=16, groups=1, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(k[0], (batch, s, h, p)),
            jax.nn.softplus(jax.random.normal(k[1], (batch, s, h)) - 2),
            -jnp.exp(jax.random.uniform(k[2], (h,), minval=0, maxval=2.7)),
            jax.random.normal(k[3], (batch, s, groups, n)),
            jax.random.normal(k[4], (batch, s, groups, n)))


def _stepwise(x, dt, a, b, c, upto=None):
    """Token-by-token steps of the plain recurrence."""
    batch, s, h, p = x.shape
    state = jnp.zeros((batch, h, p, b.shape[-1]))
    ys = []
    for t in range(s if upto is None else upto):
        y, state = ssm.ssm_step_reference(state, x[:, t], dt[:, t], a,
                                          b[:, t], c[:, t])
        ys.append(y)
    return jnp.stack(ys, 1), state


@pytest.mark.parametrize("chunk", [8, 16, 48])
@pytest.mark.parametrize("groups", [1, 2])
def test_chunked_scan_is_the_recurrence(chunk, groups):
    """Across chunk boundaries (48 = 6 x 8 = 3 x 16 = 1 x 48)."""
    with jax.default_matmul_precision("highest"):
        args = _scan_inputs(groups=groups)
        want_y, want_state = _stepwise(*args)
        y, state = ssm.ssm_chunked_scan(*args, chunk=chunk)
    np.testing.assert_allclose(y, want_y, atol=1e-4)
    np.testing.assert_allclose(state, want_state, atol=1e-4)


@pytest.mark.parametrize("lengths", [(20, 37), (1, 48), (8, 9)])
def test_padded_positions_leave_the_state_alone(lengths):
    """A right-padded row: a zero time step past its length keeps the
    state of its last real token, wherever the chunk boundaries fall."""
    with jax.default_matmul_precision("highest"):
        x, dt, a, b, c = _scan_inputs()
        real = jnp.arange(48)[None, :] < jnp.asarray(lengths)[:, None]
        y, state = ssm.ssm_chunked_scan(
            x, jnp.where(real[..., None], dt, 0.0), a, b, c, chunk=16)
        for row, n in enumerate(lengths):
            want_y, want_state = _stepwise(
                x[row:row + 1], dt[row:row + 1], a, b[row:row + 1],
                c[row:row + 1], upto=n)
            np.testing.assert_allclose(state[row], want_state[0], atol=1e-4)
            np.testing.assert_allclose(y[row, :n], want_y[0], atol=1e-4)


def test_scan_goes_on_from_a_carried_state():
    with jax.default_matmul_precision("highest"):
        x, dt, a, b, c = _scan_inputs()
        _, whole = ssm.ssm_chunked_scan(x, dt, a, b, c, chunk=8)
        _, first = ssm.ssm_chunked_scan(x[:, :16], dt[:, :16], a, b[:, :16],
                                        c[:, :16], chunk=8)
        _, both = ssm.ssm_chunked_scan(x[:, 16:], dt[:, 16:], a, b[:, 16:],
                                       c[:, 16:], first, chunk=16)
    np.testing.assert_allclose(both, whole, atol=1e-4)


@pytest.mark.parametrize("h,p,n", [(4, 8, 16), (16, 64, 128), (8, 16, 32)])
def test_ssm_step_kernel_is_the_plain_step(pallas_interpret, h, p, n):
    """The interpreted ``ssm_step`` kernel against ``jax.numpy`` on the
    packed cache, at a layer index: the other layers' bytes stay."""
    k = jax.random.split(jax.random.PRNGKey(h), 6)
    batch = 3
    state = jax.random.normal(k[0], (3, batch, h, p, n))
    packed = ssm.pack_state(state)
    assert packed.shape == (3, batch) + ssm.packed_shape(h, p, n)
    assert (ssm.unpack_state(packed, p) == state).all()
    x = jax.random.normal(k[1], (batch, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[2], (batch, h)) - 2)
    a = -jnp.exp(jax.random.uniform(k[3], (h,), minval=0, maxval=2.7))
    b = jax.random.normal(k[4], (batch, 1, n))
    c = jax.random.normal(k[5], (batch, 1, n))
    want_y, want_state = ssm.ssm_step_reference(state[1], x, dt, a, b, c)
    for use_kernel in (True, False):
        y, out = ssm.ssm_step(packed, jnp.int32(1), x, dt, a, b, c,
                              use_kernel=use_kernel)
        np.testing.assert_allclose(y, want_y, atol=1e-4)
        np.testing.assert_allclose(ssm.unpack_state(out[1], p), want_state,
                                   atol=1e-5)
        assert (out[0] == packed[0]).all() and (out[2] == packed[2]).all()


def test_conv_step_continues_the_prefill_convolution():
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(k[0], (2, 9, 12))
    w, bias = jax.random.normal(k[1], (4, 12)), jax.random.normal(k[2], (12,))
    whole = ssm.causal_conv(x, w, bias)
    lengths = jnp.asarray([2, 6])          # the first is shorter than K - 1
    tail = ssm.conv_tail(x, lengths, 4)
    assert (tail[0, 0] == 0).all() and (tail[0, 1:] == x[0, :2]).all()
    for row, n in enumerate([2, 6]):
        out, nxt = ssm.conv_step(tail[row:row + 1], x[row:row + 1, n], w,
                                 bias)
        np.testing.assert_allclose(out[0], whole[row, n], atol=1e-5)
        assert (nxt[0, -1] == x[row, n]).all()


def test_mixer_prefill_then_steps_is_the_whole_prefill(model):
    """The mixer over 20 tokens at once against 13 at once (padded to
    16) and 7 one-token steps through a state cache."""
    config, params = model
    c = config
    layer = jax.tree.map(lambda a: a[1], params["runs"][0])
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 20, c.hidden_size))
    with jax.default_matmul_precision("highest"):
        whole, _, _ = mamba2.mixer_prefill(
            jnp.pad(h, ((0, 0), (0, 4), (0, 0))), layer, c,
            jnp.asarray([20]))
        out, state, tail = mamba2.mixer_prefill(
            jnp.pad(h[:, :13], ((0, 0), (0, 3), (0, 0))), layer, c,
            jnp.asarray([13]))
        np.testing.assert_allclose(out[:, :13], whole[:, :13], atol=1e-4)
        cache = StateCache.create(c, 2)
        held = (cache.ssm.at[2, 1].set(state[0]),
                cache.conv.at[2, 1].set(tail[0]))
        for t in range(13, 20):
            step_in = jnp.stack([jnp.zeros_like(h[0, t]), h[0, t]])[:, None]
            out, *held = mamba2.mixer_step(step_in, layer, c, *held,
                                           jnp.int32(2))
            np.testing.assert_allclose(out[1, 0], whole[0, t], atol=1e-4)


# ------------------------------------------------------------ the engine

@pytest.mark.parametrize("engine", [
    {}, {"use_decode_kernel": True}, {"num_slots": 2}],
    ids=["xla", "kernels-interpreted", "two-slots"])
def test_engine_tokens_are_the_references_argmax(model, engine,
                                                 pallas_interpret):
    """Prefill (padded to 16, 32, 64 and 128: one to sixteen chunks of
    8) and ticks through the state cache choose what the reference's
    full forward chooses, with the kernels interpreted or not."""
    config, params = model
    prompts = _prompts([5, 17, 33, 70, 16])
    got, eng = _serve(config, params, prompts, **engine)
    for prompt, tokens in zip(prompts, got):
        assert tokens == _reference_tokens(params, config, prompt, 6)
    assert eng.state_installs == len(prompts)


def test_a_requests_tokens_do_not_depend_on_its_batch_nor_its_slot(model):
    """Alone in a fresh engine, or admitted with others into slots that
    earlier requests held and left their states in."""
    config, params = model
    prompts = _prompts([9, 21, 6, 40, 13, 30, 11], seed=5)
    together, eng = _serve(config, params, prompts, num_slots=2, max_new=5)
    assert eng.state_installs == len(prompts)      # slots were reused
    for prompt, tokens in zip(prompts, together):
        alone, _ = _serve(config, params, [prompt], max_new=5)
        assert alone[0] == tokens


def test_padding_that_advanced_the_state_would_show(model, monkeypatch):
    """The fault the lengths exist to prevent, shown to change tokens:
    a prefill that treats the padding of a 17-token prompt (bucket 32)
    as real leaves another state."""
    config, params = model
    prompts = _prompts([17, 19], seed=7)
    right, _ = _serve(config, params, prompts, max_new=8)
    real = mamba2.mixer_prefill
    monkeypatch.setattr(
        mamba2, "mixer_prefill", lambda h, layer, c, lengths: real(
            h, layer, c, jnp.full_like(lengths, h.shape[1])))
    wrong, _ = _serve(config, params, prompts, max_new=8)
    assert [w[0] for w in wrong] == [r[0] for r in right]  # the prefill's own
    assert wrong != right


def test_arena_holds_attention_layers_and_state_cache_the_rest(model):
    config, params = model
    eng = ContinuousBatcher(config, params=params, num_slots=4, max_len=64,
                            block_size=16)
    assert config.attn_layers == 1 and config.state_layers == 3
    assert eng.cache.k.shape[0] == 1
    assert eng.state.ssm.shape == (3, 4) + ssm.packed_shape(8, 8, 16)
    assert eng.state.ssm.dtype == jnp.float32
    assert eng.state.conv.shape == (3, 4, 3, 8 * 8 + 2 * 16)
    assert eng.prefix_cache is False and eng._prefix is None
    snap = eng.pressure_snapshot()
    assert snap["state_cache_bytes"] == eng.state.nbytes > 0
    # A tick reads and writes every slot's state, and the tied head is
    # counted once (it is the embedding); 4 slots x top 2 can touch all
    # 8 experts.
    assert eng.tick_bytes_estimate() == (
        eng.param_bytes + 2 * eng.state.nbytes)
    assert "lm_head" not in eng.params


def test_reset_rebuilds_the_state_cache(model):
    config, params = model
    eng = ContinuousBatcher(config, params=params, num_slots=2, max_len=64,
                            block_size=16)
    prompt = _prompts([12])[0]
    first = eng.submit(prompt, 4)
    want = eng.run_to_completion()[first]
    eng.submit(prompt, 4)
    eng.step()
    eng.reset()
    assert not np.asarray(eng.state.ssm).any()
    again = eng.submit(prompt, 4)
    assert eng.run_to_completion()[again] == want


@pytest.mark.parametrize("kwargs,named", [
    ({"spec_k": 2}, "speculative"),
    ({"prefix_cache": True}, "prefix cache"),
    ({"role": "prefill"}, "role='prefill'"),
    ({"role": "decode"}, "role='decode'"),
])
def test_refused_by_name_for_state_layers(model, kwargs, named):
    config, params = model
    with pytest.raises(ValueError, match="state-space") as err:
        ContinuousBatcher(config, params=params, num_slots=2, max_len=64,
                          block_size=16, **kwargs)
    assert named in str(err.value)


def test_prefix_cache_env_is_refused_and_unset_means_off(model, monkeypatch):
    config, params = model
    monkeypatch.setenv("RAY_TPU_PREFIX_CACHE", "1")
    with pytest.raises(ValueError, match="prefix cache"):
        ContinuousBatcher(config, params=params, num_slots=2, max_len=64,
                          block_size=16)
    monkeypatch.delenv("RAY_TPU_PREFIX_CACHE")
    assert not ContinuousBatcher(config, params=params, num_slots=2,
                                 max_len=64, block_size=16).prefix_cache


@pytest.mark.parametrize("call", ["export_kv_payload", "import_kv_payload"])
def test_kv_handoff_is_refused_for_state_layers(model, call):
    config, params = model
    eng = ContinuousBatcher(config, params=params, num_slots=2, max_len=64,
                            block_size=16)
    with pytest.raises(ValueError, match="no recurrent state"):
        getattr(eng, call)(0 if call.startswith("export") else {})


def test_training_forward_refuses_the_family(model):
    config, params = model
    with pytest.raises(NotImplementedError, match="engine only"):
        llama.forward(params, jnp.zeros((1, 4), jnp.int32), config)


def test_published_config_and_param_count():
    """granite-4.0-h-small as published: 9 runs, 36 + 4 layers, 32.2 B
    parameters; the cell's cut of 6 layers 5.16 B (ISSUE 29)."""
    c = llama.LlamaConfig.granite_4_0_h_small()
    runs = llama.layer_runs(c)
    assert [r[0] for r in runs] == ["mamba", "attention"] * 4 + ["mamba"]
    assert [r[2] for r in runs] == [5, 1, 9, 1, 9, 1, 9, 1, 4]
    assert [r[3] for r in runs if r[0] == "attention"] == [0, 1, 2, 3]
    assert (c.state_layers, c.attn_layers) == (36, 4)
    assert c.attn_scale == 1 / 128
    assert round(llama.num_params(c) / 1e9, 1) == 32.2
    cut = dataclasses.replace(c, num_layers=6, layer_types=c.layer_types[:6])
    assert llama.num_params(cut) == 5 * 800_941_696 + 740_597_760 + (
        100352 * 4096 + 4096)
    tree = jax.eval_shape(lambda k: llama.init_params(tiny(), k),
                          jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(tree)) == llama.num_params(
        tiny())
    with pytest.raises(ValueError, match="layer_types names"):
        llama.layer_runs(dataclasses.replace(c, num_layers=39))
