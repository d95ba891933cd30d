"""The Jamba family at ``num_experts`` 1 (Mamba-1 mixers beside MQA
attention without positions: ``LlamaConfig.mamba_dt_rank > 0``,
``models/mamba1.py``, ``ops/selective_scan.py``) on the CPU at a small
size, seeded weights, float32.

The plain reference (``benchmark/reference_jamba.py``) is held to
``transformers``' ``JambaForCausalLM`` on copied weights; the mixer, the
engine's two programs driven by hand (:class:`Hand`: the host's half,
tables and blocks, is done here) and the engine itself are held to the
reference; then each named fault, switched on by patching, must FAIL
that comparison; then what a stack with a recurrent state refuses, by
name.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import reference_jamba as reference  # noqa: E402
from ray_tpu._private import metrics_defs as mdefs  # noqa: E402
from ray_tpu.models import continuous_batching as cb  # noqa: E402
from ray_tpu.models import llama, mamba1  # noqa: E402
from ray_tpu.models.continuous_batching import ContinuousBatcher  # noqa: E402
from ray_tpu.models.paged_kv import PagedKVCache, StateCache  # noqa: E402
from ray_tpu.ops import selective_scan  # noqa: E402

BS, V = 8, 256
KERNELS = pytest.mark.parametrize("kernel", [False, True],
                                  ids=["kernels-off", "kernels-interpreted"])
# (layers, attn_layer_period, attn_layer_offset): one period cut to four
# layers with one attention layer, and the published pattern whole.
STACKS = {"four-layers": (4, 4, 1), "published-28": (28, 14, 7)}


def tiny(layers=4, period=4, offset=1, **kw):
    pattern = tuple("attention" if i % period == offset else "mamba1"
                    for i in range(layers))
    return llama.LlamaConfig.jamba2_3b(**{**dict(
        vocab_size=V, hidden_size=64, intermediate_size=96,
        num_layers=layers, num_heads=4, num_kv_heads=1, head_dim=16,
        max_seq_len=128, layer_types=pattern, mamba_d_head=128,
        mamba_dt_rank=8, dtype=jnp.float32), **kw})


@pytest.fixture(scope="module")
def model():
    config = tiny()
    return config, llama.init_params(config, jax.random.PRNGKey(1))


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, V, n).tolist() for n in lengths]


def _off(got, want):
    """The largest difference, in standard deviations of ``want``."""
    want = np.asarray(want)
    return float(np.max(np.abs(np.asarray(got) - want)) / want.std())


def _layer(run, at):
    return jax.tree.map(lambda a: a[at], run)


# ------------------------------------------------------------ the tree

def test_published_sizes_and_the_tree():
    c = llama.LlamaConfig.jamba2_3b()
    assert llama.num_params(c) == 3_029_337_472
    assert [r[0] for r in llama.layer_runs(c)] == [
        "mamba1", "attention", "mamba1", "attention", "mamba1"]
    assert [i for i, t in enumerate(c.layer_types) if t == "attention"] == [
        7, 21]
    assert (c.state_layers, c.attn_layers) == (26, 2)
    assert mamba1.state_shapes(c) == ((16, 5120), (3 * 5120,))
    state = jax.eval_shape(lambda: StateCache.create(c, 256))
    assert state.ssm.shape == (26, 256, 16, 5120)
    assert state.ssm.dtype == jnp.float32 and state.conv.dtype == c.dtype
    # 358,400 B a slot a layer: ISSUE 55's arithmetic.
    assert (state.ssm.size * 4 + state.conv.size * 2) == 256 * 26 * 358_400


def test_only_the_family_is_initialised_so():
    with pytest.raises(ValueError, match="Jamba family"):
        llama.init_params(tiny(rope=True), jax.random.PRNGKey(0))


# ---------------------------------------- the reference, to transformers

def _to_transformers(config, params, period, offset):
    import torch
    from transformers import JambaConfig, JambaForCausalLM

    c = config
    hf = JambaForCausalLM(JambaConfig(
        vocab_size=c.vocab_size, hidden_size=c.hidden_size,
        intermediate_size=c.intermediate_size,
        num_hidden_layers=c.num_layers, num_attention_heads=c.num_heads,
        num_key_value_heads=c.num_kv_heads, attn_layer_period=period,
        attn_layer_offset=offset, num_experts=1, num_experts_per_tok=1,
        mamba_d_state=c.mamba_d_state, mamba_d_conv=c.mamba_d_conv,
        mamba_expand=c.mamba_dims[0] // c.hidden_size,
        mamba_dt_rank=c.mamba_dt_rank, mamba_conv_bias=True,
        mamba_proj_bias=False, use_mamba_kernels=False,
        rms_norm_eps=c.rms_eps, tie_word_embeddings=True,
        attn_implementation="eager")).float().eval()

    def put(dst, src):
        with torch.no_grad():
            dst.copy_(torch.from_numpy(np.asarray(src, np.float32)))

    put(hf.model.embed_tokens.weight, params["embed"])
    put(hf.model.final_layernorm.weight, params["final_norm"])
    at = 0
    for (kind, _, count, _), run in zip(llama.layer_runs(c), params["runs"]):
        for j in range(count):
            w, layer = _layer(run, j), hf.model.layers[at]
            put(layer.input_layernorm.weight, w["attn_norm"])
            put(layer.pre_ff_layernorm.weight, w["mlp_norm"])
            put(layer.feed_forward.gate_proj.weight, w["w_gate"].T)
            put(layer.feed_forward.up_proj.weight, w["w_up"].T)
            put(layer.feed_forward.down_proj.weight, w["w_down"].T)
            if kind == "attention":
                E = c.hidden_size
                a = layer.self_attn
                put(a.q_proj.weight, w["wq"].reshape(E, -1).T)
                put(a.k_proj.weight, w["wk"].reshape(E, -1).T)
                put(a.v_proj.weight, w["wv"].reshape(E, -1).T)
                put(a.o_proj.weight, w["wo"].reshape(-1, E).T)
            else:
                m = layer.mamba
                put(m.in_proj.weight, w["m1_in"].T)
                put(m.conv1d.weight, w["conv_w"].T[:, None, :])
                put(m.conv1d.bias, w["conv_b"])
                put(m.x_proj.weight, w["m1_x"].T)
                put(m.dt_layernorm.weight, w["dt_norm"])
                put(m.b_layernorm.weight, w["b_norm"])
                put(m.c_layernorm.weight, w["c_norm"])
                put(m.dt_proj.weight, w["m1_dt"].T)
                put(m.dt_proj.bias, w["dt_bias"])
                put(m.A_log, w["a_log"].T)
                put(m.D, w["m1_d"])
                put(m.out_proj.weight, w["m1_out"].T)
            at += 1
    return hf


@pytest.mark.parametrize("stack", STACKS)
def test_reference_agrees_with_transformers(stack):
    import torch

    layers, period, offset = STACKS[stack]
    config = tiny(layers, period, offset)
    params = llama.init_params(config, jax.random.PRNGKey(3))
    tokens = _prompts([37], seed=4)[0]
    hf = _to_transformers(config, params, period, offset)
    with torch.no_grad():
        want = hf(torch.tensor([tokens])).logits[0].numpy()
    assert _off(reference.logits(params, tokens, config), want) < 1e-4


# ------------------------------------------------- the mixer, by itself

def _reference_mixer(h, layer, c):
    with jax.default_matmul_precision("highest"):
        return reference.mamba(h, layer, c.mamba_dt_rank, c.mamba_d_state,
                               c.rms_eps)


@KERNELS
def test_mixer_prefill_then_steps_against_the_reference(model, kernel):
    c, params = model
    layer = _layer(params["runs"][0], 0)
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 24, c.hidden_size))
    want, want_state = _reference_mixer(h[0], layer, c)
    # 16 positions as a prefill, then 8 as ticks through a cache of three
    # layers and two slots, at layer 1, slot 1.
    out, state, tail = mamba1.mixer_prefill(
        h[:, :16], layer, c, jnp.asarray([16]), use_kernel=kernel or None)
    assert _off(out[0], want[:16]) < 1e-5
    cache = StateCache.create(dataclasses.replace(c, layer_types=(
        "mamba1",) * 3 + ("attention",)), 2)
    cache = StateCache(cache.ssm.at[1, 1].set(state[0]),
                       cache.conv.at[1, 1].set(tail[0]))
    for t in range(16, 24):
        step = jnp.stack([jnp.zeros_like(h[0, t]), h[0, t]])[:, None]
        out, *cache = mamba1.mixer_step(step, layer, c, *cache, jnp.int32(1),
                                        kernel or None)
        cache = StateCache(*cache)
        assert _off(out[1, 0], want[t]) < 1e-5
    assert _off(cache.ssm[1, 1], want_state.T) < 1e-5
    assert not np.any(np.asarray(cache.ssm[0])), "another layer was written"


def test_a_padded_row_keeps_the_state_of_its_last_real_token(model):
    c, params = model
    layer = _layer(params["runs"][0], 1)
    h = jax.random.normal(jax.random.PRNGKey(6), (3, 16, c.hidden_size))
    lengths = jnp.asarray([16, 5, 2])
    _, state, tail = mamba1.mixer_prefill(h, layer, c, lengths)
    u_in = mamba1._in_proj(h, layer, c)[0]
    for row, n in enumerate(lengths.tolist()):
        _, want = _reference_mixer(h[row, :n], layer, c)
        assert _off(state[row], want.T) < 1e-5
        want_tail = np.zeros((3, u_in.shape[-1]), np.float32)
        want_tail[max(3 - n, 0):] = np.asarray(u_in[row, max(n - 3, 0):n])
        np.testing.assert_allclose(tail[row].reshape(3, -1), want_tail,
                                   atol=1e-6)


# ----------------------------------------------- both kernels, interpreted

def _scan_operands(rows, s, n, d, seed=7):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (rows, s, d)),
            jax.nn.softplus(jax.random.normal(k[1], (rows, s, d)) - 2.0),
            -jnp.exp(jax.random.uniform(k[2], (n, d), minval=0, maxval=2.7)),
            jax.random.normal(k[3], (rows, s, n)),
            jax.random.normal(k[4], (rows, s, n)),
            jax.random.normal(k[5], (rows, n, d)))


@pytest.mark.parametrize("rows,s,d,carried", [
    (2, 16, 128, True), (1, 256, 128, False), (3, 8, 1024, True)])
def test_scan_kernel_equals_the_scan_over_positions(rows, s, d, carried):
    *ops, state = _scan_operands(rows, s, 16, d)
    state = state if carried else None
    want_y, want_s = selective_scan.mamba1_scan(*ops, state, use_kernel=False)
    y, new = selective_scan.mamba1_scan(*ops, state, use_kernel=True)
    assert _off(y, want_y) < 1e-5 and _off(new, want_s) < 1e-5


@pytest.mark.parametrize("slots,d", [(3, 128), (16, 256), (8, 1024)])
def test_step_kernel_equals_one_position_and_writes_its_layer_alone(slots,
                                                                    d):
    u, dt, a_t, b, c, _ = _scan_operands(slots, 1, 16, d, seed=8)
    cache = jax.random.normal(jax.random.PRNGKey(9), (3, slots, 16, d))
    args = (jnp.int32(2), u[:, 0], dt[:, 0], a_t, b[:, 0], c[:, 0])
    want_y, want = selective_scan.mamba1_step(cache, *args, use_kernel=False)
    y, new = selective_scan.mamba1_step(cache, *args, use_kernel=True)
    assert _off(y, want_y) < 1e-5 and _off(new[2], want[2]) < 1e-5
    np.testing.assert_array_equal(new[:2], cache[:2])


def test_a_zero_time_step_is_the_identity():
    u, dt, a_t, b, c, state = _scan_operands(2, 16, 16, 128, seed=10)
    dt = dt.at[:, 5:].set(0.0)
    for kernel in (False, True):
        _, full = selective_scan.mamba1_scan(u, dt, a_t, b, c, state,
                                             use_kernel=kernel)
        _, cut = selective_scan.mamba1_scan(u[:, :5], dt[:, :5], a_t,
                                            b[:, :5], c[:, :5], state,
                                            use_kernel=False)
        assert _off(full, cut) < 1e-6


def test_the_kernels_state_the_bytes_and_operations_they_move():
    """``cost_estimate``: six operations and one ``exp`` a state element;
    the state once in and once out."""
    step = selective_scan.step_cost(256, 16, 5120)
    assert step.flops == 6 * 256 * 16 * 5120
    assert step.transcendentals == 256 * 16 * 5120
    assert step.bytes_accessed == 4 * (
        256 * (2 * 16 * 5120 + 3 * 5120 + 32) + 16 * 5120)
    scan = selective_scan.scan_cost(8, 512, 16, 5120)
    assert scan.flops == 6 * 8 * 512 * 16 * 5120
    assert scan.bytes_accessed == 4 * 8 * (
        512 * (3 * 5120 + 32) + 3 * 16 * 5120)


# --------------------------------- the engine's two programs, by hand

class Hand:
    """The engine's two forwards (``cb._prefill_chunk_paged``,
    ``cb._forward_paged``) driven by hand over a Mamba-1 stack,
    teacher-forced, for ONE sequence in slot 1 of 2: a prompt in chunks
    of ``chunk`` tokens (each goes on from the state, the conv tail and
    the arena's keys the one before it left), then a tick a token."""

    def __init__(self, config, params, kernel=False, blocks=32):
        self.c, self.kernel = config, kernel
        self.params = llama.heads_major(params)
        self.caches = (PagedKVCache.create(config, blocks, BS, "bf16"),
                       StateCache.create(config, 2))
        self.table = list(range(1, 17))
        self._prefill = jax.jit(
            lambda params, row, caches, ptables, *rest:
            cb._prefill_chunk_paged(
                params, row, ptables.shape[1] * BS + jnp.arange(row.shape[1]),
                *caches, ptables, *rest, self.c, self.kernel))
        self._tick = jax.jit(lambda *a: cb._forward_paged(
            *a, self.c, self.kernel)[:2])

    def prefill(self, tokens, chunk):
        logits = None
        for first in range(0, len(tokens), chunk):
            piece = tokens[first:first + chunk]
            padded = -(-len(piece) // BS) * BS
            row = jnp.asarray([piece + [0] * (padded - len(piece))])
            m, own = first // BS, padded // BS
            logits, *self.caches = self._prefill(
                self.params, row, self.caches,
                jnp.asarray([self.table[:m]], jnp.int32).reshape(1, m),
                jnp.asarray([self.table[m:m + own]], jnp.int32),
                jnp.asarray([len(piece) - 1]), jnp.asarray([1]))
        return logits[0, 0]

    def tick(self, token, position):
        tables = jnp.asarray([[0] * 16, self.table], jnp.int32)
        logits, self.caches = self._tick(
            self.params, jnp.asarray([[0], [token]]),
            jnp.asarray([[0], [position]]), tables,
            jnp.asarray([0, 16 * BS]), self.caches)
        return logits[1, 0]

    def state(self):
        return self.caches[1].ssm[:, 1], self.caches[1].conv[:, 1]


@pytest.mark.parametrize("chunk", [24, 16, 8],
                         ids=["one-chunk", "two-chunks-one-of-one-token",
                              "three-chunks"])
@KERNELS
def test_a_prompt_in_chunks_gives_the_same_logits_and_state(model, chunk,
                                                            kernel):
    c, params = model
    tokens = _prompts([17 + 12], seed=11)[0]
    want = reference.logits(params, tokens, c)
    hand = Hand(c, params, kernel)
    assert _off(hand.prefill(tokens[:17], chunk), want[16]) < 1e-4
    _, states = reference.forward(params, tokens[:17], c)
    ssm_rows, _ = hand.state()
    for got, ref in zip(ssm_rows, states):
        assert _off(got, ref.T) < 1e-4
    for t in range(17, 29):
        assert _off(hand.tick(tokens[t], t), want[t]) < 1e-4


def _bf16_state(real):
    def step(state_all, *args, **kw):
        y, new = real(state_all, *args, **kw)
        return y, jax.lax.reduce_precision(new, exponent_bits=8,
                                           mantissa_bits=7)
    return step


def _no_norm(real):
    return lambda x, weight, c: real(x, jnp.ones_like(weight), c)


def _tail_one_late(real):
    def step(tail, new, w, bias):
        out, nxt = real(tail, new, w, bias)
        return out, jnp.roll(nxt, new.shape[-1], axis=1)
    return step


def _no_skip(real):
    return lambda y, u, z, layer, c: real(
        y, u, z, dict(layer, m1_d=jnp.zeros_like(layer["m1_d"])), c)


def _padding_advances(real):
    # The mixer zeroes the padded positions' time steps; a softplus is
    # never exactly 0, so these are they.
    return lambda u, dt, *rest, **kw: real(
        u, jnp.where(dt == 0.0, 0.05, dt), *rest, **kw)


FAULTS = {
    "a-bf16-state": (selective_scan, "mamba1_step", _bf16_state),
    "the-inner-norms-weights-dropped": (mamba1, "_rms", _no_norm),
    "a-conv-tail-out-of-order": (mamba1, "_conv_step", _tail_one_late),
    "no-skip-term": (mamba1, "_gate_out", _no_skip),
    "padding-advances-the-state": (selective_scan, "mamba1_scan",
                                   _padding_advances),
}


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_fails_the_tolerance_the_sound_program_passes(
        model, fault, monkeypatch):
    """The tolerance of the comparisons above (1e-4 of the logits'
    standard deviation) against what each fault reads: every one lies
    thirty times outside it or more (a bf16 state, the least, a hundred)."""
    c, params = model
    tokens = _prompts([13 + 8], seed=12)[0]
    want = reference.logits(params, tokens, c)
    module, name, make = FAULTS[fault]
    monkeypatch.setattr(module, name, make(getattr(module, name)))
    hand = Hand(c, params)
    worst = _off(hand.prefill(tokens[:13], 16), want[12])
    for t in range(13, 21):
        worst = max(worst, _off(hand.tick(tokens[t], t), want[t]))
    assert worst > 3e-3, worst


# --------------------------------------------------- the engine itself

@KERNELS
def test_engine_end_to_end_against_the_reference(model, kernel):
    """Prefill (one prompt in three chunks, one ending in a chunk of ONE
    token), 16 ticks, and more requests than slots, so a slot is used
    again over another request's state."""
    c, params = model
    eng = ContinuousBatcher(c, params=params, num_slots=2, max_len=96,
                            block_size=BS, prefill_chunk=16,
                            use_decode_kernel=kernel)
    assert eng.prefill_chunk == 16 and not eng.prefix_cache
    def total(metric, suffix=""):
        return sum(v for n, _, v in metric.samples()
                   if n == metric.name + suffix)

    carries = total(mdefs.CB_PREFILL_STATE_CARRIES)
    ticks = total(mdefs.CB_STATE_LIVE_SLOTS, "_count")
    prompts = _prompts([5, 17, 40, 16, 9])
    rids = [eng.submit(p, max_new_tokens=16) for p in prompts]
    out = eng.run_to_completion()
    for rid, prompt in zip(rids, prompts):
        tokens = out[rid]
        lg = reference.logits(params, prompt + tokens[:-1], c)
        assert tokens == jnp.argmax(lg[len(prompt) - 1:], -1).tolist()
    assert eng.state_installs == 5 and eng.state_carries == 1 + 2
    # The state cache's series are the new tenant's too.
    assert total(mdefs.CB_PREFILL_STATE_CARRIES) - carries == 3
    assert total(mdefs.CB_STATE_LIVE_SLOTS, "_count") > ticks
    assert eng.pressure_snapshot()["state_cache_bytes"] == eng.state.nbytes == (
        2 * 3 * (16 * 128 * 4 + 3 * 128 * 4))


# ------------------------------------------------ what it refuses, by name

@pytest.mark.parametrize("capability,kwargs,called", [
    ("speculative", dict(spec_k=2), "speculative decoding"),
    ("prefix_cache", dict(prefix_cache=True), "prefix cache"),
    ("handoff", dict(role="prefill"), "role='prefill'"),
    ("kv_dtype", dict(kv_dtype="int8"), "kv_dtype='int8'"),
    ("second_kind", dict(), "another layer kind"),
])
def test_refused_by_name_for_mamba1(capability, kwargs, called):
    config = tiny()
    if capability == "second_kind":
        config = dataclasses.replace(config, layer_types=(
            "mamba1", "attention", "sliding_attention", "mamba1"))
    with pytest.raises(ValueError) as err:
        ContinuousBatcher(config, num_slots=2, max_len=64, block_size=BS,
                          **kwargs)
    said = str(err.value)
    assert called in said and "selective-scan layers" in said
    assert said.endswith(cb._KIND_CANNOT["mamba1"][capability])


@pytest.mark.parametrize("service", ["forward", "loss_fn"])
def test_the_training_forward_does_not_run_it(model, service):
    c, params = model
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="continuous-batching"):
        if service == "forward":
            llama.forward(params, tokens, c)
        else:
            llama.loss_fn(params, {"tokens": tokens}, c)
