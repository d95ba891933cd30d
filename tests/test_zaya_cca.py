"""The ZAYA1 family (Compressed Convolutional Attention: K/V in the
arena AND a convolution tail a slot, in one layer; a top-1 router that is
an MLP with a state carried over depth; scaled residuals; a tied head)
on the CPU at small sizes, on seeded float32 weights: the engine's two
programs (chunked prefill, the tick) against the plain reference's full
forward.

Sizes: hidden 64, 4 query heads and 2 KV heads of 16 (so q lives in 64
and k, v in 32: the tail is 2 x 96 + 16 = 208 values), 3 layers, 4
experts of 32, router width 16, blocks of 8, chunks of 16.

Tolerances. float32 against float32: both sides hold the same numbers
and differ in operation order (a blockwise softmax, one product for two
taps, a grouped multiplication), so LOGITS within 2e-4 of their standard
deviation, every route the reference's, and the engine's tokens the
reference's argmax. Each fault of :data:`FAULTS` (a dropped step of
ISSUE 46 A, or q and k rounded to bfloat16 before the scores) moves the
logits by more than FIFTY times that (1e-2 of a standard deviation):
the limit lies two orders under the smallest fault.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import reference_zaya as reference  # noqa: E402
from ray_tpu._private import metrics_defs as mdefs  # noqa: E402
from ray_tpu.models import cca, llama  # noqa: E402
from ray_tpu.models import continuous_batching as cb  # noqa: E402
from ray_tpu.models.continuous_batching import (ContinuousBatcher,  # noqa: E402
                                                _KIND_CANNOT)
from ray_tpu.models.paged_kv import PagedKVCache, TailCache  # noqa: E402

BS, CHUNK, V = 8, 16, 128
REL = 2e-4


def tiny(**kw):
    return llama.LlamaConfig.zaya1_8b(**{**dict(
        vocab_size=V, hidden_size=64, intermediate_size=32, num_layers=3,
        num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=128,
        num_experts=4, experts_held=(0, 4), router_hidden_size=16,
        dtype=jnp.float32), **kw})


@pytest.fixture(scope="module")
def model():
    config = tiny()
    return config, llama.init_params(config, jax.random.PRNGKey(1))


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, V, n).tolist()


def _close(got, want, rel=REL):
    want = np.asarray(want)
    return float(np.max(np.abs(np.asarray(got) - want)) / want.std()) < rel


def _programs(fresh: bool):
    """(prefill chunk, tick) jitted. ``fresh``: traced anew, for a test
    that has patched what they call (jit's cache is the function's)."""
    chunk, tick = cb._prefill_chunk_paged, cb._forward_paged
    if fresh:
        chunk = lambda *a: cb._prefill_chunk_paged(*a)      # noqa: E731
        tick = lambda *a: cb._forward_paged(*a)             # noqa: E731
    return (jax.jit(chunk, static_argnums=(9, 10)),
            jax.jit(tick, static_argnums=(6, 7)))


def engine_logits(config, params, tokens, n_prompt, chunk=CHUNK,
                  fresh=False):
    """The engine's two programs, driven by hand: ``tokens[:n_prompt]``
    through ``_prefill_chunk_paged`` a chunk at a time (a later chunk
    reads the arena and slot 0's tail), the rest one tick each through
    ``_forward_paged``. Returns (logits ``[len(tokens) - n_prompt + 1,
    V]``: after the prompt's last token and after each fed one; routes
    ``[ticks, L]``: each fed token's expert a layer)."""
    n_blocks = -(-len(tokens) // BS) + chunk // BS
    blocks = np.arange(1, n_blocks + 1, dtype=np.int32)
    cache = PagedKVCache.create(config, n_blocks + 1, BS)
    tail = TailCache.create(config, 1)
    slots = jnp.zeros(1, jnp.int32)
    prefill, tick = _programs(fresh)
    for at in range(0, n_prompt, chunk):
        part = tokens[at:min(at + chunk, n_prompt)]
        padded = np.zeros((1, chunk), np.int32)
        padded[0, :len(part)] = part
        m = at // BS
        logits, cache, tail = prefill(
            params, jnp.asarray(padded), at + jnp.arange(chunk), cache, tail,
            jnp.asarray(blocks[None, :m]),
            jnp.asarray(blocks[None, m:m + chunk // BS]),
            jnp.asarray([len(part) - 1], jnp.int32), slots, config, False)
    out, routes = [logits[0, 0]], []
    tables = jnp.asarray(blocks[None])
    limits = jnp.asarray([n_blocks * BS], jnp.int32)
    for p in range(n_prompt, len(tokens)):
        logits, (cache, tail), rows = tick(
            params, jnp.asarray([[tokens[p]]], jnp.int32),
            jnp.asarray([[p]], jnp.int32), tables, limits, (cache, tail),
            config, False)
        out.append(logits[0, 0])
        routes.append(np.asarray(rows)[:, config.num_experts:].ravel())
    return np.stack(out), np.asarray(routes)


def _serve(config, params, prompts, max_new=6, **engine):
    engine = {**dict(num_slots=2, max_len=96, block_size=BS,
                     prefill_chunk=CHUNK), **engine}
    eng = ContinuousBatcher(config, params=params, **engine)
    rids = [eng.submit(p, max_new, keep_routes=True) for p in prompts]
    out = eng.run_to_completion()
    return [(out[r], eng.take_routes(r)) for r in rids], eng


# ----------------------------------------------------------- the model

def test_runs_param_tree_and_counts(model):
    config, params = model
    assert llama.layer_runs(config) == [("cca_attention", 0, 3, 0)]
    run, = params["runs"]
    assert run["cca_in"].shape == (3, 64, 96 + 32)
    assert run["cca_conv2_w"].shape == (3, 6, 32, 16)
    assert run["res_attn"].shape == run["res_mlp"].shape == (3, 4, 64)
    assert run["router"]["out"].shape == (3, 16, 4)
    assert run["router"]["down"].dtype == jnp.float32
    assert "lm_head" not in params and "w_router" not in run
    assert cca.tail_width(config) == 2 * 96 + 16
    assert llama.num_params(config) == sum(
        a.size for a in jax.tree_util.tree_leaves(params))
    assert config.attn_layers == config.cca_layers == 3
    assert not config.state_layers
    # The published widths, as ISSUE 46 C counts them.
    full = llama.LlamaConfig.zaya1_8b(num_layers=10)
    shapes = jax.eval_shape(lambda k: llama.init_params(full, k),
                            jax.random.PRNGKey(0))
    run, = shapes["runs"]
    attention = sum(run[k].size // 10 for k in run if k.startswith("cca_")
                    ) + run["wo"].size // 10
    router = sum(a.size // 10 for a in jax.tree_util.tree_leaves(
        run["router"]))
    experts = sum(a.size // 10 for a in jax.tree_util.tree_leaves(
        shapes["layers"]))
    assert round(attention / 1e6, 2) == 5.58
    assert round(router / 1e6, 2) == 0.66
    assert experts == 16 * 3 * 2048 * 2048
    assert round(llama.num_params(full) / 1e9, 2) == 2.61
    assert cca.tail_width(full) == 2688
    assert PagedKVCache.create(full, 2, 64).token_bytes() == 10 * 1024


def test_training_forward_refuses_the_family(model):
    config, params = model
    with pytest.raises(NotImplementedError, match="cca-attention"):
        llama.forward(params, jnp.zeros((1, 8), jnp.int32), config)


# ------------------------------------------- the engine and the reference

@pytest.mark.parametrize("n_prompt", [5, 16, 17, 40])
def test_prefill_and_ticks_give_the_references_logits(model, n_prompt):
    """Inside one chunk, ending ON a chunk boundary, one token past it
    (a chunk of one real row behind a carried tail) and three chunks;
    then 6 ticks."""
    config, params = model
    tokens = _tokens(n_prompt + 6, seed=n_prompt)
    got, routes = engine_logits(config, params, tokens, n_prompt)
    want, chosen = reference._forward(params, tokens, config)
    assert _close(got, want[n_prompt - 1:])
    assert (routes == np.asarray(chosen)[:, n_prompt:, 0].T).all()


@pytest.mark.parametrize("n_prompt", [1, 2])
def test_positions_0_and_1_start_from_empty_tails(model, n_prompt):
    """Position 0 reads zeros for ``u``, ``a`` and the shifted value
    half; position 1 is the first to read a real tail (from the prefill
    for a 1-token prompt's first tick, inside the chunk for a 2-token
    prompt)."""
    config, params = model
    tokens = _tokens(4, seed=7)
    got, _ = engine_logits(config, params, tokens, n_prompt)
    want = reference.logits(params, tokens, config)
    assert _close(got, want[n_prompt - 1:])


@pytest.mark.parametrize("n_prompt", range(17, 33))
def test_a_split_prompt_is_the_prompt_in_one_chunk(model, n_prompt):
    """Every position a chunk boundary can fall on: two chunks of 16
    whose second holds 1 .. 16 real rows (so its tail is its own last
    REAL row behind the carried one, never a padded row's) against the
    same prompt as one chunk of 32."""
    config, params = model
    tokens = _tokens(n_prompt + 3, seed=100 + n_prompt)
    split, routes = engine_logits(config, params, tokens, n_prompt)
    whole, routes_whole = engine_logits(config, params, tokens, n_prompt,
                                        chunk=32)
    assert _close(split, whole, rel=2e-5)
    assert (routes == routes_whole).all()


def test_the_served_tokens_and_routes_are_the_references(model):
    config, params = model
    prompts = [_tokens(n, seed=n) for n in (7, 16, 17, 37)]
    served, eng = _serve(config, params, prompts, num_slots=4)
    for prompt, (tokens, routes) in zip(prompts, served):
        gaps, want = reference.gaps_and_routes(params, prompt, tokens, config)
        assert not np.asarray(gaps).any()           # each token the argmax
        assert (np.asarray(routes) == np.asarray(want)).all()
    assert eng.prefill_chunk == CHUNK and isinstance(eng.state, TailCache)
    assert eng.state.tail.shape == (3, 4, 208)


def test_two_slots_do_not_read_each_others_tail(model):
    """Two requests with different histories in one engine, prefilled in
    one batch and ticked together, against each alone."""
    config, params = model
    prompts = [_tokens(19, seed=1), _tokens(33, seed=2)]
    together, _ = _serve(config, params, prompts)
    for prompt, both in zip(prompts, together):
        (alone,), _ = _serve(config, params, [prompt])
        assert alone == both


def test_the_cut_is_the_first_layers_of_the_uncut_stack():
    """A pipeline stage's first layers: the cut's engine against the
    reference's first 3 layers of a 5-layer stack (layer 0's router has
    no layer before it in both)."""
    uncut = tiny(num_layers=5)
    params = llama.init_params(uncut, jax.random.PRNGKey(3))
    config = tiny(num_layers=3)
    first = dict(params,
                 layers=jax.tree.map(lambda a: a[:3], params["layers"]),
                 runs=[jax.tree.map(lambda a: a[:3], params["runs"][0])])
    tokens = _tokens(30, seed=5)
    got, _ = engine_logits(config, first, tokens, 24)
    assert _close(got, reference.logits(params, tokens, uncut,
                                        num_layers=3)[23:])
    assert not _close(got, reference.logits(params, tokens, uncut)[23:],
                      rel=1e-2)


# ------------------------------------------------------------- the faults

def _no_value_shift(mix):
    def fault(u, v1, v2, tail, layer, c, lengths=None):
        q, k, v, tail = mix(u, v1, v2, tail, layer, c, lengths)
        now = jnp.concatenate([v1, v2], axis=-1)
        return q, k, now.reshape(v.shape), tail
    return fault


def _second_conv_padded_with_b1(mix):
    def fault(u, v1, v2, tail, layer, c, lengths=None):
        if tail is None:
            conv_dim, half = cca.dims(c)
            tail = jnp.concatenate([
                jnp.zeros((u.shape[0], conv_dim), u.dtype),
                jnp.broadcast_to(layer["cca_conv1_b"].astype(u.dtype),
                                 (u.shape[0], conv_dim)),
                jnp.zeros((u.shape[0], half), u.dtype)], axis=-1)
        return mix(u, v1, v2, tail, layer, c, lengths)
    return fault


def _tail_not_carried(mix):
    def fault(u, v1, v2, tail, layer, c, lengths=None):
        return mix(u, v1, v2, None, layer, c, lengths)
    return fault


def _tail_of_the_padded_row(mix):
    def fault(u, v1, v2, tail, layer, c, lengths=None):
        return mix(u, v1, v2, tail, layer, c, None)
    return fault


def _bf16_scores(mix):
    def fault(*args):
        q, k, v, tail = mix(*args)
        rounded = [x.astype(jnp.bfloat16).astype(x.dtype) for x in (q, k)]
        return (*rounded, v, tail)
    return fault


def _edit(params, **leaves):
    run = dict(params["runs"][0])
    for name, fn in leaves.items():
        if name.startswith("router_"):
            run["router"] = dict(run["router"])
            run["router"][name[7:]] = fn(run["router"][name[7:]])
        else:
            run[name] = fn(run[name])
    return dict(params, runs=[run])


# name -> (a wrapper of cca.mix or None, an edit of the tree or None)
FAULTS = {
    "no value shift": (_no_value_shift, None),
    "a_-1 = b1": (_second_conv_padded_with_b1, None),
    "tail not carried into a chunk or a tick": (_tail_not_carried, None),
    "tail of the padded row": (_tail_of_the_padded_row, None),
    "q and k rounded to bf16": (_bf16_scores, None),
    "tau dropped": (None, dict(cca_tau=jnp.ones_like)),
    "depth averaging dropped": (None, dict(router_gamma=jnp.zeros_like)),
    "selection bias dropped": (None, dict(router_beta=jnp.zeros_like)),
    "residual shifts dropped": (None, dict(
        res_attn=lambda a: a.at[:, 1::2].set(0.0))),
    "second convolution's bias dropped": (None, dict(
        cca_conv2_b=jnp.zeros_like)),
}


@pytest.mark.parametrize("name", list(FAULTS))
def test_each_fault_moves_the_logits_or_the_routes(model, monkeypatch, name):
    """19 prompt tokens in two chunks (the second padded), then 5 ticks:
    every fault is outside 1e-2 of a standard deviation, fifty times the
    tolerance, or changes a route."""
    config, params = model
    wrap, edit = FAULTS[name]
    if wrap:
        monkeypatch.setattr(cca, "mix", wrap(cca.mix))
    tokens = _tokens(24, seed=11)
    got, routes = engine_logits(
        config, _edit(params, **edit) if edit else params, tokens, 19,
        fresh=bool(wrap))
    want, chosen = reference._forward(params, tokens, config)
    moved = not _close(got, want[18:], rel=1e-2)
    rerouted = (routes != np.asarray(chosen)[:, 19:, 0].T).any()
    assert moved or rerouted


# ----------------------------------------------------------- the refusals

ASKED = {
    "kv_dtype": dict(kv_dtype="int8"),
    "speculative": dict(spec_k=2),
    "prefix_cache": dict(prefix_cache=True),
    "handoff": dict(role="prefill"),
}


@pytest.mark.parametrize("capability", list(_KIND_CANNOT["cca_attention"]))
def test_each_refusal_raises_by_name(model, capability):
    config, params = model
    why = _KIND_CANNOT["cca_attention"][capability]
    with pytest.raises(ValueError) as err:
        if capability == "second_kind":
            ContinuousBatcher(dataclasses.replace(
                config, layer_types=("full_attention",)
                + config.layer_types[1:]), num_slots=2, max_len=64,
                block_size=BS)
        elif capability == "score_logprobs":
            ContinuousBatcher(config, params=params, num_slots=2,
                              max_len=64, block_size=BS).score_logprobs(
                [1, 2, 3], [4])
        else:
            ContinuousBatcher(config, num_slots=2, max_len=64,
                              block_size=BS, **ASKED[capability])
    assert "cca-attention" in str(err.value) and why in str(err.value)
    assert "'cca_attention'" in str(err.value)


def test_the_prefix_cache_defaults_to_off_and_the_tail_is_a_gauge(model):
    config, params = model
    (_, eng), = [_serve(config, params, [_tokens(20)], max_new=3)]
    assert not eng.prefix_cache
    assert eng.state.nbytes == 3 * 2 * 208 * 4
    assert eng.state.nbytes in [
        v for _, _, v in mdefs.CB_CCA_TAIL_BYTES.samples()]
    assert eng.cache.k.nbytes + eng.cache.v.nbytes in [
        v for _, _, v in mdefs.CB_CCA_KV_BYTES.samples()]
