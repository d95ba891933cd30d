"""What a layer kind's cache cannot have is written once, in
``continuous_batching._KIND_CANNOT``, and one function raises it
(``_refuse_unsupported``): every entry of that table is asked for here,
of the constructor and of the methods that offer the capability on a
live engine, and the refusal names the kind, the capability as the
caller called it, and the table's reason. The cases are made FROM the
table, so an entry a later family adds is tested when it is written, and
one that no case below knows how to ask for fails by name.

The four per-family lists (``test_refused_by_name_for_*``) stay beside
their families: they pin the wording a user of that family greps for.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import inference, llama
from ray_tpu.models.continuous_batching import (_KIND_CANNOT, _KIND_NAMES,
                                                ContinuousBatcher)

BS = 8
_SMALL = dict(vocab_size=256, hidden_size=64, intermediate_size=32,
              num_heads=4, max_seq_len=256, dtype=jnp.float32)

# A served family's tiny config for each kind of the table.
CONFIGS = {
    "mamba": lambda: llama.LlamaConfig.granite_4_0_h_small(
        **_SMALL, num_layers=4,
        layer_types=("mamba", "mamba", "attention", "mamba"),
        num_kv_heads=2, head_dim=16, attention_multiplier=1 / 16,
        num_experts=8, num_experts_per_tok=2, shared_intermediate_size=48,
        mamba_n_heads=8, mamba_d_head=8, mamba_d_state=16),
    "linear_attention": lambda: llama.LlamaConfig.qwen3_next_80b_a3b(
        **_SMALL, num_layers=4,
        layer_types=("linear_attention",) * 3 + ("full_attention",),
        num_kv_heads=2, head_dim=32, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=16,
        linear_value_head_dim=24, num_experts=8, num_experts_per_tok=2,
        shared_intermediate_size=48),
    "mamba1": lambda: llama.LlamaConfig.jamba2_3b(
        **_SMALL, num_layers=4,
        layer_types=("mamba1", "attention", "mamba1", "mamba1"),
        num_kv_heads=1, head_dim=16, mamba_d_head=128, mamba_dt_rank=8),
    "sliding_attention": lambda: llama.LlamaConfig.trinity_large_preview(
        **_SMALL, num_layers=5,
        layer_types=("sliding_attention",) * 3 + ("full_attention",
                                                  "sliding_attention"),
        sliding_window=24, num_kv_heads=2, head_dim=16, num_dense_layers=1,
        dense_intermediate_size=96, num_experts=16, num_experts_per_tok=2,
        experts_held=(4, 4), shared_intermediate_size=32,
        embedding_multiplier=8.0),
    "latent_attention": lambda: llama.LlamaConfig.kimi_k2_7_code(
        **_SMALL, num_layers=3, layer_types=("latent_attention",) * 3,
        num_kv_heads=4, head_dim=24, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        dense_intermediate_size=96, num_experts=16, num_experts_per_tok=4,
        experts_held=(4, 4), shared_intermediate_size=32),
    "eva_attention": lambda: llama.LlamaConfig.evabyte_6_5b(
        **_SMALL, num_layers=2, num_kv_heads=4, head_dim=16, eva_window=32,
        eva_chunk=4, num_pred_heads=2),
    "cca_attention": lambda: llama.LlamaConfig.zaya1_8b(
        **_SMALL, num_layers=2, num_kv_heads=2, head_dim=16, num_experts=4,
        router_hidden_size=16),
    # Not a layer kind: the whole stack applied four times.
    "looped": lambda: llama.LlamaConfig.ouro_2_6b(
        **_SMALL, num_layers=3, num_kv_heads=4, head_dim=16, loop_steps=4),
}

# How a caller asks the constructor for a capability, and what the
# refusal calls it.
ASKED_OF_THE_CONSTRUCTOR = {
    "kv_dtype": (dict(kv_dtype="int8"), "kv_dtype='int8'"),
    "speculative": (dict(spec_k=2), "speculative decoding"),
    "prefix_cache": (dict(prefix_cache=True), "prefix cache"),
    "handoff": (dict(role="decode"), "role='decode'"),
}
# A second kind whose presence makes ``kind`` the first of the table's
# order to object (the refusal is the first kind's).
A_SECOND_KIND = {
    "mamba": "linear_attention", "linear_attention": "sliding_attention",
    "mamba1": "sliding_attention",
    "sliding_attention": "latent_attention",
    "latent_attention": "full_attention",
    "eva_attention": "full_attention",
    "cca_attention": "full_attention"}
# The methods that offer a capability on a live engine.
ASKED_OF_A_METHOD = {
    "handoff": [("export_kv_payload", (0,)), ("import_kv_payload", ({},)),
                ("reserve_import", (8, 4))],
    "score_logprobs": [("score_logprobs", ([1, 2], [3]))],
}
# The services outside the engine that run the layer stack once: each
# refuses a looped stack itself, with the table's reason.
ASKED_OF_A_SERVICE = {
    "llama.forward": lambda c: llama.forward(
        llama.init_params(c, jax.random.PRNGKey(0)),
        jnp.zeros((1, 8), jnp.int32), c),
    "LlamaGenerator": inference.LlamaGenerator,
    "ExternalLlamaDrafter": inference.ExternalLlamaDrafter,
}

ENTRIES = [(kind, capability) for kind, cannot in _KIND_CANNOT.items()
           for capability in cannot]


@functools.lru_cache(maxsize=None)
def _engine(kind):
    """One live engine a kind, built when a method case first wants it."""
    return ContinuousBatcher(CONFIGS[kind](), num_slots=2, max_len=64,
                             block_size=BS)


def _names(err, kind, capability, called):
    said = str(err.value)
    assert called in said.split(" is not supported")[0], said
    assert (f"{_KIND_NAMES[kind]} (loop_steps > 1)" if kind == "looped" else
            f"{_KIND_NAMES[kind]} layers (layer_types has {kind!r})") in said
    assert said.endswith(_KIND_CANNOT[kind][capability])


def test_the_table_covers_the_kinds_the_engine_keeps_a_cache_for():
    assert set(_KIND_CANNOT) == set(_KIND_NAMES) == set(CONFIGS) == {
        *llama.STATE_KINDS, "sliding_attention", "latent_attention",
        "eva_attention", "cca_attention", "looped"}


@pytest.mark.parametrize("kind,capability", [
    e for e in ENTRIES
    if e[1] == "second_kind" or e[1] in ASKED_OF_THE_CONSTRUCTOR],
    ids=lambda v: v)
def test_the_constructor_refuses_every_entry(kind, capability):
    config = CONFIGS[kind]()
    if capability == "second_kind":
        kwargs, called = {}, "another layer kind in the same stack"
        config = dataclasses.replace(config, layer_types=(
            A_SECOND_KIND[kind],) + config.layer_types[1:])
        assert kind in config.layer_types
    else:
        kwargs, called = ASKED_OF_THE_CONSTRUCTOR[capability]
    with pytest.raises(ValueError) as err:
        ContinuousBatcher(config, num_slots=2, max_len=64, block_size=BS,
                          **kwargs)
    _names(err, kind, capability, called)


@pytest.mark.parametrize("kind,capability,method,args", [
    (kind, capability, method, args) for kind, capability in ENTRIES
    for method, args in ASKED_OF_A_METHOD.get(capability, ())],
    ids=lambda v: v if isinstance(v, str) else "")
def test_a_live_engines_methods_refuse_every_entry(kind, capability, method,
                                                   args):
    with pytest.raises(ValueError) as err:
        getattr(_engine(kind), method)(*args)
    _names(err, kind, capability, method)


@pytest.mark.parametrize("kind,service", [
    e for e in ENTRIES if e[1] in ASKED_OF_A_SERVICE], ids=lambda v: v)
def test_a_service_that_runs_the_stack_once_refuses_every_entry(kind,
                                                                service):
    with pytest.raises(NotImplementedError) as err:
        ASKED_OF_A_SERVICE[service](CONFIGS[kind]())
    said = str(err.value)
    assert "loop_steps" in said
    if service != "llama.forward":      # its message is the forward's own
        assert said.startswith(f"{service} does not run")
        assert _KIND_CANNOT[kind][service] in said


def test_every_capability_of_the_table_is_asked_for_above():
    askable = {"second_kind", *ASKED_OF_THE_CONSTRUCTOR, *ASKED_OF_A_METHOD,
               *ASKED_OF_A_SERVICE}
    assert {c for _, c in ENTRIES} <= askable


@pytest.mark.parametrize("kind,capability", [
    (kind, capability) for kind in _KIND_CANNOT
    for capability in ASKED_OF_THE_CONSTRUCTOR
    if capability not in _KIND_CANNOT[kind]], ids=lambda v: v)
def test_what_the_table_does_not_name_is_not_refused(kind, capability):
    """The other side of the table: a capability a kind's entry leaves
    out is built (an int8 arena beside a recurrent state, the prefix
    cache over latent rows)."""
    kwargs, _ = ASKED_OF_THE_CONSTRUCTOR[capability]
    eng = ContinuousBatcher(CONFIGS[kind](), num_slots=2, max_len=64,
                            block_size=BS, **kwargs)
    assert eng.kv_dtype == kwargs.get("kv_dtype", "bf16")
    assert eng.prefix_cache or capability != "prefix_cache"
