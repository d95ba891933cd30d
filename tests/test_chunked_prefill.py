"""Chunked prefill and the token cap on a prefill call (PR 32), on the
CPU at small sizes, over the families the engine serves: a prompt over
``prefill_chunk`` runs as several ``cb_prefill`` calls, each attending
the earlier chunks out of the arena, and gives the tokens one call
gives; a wave over ``PREFILL_BATCH_TOKENS`` is split into calls of
fewer rows; a model with state-space layers is never chunked; and
programs compiled before any of this existed are the ones still
compiled at their sizes.

Tolerances: float32 on both sides, the same K/V read back from the
arena (stored in the model's dtype), so greedy tokens are EQUAL; the
blockwise softmax against the all-at-once one differs in operation
order alone.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ray_tpu.models import continuous_batching as cb  # noqa: E402
from ray_tpu.models import llama  # noqa: E402
from ray_tpu.models.continuous_batching import ContinuousBatcher  # noqa: E402

FAMILIES = {
    "mistral_gqa": lambda: llama.LlamaConfig.tiny(dtype=jnp.float32,
                                                  max_seq_len=256),
    "olmoe_mha_qk_norm_moe": lambda: llama.LlamaConfig.tiny(
        num_experts=8, num_experts_per_tok=2, qk_norm=True,
        intermediate_size=32, num_kv_heads=4, dtype=jnp.float32,
        max_seq_len=256),
}
GRANITE = lambda: llama.LlamaConfig.granite_4_0_h_small(  # noqa: E731
    vocab_size=256, hidden_size=64, intermediate_size=32, num_layers=4,
    layer_types=("mamba", "mamba", "attention", "mamba"), num_heads=4,
    num_kv_heads=2, head_dim=16, attention_multiplier=1 / 16,
    num_experts=8, num_experts_per_tok=2, shared_intermediate_size=48,
    mamba_n_heads=8, mamba_d_head=8, mamba_d_state=16, max_seq_len=256,
    dtype=jnp.float32)
LENGTHS = (9, 33, 47, 64, 100)

def _prefill_batches(eng):
    """Prefill batches ``eng`` ran: ``CB_PREFILL_MS`` books one each."""
    from ray_tpu._private import metrics_defs as mdefs

    return mdefs.CB_PREFILL_MS.totals(eng._mtags)[1]



@pytest.fixture(scope="module", params=list(FAMILIES))
def family(request):
    config = FAMILIES[request.param]()
    return config, llama.init_params(config, jax.random.PRNGKey(2))


def _prompts(lengths=LENGTHS, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, n).tolist() for n in lengths]


def _serve(config, params, prompts, max_new=8, **engine):
    engine = {**dict(num_slots=4, max_len=160, block_size=16,
                     prefix_cache=False), **engine}
    eng = ContinuousBatcher(config, params=params, **engine)
    rids = [eng.submit(p, max_new) for p in prompts]
    out = eng.run_to_completion()
    return [out[r] for r in rids], eng


@pytest.mark.parametrize("blockwise", [False, True],
                         ids=["dense-scores", "blockwise"])
def test_multi_chunk_prefill_is_the_one_chunk_prefill(family, blockwise,
                                                      monkeypatch):
    """Prompts of 1 to 7 chunks of 16 against the same prompts in one
    call each: the same greedy tokens, through the all-at-once scores
    (every chunk sees at most 1024 keys) and through the blockwise
    softmax over the arena (the limit lowered to 16 keys, so every later
    chunk takes it)."""
    config, params = family
    prompts = _prompts()
    want, one = _serve(config, params, prompts)
    assert one.prefill_chunk == 1024
    if blockwise:
        monkeypatch.setattr(cb, "PREFILL_DENSE_KEYS", 16)
    got, eng = _serve(config, params, prompts, prefill_chunk=16)
    assert got == want
    # 9 -> 1 call; 33 and 47 -> 3; 64 -> 4; 100 -> 7: five groups, no two
    # prompts share a chunk count but 33 and 47.
    assert _prefill_batches(eng) == 4
    assert eng.prefill_tokens == sum(LENGTHS)


def test_chunks_follow_a_matched_prefix(family):
    """With the prefix cache on, a long suffix behind a matched prefix
    is chunked from the prefix on: same tokens as the cold prompt."""
    config, params = family
    shared = _prompts((48,), seed=3)[0]
    tails = _prompts((40, 70), seed=4)
    prompts = [shared + t for t in tails]
    want, _ = _serve(config, params, prompts)
    eng = ContinuousBatcher(config, params=params, num_slots=4, max_len=160,
                            block_size=16, prefix_cache=True,
                            prefill_chunk=16)
    first = eng.submit(prompts[0], 8)
    out = eng.run_to_completion()
    second = eng.submit(prompts[1], 8)
    out.update(eng.run_to_completion())
    assert [out[first], out[second]] == want
    assert eng.prefix_hit_tokens >= 32


def test_a_prefix_hit_past_1024_keys_takes_the_blockwise_path(
        monkeypatch, pallas_interpret):
    """What chunked prefill changed for a deployment that had no long
    prompts: a prefix-cache hit whose prefix + padded suffix pass
    ``PREFILL_DENSE_KEYS`` (here 16 matched blocks of 64 + a 128-token
    bucket, at Mistral's head geometry, GQA 32/8 of 128) scores
    blockwise over the arena, no longer all at once in float32. Same
    greedy tokens as the cold prompt scored all at once in ONE call,
    through ticks on the (interpreted) paged kernel."""
    config = llama.LlamaConfig.tiny(
        hidden_size=128, num_heads=32, num_kv_heads=8, head_dim=128,
        intermediate_size=128, dtype=jnp.float32, max_seq_len=1280)
    params = llama.init_params(config, jax.random.PRNGKey(2))
    shared = _prompts((1024,), seed=6)[0]
    prompts = [shared + tail for tail in _prompts((30, 70), seed=7)]
    engine = dict(num_slots=2, max_len=1280, block_size=64,
                  use_decode_kernel=True)
    eng = ContinuousBatcher(config, params=params, prefix_cache=True,
                            **engine)
    paths = []
    real = cb.paged_chunk_attention
    monkeypatch.setattr(cb, "paged_chunk_attention", lambda *a, **kw: (
        paths.append(a[4].shape), real(*a, **kw))[1])
    first = eng.submit(prompts[0], 6)
    got = eng.run_to_completion()
    second = eng.submit(prompts[1], 6)
    got.update(eng.run_to_completion())
    assert eng.prefix_hit_tokens == 1024
    assert (1, 128) in eng._prefill_shapes
    assert paths                    # the hit's program scored blockwise
    monkeypatch.setattr(cb, "PREFILL_DENSE_KEYS", 4096)
    want, cold = _serve(config, params, prompts, max_new=6,
                        prefill_chunk=2048, **engine)
    assert cold._prefill_shapes == {(2, 1280)}      # one call, max_len
    assert [got[first], got[second]] == want


def test_a_wave_over_the_token_cap_is_split_into_calls_of_fewer_rows(
        family, monkeypatch):
    """Four prompts of one 32-token bucket under a cap of 64 tokens a
    call: two calls of two rows, the same tokens as one call of four."""
    config, params = family
    prompts = _prompts((20, 25, 30, 31), seed=5)
    want, whole = _serve(config, params, prompts)
    monkeypatch.setattr(cb, "PREFILL_BATCH_TOKENS", 64)
    got, capped = _serve(config, params, prompts)
    assert got == want
    assert (_prefill_batches(whole), _prefill_batches(capped)) == (1, 2)
    assert (4, 32) in whole._prefill_shapes
    assert capped._prefill_shapes == {(2, 32)}
    # A cap under one row's tokens still admits a row at a time.
    monkeypatch.setattr(cb, "PREFILL_BATCH_TOKENS", 8)
    got, single = _serve(config, params, prompts)
    assert got == want and _prefill_batches(single) == 4


def test_the_default_cap_and_chunk_split_no_batch_of_the_existing_cells():
    """8 rows x 1024 tokens, the largest batch the benchmark's six older
    cells warm, is one call at the defaults; 48 rows x 128 too."""
    eng = ContinuousBatcher(llama.LlamaConfig.tiny(), num_slots=48,
                            max_len=64, block_size=16)
    assert (eng.prefill_chunk, cb.PREFILL_BATCH_TOKENS) == (1024, 8192)
    many = {(1024, 0, 1): list(range(8)), (128, 0, 1): list(range(48))}
    assert [len(g) for _, g in eng._prefill_batches(many)] == [8, 48]
    assert [len(g) for _, g in eng._prefill_batches(
        {(1024, 0, 3): list(range(11))})] == [8, 3]


def test_a_state_space_model_is_never_chunked():
    """Its recurrent state would have to be carried between chunks: a
    long prompt stays ONE call, whatever ``prefill_chunk`` says."""
    config = GRANITE()
    params = llama.init_params(config, jax.random.PRNGKey(2))
    prompts = _prompts((100,))
    want, _ = _serve(config, params, prompts, prefix_cache=None)
    got, eng = _serve(config, params, prompts, prefix_cache=None,
                      prefill_chunk=16)
    assert eng.prefill_chunk is None
    assert got == want and (1, 128) in eng._prefill_shapes


def test_chunk_phase_is_booked_once_a_program_call(family):
    from ray_tpu._private import metrics_defs as mdefs

    def count():
        return sum(v for n, _, v in mdefs.CB_PREFILL_CHUNK_MS.samples()
                   if n.endswith("_count"))

    config, params = family
    before = count()
    _serve(config, params, _prompts((100, 9)), prefill_chunk=16)
    assert count() - before == 7 + 1


def test_a_chunk_under_one_block_is_refused():
    with pytest.raises(ValueError, match="under one block"):
        ContinuousBatcher(llama.LlamaConfig.tiny(), num_slots=2, max_len=64,
                          block_size=16, prefill_chunk=8)


def test_a_state_space_models_long_prompt_keeps_the_dense_path(monkeypatch):
    """Over the dense-score limit too (lowered to 16 keys here): its
    prefill must install a state, which the blockwise path does not."""
    monkeypatch.setattr(cb, "PREFILL_DENSE_KEYS", 16)
    config = GRANITE()
    params = llama.init_params(config, jax.random.PRNGKey(2))
    prompts = _prompts((100, 20))
    got, eng = _serve(config, params, prompts, prefix_cache=None)
    monkeypatch.undo()
    want, _ = _serve(config, params, prompts, prefix_cache=None)
    assert got == want and eng.state_installs == 2
