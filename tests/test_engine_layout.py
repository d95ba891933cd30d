"""The engine's parameter layout (PR 30): ``ContinuousBatcher`` holds
every attention layer's ``wq``/``wk``/``wv`` heads-major (``wq_heads``
``[L, H, E, D]``, :func:`llama.heads_major`) while everything outside it
(``init_params``, the trainer, checkpoints, weight sync, the benchmark's
references) keeps the canonical ``[L, E, H, D]``. On the CPU at small
sizes, over the three families the engine serves: GQA Llama, OLMoE (MHA,
QK-norm, routed experts) and Granite 4.0-H (attention runs beside
Mamba-2 runs in ``params["runs"]``).

Tolerances are the files' beside this one: float32 on both sides, the
same numbers in another operation order, so 1e-4 on O(1) logits and log
probabilities, and greedy tokens equal to the canonical tree's argmax.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import reference_granite_hybrid  # noqa: E402
from ray_tpu.models import llama  # noqa: E402
from ray_tpu.models.continuous_batching import (  # noqa: E402
    ContinuousBatcher, init_engine_params)
from ray_tpu.models.inference import ExternalLlamaDrafter  # noqa: E402

FAMILIES = {
    "gqa": lambda: llama.LlamaConfig.tiny(dtype=jnp.float32),
    "mha_qk_norm_moe": lambda: llama.LlamaConfig.tiny(
        num_experts=8, num_experts_per_tok=2, qk_norm=True,
        intermediate_size=32, num_kv_heads=4, dtype=jnp.float32,
        attention="reference"),
    "hybrid_runs": lambda: llama.LlamaConfig.granite_4_0_h_small(
        vocab_size=256, hidden_size=64, intermediate_size=32, num_layers=4,
        layer_types=("mamba", "mamba", "attention", "mamba"), num_heads=4,
        num_kv_heads=2, head_dim=16, attention_multiplier=1 / 16,
        num_experts=8, num_experts_per_tok=2, shared_intermediate_size=48,
        mamba_n_heads=8, mamba_d_head=8, mamba_d_state=16, max_seq_len=128,
        dtype=jnp.float32),
}
PROMPTS = [[5, 17, 3, 201, 44, 9, 120], [77, 2, 31], [8] * 18 + [41, 6]]


@pytest.fixture(scope="module", params=list(FAMILIES))
def family(request):
    config = FAMILIES[request.param]()
    return config, llama.init_params(config, jax.random.PRNGKey(4))


def _homes(params):
    """Every tree a layer scan takes: ``layers``, and each run."""
    return [params["layers"], *params.get("runs", ())]


def _engine(config, params=None, **kw):
    return ContinuousBatcher(config, params=params, num_slots=4,
                             max_len=128, block_size=16, **kw)


def _generate(eng, max_new=6):
    rids = [eng.submit(p, max_new) for p in PROMPTS]
    out = eng.run_to_completion()
    return [out[r] for r in rids]


def _canonical_logits(config, params, seq):
    """Float32 logits [len(seq), V] of the CANONICAL tree: the training
    forward, or for the hybrid family (which it refuses) the benchmark's
    plain reference."""
    if config.layer_types:
        return reference_granite_hybrid.logits(params, seq, config)
    return llama.forward(params, jnp.asarray([seq], jnp.int32), config)[0]


def _canonical_tokens(config, params, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(jnp.argmax(_canonical_logits(config, params, seq)[-1])))
    return seq[len(prompt):]


def _assert_trees_equal(got, want):
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_install_is_a_transpose_under_names_of_its_own(family):
    """Both homes of the weights are re-laid, the canonical names are
    gone from the engine's tree (so ``project_qkv`` reads the layout from
    the tree), every other leaf is untouched, the inverse gives the
    caller's tree back bit for bit, and a tree already re-laid (another
    engine's) is taken as it is."""
    config, params = family
    eng = _engine(config, params)
    seen = 0
    for got, want in zip(_homes(eng.params), _homes(params)):
        assert not set(llama.HEADS_MAJOR) & set(got)
        for name, relaid in llama.HEADS_MAJOR.items():
            if name not in want:
                assert relaid not in got
                continue
            seen += 1
            layers, embed, heads, dim = want[name].shape
            assert got[relaid].shape == (layers, heads, embed, dim)
            np.testing.assert_array_equal(
                np.asarray(got[relaid]),
                np.asarray(want[name]).transpose(0, 2, 1, 3))
        for name in set(want) - set(llama.HEADS_MAJOR):
            np.testing.assert_array_equal(np.asarray(got[name]),
                                          np.asarray(want[name]))
    assert seen == 3        # one attention home a family
    _assert_trees_equal(llama.canonical_layout(eng.params), params)
    _assert_trees_equal(llama.canonical_layout(params), params)
    _assert_trees_equal(_engine(config, eng.params).params, eng.params)
    # init_params' tree keeps its names: the layout is the engine's.
    assert all(set(llama.HEADS_MAJOR.values()).isdisjoint(home)
               for home in _homes(params))


def test_engine_seeds_its_own_weights_in_its_layout(family):
    """``cb_init`` is ``init_params`` re-laid inside the one program: the
    seeded numbers are ``init_params``' own."""
    config, _ = family
    eng = _engine(config, seed=11)
    key = jax.random.PRNGKey(11)
    # Jitted, as the benchmark's runners seed their references' weights
    # (an eager init_params rounds its products an ulp apart).
    want = jax.jit(lambda k: llama.init_params(config, k))(key)
    _assert_trees_equal(llama.canonical_layout(eng.params), want)
    _assert_trees_equal(
        eng.params, jax.jit(lambda k: init_engine_params(config, k))(key))


def test_engine_generates_the_canonical_trees_argmax(family):
    """Prefill and ticks on the re-laid tree choose the tokens the
    canonical tree's forward chooses; for the families ``llama.forward``
    runs, that forward on the ENGINE's tree gives the canonical logits
    (the one ``project_qkv`` contracting either layout)."""
    config, params = family
    eng = _engine(config, params)
    got = _generate(eng)
    for prompt, tokens in zip(PROMPTS, got):
        assert tokens == _canonical_tokens(config, params, prompt, 6)
    if not config.layer_types:
        tokens = jnp.asarray([PROMPTS[2]], jnp.int32)
        np.testing.assert_allclose(
            np.asarray(llama.forward(eng.params, tokens, config)),
            np.asarray(llama.forward(params, tokens, config)),
            rtol=0, atol=1e-4)


def test_swap_params_takes_a_canonical_tree(family):
    """A swap hands over what ``rl/weight_sync`` hands over today, the
    canonical tree, and the engine then generates what a fresh engine
    built on those weights generates."""
    config, params = family
    fresh = llama.init_params(config, jax.random.PRNGKey(9))
    # No prefix cache: a swap does not flush it (PERF.md section 7), so
    # a repeated prompt would attend K/V the old weights wrote.
    eng = _engine(config, params, prefix_cache=False)
    before = _generate(eng)
    assert eng.swap_params(jax.tree.map(np.asarray, fresh)) == 1
    for home in _homes(eng.params):
        assert not set(llama.HEADS_MAJOR) & set(home)
    after = _generate(eng)
    assert after == _generate(_engine(config, fresh))
    assert after != before


def test_swap_params_still_refuses_what_it_refused(family):
    """Validation is against the CANONICAL signature of the tree the
    engine was built with, with the messages it had: a missing leaf, a
    leaf of another shape, and the engine's own layout handed back."""
    config, params = family
    eng = _engine(config, params)
    missing = dict(params)
    del missing["final_norm"]
    with pytest.raises(ValueError, match="swap_params treedef mismatch"):
        eng.swap_params(missing)
    with pytest.raises(ValueError, match="swap_params treedef mismatch"):
        eng.swap_params(eng.params)
    wrong = dict(params, embed=np.zeros((3, 5), np.float32))
    with pytest.raises(ValueError,
                       match=r"swap_params leaf \d+ mismatch: engine has "
                             r"\(256, 64\)/float32, swap brought \(3, 5\)"):
        eng.swap_params(wrong)
    home = "runs" if config.layer_types else "layers"
    trees = params[home] if config.layer_types else [params[home]]
    swapped = [dict(t, wq=np.swapaxes(np.asarray(t["wq"]), 1, 2))
               if "wq" in t else t for t in trees]
    transposed = dict(params, **{
        home: swapped if config.layer_types else swapped[0]})
    with pytest.raises(ValueError, match=r"swap_params leaf \d+ mismatch"):
        eng.swap_params(transposed)
    assert eng.weight_version == 0


def test_score_logprobs_runs_on_the_engines_tree(family):
    """The teacher-forced score is ``llama.forward`` on ``self.params``,
    the re-laid tree: it prices the canonical tree's log probabilities
    (the hybrid family is refused by that forward, as before PR 30)."""
    config, params = family
    eng = _engine(config, params)
    prompt, out = PROMPTS[0], [12, 250, 3, 99]
    if config.layer_types:
        with pytest.raises(NotImplementedError, match="layer_types"):
            eng.score_logprobs(prompt, out)
        return
    logp = jax.nn.log_softmax(
        _canonical_logits(config, params, prompt + out))
    at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(out))
    np.testing.assert_allclose(eng.score_logprobs(prompt, out),
                               np.asarray(logp)[at, out], rtol=0, atol=1e-4)


def test_external_drafters_tree_is_installed_too():
    """The drafter's canonical tree goes through the same install, and
    speculation still commits the target's greedy stream."""
    config = FAMILIES["gqa"]()
    params = llama.init_params(config, jax.random.PRNGKey(4))
    drafter = ExternalLlamaDrafter(config, params=params)
    eng = _engine(config, params, spec_k=2, spec_adaptive=False,
                  drafter=drafter)
    assert set(llama.HEADS_MAJOR.values()) <= set(eng._draft_params["layers"])
    assert "wq" in drafter.params["layers"]      # the caller's is untouched
    assert _generate(eng) == _generate(_engine(config, params))
    assert eng.spec_draft_tokens > 0
