"""The plain reference against the program's own forward pass and loss,
at ``LlamaConfig.tiny()`` widths on the CPU, float32 on both sides."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference
from ray_tpu.models import llama


def _setup(seed=0, seq=48):
    config = llama.LlamaConfig.tiny(dtype=jnp.float32, attention="reference")
    params = llama.init_params(config, jax.random.PRNGKey(seed))
    tokens = np.random.default_rng(seed).integers(0, config.vocab_size, seq)
    return config, params, tokens


def test_logits_match_llama_forward():
    config, params, tokens = _setup()
    with jax.default_matmul_precision("highest"):
        want = llama.forward(params, jnp.asarray(tokens)[None], config)[0]
    got = reference.logits(params, tokens, config)
    # float32 both sides, other operation order: a few ulps of O(1) logits.
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=2e-4)


def test_loss_matches_llama_loss_fn():
    config, params, tokens = _setup(seed=3)
    batch = {"tokens": jnp.asarray(tokens)[None],
             "mask": jnp.ones((1, len(tokens)), jnp.int32)}
    with jax.default_matmul_precision("highest"):
        want, _ = llama.loss_fn(params, batch, config)
    got = reference.loss(params, tokens, config) / (len(tokens) - 1)
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))


def test_chosen_gap_is_zero_for_the_argmax_and_positive_otherwise():
    config, params, tokens = _setup(seed=5, seq=20)
    prompt = tokens[:12].tolist()
    chosen = []
    for _ in range(4):                      # greedy by the reference itself
        lg = reference.logits(params, prompt + chosen, config)
        chosen.append(int(jnp.argmax(lg[-1])))
    gaps = np.asarray(reference.chosen_gaps(params, prompt, chosen, config))
    assert gaps.shape == (4,) and np.all(gaps == 0.0)
    padded = np.asarray(reference.chosen_gaps(params, prompt, chosen, config,
                                              pad_to=40))
    assert np.all(padded == 0.0)             # padding changes nothing
    wrong = [(t + 1) % config.vocab_size for t in chosen]
    gaps = np.asarray(reference.chosen_gaps(params, prompt, wrong, config))
    assert gaps[0] > 0.0
