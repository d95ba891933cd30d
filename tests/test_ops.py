"""Tests for ray_tpu.ops: flash attention, ring/Ulysses attention, norms, rope."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops.attention import flash_attention, mha_reference
from ray_tpu.ops.norms import layer_norm, rms_norm
from ray_tpu.ops.ring_attention import ring_attention, ulysses_attention
from ray_tpu.ops.rope import apply_rope, rope_frequencies


def _qkv(b=2, s=256, hq=4, hkv=2, d=128, dtype=jnp.float32):
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, hq, d), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, hkv, d), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, hkv, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_reference(causal):
    q, k, v = _qkv()
    with jax.default_matmul_precision("highest"):
        ref = mha_reference(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_grads(causal):
    q, k, v = _qkv(s=256)

    with jax.default_matmul_precision("highest"):
        g1 = jax.grad(
            lambda *a: jnp.sum(
                flash_attention(*a, causal=causal, block_q=128, block_k=128) ** 2
            ),
            argnums=(0, 1, 2),
        )(q, k, v)
        g2 = jax.grad(
            lambda *a: jnp.sum(mha_reference(*a, causal=causal) ** 2),
            argnums=(0, 1, 2),
        )(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)


@pytest.mark.parametrize("sq,sk", [(128, 256), (64, 256), (256, 128)])
def test_flash_attention_cross_length_causal(sq, sk):
    # sq != sk must use bottom-right mask alignment (tril k=sk-sq), matching
    # mha_reference — the chunked-prefill / decode-with-cache shapes.
    q, _, _ = _qkv(s=sq)
    _, k, v = _qkv(s=sk)
    with jax.default_matmul_precision("highest"):
        ref = mha_reference(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    with jax.default_matmul_precision("highest"):
        g1 = jax.grad(
            lambda *a: jnp.sum(
                flash_attention(*a, causal=True, block_q=64, block_k=64) ** 2
            ),
            argnums=(0, 1, 2),
        )(q, k, v)
        g2 = jax.grad(
            lambda *a: jnp.sum(mha_reference(*a, causal=True) ** 2),
            argnums=(0, 1, 2),
        )(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)


def test_flash_attention_small_fallback():
    # Sequences below one block fall back to the reference path.
    q, k, v = _qkv(s=32, d=64)
    out = flash_attention(q, k, v, causal=True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def _seq_mesh():
    return Mesh(np.array(jax.devices()).reshape(4, 2), ("seq", "other"))


@pytest.mark.parametrize("impl", [ring_attention, ulysses_attention])
@pytest.mark.parametrize("causal", [True, False])
def test_sequence_parallel_attention(impl, causal):
    mesh = _seq_mesh()
    q, k, v = _qkv(b=2, s=512, hq=8, hkv=4, d=64)
    with jax.default_matmul_precision("highest"):
        ref = mha_reference(q, k, v, causal=causal)
        out = jax.shard_map(
            functools.partial(impl, causal=causal, axis_name="seq"),
            mesh=mesh,
            in_specs=(P(None, "seq"),) * 3,
            out_specs=P(None, "seq"),
            check_vma=False,
        )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ring_attention_grads():
    mesh = _seq_mesh()
    q, k, v = _qkv(b=1, s=256, hq=4, hkv=4, d=64)

    def loss_ring(q, k, v):
        out = jax.shard_map(
            functools.partial(ring_attention, causal=True, axis_name="seq"),
            mesh=mesh, in_specs=(P(None, "seq"),) * 3,
            out_specs=P(None, "seq"), check_vma=False,
        )(q, k, v)
        return jnp.sum(out ** 2)

    with jax.default_matmul_precision("highest"):
        g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(
            lambda *a: jnp.sum(mha_reference(*a, causal=True) ** 2),
            argnums=(0, 1, 2),
        )(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)


def test_rms_norm():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 32), jnp.float32)
    w = jnp.ones((32,)) * 2.0
    out = rms_norm(x, w)
    expected = x / np.sqrt(np.mean(np.square(np.asarray(x)), -1, keepdims=True) + 1e-6) * 2.0
    np.testing.assert_allclose(np.asarray(out), expected, atol=1e-5)


def test_layer_norm():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 32), jnp.float32)
    out = layer_norm(x, jnp.ones((32,)), jnp.zeros((32,)))
    xn = np.asarray(x)
    expected = (xn - xn.mean(-1, keepdims=True)) / np.sqrt(xn.var(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(np.asarray(out), expected, atol=1e-4)


def test_rope_rotation_preserves_norm():
    cos, sin = rope_frequencies(64, 128)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 128, 4, 64), jnp.float32)
    out = apply_rope(x, cos, sin)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(out), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1),
        rtol=1e-5,
    )
    # position 0 is the identity rotation
    np.testing.assert_allclose(
        np.asarray(out)[:, 0], np.asarray(x)[:, 0], atol=1e-6
    )
