"""Tests for ray_tpu.ops: flash attention, ring/Ulysses attention, norms, rope."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops.attention import (_causal_step, flash_attention,
                                   flash_block_steps, mha_reference)
from ray_tpu.ops.norms import layer_norm, rms_norm
from ray_tpu.ops.ring_attention import ring_attention, ulysses_attention
from ray_tpu.ops.rope import apply_rope, rope_frequencies


def _qkv(b=2, s=256, hq=4, hkv=2, d=128, dtype=jnp.float32):
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, hq, d), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, hkv, d), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, hkv, d), dtype)
    return q, k, v


# (sq, sk, block_q, block_k, q heads, kv heads, causal, dtype). Blocks of
# 512 are cut 4 x 4 on the diagonal (sub-tiles of one 128-lane tile, the
# smallest the kernels cut); smaller blocks are taken whole there.
_F32, _BF16 = jnp.float32, jnp.bfloat16
FLASH_CASES = [
    # the file's cases before PR 56: S in 2 blocks, and sq != sk
    (256, 256, 128, 128, 4, 2, True, _F32),
    (256, 256, 128, 128, 4, 2, False, _F32),
    (128, 256, 64, 64, 4, 2, True, _F32),
    (64, 256, 64, 64, 4, 2, True, _F32),
    (256, 128, 64, 64, 4, 2, True, _F32),       # sq > sk: the reference path
    # S in 2 and in 4 blocks with cut diagonal blocks; GQA groups 1, 2, 4
    (1024, 1024, 512, 512, 2, 2, True, _F32),
    (1024, 1024, 512, 512, 2, 1, True, _BF16),
    (2048, 2048, 512, 512, 1, 1, True, _BF16),
    (1024, 1024, 512, 512, 4, 1, False, _F32),
    # offs > 0: aligned (the diagonal through a block's corner), a whole
    # block of offset, and NOT aligned (the mask shifted by a traced
    # distance, the block one piece)
    (512, 1024, 512, 512, 4, 1, True, _F32),
    (512, 1536, 512, 512, 2, 1, True, _BF16),
    (192, 256, 64, 128, 2, 1, True, _F32),
    (256, 384, 128, 128, 4, 2, True, _BF16),
    (1024, 1024, 256, 256, 2, 2, True, _F32),   # 4 blocks, each whole
]


def _flash_id(case):
    sq, sk, bq, bk, hq, hkv, causal, dtype = case
    return (f"{sq}x{sk}-b{bq}x{bk}-h{hq}.{hkv}-"
            f"{'causal' if causal else 'full'}-{jnp.dtype(dtype).name}")


@pytest.mark.parametrize("case", FLASH_CASES, ids=_flash_id)
def test_flash_attention_matches_reference(case):
    """Values and all three gradients against ``mha_reference``: float32
    inputs at the tolerances this file always had, bf16 inputs (bf16
    operands into the products, as on the chip) at ``chip_smoke.py``'s
    (a share of the reference's largest magnitude)."""
    sq, sk, bq, bk, hq, hkv, causal, dtype = case
    q, _, _ = _qkv(b=1, s=sq, hq=hq, hkv=hkv, dtype=dtype)
    _, k, v = _qkv(b=1, s=sk, hq=hq, hkv=hkv, dtype=dtype)
    if causal and sq <= sk:
        skipped, interior, diagonal, _ = flash_block_steps(sq, sk, bq, bk)
        assert diagonal and (interior or skipped)

    def flash(*a):
        return flash_attention(*a, causal=causal, block_q=bq, block_k=bk)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2)

    with jax.default_matmul_precision("highest"):
        ref = mha_reference(q, k, v, causal=causal)
        out = flash(q, k, v)
        g1 = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss(functools.partial(mha_reference, causal=causal)),
                      argnums=(0, 1, 2))(q, k, v)

    def close(a, b, atol, share):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if dtype == _BF16:
            atol = share * np.abs(b).max()
        np.testing.assert_allclose(a, b, atol=atol, rtol=0)

    close(out, ref, 2e-5, 1e-2)
    for a, b in zip(g1, g2):
        close(a, b, 2e-3, 2e-2)


def test_flash_block_steps_is_the_kernels_schedule():
    """``flash_block_steps`` at the two train cells' shapes, and step by
    step against the mask itself: a step is skipped when none of its
    scores is live, interior when all are."""
    assert flash_block_steps(2048, 2048, 1024, 1024) == (1, 1, 2, 0.75)
    assert flash_block_steps(4096, 4096, 1024, 1024) == (6, 6, 4, 0.85)
    assert flash_block_steps(2048, 2048, 1024, 1024, causal=False) == (
        0, 4, 0, 1.0)
    for sq, sk, bq, bk in [(512, 512, 128, 128), (256, 512, 64, 128),
                           (192, 256, 64, 128), (128, 512, 128, 64)]:
        live = np.tril(np.ones((sq, sk), bool), k=sk - sq)
        blocks = live.reshape(sq // bq, bq, sk // bk, bk).transpose(0, 2, 1, 3)
        want = [(bool(blk.any()), bool(blk.all()))
                for row in blocks for blk in row]
        got = [tuple(map(bool, _causal_step(iq, ik, bq, bk, sk - sq)))
               for iq in range(sq // bq) for ik in range(sk // bk)]
        assert got == want
        skipped, interior, diagonal, _ = flash_block_steps(sq, sk, bq, bk)
        assert (skipped, interior, diagonal) == (
            sum(not r for r, _ in want), sum(i for _, i in want),
            sum(r and not i for r, i in want))


def test_flash_multiplies_nothing_above_the_diagonal():
    """NaNs where a dead product would read them. The last sub-tile's
    keys are NaN: only the last 128 queries see them; the mask hides
    their scores from every other query, but a product of an earlier
    query's (zero) ``ds`` with them would still be NaN. The first
    sub-tile's queries carry a NaN cotangent: only the first 128 keys'
    gradients may hold it."""
    s, blk, t = 1024, 512, 128
    q, k, v = _qkv(b=1, s=s, hq=2, hkv=1)

    def loss(w):
        return lambda *a: jnp.sum(
            flash_attention(*a, block_q=blk, block_k=blk) * w)

    bad_k = k.at[:, s - t:].set(jnp.nan)
    out = flash_attention(q, bad_k, v, block_q=blk, block_k=blk)
    assert np.isfinite(np.asarray(out[:, :s - t])).all()
    dq, _, _ = jax.grad(loss(1.0), argnums=(0, 1, 2))(q, bad_k, v)
    assert np.isfinite(np.asarray(dq[:, :s - t])).all()
    w = jnp.ones_like(q).at[:, :t].set(jnp.nan)
    _, dk, dv = jax.grad(loss(w), argnums=(0, 1, 2))(q, k, v)
    assert np.isfinite(np.asarray(dk[:, t:])).all()
    assert np.isfinite(np.asarray(dv[:, t:])).all()


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
