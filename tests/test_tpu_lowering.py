"""Every Pallas kernel, and the sharded train step, must LOWER for TPU.

Interpret mode (what the rest of tier-1 runs) turns a kernel into plain
HLO and skips the TPU lowering's rules — block shapes in whole trailing
tiles, rank-1 SMEM blocks, Mosaic kernels under GSPMD — so a kernel that
can only be interpreted passes every CPU test and is refused on the chip.
``jax.export`` with ``platforms=["tpu"]`` runs that lowering on a CPU
host in milliseconds; ``RAY_TPU_PALLAS_INTERPRET=0`` makes the
dispatchers emit the real kernel. (Lowering is not compiling: Mosaic's
own checks and VMEM limits need the chipless AOT compile described in
README "Development", or the chip.)
"""

import functools

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import llama
from ray_tpu.models.training import ShardedTrainer
from ray_tpu.ops.attention import flash_attention
from ray_tpu.ops.decode_attention import decode_attention
from ray_tpu.ops.paged_decode_attention import paged_decode_attention
from ray_tpu.parallel import MeshConfig, make_mesh

S = jax.ShapeDtypeStruct
BF16 = jnp.bfloat16


@pytest.fixture(autouse=True)
def _real_kernels(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "0")


def _mosaic_calls(fn, *specs) -> int:
    exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(*specs)
    return exported.mlir_module().count("tpu_custom_call")


# (q heads, kv heads): the 953M config's MHA, and one GQA shape.
HEADS = [(16, 16), (32, 8)]


@pytest.mark.parametrize("hq,hkv", HEADS)
def test_flash_forward_and_gradient_lower(hq, hkv):
    q = S((2, 2048, hq, 128), BF16)
    kv = S((2, 2048, hkv, 128), BF16)
    fwd = functools.partial(flash_attention, causal=True)
    assert _mosaic_calls(fwd, q, kv, kv) == 1

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    # forward (for residuals) + dq + dk/dv
    assert _mosaic_calls(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv) == 3


@pytest.mark.parametrize("hq,hkv", HEADS)
def test_dense_decode_lowers(hq, hkv):
    q = S((32, hq, 128), BF16)
    cache = S((32, 512, hkv, 128), BF16)
    fn = functools.partial(decode_attention, use_kernel=True)
    assert _mosaic_calls(fn, q, cache, cache, S((32,), jnp.int32)) == 1


@pytest.mark.parametrize("hq,hkv", HEADS)
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_decode_lowers(hq, hkv, kv_dtype):
    """32 slots, block 64, 8-block tables over a 257-block arena."""
    q = S((32, hq, 128), BF16)
    tables, positions = S((32, 8), jnp.int32), S((32,), jnp.int32)
    if kv_dtype == "int8":
        arena = S((257, hkv, 64, 128), jnp.int8)
        scale = S((257, hkv, 64), jnp.float32)

        def fn(q, k, v, t, p, ks, vs):
            return paged_decode_attention(q, k, v, t, p, k_scale=ks,
                                          v_scale=vs, use_kernel=True)

        assert _mosaic_calls(fn, q, arena, arena, tables, positions,
                             scale, scale) == 1
    else:
        arena = S((257, hkv, 64, 128), BF16)
        fn = functools.partial(paged_decode_attention, use_kernel=True)
        assert _mosaic_calls(fn, q, arena, arena, tables, positions) == 1


@pytest.mark.parametrize("fsdp", [1, 4])
def test_sharded_train_step_lowers_with_flash(fsdp):
    """GSPMD cannot partition a Mosaic kernel: on a mesh of more than one
    device the flash call must sit inside a shard_map, or this raises
    ``Mosaic kernels cannot be automatically partitioned``."""
    config = llama.LlamaConfig.tiny(
        vocab_size=512, hidden_size=256, intermediate_size=512,
        num_layers=2, num_heads=2, num_kv_heads=2, head_dim=128,
        max_seq_len=128, remat=True)
    mesh = make_mesh(MeshConfig(fsdp=fsdp), devices=jax.devices()[:fsdp])
    trainer = ShardedTrainer(config, mesh)
    state = jax.eval_shape(trainer._init._jitted, jax.random.PRNGKey(0))
    batch = {"tokens": S((4, 128), jnp.int32), "mask": S((4, 128), jnp.int32)}
    with mesh:
        exported = jax.export.export(
            trainer._step._jitted, platforms=["tpu"])(state, batch)
    # flash forward, its remat replay, dq, dk/dv — inside the layer scan.
    assert exported.mlir_module().count("tpu_custom_call") >= 3
