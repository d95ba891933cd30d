"""Tests for the flagship model + sharded training across mesh layouts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.models.training import (
    ShardedTrainer,
    default_optimizer,
    synthetic_batch,
)
from ray_tpu.ops.attention import FLASH_RESIDUAL_NAMES, flash_attention
from ray_tpu.parallel import MeshConfig, make_mesh, mesh_shape


def _trainer(mesh_cfg: MeshConfig, **model_kw):
    cfg = llama.LlamaConfig.tiny(**model_kw)
    mesh = make_mesh(mesh_cfg)
    return cfg, ShardedTrainer(
        cfg, mesh, optimizer=default_optimizer(warmup_steps=2, total_steps=50,
                                               learning_rate=1e-2)
    )


def test_forward_shapes():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 32), jnp.int32)
    logits = llama.forward(params, tokens, cfg)
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_num_params_matches():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    actual = sum(x.size for x in jax.tree.leaves(params))
    assert actual == llama.num_params(cfg)


@pytest.mark.parametrize(
    "mesh_cfg",
    [
        MeshConfig(data=8, fsdp=1),                      # pure DP
        MeshConfig(data=1, fsdp=8),                      # pure FSDP
        MeshConfig(data=1, fsdp=2, tensor=4),            # FSDP + TP
        MeshConfig(data=1, fsdp=2, tensor=2, seq=2),     # FSDP + TP + SP(ring)
    ],
    ids=["dp", "fsdp", "fsdp_tp", "fsdp_tp_sp"],
)
def test_train_step_all_mesh_layouts(mesh_cfg):
    cfg, trainer = _trainer(mesh_cfg)
    state = trainer.init_state(0)
    batch = trainer.shard_batch(synthetic_batch(8, 64, cfg.vocab_size))
    state, metrics = trainer.train_step(state, batch)
    assert int(state.step) == 1
    assert np.isfinite(float(metrics["loss"]))


def test_loss_decreases_under_training():
    cfg, trainer = _trainer(MeshConfig(data=1, fsdp=8))
    state = trainer.init_state(0)
    batch = trainer.shard_batch(synthetic_batch(8, 64, cfg.vocab_size))
    first = None
    for _ in range(20):
        state, metrics = trainer.train_step(state, batch)
        first = first if first is not None else float(metrics["loss"])
    last = float(metrics["loss"])
    assert last < first * 0.7, (first, last)


def test_sharding_layouts_agree():
    """The same model step computed under DP and FSDP+TP meshes must match."""
    batch = synthetic_batch(8, 64, 256)
    losses = {}
    with jax.default_matmul_precision("highest"):
        for name, mesh_cfg in {
            "dp": MeshConfig(data=8, fsdp=1),
            "fsdp_tp": MeshConfig(data=1, fsdp=2, tensor=4),
        }.items():
            cfg, trainer = _trainer(mesh_cfg, dtype=jnp.float32)
            state = trainer.init_state(0)
            _, metrics = trainer.train_step(state, trainer.shard_batch(batch))
            losses[name] = float(metrics["loss"])
    assert abs(losses["dp"] - losses["fsdp_tp"]) < 1e-3, losses


@pytest.mark.parametrize(
    "mesh_cfg", [MeshConfig(fsdp=4), MeshConfig(fsdp=2, tensor=2)],
    ids=["fsdp4", "fsdp2_tp2"])
def test_sharded_step_matches_the_one_device_step(mesh_cfg):
    """The head made whole before the loss scan, with its gradient summed
    across chips once after it, and the norm weights never sharded: the
    step's loss and gradients are the one-device step's, and ten steps'
    losses follow it. Gradients are read off a plain SGD step
    (``(before - after) / rate``), so it is the trainer's own step that
    is compared."""
    import optax

    rate = 0.1
    batch = synthetic_batch(4, 64, 256)

    def ten_steps(cfg_mesh):
        mesh = make_mesh(cfg_mesh, devices=jax.devices()[
            :cfg_mesh.fsdp * cfg_mesh.tensor])
        trainer = ShardedTrainer(llama.LlamaConfig.tiny(dtype=jnp.float32),
                                 mesh, optimizer=optax.sgd(rate))
        state = trainer.init_state(0)
        before = jax.tree.map(np.asarray, state.params)
        sharded = trainer.shard_batch(batch)
        state, metrics = trainer.train_step(state, sharded)
        grads = jax.tree.map(lambda b, a: (b - np.asarray(a)) / rate,
                             before, state.params)
        losses = [float(metrics["loss"])]
        for _ in range(9):
            state, metrics = trainer.train_step(state, sharded)
            losses.append(float(metrics["loss"]))
        return losses, grads

    with jax.default_matmul_precision("highest"):
        ref_losses, ref = ten_steps(MeshConfig(fsdp=1))
        losses, got = ten_steps(mesh_cfg)
    assert np.all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    # test_sharding_layouts_agree's tolerance.
    np.testing.assert_allclose(losses, ref_losses, atol=1e-3, rtol=0)
    for name in ("lm_head", "attn_norm", "mlp_norm", "w_down"):
        g, r = (tree[name] if name in tree else tree["layers"][name]
                for tree in (got, ref))
        assert np.abs(r).max() > 1e-3, name     # a gradient, not zeros
        np.testing.assert_allclose(
            g, r, rtol=0, atol=1e-4 * np.abs(r).max(), err_msg=name)


def test_params_actually_sharded():
    cfg, trainer = _trainer(MeshConfig(data=1, fsdp=8))
    state = trainer.init_state(0)
    # w_gate is embed-sharded on fsdp: each device holds 1/8 of it.
    w = state.params["layers"]["w_gate"]
    shard = w.addressable_shards[0]
    assert shard.data.size == w.size // 8
    mesh = trainer.mesh
    assert mesh_shape(mesh)["fsdp"] == 8


# --------------------------------------------- what a rematted backward keeps

REMAT_POLICIES = ["full", "attn_out", "mlp_only"]
# The smallest shape the flash KERNEL takes (head_dim a multiple of 128, a
# sequence that tiles): interpreted on CPU, GQA 2/1, float32 throughout.
_B, _S, _H, _KVH, _D = 3, 32, 2, 1, 128


def _flash_model(**kw):
    cfg = llama.LlamaConfig.tiny(
        hidden_size=64, num_heads=_H, num_kv_heads=_KVH, head_dim=_D,
        max_seq_len=_S, dtype=jnp.float32, **kw)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (_B, _S), 0, cfg.vocab_size)}
    return cfg, params, batch


def _loss_grads(cfg, params, batch):
    return jax.jit(jax.grad(
        lambda p: llama.loss_fn(p, batch, cfg)[0]))(params)


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        v = getattr(v, "jaxpr", v)
        if hasattr(v, "eqns"):
            yield v


def _eqns(jaxpr, primitive):
    """Every equation of ``primitive`` in ``jaxpr`` and below it."""
    for e in jaxpr.eqns:
        if e.primitive.name == primitive:
            yield e
        for sub in _sub_jaxprs(e):
            yield from _eqns(sub, primitive)


def _kernels(jaxpr):
    return sorted(e.params["name"] for e in _eqns(jaxpr, "pallas_call"))


@pytest.mark.parametrize("policy", REMAT_POLICIES)
def test_remat_gradients_match_unrematted(pallas_interpret, policy):
    """A checkpointed layer changes what the backward RECOMPUTES, never
    what it computes. The flash kernel's out and lse are kept, not
    recomputed, so attention adds nothing to the difference; the norms,
    rope, SwiGLU and (under "attn_out"/"mlp_only") the matmuls are
    recomputed and may fuse in another order than the forward's. In
    float32 that is rounding in the last bits: 1e-5 of each leaf's largest
    gradient is 25 times what this shape reads (4.1e-7 under every policy)
    and far under what a dropped or stale residual would show."""
    cfg, params, batch = _flash_model(remat=False)
    want = _loss_grads(cfg, params, batch)
    cfg_r, _, _ = _flash_model(remat=True, remat_policy=policy)
    got = _loss_grads(cfg_r, params, batch)
    for (path, w), g in zip(jax.tree_util.tree_leaves_with_path(want),
                            jax.tree.leaves(got)):
        w, g = np.asarray(w), np.asarray(g)
        assert np.abs(w).max() > 0, path
        assert np.abs(g - w).max() <= 1e-5 * np.abs(w).max(), path


@pytest.mark.parametrize("policy", REMAT_POLICIES)
def test_remat_keeps_flash_residuals(pallas_interpret, policy):
    """The forward layer scan hands the backward the kernel's output
    ``flash_out`` (the kernel's layout, ``[B, H, S, D]``) and
    ``flash_lse`` as ``[B, H, S]``, one of each a layer; nothing of shape
    ``[B, H, S, 1]`` is saved (a minor dimension of 1 pads to 128 lanes on
    the chip); and the backward scan runs the two backward kernels and NO
    second flash forward."""
    cfg, params, batch = _flash_model(remat=True, remat_policy=policy)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: llama.loss_fn(p, batch, cfg)[0]))(params).jaxpr
    fwd, bwd = [e for e in jaxpr.eqns if e.primitive.name == "scan"
                and e.params["length"] == cfg.num_layers]
    body = fwd.params["jaxpr"].jaxpr
    named = {e.params["name"]: e.outvars[0] for e in _eqns(body, "name")}
    for name, shape in (("flash_out", (_B, _H, _S, _D)),
                        ("flash_lse", (_B, _H, _S))):
        assert named[name].aval.shape == shape
    # What the scan stacks for the backward: each named buffer once a layer.
    saved = [v.aval.shape for v in fwd.outvars]
    assert saved.count((cfg.num_layers, _B, _H, _S, _D)) == 1
    assert saved.count((cfg.num_layers, _B, _H, _S)) == 1
    assert (cfg.num_layers, _B, _H, _S, 1) not in saved
    assert _kernels(body) == ["flash_fwd"]
    assert _kernels(bwd.params["jaxpr"].jaxpr) == [
        "flash_bwd_dkv", "flash_bwd_dq"]


def _flash_grads(wrap):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (_B, _S, _H, _D), jnp.float32)
    k, v = (jax.random.normal(key, (_B, _S, _KVH, _D), jnp.float32)
            for key in ks[1:])
    attend = wrap(lambda q, k, v: flash_attention(q, k, v, causal=True))
    grad = jax.grad(lambda *a: jnp.sum(jnp.sin(attend(*a))),
                    argnums=(0, 1, 2))
    return jax.make_jaxpr(grad)(q, k, v).jaxpr, jax.jit(grad)(q, k, v)


@pytest.mark.parametrize("how", ["bare", "kept", "replayed"])
def test_flash_residual_names_are_inert(pallas_interpret, how):
    """Outside a ``jax.checkpoint`` the names change nothing: a bare
    gradient is the forward kernel once and the two backward kernels, as
    before they were named. Under a checkpoint that keeps the names the
    program is those same three kernels; under one that saves nothing it
    is four, the forward run again. All three give the SAME BITS: the
    backward kernels read the same out and lse either way."""
    policies = jax.checkpoint_policies
    wrap = {
        "bare": lambda f: f,
        "kept": lambda f: jax.checkpoint(
            f, policy=policies.save_only_these_names(*FLASH_RESIDUAL_NAMES)),
        "replayed": lambda f: jax.checkpoint(
            f, policy=policies.nothing_saveable),
    }[how]
    jaxpr, got = _flash_grads(wrap)
    assert _kernels(jaxpr) == (
        ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
        + ["flash_fwd"] * (how == "replayed"))
    _, want = _flash_grads(lambda f: f)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
