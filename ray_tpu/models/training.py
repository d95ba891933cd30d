"""Sharded training step for ray_tpu models.

Builds the jitted GSPMD train step the Train library and the benchmarks run:
parameters/optimizer state are sharded by the logical-axis rule table
(:mod:`ray_tpu.parallel.sharding`), the batch is sharded over the data axes,
and XLA inserts all collectives (reduce-scatter/all-gather for FSDP, psum for
DP) — the TPU-native equivalent of the reference's DDP/FSDP wrappers
(reference: ``python/ray/train/torch/train_loop_utils.py:162-201``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu._private import xla_monitor
from ray_tpu.models import llama
from ray_tpu.parallel import sharding as shd


@dataclasses.dataclass
class TrainState:
    step: jnp.ndarray
    params: Any
    opt_state: Any


jax.tree_util.register_pytree_node(
    TrainState,
    lambda s: ((s.step, s.params, s.opt_state), None),
    lambda _, c: TrainState(step=c[0], params=c[1], opt_state=c[2]),
)


def _divisible_spec(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Remove mesh axes from a PartitionSpec where they don't divide the dim."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def axis_size(entry) -> int:
        names = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for name in names:
            n *= sizes.get(name, 1)
        return n

    fixed = []
    for dim, entry in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if entry is None or dim % axis_size(entry) == 0:
            fixed.append(entry)
        else:
            fixed.append(None)
    return P(*fixed)


def _spec_tree_for_state(state_shapes, params_treedef, param_specs):
    """Map PartitionSpecs onto an arbitrary (optax) state pytree.

    Any subtree structurally identical to the params pytree gets the param
    specs (optimizer moments mirror params); every other leaf is replicated.
    """

    def visit(node):
        try:
            if jax.tree.structure(node) == params_treedef:
                return param_specs
        except Exception:
            pass
        if hasattr(node, "_fields"):  # namedtuple (optax states)
            return type(node)(*[visit(x) for x in node])
        if isinstance(node, tuple):
            return tuple(visit(x) for x in node)
        if isinstance(node, list):
            return [visit(x) for x in node]
        if isinstance(node, dict):
            return {k: visit(v) for k, v in node.items()}
        return P()  # scalar leaf (e.g. count) — replicated

    return visit(state_shapes)


def default_optimizer(
    learning_rate: float = 3e-4,
    weight_decay: float = 0.1,
    b1: float = 0.9,
    b2: float = 0.95,
    grad_clip: float = 1.0,
    warmup_steps: int = 100,
    total_steps: int = 10000,
    mu_dtype=None,
) -> optax.GradientTransformation:
    """AdamW with warmup-cosine.

    Moment dtypes: optax inits BOTH moments in the params' dtype — with
    bf16 params (this framework's default) the default optimizer state is
    already bf16 mu AND bf16 nu. ``mu_dtype`` can RAISE the first
    moment's precision (e.g. ``jnp.float32`` for bf16 params) at
    +4 bytes/param; note the second moment has no such knob in optax and
    stays in the params' dtype.
    """
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, learning_rate, warmup_steps, max(total_steps, warmup_steps + 1)
    )
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(schedule, b1=b1, b2=b2, weight_decay=weight_decay,
                    mu_dtype=mu_dtype),
    )


class ShardedTrainer:
    """Compiled sharded train step + state management for one model family.

    ``rules`` defaults to :data:`ray_tpu.parallel.sharding.DEFAULT_RULES`
    (FSDP on embed, TP on heads/mlp/vocab, batch over (data, fsdp)).
    """

    def __init__(
        self,
        config: llama.LlamaConfig,
        mesh: Mesh,
        optimizer: Optional[optax.GradientTransformation] = None,
        rules: Optional[shd.LogicalRules] = None,
        microbatches: int = 1,
        grad_accum_dtype: Any = None,
    ):
        self.config = config
        self.mesh = mesh
        self.rules = rules
        self.optimizer = optimizer or default_optimizer()
        # Gradient-accumulation microbatching: the jitted step lax.scans
        # over M microbatches (token-weighted grad accumulation, ONE
        # optimizer update) so the global batch scales for DCN without a
        # second compiled signature. M=1 keeps the direct path.
        # ``grad_accum_dtype`` is the accumulator precision: fp32 by
        # default (bf16 += over M terms drops low bits); pass the param
        # dtype to halve the carry's HBM at memory-bound shapes.
        self.microbatches = max(int(microbatches), 1)
        self.grad_accum_dtype = grad_accum_dtype or jnp.float32

        axes = llama.logical_axes(config)
        param_specs = shd.tree_specs(axes, rules)
        param_shapes = jax.eval_shape(
            functools.partial(llama.init_params, config), jax.random.PRNGKey(0)
        )
        # Drop mesh axes that do not divide the corresponding dim (e.g. 2 kv
        # heads on a tensor=4 mesh): those dims stay replicated, matching
        # GSPMD's divisibility requirement.
        self.param_specs = jax.tree.map(
            lambda spec, shape: _divisible_spec(spec, shape.shape, mesh),
            param_specs, param_shapes,
        )
        self.param_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), self.param_specs
        )
        self.batch_spec = P(("data", "fsdp"))
        self.batch_sharding = NamedSharding(mesh, self.batch_spec)
        self._build()

    def _build(self):
        config, mesh, optimizer = self.config, self.mesh, self.optimizer

        def init_fn(key):
            params = llama.init_params(config, key)
            params = jax.tree.map(
                jax.lax.with_sharding_constraint, params, self.param_shardings
            )
            opt_state = optimizer.init(params)
            return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                              opt_state=opt_state)

        # Derive opt-state shardings structurally, then jit init with explicit
        # output shardings so even the first state materializes sharded
        # (never a full replica per host).
        state_shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
        params_treedef = jax.tree.structure(
            jax.eval_shape(functools.partial(llama.init_params, config),
                           jax.random.PRNGKey(0))
        )
        opt_specs = _spec_tree_for_state(
            state_shapes.opt_state, params_treedef, self.param_specs
        )
        self.state_shardings = TrainState(
            step=NamedSharding(mesh, P()),
            params=self.param_shardings,
            opt_state=jax.tree.map(
                lambda s: NamedSharding(mesh, s), opt_specs,
                is_leaf=lambda x: isinstance(x, P),
            ),
        )
        self._init = xla_monitor.instrument(
            init_fn, name="train_init", shape_policy="free",
            out_shardings=self.state_shardings)

        M = self.microbatches

        def _grads_direct(params, batch):
            def loss(p):
                return llama.loss_fn(p, batch, config, mesh)

            (loss_val, metrics), grads = jax.value_and_grad(
                loss, has_aux=True
            )(params)
            metrics = dict(metrics)
            return loss_val, metrics, grads

        def _grads_microbatched(params, batch):
            """lax.scan over M microbatches with token-weighted grad
            accumulation — the summed grads equal the single-big-batch
            grads EXACTLY (up to fp reduction order): each microbatch's
            mean loss is rescaled by tokens_i/total so grad sums, not
            averages, reproduce d(nll_total/total)/dparams regardless of
            per-microbatch mask imbalance."""
            tokens = batch["tokens"]
            g = tokens.shape[0]
            if g % M:
                raise ValueError(
                    f"global batch {g} not divisible by "
                    f"microbatches={M}")
            mask = batch.get("mask")
            m_full = (mask[:, 1:] if mask is not None else
                      jnp.ones_like(tokens[:, 1:])).astype(jnp.float32)
            total = jnp.maximum(jnp.sum(m_full), 1.0)

            def to_micro(x):
                mb = x.reshape((M, g // M) + x.shape[1:])
                spec = _divisible_spec(
                    P(None, ("data", "fsdp")), mb.shape, mesh)
                return jax.lax.with_sharding_constraint(
                    mb, NamedSharding(mesh, spec))

            micro = jax.tree.map(to_micro, batch)

            def body(carry, mb):
                gsum, loss_sum, correct_sum = carry

                def scaled(p):
                    loss, metrics = llama.loss_fn(p, mb, config, mesh)
                    # loss_i * tokens_i = nll_sum_i; /total makes the
                    # M-term SUM equal the big-batch mean loss.
                    return loss * (metrics["tokens"] / total), metrics

                (loss_i, metrics_i), grads_i = jax.value_and_grad(
                    scaled, has_aux=True)(params)
                # grad_accum_dtype (default fp32) accumulation: bf16 +=
                # over M terms loses low bits the single-batch step keeps.
                gsum = jax.tree.map(
                    lambda a, b: a + b.astype(a.dtype), gsum, grads_i)
                correct = metrics_i["accuracy"] * metrics_i["tokens"]
                return (gsum, loss_sum + loss_i,
                        correct_sum + correct), None

            gzero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, self.grad_accum_dtype),
                params)
            (gsum, loss_val, correct_sum), _ = jax.lax.scan(
                body, (gzero, jnp.zeros(()), jnp.zeros(())), micro)
            grads = jax.tree.map(
                lambda acc, p: acc.astype(p.dtype), gsum, params)
            metrics = {"loss": loss_val,
                       "accuracy": correct_sum / total,
                       "tokens": total}
            return loss_val, metrics, grads

        def step_fn(state: TrainState, batch: Dict[str, jnp.ndarray]):
            compute = _grads_direct if M == 1 else _grads_microbatched
            loss_val, metrics, grads = compute(state.params, batch)
            with jax.named_scope("optimizer"):
                updates, new_opt = optimizer.update(
                    grads, state.opt_state, state.params
                )
                new_params = optax.apply_updates(state.params, updates)
            new_params = jax.tree.map(
                jax.lax.with_sharding_constraint, new_params, self.param_shardings
            )
            new_state = TrainState(
                step=state.step + 1, params=new_params, opt_state=new_opt
            )
            metrics["grad_norm"] = optax.global_norm(grads)
            return new_state, metrics

        # One legitimate signature per trainer: a second compile means
        # the batch shape churned (a classic silent-retrace source in
        # training loops) and raises ray_tpu_xla_retraces_total.
        # Microbatching lives INSIDE this signature (the scan count is a
        # closure constant), so M never multiplies compiled programs.
        # Achieved-FLOPs/MFU gauges: the call-cadence fallback is only
        # honest when the loop syncs per step (fetching the loss does);
        # async loops (ray_tpu.train.loop.AsyncStepLoop) instead feed
        # measured window wall time via self._step.note_execution, the
        # same windowed accounting the buffered serve engine uses.
        self._step = xla_monitor.instrument(
            step_fn,
            name="train_step",
            in_shardings=(self.state_shardings,
                          {"tokens": self.batch_sharding,
                           "mask": self.batch_sharding}),
            out_shardings=(self.state_shardings, None),
            donate_argnums=(0,),
        )

    # -- public API --------------------------------------------------------
    def init_state(self, seed: int = 0) -> TrainState:
        with self.mesh:
            return self._init(jax.random.PRNGKey(seed))

    def train_step(
        self, state: TrainState, batch: Dict[str, jnp.ndarray]
    ) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
        g = batch["tokens"].shape[0]
        if g % self.microbatches:
            raise ValueError(
                f"global batch {g} not divisible by "
                f"microbatches={self.microbatches}")
        with self.mesh:
            return self._step(state, batch)

    def shard_batch(self, batch: Dict[str, jnp.ndarray]):
        return jax.tree.map(
            lambda x: jax.device_put(x, self.batch_sharding), batch
        )

    # -- checkpoint plane hooks --------------------------------------------
    def save_state(self, plane, state: TrainState, step: Optional[int] = None):
        """Async-save ``state`` through a checkpoint plane
        (:class:`ray_tpu.checkpoint.CheckpointPlane`). The device→host
        handoff happens before this returns; serialization + write +
        manifest commit run in the background. Returns the SaveHandle."""
        if step is None:
            step = int(state.step)  # syncs the step scalar only
        return plane.save_async(int(step), state)

    def restore_state(self, plane, step: Optional[int] = None) -> TrainState:
        """Restore a committed checkpoint onto THIS trainer's mesh layout.

        The saving topology is irrelevant: shards are reassembled and
        re-sharded per ``self.state_shardings`` (elastic restore — save on
        ``fsdp=8``, restore on ``fsdp=4×tp=2`` is bit-identical)."""
        with self.mesh:
            return plane.restore(self.state_shardings, step=step)


def synthetic_batch(
    batch_size: int, seq_len: int, vocab_size: int, seed: int = 0
) -> Dict[str, jnp.ndarray]:
    key = jax.random.PRNGKey(seed)
    tokens = jax.random.randint(key, (batch_size, seq_len), 0, vocab_size, jnp.int32)
    return {"tokens": tokens, "mask": jnp.ones_like(tokens)}
