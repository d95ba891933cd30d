"""Logical-axis sharding rules (GSPMD annotation layer).

The reference expresses DP/FSDP by wrapping modules
(``DistributedDataParallel`` / ``FullyShardedDataParallel`` — reference:
``python/ray/train/torch/train_loop_utils.py:162-201``). TPU-native, the same
strategies are *shardings*, not wrappers: every parameter/activation carries
logical axis names, and a rule table maps logical axes to mesh axes. Swapping
DP → FSDP → TP → any hybrid is a rule-table change; XLA inserts the
all-gathers/reduce-scatters that DDP/FSDP perform by hand.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Logical activation/parameter axis names used by ray_tpu models.
#   "batch"       – per-example dimension
#   "seq"         – sequence/token dimension (activations)
#   "embed"       – model/hidden dimension
#   "mlp"         – feed-forward intermediate dimension
#   "heads"       – attention heads
#   "kv_heads"    – key/value heads (GQA)
#   "head_dim"    – per-head dimension
#   "vocab"       – vocabulary dimension
#   "kv_seq"      – key/value sequence (ring-attention shifted axis)
#   "experts"     – MoE expert dimension
#   "layers"      – scanned layer dimension (never sharded)
#   "norm"        – a norm weight's feature dimension (never sharded: a
#                   few KB that every layer of a scan reads whole)

LogicalRules = Tuple[Tuple[str, Union[str, Tuple[str, ...], None]], ...]

# Default rule table: FSDP shards params on the embed dim, TP on heads/mlp/vocab,
# batch over (data, fsdp), sequence over seq. This is the Llama-2-7B
# "FSDP + optional TP" north-star layout (BASELINE.md) expressed as rules.
DEFAULT_RULES: LogicalRules = (
    # parameter axes
    ("embed", "fsdp"),
    ("mlp", "tensor"),
    ("heads", "tensor"),
    ("kv_heads", "tensor"),
    ("head_dim", None),
    ("vocab", "tensor"),
    ("experts", "expert"),
    ("layers", None),
    ("norm", None),
    # activation axes (distinct from param axes: an activation's feature dim
    # stays unsharded on the fsdp axis — fsdp gathers params for compute)
    ("batch", ("data", "fsdp")),
    ("seq", "seq"),
    ("kv_seq", None),
    ("act_embed", None),
    ("act_mlp", "tensor"),
    ("act_heads", "tensor"),
    ("act_kv_heads", "tensor"),
    ("act_vocab", "tensor"),
)


def rules_dict(rules: Optional[LogicalRules] = None) -> Dict[str, Any]:
    return dict(rules if rules is not None else DEFAULT_RULES)


def logical_to_spec(
    logical_axes: Sequence[Optional[str]], rules: Optional[LogicalRules] = None
) -> P:
    """Map a tuple of logical axis names to a PartitionSpec via the rule table."""
    table = rules_dict(rules)
    return P(*[table.get(a) if a is not None else None for a in logical_axes])


def tree_specs(logical_tree: Any, rules: Optional[LogicalRules] = None) -> Any:
    """Map a pytree of logical-axis tuples to a pytree of PartitionSpecs."""
    return jax.tree.map(
        lambda axes: logical_to_spec(axes, rules),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(a is None or isinstance(a, str) for a in x),
    )


def tree_shardings(
    mesh: Mesh, logical_tree: Any, rules: Optional[LogicalRules] = None
) -> Any:
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec), tree_specs(logical_tree, rules)
    )


def constrain(x: Any, mesh: Mesh, *logical_axes: Optional[str],
              rules: Optional[LogicalRules] = None) -> Any:
    """``with_sharding_constraint`` by logical axis names (no-op off-mesh)."""
    if mesh.empty:
        return x
    spec = logical_to_spec(logical_axes, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def shard_tree(tree: Any, shardings: Any) -> Any:
    """Device-put a pytree of arrays onto the given shardings."""
    return jax.tree.map(jax.device_put, tree, shardings)
