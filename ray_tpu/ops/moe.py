"""Mixture-of-experts layer with expert parallelism.

The reference has no MoE of its own (experts arrive via hosted engines —
SURVEY.md §2.3); ray_tpu provides EP natively as the ``expert`` mesh axis:

* router: top-k softmax gating (jittable, static shapes);
* dispatch: capacity-bounded one-hot combine — tokens over capacity drop
  (standard Switch/GShard semantics) so shapes stay static for XLA;
* expert compute: experts stacked on a leading axis sharded over the
  ``expert`` mesh axis; dispatch/combine einsums become all-to-alls on ICI
  when sharded (XLA inserts them from the shardings — the
  ``ragged_all_to_all`` of SURVEY §2.3 expressed GSPMD-style).

That capacity-bounded :func:`moe_layer` is the TRAINING block (Mixtral,
the ``expert`` mesh axis). Serving routes through :func:`routed_block`
instead, which is DROPLESS: in a served batch a dropped token would make
one user's answer depend on who else shares the batch. It sorts the
``T x k`` assignments by expert and runs a grouped matrix multiplication
over the sorted rows (:func:`grouped_swiglu` for gate and up in one
call, :func:`grouped_matmul` for down: the ``moe_gmm`` Mosaic kernel on
the TPU, ``jax.lax.ragged_dot`` elsewhere), so no ``[T, E, C]`` tensor
exists, an untouched expert's weights are never read, and every routed
token is computed. The kernel takes the STACKED ``[L, X, K, N]``
weights with the layer as a scalar-prefetch operand (as the paged
attention kernels take the arena), so a layer scan never slices, and so
never copies, a layer's experts.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.dispatch import interpret_default


def router_topk(
    gate_logits: jnp.ndarray, k: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k gating. gate_logits [T, E] → (weights [T, k], idx [T, k])."""
    weights, idx = jax.lax.top_k(gate_logits, k)
    weights = jax.nn.softmax(weights, axis=-1)
    return weights, idx


def dispatch_mask(
    expert_idx: jnp.ndarray, num_experts: int, capacity: int
) -> jnp.ndarray:
    """[T, k] expert ids → dispatch tensor [T, E, C] (0/1).

    Position within an expert's buffer = running count of tokens routed to
    that expert; tokens beyond ``capacity`` are dropped (their row is zero).
    """
    t, k = expert_idx.shape
    onehot = jax.nn.one_hot(expert_idx, num_experts, dtype=jnp.int32)  # [T,k,E]
    flat = onehot.reshape(t * k, num_experts)
    position = jnp.cumsum(flat, axis=0) - 1                  # slot per token
    in_cap = position < capacity
    slot_onehot = jax.nn.one_hot(position, capacity, dtype=jnp.float32)
    disp = (flat[..., None] * in_cap[..., None] * slot_onehot)
    return disp.reshape(t, k, num_experts, capacity).sum(axis=1)


def moe_layer(
    x: jnp.ndarray,
    params: Dict[str, jnp.ndarray],
    *,
    num_experts: int,
    top_k: int = 2,
    capacity_factor: float = 1.25,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Apply a SwiGLU MoE block. x: [B, S, E_model].

    params: ``w_router`` [E_model, E], stacked expert weights ``w_gate`` /
    ``w_up`` [E, E_model, M] and ``w_down`` [E, M, E_model] (leading axis
    logical name "experts" → shard over the ``expert`` mesh axis).
    Returns (output, aux) where aux carries the load-balancing loss.
    """
    b, s, d = x.shape
    tokens = x.reshape(b * s, d)
    t = tokens.shape[0]
    capacity = max(int(capacity_factor * t * top_k / num_experts), top_k)

    gate_logits = tokens.astype(jnp.float32) @ params["w_router"].astype(
        jnp.float32)
    weights, idx = router_topk(gate_logits, top_k)
    disp = dispatch_mask(idx, num_experts, capacity)          # [T, E, C]

    # Expert buffers: [E, C, D] — this einsum is the dispatch all-to-all when
    # tokens are batch-sharded and experts are expert-sharded.
    expert_in = jnp.einsum("tec,td->ecd", disp, tokens.astype(jnp.float32))
    expert_in = expert_in.astype(x.dtype)

    def expert_fn(buf, wg, wu, wd):
        act = jax.nn.silu(buf @ wg) * (buf @ wu)
        return act @ wd

    expert_out = jax.vmap(expert_fn)(
        expert_in, params["w_gate"].astype(x.dtype),
        params["w_up"].astype(x.dtype), params["w_down"].astype(x.dtype))

    # Combine weights: scatter the router weight of each kept (token, expert).
    w_per_expert = jnp.einsum(
        "tke,tk->te", jax.nn.one_hot(idx, num_experts, dtype=jnp.float32),
        weights)
    combine = disp * w_per_expert[:, :, None]                 # [T, E, C]
    out = jnp.einsum("tec,ecd->td", combine,
                     expert_out.astype(jnp.float32))

    # Load-balancing aux loss (Switch Transformer eq. 4).
    probs = jax.nn.softmax(gate_logits, axis=-1)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(idx[:, 0], num_experts, dtype=jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux_loss = num_experts * jnp.sum(frac_tokens * frac_probs)

    return out.reshape(b, s, d).astype(x.dtype), {
        "aux_loss": aux_loss,
        "dropped_fraction": 1.0 - jnp.sum(disp) / (t * top_k),
    }


def init_moe_params(
    key: jax.Array, d_model: int, d_ff: int, num_experts: int,
    dtype=jnp.bfloat16,
) -> Dict[str, jnp.ndarray]:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    scale_in = d_model ** -0.5
    scale_out = d_ff ** -0.5
    return {
        "w_router": (jax.random.normal(k1, (d_model, num_experts))
                     * scale_in).astype(jnp.float32),
        "w_gate": (jax.random.normal(k2, (num_experts, d_model, d_ff))
                   * scale_in).astype(dtype),
        "w_up": (jax.random.normal(k3, (num_experts, d_model, d_ff))
                 * scale_in).astype(dtype),
        "w_down": (jax.random.normal(k4, (num_experts, d_ff, d_model))
                   * scale_out).astype(dtype),
    }


MOE_LOGICAL_AXES = {
    "w_router": ("embed", None),
    "w_gate": ("experts", "embed", "mlp"),
    "w_up": ("experts", "embed", "mlp"),
    "w_down": ("experts", "mlp", "embed"),
}


# ---------------------------------------------------------------------------
# Dropless routed block (serving; the OLMoE family)
# ---------------------------------------------------------------------------

def route_softmax_topk(h: jnp.ndarray, w_router: jnp.ndarray, k: int,
                       renormalise: bool = False
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """OLMoE's router: softmax over ALL experts in float32, then the top
    ``k`` of the probabilities, their weights those ``k`` entries as
    they are (``renormalise`` divides them by their sum: the published
    ``norm_topk_prob``). h [T, E], w_router [E, X] -> (weights [T, k]
    float32, idx [T, k] int32). Not :func:`router_topk`, which is
    Mixtral's softmax over the top-k logits."""
    logits = jnp.dot(h.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    weights, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    if renormalise:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, idx.astype(jnp.int32)


def route_sigmoid_topk(h: jnp.ndarray, w_router: jnp.ndarray, k: int,
                       renormalise: bool = True, *, bias: jnp.ndarray,
                       scale: float = 1.0
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The afmoe router: scores ``sigmoid(h Wr)`` over ALL experts in
    float32; the top ``k`` of ``score + bias`` (``bias [X]`` steers the
    SELECTION only: the load balancer's handle); weights the chosen
    experts' own scores, over their sum when ``renormalise`` (the
    published ``route_norm``), times ``scale`` (``route_scale``).
    Returns (weights [T, k] float32, idx [T, k] int32)."""
    logits = jnp.dot(h.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
    weights = jnp.take_along_axis(scores, idx, axis=-1)
    if renormalise:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + 1e-20)
    return weights * scale, idx.astype(jnp.int32)


def init_mlp_router(key, n: int, hidden: int, width: int, experts: int):
    """``n`` layers' MLP routers (:func:`route_mlp_top1`), stacked,
    float32 like every router. Seeded so that dropping a term shows and
    a route has a margin: ``out`` at 5 x fan-in scale, so the chosen
    expert's probability is some 0.3-0.6 of 16 and not 1/16 (top 1 is
    NOT renormalised: at fan-in scale the routed block would add a
    sixteenth of an expert); ``gamma`` uniform in 0.25..0.75; the norm's
    weight in 0.5..1.5; the three biases normal at 0.1; ``beta`` normal
    at 0.05, the spacing of the top probabilities."""
    k = jax.random.split(key, 10)
    f32 = jnp.float32

    def dense(key, fan_in, *shape, gain=1.0):
        return jax.random.normal(key, shape, f32) * gain * fan_in ** -0.5

    def centred(key, *shape, gain=1.0):
        # Every column sums to zero over its inputs: a gelu's output has
        # a positive mean, which would otherwise reach every token's
        # logits as the same offset an expert and collapse the load onto
        # one or two of them.
        w = dense(key, width, n, width, *shape, gain=gain)
        return w - w.mean(axis=1, keepdims=True)

    return {
        "down": dense(k[0], hidden, n, hidden, width),
        "down_b": 0.1 * jax.random.normal(k[1], (n, width), f32),
        "gamma": jax.random.uniform(k[2], (n, width), f32, 0.25, 0.75),
        "norm": jax.random.uniform(k[3], (n, width), f32, 0.5, 1.5),
        "w1": dense(k[4], width, n, width, width),
        "b1": 0.1 * jax.random.normal(k[5], (n, width), f32),
        "w2": centred(k[6], width),
        "b2": 0.1 * jax.random.normal(k[7], (n, width), f32),
        "out": centred(k[8], experts, gain=5.0),
        "beta": 0.05 * jax.random.normal(k[9], (n, experts), f32),
    }


def route_mlp_top1(h: jnp.ndarray, router: Dict[str, jnp.ndarray],
                   carry: jnp.ndarray, eps: float):
    """The ZAYA router: not ``h @ w_router`` but a small MLP with a state
    that runs over DEPTH. ``r = h down + down_b`` (``[T, R]``); ``r +=
    gamma * carry``, ``carry [T, R]`` the same tokens' ``r`` of the
    layer BEFORE (zeros into the first layer, which so has no such
    term); ``z = gelu(rms(r; norm) w1 + b1)``, ``z = gelu(z w2 + b2)``,
    ``p = softmax(z out)`` over all X experts; the expert is
    ``argmax(p + beta)`` (``beta`` steers the SELECTION only), its weight
    that expert's ``p``, not renormalised. All float32, exact (erf)
    gelu. Returns (weights [T, 1] float32, idx [T, 1] int32, ``r``: the
    next layer's carry, the averaged one)."""
    f32, hp = jnp.float32, jax.lax.Precision.HIGHEST
    w = {name: a.astype(f32) for name, a in router.items()}

    def dot(x, name):
        return jnp.dot(x, w[name], precision=hp)

    r = dot(h.astype(f32), "down") + w["down_b"] + w["gamma"] * carry
    z = r * jax.lax.rsqrt(jnp.mean(r * r, axis=-1, keepdims=True) + eps)
    z = jax.nn.gelu(dot(z * w["norm"], "w1") + w["b1"], approximate=False)
    z = jax.nn.gelu(dot(z, "w2") + w["b2"], approximate=False)
    probs = jax.nn.softmax(dot(z, "out"), axis=-1)
    idx = jnp.argmax(probs + w["beta"], axis=-1)[:, None]
    return (jnp.take_along_axis(probs, idx, axis=-1), idx.astype(jnp.int32),
            r)


# What one ``moe_gmm`` call may hold in VMEM, declared to the compiler
# (``vmem_limit_bytes``; Mosaic's own default is 16 MiB of a v5e's 128).
GMM_VMEM_BYTES = 40 << 20
# An expert's ``[K, N]`` matrix up to this size is one window, fetched
# whole. A step holds one (down) or two (gate beside up), each
# double-buffered: 16 MiB of the budget at most, the rest the row tiles
# and the float32 accumulators.
GMM_WHOLE_BYTES = 4 << 20
# A larger matrix is fetched in windows ``[K, tn]`` of at most this.
GMM_WINDOW_BYTES = 2 << 20


def _gmm_tiles(m: int, k: int, n: int, num_groups: int, itemsize: int):
    """(tm, tn), from the shapes alone.

    ``tn``: an expert's matrix of at most :data:`GMM_WHOLE_BYTES` is
    fetched WHOLE, one contiguous read and one grid step a visit; a
    larger one in the widest ``N / 2^i`` of whole 128-lane tiles whose
    ``[K, tn]`` window is at most :data:`GMM_WINDOW_BYTES`. A call is
    its weight DMAs (timed on the chip on each configuration's own
    stack, PR 37, PERF.md section 6: 730-740 GB/s), and a window that
    is half of a 2 KiB-wide matrix (``[2048, 512]``: 16 KiB runs at a
    32 KiB stride) read 633 GB/s from OLMoE's 12-layer stack, which
    cost its tick a tenth. Wider windows of the larger experts were
    timed too: 4.5 MiB of a ``[3072, 3072]`` reads 4% faster than 2.25,
    3.5 MiB of a ``[7168, 2048]`` 3% SLOWER than 1.75, 3-6 MiB of a
    ``[4096, 768]`` the same: they keep the 2 MiB rule.

    ``tm``: a group that straddles a row tile is visited, and its
    weights read, once per tile, so tiles are tall where groups are
    (about twice the mean group, 128 to 512 rows), and ``[tm, K]`` stays
    at or under 2 MiB (K = 4096, Granite 4.0-H). Tiles of 64, 32 and 16
    rows were timed at 6 rows a group and lose 1-6%: a step costs what
    its window's bytes cost whatever the rows, and shorter tiles are
    more visits."""
    tm = 128
    while tm < 512 and tm * num_groups < 2 * m:
        tm *= 2
    while tm > 128 and tm * k * itemsize > (2 << 20):
        tm //= 2
    tn = n
    if k * n * itemsize > GMM_WHOLE_BYTES:
        while tn % 256 == 0 and k * tn * itemsize > GMM_WINDOW_BYTES:
            tn //= 2
    return tm, tn


def gmm_applicable(k: int, n: int, itemsize: int = 2) -> bool:
    """True when auto-dispatch takes the ``moe_gmm`` kernel on the TPU:
    lane-tiling widths, and a ``[K, 128]`` weight tile that fits."""
    return not (k % 128 or n % 128) and k * 128 * itemsize <= (4 << 20)


def _visits(group_sizes, tm: int, tiles_m: int):
    """The kernel's schedule. Rows are sorted by group, so group ``g``
    owns rows ``[bounds[g], bounds[g + 1])`` and meets the row tiles
    ``bounds[g] // tm .. (bounds[g + 1] - 1) // tm``: one VISIT each.
    At most ``tiles_m + X - 1`` visits exist (a tile boundary splits at
    most one group); the schedule is padded to that length with repeats
    of the last real visit, which fetch nothing new and compute nothing.
    Returns (group of visit, tile of visit, bounds [X + 1], visits)."""
    x = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    bounds = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    first = bounds[:-1] // tm
    per_group = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_ends = jnp.cumsum(per_group)
    total = visit_ends[-1]
    v = jnp.minimum(jnp.arange(tiles_m + x - 1), jnp.maximum(total - 1, 0))
    gid = jnp.minimum(jnp.searchsorted(visit_ends, v, side="right"), x - 1)
    tid = first[gid] + v - (visit_ends[gid] - per_group[gid])
    return (gid.astype(jnp.int32), tid.astype(jnp.int32),
            bounds.astype(jnp.int32), total.astype(jnp.int32).reshape(1))


def _gmm_kernel(layer_ref, gid_ref, tid_ref, bounds_ref, total_ref,
                lhs_ref, *refs, tm):
    *rhs_refs, out_ref = refs
    v = pl.program_id(1)

    @pl.when(v < total_ref[0])
    def _visit():
        g = gid_ref[v]
        lhs = lhs_ref[...]
        acc = jnp.dot(lhs, rhs_refs[0][0, 0],
                      preferred_element_type=jnp.float32)
        if len(rhs_refs) == 2:
            # Gate's window beside up's: the activation from the two
            # float32 accumulators, rounded once on its way out.
            acc = jax.nn.silu(acc) * jnp.dot(
                lhs, rhs_refs[1][0, 0], preferred_element_type=jnp.float32)
        rows = tid_ref[v] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc.shape, 0)
        mine = (rows >= bounds_ref[g]) & (rows < bounds_ref[g + 1])
        # The tile stays resident while consecutive visits name it: each
        # group of the tile fills in its own rows.
        out_ref[...] = jnp.where(
            mine, acc, out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


def _gmm_call(lhs, weights, group_sizes, layer, *, interpret, tiles=None):
    """One ``moe_gmm`` Pallas call over ``weights``, one stacked
    ``[L, X, K, N]`` array (``lhs @ w``) or two (``silu(lhs @ w0) *
    (lhs @ w1)``, both windows of a visit fetched in the same grid
    step). ``tiles`` overrides :func:`_gmm_tiles` (tests that walk
    several tiles at small sizes; timing scripts)."""
    m, k = lhs.shape
    _, x, _, n = weights[0].shape
    itemsize = jnp.dtype(weights[0].dtype).itemsize
    tm, tn = tiles or _gmm_tiles(m, k, n, x, itemsize)
    tiles_m = -(-m // tm)
    if tiles_m * tm != m:
        lhs = jnp.pad(lhs, ((0, tiles_m * tm - m), (0, 0)))
    gid, tid, bounds, total = _visits(group_sizes, tm, tiles_m)
    window = pl.BlockSpec((1, 1, k, tn),
                          lambda j, v, ly, gid, tid, bnd, tot:
                          (ly[0], gid[v], 0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n // tn, tiles_m + x - 1),
        in_specs=[
            pl.BlockSpec((tm, k),
                         lambda j, v, ly, gid, tid, bnd, tot: (tid[v], 0)),
            *[window] * len(weights),
        ],
        out_specs=pl.BlockSpec(
            (tm, tn), lambda j, v, ly, gid, tid, bnd, tot: (tid[v], j)),
    )
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tiles_m * tm, n), lhs.dtype),
        interpret=interpret,
        name="moe_gmm",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=GMM_VMEM_BYTES),
        cost_estimate=pl.CostEstimate(
            # Static worst case: every expert touched.
            flops=2 * m * k * n * len(weights),
            transcendentals=m * n * (len(weights) - 1),
            bytes_accessed=(len(weights) * x * k * n + m * k + m * n)
            * itemsize),
    )(jnp.asarray(layer, jnp.int32).reshape(1), gid, tid, bounds, total,
      lhs, *weights)
    return out[:m]


def _grouped(lhs, weights, group_sizes, layer, use_kernel, ragged):
    """The dispatch both grouped multiplications share: the ``moe_gmm``
    call over ``weights`` (one layer's ``[X, K, N]`` given a unit layer
    axis), or ``ragged(lhs, weights, sizes)`` over the layer's own
    ``[X, K, N]`` (the stacked ones indexed at ``layer``)."""
    stacked = weights[0].ndim == 4
    if (layer is None) == stacked:
        raise ValueError("stacked weights need `layer`; one layer's take none")
    interpret = interpret_default()
    tiles = gmm_applicable(weights[0].shape[-2], weights[0].shape[-1],
                           jnp.dtype(weights[0].dtype).itemsize)
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu" and tiles
    weights = [w.astype(lhs.dtype) for w in weights]
    if use_kernel and (interpret or tiles):
        if not stacked:
            layer, weights = 0, [w[None] for w in weights]
        return _gmm_call(lhs, weights, group_sizes, layer,
                         interpret=interpret)
    if stacked:
        weights = [jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False)
                   for w in weights]
    return ragged(lhs, weights, group_sizes.astype(jnp.int32))


def grouped_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray,
                   group_sizes: jnp.ndarray, layer=None, *,
                   use_kernel: Optional[bool] = None) -> jnp.ndarray:
    """``out[r] = lhs[r] @ rhs[layer, g(r)]`` for rows sorted by group.

    lhs [M, K], its first ``sum(group_sizes)`` rows in group order (rows
    past them come back undefined); rhs the stacked ``[L, X, K, N]``
    read at ``layer`` (a traced int32 scalar), or ``[X, K, N]`` with
    ``layer`` None; group_sizes [X] int32. ``use_kernel``: None = the
    ``moe_gmm`` kernel on the TPU when the widths tile,
    ``jax.lax.ragged_dot`` elsewhere; True forces the kernel (interpret
    mode off the TPU: the CPU tier-1 path); False forces ``ragged_dot``,
    which needs the layer's experts as an array of their own. The
    kernel's tiles are :func:`_gmm_tiles`' and its VMEM budget
    :data:`GMM_VMEM_BYTES`."""
    return _grouped(lhs, [rhs], group_sizes, layer, use_kernel,
                    lambda lhs, ws, sizes: jax.lax.ragged_dot(lhs, ws[0],
                                                              sizes))


def _ragged_swiglu(lhs, weights, sizes):
    gate, up = (jax.lax.ragged_dot(lhs, w, sizes,
                                   preferred_element_type=jnp.float32)
                for w in weights)
    return (jax.nn.silu(gate) * up).astype(lhs.dtype)


def grouped_swiglu(lhs: jnp.ndarray, gate: jnp.ndarray, up: jnp.ndarray,
                   group_sizes: jnp.ndarray, layer=None, *,
                   use_kernel: Optional[bool] = None) -> jnp.ndarray:
    """``out[r] = silu(lhs[r] @ gate[layer, g(r)]) * (lhs[r] @ up[layer,
    g(r)])`` for rows sorted by group: :func:`grouped_matmul`'s operands
    and dispatch, with two weight arrays of one shape. ONE ``moe_gmm``
    call: a visit fetches its gate window and its up window, multiplies
    the one row tile by both, and writes the activation; no ``[M, N]``
    gate or up output exists. Both products are accumulated in float32
    and the activation is taken from the accumulators and ROUNDED ONCE,
    to ``lhs.dtype``, on every path (kernel, interpreted kernel,
    ``ragged_dot``)."""
    return _grouped(lhs, [gate, up], group_sizes, layer, use_kernel,
                    _ragged_swiglu)


class Routed(NamedTuple):
    """What :func:`routed_block` did besides its output."""
    rows: jnp.ndarray      # [X] int32: assignments each HELD expert computed
    experts: jnp.ndarray   # [T, k] int32: each token's experts, best first
    # What a router with a state over depth hands the next layer's
    # (:func:`route_mlp_top1`); None for every other router.
    carry: Any = None


def routed_block(x: jnp.ndarray, w_router: jnp.ndarray,
                 experts: Dict[str, jnp.ndarray], layer=None, *,
                 top_k: int, norm_topk: bool = False,
                 use_kernel: Optional[bool] = None,
                 route=route_softmax_topk,
                 held: Optional[Tuple[int, int]] = None,
                 routing: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None
                 ) -> Tuple[jnp.ndarray, Routed]:
    """The dropless SwiGLU expert block on normed tokens x [T, E]:
    ``sum_e p_e * down_e(silu(gate_e x) * up_e x)`` over each token's
    ``top_k`` experts. ``experts``: ``moe_gate``/``moe_up`` [L, X, E, M]
    and ``moe_down`` [L, X, M, E] read at ``layer``, or one layer's
    ``[X, ...]`` with ``layer`` None. No capacity: every assignment is
    computed, and a token's result does not depend on the other rows.
    ``route(x, w_router, top_k, norm_topk)`` is the router; a caller
    whose router is not a function of ``x`` and one matrix (it carries a
    state from layer to layer: :func:`route_mlp_top1`) has routed
    already and passes ``routing = (weights [T, k], idx [T, k])``, with
    ``w_router`` None. The experts
    are TWO grouped calls on one schedule: :func:`grouped_swiglu` (gate
    and up: the activation from float32 accumulators, rounded once to
    ``x.dtype``; no ``[T * k, M]`` gate or up array exists), then
    :func:`grouped_matmul` (down), at :func:`_gmm_tiles`' tiles. The
    weighted sum is float32, in the token's own top-k order, rounded
    once; no float32 copy of the assignments exists.

    ``held = (first, count)``: of the router's X experts, ``experts``
    holds ``[first, first + count)`` (``[L, count, ...]``): the chip's
    share under expert parallelism. The router still scores all X; only
    assignments to a held expert are computed (they sort ahead of the
    rest, and the grouped multiplication's schedule is made from the
    held groups' sizes, so an absent assignment costs no visit), and the
    others add nothing: their rows are never written, so they are
    selected away, not multiplied by zero. ``rows`` then counts the held
    experts' ``[count]``. What the absent experts would add is some
    other chip's part of the sum.
    Returns (out [T, E], :class:`Routed`)."""
    t, _ = x.shape
    num_experts = (experts["moe_gate"].shape[-3] if w_router is None
                   else w_router.shape[-1])
    with jax.named_scope("moe"):
        if routing is not None:
            weights, idx = routing
        else:
            with jax.named_scope("route"):
                weights, idx = route(x, w_router, top_k, norm_topk)
        with jax.named_scope("sort"):
            flat = idx.reshape(-1)                       # [T * k]
            if held is not None:
                first, num_experts = held
                here = (flat >= first) & (flat < first + num_experts)
                # Absent assignments sort behind every held group.
                flat = jnp.where(here, flat - first, num_experts)
                here = here.reshape(t, top_k)
            order = jnp.argsort(flat, stable=True)       # sorted -> flat
            rows = jnp.zeros(num_experts + (held is not None),
                             jnp.int32).at[flat].add(1)
            if held is not None:
                rows = rows[:num_experts]
            xs = x[order // top_k]                       # [T * k, E]
        with jax.named_scope("experts"):
            act = grouped_swiglu(xs, experts["moe_gate"], experts["moe_up"],
                                 rows, layer, use_kernel=use_kernel)
            ys = grouped_matmul(act, experts["moe_down"], rows, layer,
                                use_kernel=use_kernel)
        with jax.named_scope("combine"):
            # ONE k-major gather of the rows as the dtype they have, the
            # slabs widened, weighted and added in float32 in top-k
            # order: the same terms in the same order whoever else is in
            # the batch. Not a gather a ``j``: unrolled gathers grow every
            # program's load time (PERF.md, PR 58).
            place = jnp.zeros_like(order).at[order].set(
                jnp.arange(order.shape[0], dtype=order.dtype)
            ).reshape(t, top_k)
            mine = ys[place.T.reshape(-1)].reshape(top_k, t, -1)
            out = jnp.zeros((t, ys.shape[-1]), jnp.float32)
            for j in range(top_k):
                term = weights[:, j, None] * mine[j].astype(jnp.float32)
                if held is not None:
                    term = jnp.where(here[:, j, None], term, 0.0)
                out = out + term
    return out.astype(x.dtype), Routed(rows, idx)
