"""The OLMoE family (a config with experts) on the CPU at small sizes:
8 experts, top 2, hidden 64, 2 layers, MHA, QK-norm. The dropless
routed block, the one MLP / one q-k-v hook, and the engine, against
``benchmark/reference_olmoe.py`` (plain float32, a masked loop over the
experts) on seeded weights.

Tolerances. float32 against the float32 reference: 1e-4 on O(1) logits,
since both sides hold the same numbers and differ only in operation
order. bfloat16 against float32: a bf16 router can flip an expert where
the k-th and (k+1)-th probabilities nearly tie, which moves a logit by a
STEP, not by rounding; so bf16 is held to the chosen-token gap the
benchmark uses (how far under the reference's maximum the engine's
token lies, in standard deviations of that position's logits), at 0.5:
a token drawn at random lies about 3 under at this vocabulary.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import reference, reference_olmoe  # noqa: E402
from ray_tpu.models import llama  # noqa: E402
from ray_tpu.models.continuous_batching import ContinuousBatcher  # noqa: E402
from ray_tpu.models.inference import LlamaGenerator  # noqa: E402
from ray_tpu.ops import moe  # noqa: E402


def tiny(**kw):
    kw = {**dict(num_experts=8, num_experts_per_tok=2, qk_norm=True,
                 intermediate_size=32, num_kv_heads=4, dtype=jnp.float32,
                 attention="reference"), **kw}
    return llama.LlamaConfig.tiny(**kw)


@pytest.fixture(scope="module")
def model():
    config = tiny()
    return config, llama.init_params(config, jax.random.PRNGKey(0))


def _prompts(n, lengths=(5, 9, 17, 12, 7, 21, 3, 14), seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, lengths[i % len(lengths)]).tolist()
            for i in range(n)]


def _serve(config, params, prompts, max_new=6, **engine):
    engine = {**dict(num_slots=4, max_len=64, block_size=16), **engine}
    eng = ContinuousBatcher(config, params=params, **engine)
    rids = [eng.submit(p, max_new) for p in prompts]
    out = eng.run_to_completion()
    return [out[r] for r in rids]


# --------------------------------------------------------------- router

def test_router_is_softmax_over_all_then_topk_unrenormalised():
    h = jax.random.normal(jax.random.PRNGKey(1), (11, 64))
    w = jax.random.normal(jax.random.PRNGKey(2), (64, 8)) / 8
    weights, idx = moe.route_softmax_topk(h, w, 3)
    probs = np.asarray(jax.nn.softmax(h @ w, axis=-1))
    order = np.argsort(-probs, axis=-1)[:, :3]
    np.testing.assert_array_equal(np.asarray(idx), order)
    np.testing.assert_allclose(np.asarray(weights),
                               np.take_along_axis(probs, order, -1),
                               rtol=1e-5)
    assert np.all(np.asarray(weights).sum(-1) < 0.95)  # not renormalised
    renorm, _ = moe.route_softmax_topk(h, w, 3, renormalise=True)
    np.testing.assert_allclose(np.asarray(renorm).sum(-1), 1.0, rtol=1e-6)
    # Mixtral's router (softmax over the top-k logits) is another function.
    mixtral, _ = moe.router_topk(h @ w, 3)
    assert not np.allclose(np.asarray(mixtral), np.asarray(weights))


def test_norm_topk_prob_changes_the_block(model):
    config, params = model
    tokens = jnp.asarray(_prompts(1, (24,))[0])[None]
    plain = llama.forward(params, tokens, config)
    renorm = llama.forward(
        params, tokens, llama.dataclasses.replace(config, norm_topk_prob=True))
    assert float(jnp.max(jnp.abs(plain - renorm))) > 1e-2


# ------------------------------------------------------ the routed block

def _masked_loop(x, w_router, experts, top_k):
    weights, idx = moe.route_softmax_topk(x, w_router, top_k)
    out = jnp.zeros_like(x)
    for e in range(w_router.shape[1]):
        p = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=-1)
        y = (jax.nn.silu(x @ experts["moe_gate"][e])
             * (x @ experts["moe_up"][e])) @ experts["moe_down"][e]
        out = out + p[:, None] * y
    return out


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["ragged_dot", "moe_gmm_interpreted"])
@pytest.mark.parametrize("tokens", [6, 48, 300])
def test_routed_block_computes_every_assignment(tokens, use_kernel):
    """Dropless at any load: 6 rows leave experts untouched, 300 rows of
    top 2 over 8 experts put about 75 on each (a capacity of 1.25 x the
    mean would drop some), several row tiles deep."""
    keys = jax.random.split(jax.random.PRNGKey(tokens), 5)
    x = jax.random.normal(keys[0], (tokens, 64))
    w_router = jax.random.normal(keys[1], (64, 8))
    experts = {"moe_gate": jax.random.normal(keys[2], (3, 8, 64, 32)) / 8,
               "moe_up": jax.random.normal(keys[3], (3, 8, 64, 32)) / 8,
               "moe_down": jax.random.normal(keys[4], (3, 8, 32, 64)) / 6}
    out, routed = jax.jit(lambda x, li: moe.routed_block(
        x, w_router, experts, li, top_k=2, use_kernel=use_kernel))(
            x, jnp.int32(1))
    want = _masked_loop(x, w_router,
                        {k: v[1] for k, v in experts.items()}, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    assert int(routed.rows.sum()) == tokens * 2
    np.testing.assert_array_equal(
        np.asarray(routed.rows),
        np.bincount(np.asarray(routed.experts).ravel(), minlength=8))


def _combine_case(top_k, held, dtype, tokens=24, seed=0):
    """Operands of one routed layer over 16 experts, E = 64, M = 32:
    ``held`` a ``(first, count)`` share of them or None."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    _, count = held or (0, 16)
    x = jax.random.normal(keys[0], (tokens, 64)).astype(dtype)
    w_router = jax.random.normal(keys[1], (64, 16))
    experts = {
        "moe_gate": (jax.random.normal(keys[2], (count, 64, 32)) / 8
                     ).astype(dtype),
        "moe_up": (jax.random.normal(keys[3], (count, 64, 32)) / 8
                   ).astype(dtype),
        "moe_down": (jax.random.normal(keys[4], (count, 32, 64)) / 6
                     ).astype(dtype)}
    return x, w_router, experts


def _plain_combine(x, w_router, experts, top_k, held):
    """``sum_j w[t, j] * float32(ys_j)`` in ``j`` order with numpy, absent
    assignments skipped, rounded once: the sorted rows and the grouped
    calls are the block's own, the sum is not."""
    t = x.shape[0]
    weights, idx = moe.route_softmax_topk(x, w_router, top_k)
    weights, flat = np.asarray(weights), np.asarray(idx).reshape(-1)
    first, count = held or (0, w_router.shape[1])
    here = (flat >= first) & (flat < first + count)
    flat = np.where(here, flat - first, count)
    order = np.argsort(flat, kind="stable")
    rows = jnp.asarray(np.bincount(flat, minlength=count + 1)[:count],
                       jnp.int32)
    act = moe.grouped_swiglu(x[order // top_k], experts["moe_gate"],
                             experts["moe_up"], rows)
    ys = np.asarray(moe.grouped_matmul(act, experts["moe_down"], rows),
                    np.float32)
    place = np.empty_like(order)
    place[order] = np.arange(order.size)
    place, here = place.reshape(t, top_k), here.reshape(t, top_k)
    out = np.zeros((t, ys.shape[1]), np.float32)
    for j in range(top_k):
        out = out + np.where(here[:, j, None],
                             weights[:, j, None] * ys[place[:, j]], 0)
    return np.asarray(jnp.asarray(out).astype(x.dtype), np.float32)


COMBINE_CASES = [(1, None), (4, (4, 6)), (8, None), (10, (2, 9))]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("top_k,held", COMBINE_CASES,
                         ids=["top1", "top4_held", "top8", "top10_held"])
def test_combine_is_the_float32_sum_in_topk_order_rounded_once(
        top_k, held, dtype):
    """The weighted sum over a token's assignments, top 1 (no sum) to top
    10, whole and as a held share (most assignments absent): float32
    products added in the token's own order and rounded ONCE to the
    block's dtype, an absent assignment adding nothing; and a token's
    result is the same bits when every other row of the batch is
    replaced."""
    x, w_router, experts = _combine_case(top_k, held, dtype)
    block = jax.jit(lambda x: moe.routed_block(
        x, w_router, experts, top_k=top_k, held=held)[0])
    got = np.asarray(block(x), np.float32)
    want = _plain_combine(x, w_router, experts, top_k, held)
    if dtype == jnp.float32:
        # the same products; a fused multiply-add may round a sum's last bit
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    else:
        # one rounding to bfloat16: that last bit can move a result
        # across a rounding boundary, one step (2 ** -8 relative), rarely
        np.testing.assert_allclose(got, want, rtol=2.0 ** -7, atol=1e-6)
        assert np.mean(got == want) > 0.99
    others = jax.random.normal(jax.random.PRNGKey(7), x.shape).astype(dtype)
    mixed = jnp.concatenate([x[:5], others[5:]])
    np.testing.assert_array_equal(np.asarray(block(mixed), np.float32)[:5],
                                  got[:5])


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            if hasattr(value, "jaxpr"):
                yield from _eqns(value.jaxpr)


@pytest.mark.parametrize("top_k,held", COMBINE_CASES[1:],
                         ids=["top4_held", "top8", "top10_held"])
def test_routed_block_holds_no_float32_copy_of_the_assignments(top_k, held):
    """No value of ``routed_block``'s jaxpr is a float32 array of
    ``T * k * E`` elements (the ``[T, k, E]`` copy the weighted sum
    once made of ``ys``, twice its bytes): every value of that size
    (the sorted rows, the results, the results in top-k order) is
    bfloat16, as the block's input is."""
    x, w_router, experts = _combine_case(top_k, held, jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda x: moe.routed_block(
        x, w_router, experts, top_k=top_k, held=held)[0])(x)
    size = x.shape[0] * top_k * x.shape[1]
    big = [v.aval for eqn in _eqns(jaxpr.jaxpr) for v in eqn.outvars
           if getattr(v.aval, "size", 0) == size]
    assert len(big) >= 2 and {a.dtype for a in big} == {
        jnp.dtype(jnp.bfloat16)}, big


@pytest.mark.parametrize("shared", [0, 48],
                         ids=["no_shared_expert", "shared_expert"])
def test_a_shared_experts_sum_meets_the_routed_sum_behind_a_barrier(shared):
    """Where a shared expert's output is added, the routed sum is an
    array of its own behind ONE optimisation barrier, taken as the block
    returns it, ``[T, E]``: left free, XLA fuses the sum's gather into
    the shared down projection's epilogue, and behind the reshape to
    ``[B, S, E]`` the barrier compiles to float32 slabs (PERF.md, PR 58).
    A layer with no shared expert has no barrier: its sum may fuse into
    the residual's addition."""
    x, w_router, experts = _combine_case(4, None, jnp.bfloat16)
    config = tiny(hidden_size=64, num_experts=16, num_experts_per_tok=4,
                  shared_intermediate_size=shared, dtype=jnp.bfloat16)
    layer = {"w_router": w_router}
    if shared:
        layer.update(
            shared_gate=jnp.ones((64, shared), jnp.bfloat16),
            shared_up=jnp.ones((64, shared), jnp.bfloat16),
            shared_down=jnp.ones((shared, 64), jnp.bfloat16))
    jaxpr = jax.make_jaxpr(lambda h: llama.mlp_block(
        h, layer, config, experts)[0])(x.reshape(2, 12, 64))
    barred = [eqn.invars[0].aval.shape for eqn in _eqns(jaxpr.jaxpr)
              if eqn.primitive.name == "optimization_barrier"]
    assert barred == ([(24, 64)] if shared else [])


# The fused gate-and-up call: the four configurations' (K, N) an eighth
# (Kimi: a sixteenth) as wide, with what a schedule can meet. ``sizes``
# are the groups' rows in order; ``rows`` the row count (past the
# groups' sum: absent assignments, as a held share sorts them); ``tiles``
# an explicit (tm, tn) so that the small case still walks several row
# tiles and column windows.
SWIGLU_CASES = {
    # OLMoE's tick in small: every group a few rows, one row tile.
    "olmoe_every_group_small": dict(
        k=256, n=128, sizes=[6, 3, 9, 1, 7, 5, 8, 9], rows=48,
        tiles=(16, 128)),
    # Granite: K over N; empty groups; rows not a multiple of tm.
    "granite_empty_groups_ragged_rows": dict(
        k=512, n=96, sizes=[0, 13, 0, 0, 20, 4, 0, 0, 0], rows=37,
        tiles=(16, 96)),
    # Trinity's held share: 3 of 24 rows absent behind the held groups,
    # two column windows, a group (40 rows) straddling three row tiles.
    "trinity_held_absent_rows_straddling_group": dict(
        k=384, n=384, sizes=[2, 40, 0, 3], rows=48, tiles=(16, 128)),
    # Kimi's held share: most rows absent, whole row tiles never visited.
    "kimi_held_mostly_absent": dict(
        k=448, n=128, sizes=[2, 0, 3, 1], rows=96, tiles=(32, 128)),
    # No group has a row: nothing is visited, nothing defined is read.
    "no_rows_at_all": dict(
        k=256, n=128, sizes=[0, 0, 0], rows=32, tiles=(16, 128)),
    # The tile rule's own choice (None) at a prefill's row count.
    "prefill_rows_default_tiles": dict(
        k=256, n=256, sizes=[150, 0, 90, 260, 12], rows=512, tiles=None),
}


def _swiglu_operands(case, dtype):
    c = SWIGLU_CASES[case]
    x = len(c["sizes"])
    keys = jax.random.split(jax.random.PRNGKey(c["k"] + c["rows"]), 3)
    lhs = jax.random.normal(keys[0], (c["rows"], c["k"])).astype(dtype)
    gate, up = (
        (jax.random.normal(key, (2, x, c["k"], c["n"])) / c["k"] ** 0.5
         ).astype(dtype) for key in keys[1:])
    return lhs, gate, up, jnp.asarray(c["sizes"], jnp.int32)


def _swiglu_reference(lhs, gate, up, sizes, layer):
    """float64 on the operands as given, the groups' rows alone."""
    lhs, gate, up = (np.asarray(a, np.float64) for a in (lhs, gate, up))
    out, r = [], 0
    for g, size in enumerate(np.asarray(sizes)):
        rows = lhs[r:r + size]
        a = rows @ gate[layer, g]
        out.append(a / (1 + np.exp(-a)) * (rows @ up[layer, g]))
        r += size
    return np.concatenate(out) if out else np.zeros((0, gate.shape[-1]))


@pytest.mark.parametrize("path", ["ragged_dot", "moe_gmm_interpreted",
                                  "moe_gmm_interpreted_bf16"])
@pytest.mark.parametrize("case", list(SWIGLU_CASES))
def test_grouped_swiglu_is_silu_gate_times_up_rounded_once(case, path):
    """One call for gate and up, against the float64 reference: in
    float32 to operation order; in bfloat16 to ONE rounding of the
    activation (half an ulp: at most 2^-8 of the value; gate and up each
    rounded first, as the block did before PR 37, is three roundings)."""
    dtype = jnp.bfloat16 if path.endswith("bf16") else jnp.float32
    lhs, gate, up, sizes = _swiglu_operands(case, dtype)
    real = int(sizes.sum())
    want = _swiglu_reference(lhs, gate, up, sizes, 1)
    if path == "ragged_dot":
        got = moe.grouped_swiglu(lhs, gate[1], up[1], sizes,
                                 use_kernel=False)
    else:
        got = jax.jit(lambda *a: moe._gmm_call(
            *a, interpret=True, tiles=SWIGLU_CASES[case]["tiles"]))(
                lhs, [gate, up], sizes, jnp.int32(1))
    assert got.shape == (lhs.shape[0], gate.shape[-1])
    assert got.dtype == dtype
    got = np.asarray(got[:real], np.float64)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, atol=2e-5)
    else:
        half_ulp = 2.0 ** -8 * 1.01 * np.abs(want)
        assert np.all(np.abs(got - want) <= half_ulp + 1e-6)


@pytest.mark.parametrize("case", list(SWIGLU_CASES))
def test_grouped_swiglu_kernel_and_ragged_dot_agree(case):
    """The two paths of the dispatch compute one function (float32: to
    operation order), through the public call and its own tile rule."""
    lhs, gate, up, sizes = _swiglu_operands(case, jnp.float32)
    real = int(sizes.sum())
    kernel = moe.grouped_swiglu(lhs, gate, up, sizes, jnp.int32(0),
                                use_kernel=True)
    ragged = moe.grouped_swiglu(lhs, gate, up, sizes, jnp.int32(0),
                                use_kernel=False)
    np.testing.assert_allclose(np.asarray(kernel[:real]),
                               np.asarray(ragged[:real]), atol=2e-5)


def _pallas_calls(jaxpr):
    return (eqn for eqn in _eqns(jaxpr)
            if eqn.primitive.name == "pallas_call")


@pytest.mark.parametrize("tm,tiles_m", [(128, 5), (16, 35)])
def test_gmm_schedule_visits_every_group_tile_pair_once(tm, tiles_m):
    sizes = jnp.asarray([0, 130, 0, 5, 121, 0, 300, 0], jnp.int32)
    gid, tid, bounds, total = moe._visits(sizes, tm, tiles_m)
    n = int(total[0])
    pairs = list(zip(np.asarray(gid)[:n].tolist(),
                     np.asarray(tid)[:n].tolist()))
    # every (group, row tile) pair that shares a row, once, in row order
    owner = np.repeat(np.arange(8), np.asarray(sizes))
    want = sorted({(int(g), r // tm) for r, g in enumerate(owner)},
                  key=lambda p: (p[1], p[0]))
    assert pairs == want and len(set(pairs)) == n
    if tm == 128:
        assert pairs == [(1, 0), (1, 1), (3, 1), (4, 1), (6, 2), (6, 3),
                         (6, 4)]
    assert n <= tiles_m + 8 - 1 == gid.shape[0]
    # the tail repeats the last real visit: nothing new to fetch
    assert set(zip(np.asarray(gid)[n:].tolist(),
                   np.asarray(tid)[n:].tolist())) == {pairs[-1]}
    assert np.asarray(bounds).tolist() == [0, 0, 130, 130, 135, 256, 256,
                                           556, 556]


def test_routed_block_is_two_moe_gmm_calls_on_one_schedule_grid():
    """The block's ``experts`` scope: gate-and-up in ONE call (three
    operands behind the five scalars: the rows and two weight arrays),
    then down; both named ``moe_gmm`` (the benchmark's readers match the
    name), both over ``(N / tn, row tiles + X - 1)`` grid steps, both
    declaring the module's VMEM budget."""
    experts = {"moe_gate": jnp.zeros((2, 8, 64, 32)),
               "moe_up": jnp.zeros((2, 8, 64, 32)),
               "moe_down": jnp.zeros((2, 8, 32, 64))}
    jaxpr = jax.make_jaxpr(lambda x: moe.routed_block(
        x, jnp.zeros((64, 8)), experts, jnp.int32(1), top_k=2,
        use_kernel=True)[0])(jnp.zeros((300, 64)))
    calls = list(_pallas_calls(jaxpr.jaxpr))
    assert [c.params["name"] for c in calls] == ["moe_gmm", "moe_gmm"]
    tm, _ = moe._gmm_tiles(600, 64, 32, 8, 4)
    steps = -(-600 // tm) + 8 - 1
    assert [c.params["grid_mapping"].grid for c in calls] == [
        (1, steps), (1, steps)]
    operands = [len(c.params["grid_mapping"].block_mappings) - 1
                for c in calls]             # less the output
    assert operands == [3, 2]
    for call in calls:
        assert (call.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
                == moe.GMM_VMEM_BYTES)


# ------------------------------------------- system against the reference

def test_forward_matches_reference_and_routes_agree(model):
    config, params = model
    tokens = _prompts(1, (40,), seed=4)[0]
    want = reference_olmoe.logits(params, tokens, config)
    with jax.default_matmul_precision("highest"):
        got, routes = llama.forward(params, jnp.asarray(tokens)[None],
                                    config, return_routes=True)
    # float32 both sides, other operation order (see the module's top).
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=0, atol=1e-4)
    ref_routes = reference_olmoe.router_choices(params, tokens, config)
    np.testing.assert_array_equal(np.sort(np.asarray(routes), -1),
                                  np.sort(np.asarray(ref_routes), -1))


@pytest.mark.parametrize("engine", [
    dict(), dict(use_decode_kernel=True)],
    ids=["paged", "paged_kernels_interpreted"])
def test_engine_tokens_are_the_references_argmax(model, engine):
    """Prefill, then decode through the cache: every token the float32
    engine chose is the reference's own argmax (gap 0), or lies within
    1e-4 of it in logits."""
    config, params = model
    prompts = _prompts(3, seed=7)
    for prompt, chosen in zip(prompts, _serve(config, params, prompts,
                                              **engine)):
        lg = reference_olmoe.logits(params, (prompt + chosen)[:-1], config)
        lg = np.asarray(lg[len(prompt) - 1:])
        under = lg.max(-1) - lg[np.arange(len(chosen)), chosen]
        assert np.all(under <= 1e-4), under


def test_generator_matches_engine(model):
    config, params = model
    prompt = _prompts(1, (9,), seed=2)
    gen = LlamaGenerator(config, params=params, max_len=32)
    want = np.asarray(gen.generate(np.asarray(prompt), max_new_tokens=5))[0]
    assert _serve(config, params, prompt, max_new=5)[0] == want.tolist()


def test_bf16_engine_within_the_stated_gap():
    config = tiny(dtype=jnp.bfloat16)
    params = llama.init_params(config, jax.random.PRNGKey(0))
    prompts = _prompts(3, seed=9)
    worst = 0.0
    for prompt, chosen in zip(prompts, _serve(config, params, prompts)):
        gaps = reference_olmoe.chosen_gaps(params, prompt, chosen, config)
        worst = max(worst, float(jnp.max(gaps)))
    assert worst <= 0.5, worst     # the module's top says why 0.5


def test_qk_norm_on_and_off_differ(model):
    config, params = model
    tokens = jnp.asarray(_prompts(1, (24,))[0])[None]
    on = llama.forward(params, tokens, config)
    off = llama.forward(params, tokens,
                        llama.dataclasses.replace(config, qk_norm=False))
    assert float(jnp.max(jnp.abs(on - off))) > 1e-2
    ref_off = reference_olmoe.logits(
        params, tokens[0], llama.dataclasses.replace(config, qk_norm=False))
    np.testing.assert_allclose(np.asarray(off[0]), np.asarray(ref_off),
                               atol=1e-4)


# ------------------------------------------------- the dropless property

@pytest.mark.parametrize("use_decode_kernel", [None, True],
                         ids=["xla", "kernels_interpreted"])
def test_a_requests_tokens_do_not_depend_on_its_batch(model,
                                                      use_decode_kernel):
    """One request alone against the same request among 7 others:
    identical tokens. Under capacity routing the neighbours' rows could
    push this request's tokens out of an expert."""
    config, params = model
    prompts = _prompts(8, seed=11)
    alone = _serve(config, params, prompts[:1], max_new=10, num_slots=8,
                   use_decode_kernel=use_decode_kernel)[0]
    crowd = _serve(config, params, prompts, max_new=10, num_slots=8,
                   use_decode_kernel=use_decode_kernel)[0]
    assert alone == crowd


# ------------------------------------- one expert is the dense Llama model

def _as_dense(config, params):
    dense = llama.dataclasses.replace(config, num_experts=0,
                                      num_experts_per_tok=0)
    layers = dict(params["layers"])
    for moe_key, key in (("moe_gate", "w_gate"), ("moe_up", "w_up"),
                         ("moe_down", "w_down")):
        layers[key] = layers.pop(moe_key)[:, 0]
    del layers["w_router"]
    return dense, dict(params, layers=layers)


def test_one_expert_top_one_is_the_dense_llama_engine():
    """softmax over one expert is 1, so the routed block IS the dense
    SwiGLU block on the same weights."""
    config = tiny(num_experts=1, num_experts_per_tok=1, qk_norm=False)
    params = llama.init_params(config, jax.random.PRNGKey(3))
    dense, dense_params = _as_dense(config, params)
    prompts = _prompts(3, seed=5)
    assert (_serve(config, params, prompts)
            == _serve(dense, dense_params, prompts))
    tokens = prompts[1] + [7, 8, 9]
    np.testing.assert_allclose(
        np.asarray(reference_olmoe.logits(params, tokens, config)),
        np.asarray(reference.logits(dense_params, tokens, dense)),
        atol=1e-5)


# ------------------------------------------------------------ bookkeeping

def test_tick_reports_expert_rows_in_the_token_fetch(model):
    from ray_tpu._private import metrics_defs as mdefs

    config, params = model
    eng = ContinuousBatcher(config, params=params, num_slots=4, max_len=64,
                            block_size=16)

    def total(metric):
        return sum(v for name, _, v in metric.samples()
                   if name.endswith(("_total", "_count")))

    before = total(mdefs.CB_MOE_ASSIGNMENTS), total(mdefs.CB_MOE_TOUCHED_SHARE)
    eng.submit(_prompts(1)[0], 4)
    eng.run_to_completion()
    ticks = eng.base_tick_count
    # every slot routes, live or not: slots x top-k x layers a tick
    assert (total(mdefs.CB_MOE_ASSIGNMENTS) - before[0]
            == ticks * 4 * 2 * config.num_layers)
    assert total(mdefs.CB_MOE_TOUCHED_SHARE) - before[1] == ticks


def test_tick_bytes_estimate_counts_the_experts_a_tick_can_touch():
    config = tiny(num_experts=64, num_experts_per_tok=2)
    eng = ContinuousBatcher(config, num_slots=4, max_len=32, block_size=16)
    expert_bytes = sum(eng.params["layers"][k].nbytes
                       for k in llama.EXPERT_KEYS)
    # 4 slots x top 2 touch at most 8 of each layer's 64 experts
    assert (eng.tick_bytes_estimate()
            == eng.param_bytes - expert_bytes * 56 // 64)
    full = ContinuousBatcher(config, num_slots=32, max_len=32, block_size=16)
    assert full.tick_bytes_estimate() == full.param_bytes


def test_params_and_axes_of_the_family():
    config = tiny()
    shapes = jax.eval_shape(lambda k: llama.init_params(config, k),
                            jax.random.PRNGKey(0))
    layers = shapes["layers"]
    assert layers["w_router"].shape == (2, 64, 8)
    assert layers["w_router"].dtype == jnp.float32
    assert layers["moe_gate"].shape == (2, 8, 64, 32)
    assert layers["moe_down"].shape == (2, 8, 32, 64)
    assert layers["q_norm"].shape == (2, 64)
    assert "w_gate" not in layers
    axes = llama.logical_axes(config)["layers"]
    assert set(axes) == set(layers)
    assert all(len(axes[k]) == layers[k].ndim for k in layers)
    assert llama.num_params(config) == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    published = llama.LlamaConfig.olmoe_1b_7b()
    assert 6.9e9 < llama.num_params(published) < 6.93e9
