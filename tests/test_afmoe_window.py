"""The afmoe family (Trinity: sliding-window and full attention layers
in one stack, per-head QK-norm, a gated attention output, four norms a
layer, a leading dense layer, a sigmoid router over 16 experts of which
this "chip" holds 4, a shared expert) on the CPU at small sizes: the
engine (chunked prefill, the ring, ticks across the window's edge and
across a ring wrap, refusals) against the float32 reference, and the
pieces (router, held experts, the window kernel, the blockwise prefill
attention) against plain formulas, on seeded weights.

Tolerances. float32 against float32: both sides hold the same numbers
and differ in operation order, so logits within 2e-4 of their standard
deviation, and the engine's tokens are the reference's ARGMAX at every
generated position.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import reference_afmoe as reference  # noqa: E402
from ray_tpu.models import continuous_batching as cb  # noqa: E402
from ray_tpu.models import llama  # noqa: E402
from ray_tpu.models.continuous_batching import ContinuousBatcher  # noqa: E402
from ray_tpu.models.inference import _attend_cached  # noqa: E402
from ray_tpu.models.paged_kv import (PagedKVCache, RingKVCache,  # noqa: E402
                                     ring_blocks)
from ray_tpu.ops import moe  # noqa: E402
from ray_tpu.ops.attention import paged_chunk_attention  # noqa: E402
from ray_tpu.ops.paged_decode_attention import (  # noqa: E402
    MAX_VISIT_BLOCKS, paged_decode_attention, paged_visits)

TYPES = ("sliding_attention", "sliding_attention", "sliding_attention",
         "full_attention", "sliding_attention")
WINDOW, BS, CHUNK = 24, 8, 16       # ring: 24 / 8 + 2 = 5 blocks = 40 tokens

def _prefill_batches(eng):
    """Prefill batches ``eng`` ran: ``CB_PREFILL_MS`` books one each."""
    from ray_tpu._private import metrics_defs as mdefs

    return mdefs.CB_PREFILL_MS.totals(eng._mtags)[1]



def tiny(**kw):
    return llama.LlamaConfig.trinity_large_preview(**{**dict(
        vocab_size=256, hidden_size=64, intermediate_size=32, num_layers=5,
        layer_types=TYPES, sliding_window=WINDOW, num_heads=4,
        num_kv_heads=2, head_dim=16, num_dense_layers=1,
        dense_intermediate_size=96, num_experts=16, num_experts_per_tok=2,
        experts_held=(4, 4), shared_intermediate_size=32,
        embedding_multiplier=8.0, max_seq_len=256, dtype=jnp.float32), **kw})


@pytest.fixture(scope="module")
def model():
    config = tiny()
    return config, llama.init_params(config, jax.random.PRNGKey(1))


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, n).tolist() for n in lengths]


def _serve(config, params, prompts, max_new=6, **engine):
    engine = {**dict(num_slots=4, max_len=160, block_size=BS,
                     prefill_chunk=CHUNK), **engine}
    eng = ContinuousBatcher(config, params=params, **engine)
    rids = [eng.submit(p, max_new) for p in prompts]
    out = eng.run_to_completion()
    return [out[r] for r in rids], eng


def _reference_tokens(params, config, prompt, out):
    """The reference's greedy choice at each position of ``out``, teacher
    forced on ``out`` itself: equal to ``out`` exactly when greedy
    decoding by the reference's full forward gives ``out``."""
    seq = list(prompt) + list(out)
    lg = reference.logits(params, seq[:-1], config)[len(prompt) - 1:]
    return [int(t) for t in jnp.argmax(lg, axis=-1)]


# ----------------------------------------------------------- the model

def test_runs_end_where_the_kind_or_the_mlp_changes():
    assert llama.layer_runs(tiny()) == [
        ("sliding_attention", 0, 1, 0), ("sliding_attention", 1, 2, 1),
        ("full_attention", 3, 1, 0), ("sliding_attention", 4, 1, 3)]
    c = tiny()
    assert (c.window_layers, c.attn_layers, c.moe_layers) == (4, 1, 4)


def test_published_config_and_param_count():
    """The share ISSUE 32 sizes: 1 dense + 4 routed layers holding 32 of
    256 experts and an eighth of the vocabulary is 4.32B parameters."""
    c = llama.LlamaConfig.trinity_large_preview(
        num_layers=5, layer_types=TYPES, num_dense_layers=1,
        experts_held=(0, 32), vocab_size=25024)
    assert abs(llama.num_params(c) / 1e9 - 4.32) < 0.01
    whole = llama.LlamaConfig.trinity_large_preview()
    assert whole.layer_types.count("full_attention") == 15
    assert whole.window_layers == 45 and whole.moe_layers == 54


def test_training_forward_refuses_the_family(model):
    config, params = model
    with pytest.raises(NotImplementedError, match="sliding-window"):
        llama.forward(params, jnp.zeros((1, 8), jnp.int32), config)


# ---------------------------------------------------------- the router

def test_sigmoid_router_is_the_formula():
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(7, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 16)) / 32 ** 0.5, jnp.float32)
    bias = jnp.asarray(0.2 * rng.normal(size=16), jnp.float32)
    weights, idx = moe.route_sigmoid_topk(h, w, 3, bias=bias, scale=2.448)
    s = 1 / (1 + np.exp(-np.asarray(h) @ np.asarray(w)))
    want = np.argsort(-(s + np.asarray(bias)), axis=-1)[:, :3]
    assert np.array_equal(np.asarray(idx), want)
    picked = np.take_along_axis(s, want, -1)
    np.testing.assert_allclose(
        weights, 2.448 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    # The bias selects only: it is not in the weights, and without it
    # other experts are chosen.
    _, plain = moe.route_sigmoid_topk(h, w, 3, bias=jnp.zeros(16), scale=1.0)
    assert not np.array_equal(np.asarray(plain), want)


def _experts(rng, x, e, m, layers=1):
    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) / shape[-2] ** 0.5,
                           jnp.float32)
    return {"moe_gate": w(layers, x, e, m), "moe_up": w(layers, x, e, m),
            "moe_down": w(layers, x, m, e)}


@pytest.mark.parametrize("use_kernel", [False, True])
def test_the_eight_shares_and_the_shared_expert_once_are_the_layer(
        use_kernel, pallas_interpret):
    """The guide's share test: a layer's routed experts held 2 each by
    8 "chips"; the parts the shares compute, added up, are what the
    uncut block gives (the shared expert is every chip's alike and is
    added once, outside the routed block)."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(24, 32)), jnp.float32)
    w_router = jnp.asarray(rng.normal(size=(32, 16)) / 32 ** 0.5, jnp.float32)
    experts = _experts(rng, 16, 32, 16)
    route = lambda *a: moe.route_sigmoid_topk(  # noqa: E731
        *a, bias=jnp.zeros(16), scale=2.448)
    whole, routed = moe.routed_block(x, w_router, experts, 0, top_k=4,
                                     norm_topk=True, route=route,
                                     use_kernel=use_kernel)
    parts, rows = 0.0, []
    for first in range(0, 16, 2):
        share = {k: v[:, first:first + 2] for k, v in experts.items()}
        part, r = moe.routed_block(x, w_router, share, 0, top_k=4,
                                   norm_topk=True, route=route,
                                   held=(first, 2), use_kernel=use_kernel)
        parts = parts + part
        rows.append(np.asarray(r.rows))
    np.testing.assert_allclose(parts, whole, atol=2e-5)
    assert np.array_equal(np.concatenate(rows), np.asarray(routed.rows))
    assert int(routed.rows.sum()) == 24 * 4


def test_absent_assignments_never_read_an_unwritten_row(pallas_interpret):
    """A share that holds nothing any token chose returns exact zeros,
    whatever the grouped kernel left in the rows it never wrote."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(8, 32)), jnp.float32)
    # Scores that put experts 0..3 far ahead of the held 12..15.
    w_router = jnp.zeros((32, 16)).at[:, :4].set(1.0)
    x = jnp.abs(x)
    share = _experts(rng, 4, 32, 16)
    route = lambda *a: moe.route_sigmoid_topk(  # noqa: E731
        *a, bias=jnp.zeros(16), scale=1.0)
    out, routed = moe.routed_block(x, w_router, share, 0, top_k=4,
                                   route=route, held=(12, 4),
                                   use_kernel=True)
    assert int(routed.rows.sum()) == 0
    assert np.array_equal(np.asarray(out), np.zeros_like(out))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_a_held_shares_sum_selects_unwritten_rows_away(monkeypatch, dtype):
    """Trinity's share in small (top 4, 4 of 16 experts held, so three
    assignments in four are absent) with every row the grouped
    multiplication did not write made NaN: the weighted sum still reads
    one row an assignment, and an absent one is SELECTED away (0 x NaN
    would be NaN), so the result is finite and is the sum with those
    rows zero; a token none of whose experts is held gets exact zeros."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(40, 32)), dtype)
    w_router = jnp.asarray(rng.normal(size=(32, 16)) / 32 ** 0.5, jnp.float32)
    share = {k: v.astype(dtype) for k, v in _experts(rng, 4, 32, 16).items()}
    route = lambda *a: moe.route_sigmoid_topk(  # noqa: E731
        *a, bias=jnp.zeros(16), scale=2.448)
    block = lambda: moe.routed_block(  # noqa: E731
        x, w_router, share, 0, top_k=4, norm_topk=True, route=route,
        held=(8, 4))
    clean, routed = block()
    written = int(routed.rows.sum())
    assert 0 < written < 40 * 4 // 2
    matmul = moe.grouped_matmul

    def poisoned(lhs, rhs, sizes, layer=None, **kw):
        ys = matmul(lhs, rhs, sizes, layer, **kw)
        return jnp.where(jnp.arange(ys.shape[0])[:, None] < sizes.sum(),
                         ys, jnp.nan)

    monkeypatch.setattr(moe, "grouped_matmul", poisoned)
    out, _ = block()
    assert np.isfinite(np.asarray(out, np.float32)).all()
    assert np.array_equal(np.asarray(out, np.float32),
                          np.asarray(clean, np.float32))
    experts = np.asarray(routed.experts)
    nothing_held = ~((experts >= 8) & (experts < 12)).any(axis=1)
    assert nothing_held.any()
    assert not np.asarray(out, np.float32)[nothing_held].any()


def test_held_none_and_a_softmax_router_are_the_parents_block():
    """``held=None`` with the default router is the block with its new
    arguments left out, bit for bit, and computes what the ops PR 25
    wrote compute (their weighted sum an einsum over a float32 copy:
    the same terms, so equal to float32 summation order)."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(12, 32)), jnp.float32)
    w_router = jnp.asarray(rng.normal(size=(32, 8)), jnp.float32)
    experts = _experts(rng, 8, 32, 16)

    def pr25(x, w, e):
        t, top_k = x.shape[0], 2
        weights, idx = moe.route_softmax_topk(x, w, top_k, False)
        flat = idx.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        rows = jnp.zeros(8, jnp.int32).at[flat].add(1)
        xs = x[order // top_k]
        gmm = lambda a, b: moe.grouped_matmul(a, b, rows, layer=0)  # noqa: E731
        act = jax.nn.silu(gmm(xs, e["moe_gate"])) * gmm(xs, e["moe_up"])
        ys = gmm(act, e["moe_down"])
        place = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        return jnp.einsum("tk,tke->te", weights,
                          ys[place].reshape(t, top_k, -1).astype(
                              jnp.float32)).astype(x.dtype)

    new = moe.routed_block(x, w_router, experts, 0, top_k=2)[0]
    spelled = moe.routed_block(x, w_router, experts, 0, top_k=2, held=None,
                               route=moe.route_softmax_topk, routing=None)[0]
    assert np.array_equal(np.asarray(new), np.asarray(spelled))
    np.testing.assert_allclose(np.asarray(new),
                               np.asarray(pr25(x, w_router, experts)),
                               rtol=1e-5, atol=1e-6)


# ------------------------------------------------- the window's kernels

def _ring_case(positions, ring, seed=0):
    """A ring cache filled as the engine fills it: position p of slot b
    in entry (p // BS) % ring of the slot's own blocks, plus the dense
    K/V it stands for."""
    rng = np.random.default_rng(seed)
    b, kvh, d, total = len(positions), 2, 16, max(positions) + 1
    k = rng.normal(size=(b, total, kvh, d)).astype(np.float32)
    v = rng.normal(size=(b, total, kvh, d)).astype(np.float32)
    arena_k = np.zeros((1, 1 + b * ring, kvh, BS, d), np.float32)
    arena_v = np.zeros_like(arena_k)
    for s, last in enumerate(positions):
        for p in range(last + 1):       # later positions overwrite
            blk = 1 + s * ring + (p // BS) % ring
            arena_k[0, blk, :, p % BS] = k[s, p]
            arena_v[0, blk, :, p % BS] = v[s, p]
    q = rng.normal(size=(b, 4, d)).astype(np.float32)
    return q, k, v, jnp.asarray(arena_k), jnp.asarray(arena_v)


def _window_attention(q, k, v, positions, window):
    out = []
    for s, p in enumerate(positions):
        lo = max(p - window + 1, 0)
        ks, vs = k[s, lo:p + 1], v[s, lo:p + 1]          # [T, KVH, D]
        qg = q[s].reshape(2, 2, -1)
        sc = np.einsum("hgd,thd->hgt", qg, ks) * 0.25
        pr = np.exp(sc - sc.max(-1, keepdims=True))
        pr /= pr.sum(-1, keepdims=True)
        out.append(np.einsum("hgt,thd->hgd", pr, vs).reshape(4, -1))
    return np.stack(out)


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("positions", [
    (3, 23, 24), (25, 39, 40), (41, 79, 120), (0, 7, 8)])
def test_window_decode_attention_over_a_ring(use_kernel, positions,
                                             pallas_interpret):
    """Under the window, at its edge, past the ring's first wrap and
    several wraps on: a query sees its last WINDOW keys and no other,
    whichever ring entries they lie in."""
    ring = ring_blocks(WINDOW, BS)
    q, k, v, ak, av = _ring_case(positions, ring)
    pos = jnp.asarray(positions, jnp.int32)
    tables = RingKVCache.tables(jnp.arange(len(positions)), ring)
    out = paged_decode_attention(
        jnp.asarray(q), ak, av, tables, pos, 0.25, layer=0,
        use_kernel=use_kernel, window=WINDOW)
    np.testing.assert_allclose(
        out, _window_attention(q, k, v, positions, WINDOW), atol=2e-5)


def test_window_visits_start_at_the_first_live_block():
    pos = jnp.asarray([3, 40, 100], jnp.int32)
    tables = RingKVCache.tables(jnp.arange(3), 5)
    per = 2
    slot, block, where, count = paged_visits(
        tables, pos, jnp.asarray([8, 8, 0]), block_size=BS, per_visit=per,
        window=WINDOW)
    n = int(count[0])
    # Slot 0: block 0; slot 1: keys 17..40 = blocks 2..5, in runs of
    # ``per`` from block 2; slot 2 freed.
    starts = list(range(2, 6, per))
    assert n == 1 + len(starts)
    assert list(map(int, slot[:n])) == [0] + [1] * len(starts)
    assert list(map(int, block[:n])) == [0] + starts
    # Each visit's first sub-block is its ring entry, block % ring.
    assert list(map(int, where[:n])) == [0] + [5 + b % 5 for b in starts]


@pytest.mark.parametrize("window", [0, 20])
@pytest.mark.parametrize("m", [0, 3, 6])
def test_chunk_attention_is_the_dense_softmax(window, m):
    rng = np.random.default_rng(4)
    n, s, kvh, h, d = 2, 16, 2, 4, 16
    def r(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)
    ak, av = r(2, 20, kvh, BS, d), r(2, 20, kvh, BS, d)
    tables = jnp.asarray(rng.permutation(19)[:n * m].reshape(n, m) + 1,
                         jnp.int32)
    q, kn, vn = r(n, s, h, d), r(n, s, kvh, d), r(n, s, kvh, d)
    out = paged_chunk_attention(q, kn, vn, ak, av, 1, tables, 0, m * BS,
                                0.25, window=window, key_blocks=2,
                                key_step=8)
    def ctx(a):
        return jnp.swapaxes(a[1][tables], 2, 3).reshape(n, m * BS, kvh, d)
    ck = jnp.concatenate([ctx(ak), kn], 1)
    cv = jnp.concatenate([ctx(av), vn], 1)
    pos = m * BS + jnp.arange(s)
    if not window:
        want = _attend_cached(q, ck, cv, pos, 0.25)
    else:
        kp = jnp.arange(m * BS + s)
        seen = (pos[:, None] >= kp) & (pos[:, None] - kp < window)
        lg = jnp.einsum("bqhgd,bkhd->bqhgk", q.reshape(n, s, kvh, 2, d),
                        ck) * 0.25
        pr = jax.nn.softmax(jnp.where(seen[None, :, None, None], lg, -1e30),
                            -1)
        want = jnp.einsum("bqhgk,bkhd->bqhgd", pr, cv).reshape(n, s, h, d)
    np.testing.assert_allclose(out, want, atol=2e-6)


# ----------------------------------------------------------- the engine

LENGTHS = (5, 20, 37, 70)     # one call; past the window; ring wrapped twice


@pytest.mark.parametrize("engine", [
    dict(use_decode_kernel=False), dict(use_decode_kernel=True)],
    ids=["kernels-off", "kernels-interpreted"])
def test_engine_tokens_are_the_references_argmax(model, engine,
                                                 pallas_interpret):
    """Prompts under the window, crossing it DURING decode (20 + 12),
    multi-chunk beyond it, and long enough that prefill and ticks both
    wrap the ring: every generated token is the reference's argmax."""
    config, params = model
    prompts = _prompts(LENGTHS)
    outs, eng = _serve(config, params, prompts, max_new=12, **engine)
    for prompt, out in zip(prompts, outs):
        assert out == _reference_tokens(params, config, prompt, out)
    assert _prefill_batches(eng) == 4     # four chunk counts, four groups


def test_kept_routes_are_the_references_choices(model):
    """``keep_routes``: one entry a decoded position (the position token
    j was fed at), each the reference's top k over the router's WHOLE
    width in every routed layer, whatever else is in the batch; a
    request that did not ask keeps none, and a model without a held
    share refuses."""
    config, params = model
    prompts = _prompts(LENGTHS)
    eng = ContinuousBatcher(config, params=params, num_slots=4, max_len=160,
                            block_size=BS, prefill_chunk=CHUNK)
    rids = [eng.submit(p, 12, keep_routes=i != 1)
            for i, p in enumerate(prompts)]
    outs = eng.run_to_completion()
    assert eng.take_routes(rids[1]) is None
    for i in (0, 2, 3):
        prompt, out = prompts[i], outs[rids[i]]
        got = np.asarray(eng.take_routes(rids[i]))     # [11, L_moe, k]
        want = np.asarray(reference.router_choices(
            params, prompt + out[:-1], config))[:, len(prompt):]
        assert got.shape == (11, 4, 2)
        assert (np.sort(got, -1)
                == np.sort(want.transpose(1, 0, 2), -1)).all()
        assert eng.take_routes(rids[i]) is None         # taken once
    with pytest.raises(ValueError, match="held expert share"):
        ContinuousBatcher(llama.LlamaConfig.tiny(), num_slots=2,
                          max_len=32).submit([1, 2], 2, keep_routes=True)


def test_the_stream_ends_with_the_routes_when_asked(model):
    """``"return_routes": true`` through the deployment: the tokens,
    then ONE control object holding the engine's kept routes."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import ContinuousLlamaDeployment

    config, params = model
    prompt = _prompts((37,))[0]
    (want,), eng = _serve(config, params, [prompt], max_new=5)
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, num_tpus=0)
    try:
        h = serve.run(ContinuousLlamaDeployment.options(
            num_replicas=1).bind(config, params, 4, 160, block_size=BS,
                                 prefill_chunk=CHUNK))
        stream = h.options("generate", stream=True)
        items = list(stream.remote({"prompt_token_ids": prompt,
                                    "max_tokens": 5, "return_routes": True}))
        assert items[:5] == want and len(items) == 6
        assert np.asarray(items[5]["routes"]).shape == (4, 4, 2)
        plain = list(stream.remote({"prompt_token_ids": prompt,
                                    "max_tokens": 5}))
        assert plain == want
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def test_chunked_prefill_then_ticks_give_the_references_logits(model):
    """The engine's two forwards by hand: a 70-token prompt as five
    chunks of 16 through ring and arena (``cb_prefill``'s body), then 20
    teacher-forced ticks (``cb_tick``'s): the logits at the prompt's end
    and at every decoded position, across two wraps of the ring, within
    2e-4 of the reference's standard deviation."""
    config, params = model
    eparams = llama.heads_major(params)
    seq = _prompts((90,))[0]
    n_prompt, slot = 70, 1
    cache = PagedKVCache.create(config, 16, BS)
    ring = RingKVCache.create(config, 2, BS)
    blocks = jnp.arange(1, 13, dtype=jnp.int32)[None]        # 96 tokens
    per = CHUNK // BS
    for ci in range(5):
        part = seq[ci * CHUNK:min((ci + 1) * CHUNK, n_prompt)]
        tokens = jnp.zeros((1, CHUNK), jnp.int32).at[0, :len(part)].set(
            jnp.asarray(part))
        logits, cache, ring = cb._prefill_chunk_paged(
            eparams, tokens, ci * CHUNK + jnp.arange(CHUNK), cache, ring,
            blocks[:, :ci * per], blocks[:, ci * per:(ci + 1) * per],
            jnp.asarray([len(part) - 1]), jnp.asarray([slot]), config, False)
    got = [np.asarray(logits[0, 0])]
    tables = jnp.zeros((2, 12), jnp.int32).at[slot].set(blocks[0])
    limits = jnp.asarray([0, 96], jnp.int32)
    caches = (cache, ring)
    for p in range(n_prompt, len(seq) - 1):
        tokens = jnp.zeros((2, 1), jnp.int32).at[slot, 0].set(seq[p])
        positions = jnp.zeros((2, 1), jnp.int32).at[slot, 0].set(p)
        logits, caches, rows = cb._forward_paged(
            eparams, tokens, positions, tables, limits, caches, config, False)
        got.append(np.asarray(logits[slot, 0]))
    # Routed layers x (held experts' counts + 2 rows' top 2 of 16).
    assert rows.shape == (4, 4 + 2 * 2)
    assert int(rows[:, 4:].max()) < 16
    want = reference.logits(params, seq[:-1], config)[n_prompt - 1:]
    assert np.max(np.abs(np.stack(got) - np.asarray(want))) < 2e-4 * float(
        jnp.std(want))


def test_a_requests_tokens_do_not_depend_on_its_batch_nor_its_slot(model):
    config, params = model
    prompts = _prompts(LENGTHS, seed=1)
    together, _ = _serve(config, params, prompts, max_new=8)
    for prompt, out in zip(reversed(prompts), reversed(together)):
        assert _serve(config, params, [prompt], max_new=8)[0][0] == out


def test_padded_rows_never_overwrite_a_live_ring_entry(model):
    """A prompt of 35 tokens in chunks of 16 ends 3 tokens into its last
    chunk; the chunk's second block is padding only. Written into the
    ring it would land on logical block 0's successor entries that the
    first decode queries still see; it goes to the garbage block, so the
    slot's ring holds exactly the prompt's K/V."""
    config, params = model
    prompt = _prompts((35,))[0]
    eng = ContinuousBatcher(config, params=params, num_slots=2, max_len=160,
                            block_size=BS, prefill_chunk=CHUNK)
    before = np.asarray(eng.state.k)
    eng.submit(prompt, 1)
    eng.run_to_completion()
    ring = ring_blocks(WINDOW, BS)
    changed = {int(b) for b in np.nonzero(np.any(
        np.asarray(eng.state.k) != before, axis=(0, 2, 3, 4)))[0]}
    slot_blocks = changed - {0}
    # Logical blocks 0..4 (35 tokens) of ONE slot, each in its own entry;
    # the pad-only block 5 (entry 0 again) was not written there.
    base = min(slot_blocks) - (min(slot_blocks) - 1) % ring
    assert slot_blocks == {base + b for b in range(5)}
    # ... and entry 0 still holds logical block 0, not padding.
    outs, _ = _serve(config, params, [prompt], max_new=4)
    assert outs[0] == _reference_tokens(params, config, prompt, outs[0])


def test_arena_holds_full_layers_and_the_ring_the_rest(model):
    config, params = model
    eng = ContinuousBatcher(config, params=params, num_slots=3, max_len=160,
                            block_size=BS)
    assert isinstance(eng.cache, PagedKVCache)
    assert eng.cache.k.shape[0] == 1
    assert eng.state.k.shape[:2] == (4, 1 + 3 * ring_blocks(WINDOW, BS))
    assert eng._prefix is None


def test_reset_rebuilds_the_ring(model):
    config, params = model
    eng = ContinuousBatcher(config, params=params, num_slots=2, max_len=160,
                            block_size=BS, prefill_chunk=CHUNK)
    eng.submit(_prompts((30,))[0], 4)
    eng.step()
    eng.reset()
    assert not np.any(np.asarray(eng.state.k))
    rid = eng.submit(_prompts((30,))[0], 4)
    assert len(eng.run_to_completion()[rid]) == 4


@pytest.mark.parametrize("kwargs,named", [
    (dict(prefix_cache=True), "prefix cache"),
    (dict(spec_k=2), "speculative"),
    (dict(role="prefill"), "role='prefill'"),
    (dict(kv_dtype="int8"), "kv_dtype='int8'"),
])
def test_refused_by_name_for_window_layers(model, kwargs, named):
    config, params = model
    with pytest.raises(ValueError, match="sliding-window") as err:
        ContinuousBatcher(config, params=params, num_slots=2, max_len=64,
                          block_size=BS, **kwargs)
    assert named in str(err.value)


def test_prefix_cache_env_is_refused_and_unset_means_off(model, monkeypatch):
    config, params = model
    monkeypatch.setenv("RAY_TPU_PREFIX_CACHE", "1")
    with pytest.raises(ValueError, match="prefix cache"):
        ContinuousBatcher(config, params=params, num_slots=2, max_len=64,
                          block_size=BS)


@pytest.mark.parametrize("call", ["export_kv_payload", "import_kv_payload"])
def test_kv_handoff_is_refused_for_window_layers(model, call):
    config, params = model
    eng = ContinuousBatcher(config, params=params, num_slots=2, max_len=64,
                            block_size=BS)
    with pytest.raises(ValueError, match="sliding-window"):
        getattr(eng, call)({} if call.startswith("import") else 0)


def test_window_and_expert_metrics_are_booked(model):
    from ray_tpu._private import metrics_defs as mdefs

    def total(metric, suffix=""):
        return sum(v for n, _, v in metric.samples()
                   if n == metric.name + suffix)

    config, params = model
    before = {m: total(m) for m in (mdefs.CB_MOE_ASSIGNMENTS,
                                    mdefs.CB_MOE_LOCAL_ASSIGNMENTS)}
    chunks = total(mdefs.CB_PREFILL_CHUNK_MS, "_count")
    shares = total(mdefs.CB_WINDOW_LIVE_BLOCK_SHARE, "_count")
    fills = {end: total(mdefs.CB_PAGED_VISIT_FILL_SHARE, end)
             for end in ("_sum", "_count")}
    outs, eng = _serve(config, params, _prompts((70,)), max_new=10)
    ticks = eng.base_tick_count
    asked = total(mdefs.CB_MOE_ASSIGNMENTS) - before[mdefs.CB_MOE_ASSIGNMENTS]
    local = (total(mdefs.CB_MOE_LOCAL_ASSIGNMENTS)
             - before[mdefs.CB_MOE_LOCAL_ASSIGNMENTS])
    # Every slot routes top 2 in each of the 4 routed layers, every tick.
    assert asked == ticks * 4 * 2 * 4
    assert 0 < local < asked
    assert total(mdefs.CB_PREFILL_CHUNK_MS, "_count") - chunks == 5  # 70/16
    assert total(mdefs.CB_WINDOW_LIVE_BLOCK_SHARE, "_count") - shares == ticks
    # Rings and table, each by its layer count: a share in (0, 1] a tick.
    fills = {end: total(mdefs.CB_PAGED_VISIT_FILL_SHARE, end) - was
             for end, was in fills.items()}
    assert fills["_count"] == ticks
    assert 1 / MAX_VISIT_BLOCKS <= fills["_sum"] / ticks <= 1
    assert eng._window_blocks()[1] == 0         # nothing live any more
    gauges = {n: v for m in (mdefs.CB_WINDOW_KV_BYTES, mdefs.CB_FULL_KV_BYTES)
              for n, tags, v in m.samples()
              if dict(tags).get("engine") == eng._mtags["engine"]}
    assert gauges[mdefs.CB_WINDOW_KV_BYTES.name] == eng.state.nbytes
    assert gauges[mdefs.CB_FULL_KV_BYTES.name] == 2 * eng.cache.k.nbytes
