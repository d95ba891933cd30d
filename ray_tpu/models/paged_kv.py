"""Paged KV cache: a shared block arena + host-side block allocator.

The dense pooled cache (``inference.KVCache``) gives every slot a
private ``[S_max]`` stripe, so every decode tick streams ``S_max``
entries per slot regardless of how many are live — at 32 slots x 512
max_len with ~40-token requests that is >10x pure padding traffic. The
paged layout mirrors vLLM's KV manager: one arena of fixed-size blocks
(``[L, num_blocks, KVH, block_size, D]``, heads ahead of the block's
token axis so the paged kernel streams whole trailing tiles — see
``ops/paged_decode_attention.py``) shared by all slots, a
per-slot block table naming the blocks it filled, and a free-list
allocator on the host. A slot's attention reads only its live blocks;
freeing a slot returns its blocks for immediate reuse; and block
granularity is the unit future prefix/radix sharing needs (ROADMAP
item 2).

Optional int8 quantization stores the arena as int8 with fp32
per-token/per-kv-head scales in block-shaped sidecars — block-local
scale state that travels with its block through the same table
indirection (``RAY_TPU_KV_DTYPE=int8`` or the engine's ``kv_dtype``
knob). Block 0 is a reserved GARBAGE block: freed slots' masked lanes
keep scattering somewhere harmless without branching in the tick.

CROSS-REQUEST PREFIX REUSE (ROADMAP item 2, SGLang RadixAttention /
vLLM automatic-prefix-caching analog): :class:`RadixBlockIndex` maps
block-aligned token-id chunks to the arena blocks already holding their
K/V, so a chat fleet's shared system prompts prefill once per replica
and every later request splices the cached blocks into its table
read-only. A block is then in one of three states:

* **free** — on the :class:`BlockAllocator` free list;
* **live** — referenced by ≥1 slot; indexed blocks carry a per-node
  refcount (two requests sharing a system prompt both pin its blocks)
  and are NEVER reclaimed while any reference is live;
* **cached** — refcount dropped to 0 on slot release, but the block is
  parked in the index's LRU instead of freed: a later prefix match
  revives it for free, and arena pressure reclaims it (leaf-first,
  oldest-first) before admission ever blocks on the arena.
"""

from __future__ import annotations

import os
from collections import OrderedDict, deque
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax.numpy as jnp

from ray_tpu.models import llama

GARBAGE_BLOCK = 0

KV_DTYPES = ("bf16", "int8")


def resolve_kv_dtype(kv_dtype: Optional[str]) -> str:
    """Explicit arg > ``RAY_TPU_KV_DTYPE`` env > bf16 (the model's own
    dtype: nothing is quantized)."""
    if kv_dtype is None:
        kv_dtype = os.environ.get("RAY_TPU_KV_DTYPE", "").strip().lower() \
            or "bf16"
    kv_dtype = str(kv_dtype).lower()
    if kv_dtype not in KV_DTYPES:
        raise ValueError(
            f"kv_dtype {kv_dtype!r} not supported (one of {KV_DTYPES})")
    return kv_dtype


def quantize_kv(x):
    """Symmetric per-token/per-kv-head int8: x [..., H, D] -> (int8 same
    shape, fp32 scales [..., H]). Zero vectors quantize to zeros with a
    zero scale (dequantizing back to exact zeros)."""
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1)                  # [..., H]
    scale = amax / 127.0
    q = jnp.round(x / jnp.where(scale == 0.0, 1.0, scale)[..., None])
    q = jnp.clip(q, -127, 127).astype(jnp.int8)
    return q, scale


class PagedKVCache(NamedTuple):
    """KV arena: k/v ``[L, NB, KVH, bs, D]``; scales ``[L, NB, KVH, bs]``
    fp32 when the arena is int8, else None."""

    k: jnp.ndarray
    v: jnp.ndarray
    k_scale: Optional[jnp.ndarray] = None
    v_scale: Optional[jnp.ndarray] = None

    @property
    def block_size(self) -> int:
        return self.k.shape[3]

    @property
    def num_blocks(self) -> int:
        return self.k.shape[1]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @classmethod
    def create(cls, config: llama.LlamaConfig, num_blocks: int,
               block_size: int, kv_dtype: str = "bf16") -> "PagedKVCache":
        kv_dtype = resolve_kv_dtype(kv_dtype)
        # Attention layers only, times the steps of a looped stack.
        shape = (config.loop_steps * config.attn_layers, num_blocks,
                 config.num_kv_heads, block_size, config.head_dim)
        if kv_dtype == "int8":
            return cls(k=jnp.zeros(shape, jnp.int8),
                       v=jnp.zeros(shape, jnp.int8),
                       k_scale=jnp.zeros(shape[:-1], jnp.float32),
                       v_scale=jnp.zeros(shape[:-1], jnp.float32))
        return cls(k=jnp.zeros(shape, config.dtype),
                   v=jnp.zeros(shape, config.dtype))

    def token_bytes(self) -> int:
        """Arena bytes one live token occupies across all layers (the
        live-traffic estimate the achieved-bandwidth gauges use)."""
        layers, _, kvh, _, d = self.k.shape
        n = 2 * layers * kvh * d * jnp.dtype(self.k.dtype).itemsize
        if self.k_scale is not None:
            n += 2 * layers * kvh * 4
        return n

    def _parts(self):
        parts = [("k", self.k), ("v", self.v)]
        if self.quantized:
            parts += [("k_scale", self.k_scale),
                      ("v_scale", self.v_scale)]
        return parts

    def gather_blocks(self, blocks: Sequence[int]):
        """Fetch the named arena blocks (K/V plus int8 scale sidecars)
        to host, packed into ONE contiguous uint8 staging buffer.
        Returns ``(staging, layout)`` where ``layout`` is
        ``[(name, dtype_str, shape, offset, nbytes), ...]`` — the
        per-array regions are zero-copy VIEWS of the staging buffer, so
        a transfer plane ships one buffer + a small manifest, never a
        pickle of the arena (see :func:`unpack_staging`)."""
        import numpy as np

        idx = jnp.asarray(list(blocks), dtype=jnp.int32)
        host = [(name, np.asarray(arr[:, idx]))
                for name, arr in self._parts()]
        staging = np.empty(sum(a.nbytes for _, a in host), np.uint8)
        layout = []
        off = 0
        for name, a in host:
            end = off + a.nbytes
            staging[off:end].view(a.dtype).reshape(a.shape)[...] = a
            layout.append((name, str(a.dtype), a.shape, off, a.nbytes))
            off = end
        return staging, layout

    def scatter_blocks(self, blocks: Sequence[int], staging,
                       layout) -> "PagedKVCache":
        """Land a :meth:`gather_blocks` staging buffer in THIS arena's
        ``blocks`` through the same ``.at[:, idx].set`` table-scatter
        path prefill write-back uses. Returns the new cache value."""
        views = unpack_staging(staging, layout)
        idx = jnp.asarray(list(blocks), dtype=jnp.int32)
        fields = {}
        for name, arr in self._parts():
            src = views[name]
            if src.shape[1] != len(blocks):
                raise ValueError(
                    f"scatter_blocks: payload carries {src.shape[1]} "
                    f"blocks for {name}, caller named {len(blocks)}")
            fields[name] = arr.at[:, idx].set(
                jnp.asarray(src, dtype=arr.dtype))
        return PagedKVCache(**fields)


class LatentKVCache(NamedTuple):
    """The cache of a model whose every layer is LATENT attention
    (``models/mla.py``), in the arena's place: ``k [L, NB, 1, bs, W]``,
    one row a token a layer, ``[c_kv | k_rope | 0]`` padded to whole
    lane tiles (``mla.row_width``), key and value at once and shared by
    every head; no V plane, no scales. The arena's block layout with one
    "kv head", so ONE block table a slot serves every layer, the
    :class:`BlockAllocator` and the :class:`RadixBlockIndex` hand its
    blocks out as they do the arena's, and ``paged_kv_write`` and the
    ``paged_visits`` schedule take it as they take the arena."""

    k: jnp.ndarray

    @classmethod
    def create(cls, config: llama.LlamaConfig, num_blocks: int,
               block_size: int) -> "LatentKVCache":
        from ray_tpu.models import mla

        return cls(k=jnp.zeros(
            (config.latent_layers, num_blocks, 1, block_size,
             mla.row_width(config)), config.dtype))

    @property
    def block_size(self) -> int:
        return self.k.shape[3]

    @property
    def num_blocks(self) -> int:
        return self.k.shape[1]

    quantized = False

    @property
    def nbytes(self) -> int:
        return int(self.k.nbytes)

    def token_bytes(self) -> int:
        """Bytes one live token occupies across all layers (the pad to
        whole lanes included: the kernel reads it)."""
        layers, _, _, _, w = self.k.shape
        return layers * w * jnp.dtype(self.k.dtype).itemsize


class StateCache(NamedTuple):
    """What the recurrent layers of a hybrid model keep for each slot,
    beside the K/V arena: ``ssm [L_state, slots, *state]`` float32, the
    recurrent state in the layout the tick's kernel updates in place,
    and ``conv [L_state, slots, K - 1, conv_dim]``, the convolution's
    last inputs, oldest first, in the model's dtype. The shapes are the
    mixer's that the config names: Mamba-2 (``layer_types`` "mamba")
    ``[H, N / f, f * P]`` (``ops/ssm.py::packed_shape``) over ``x | B |
    C``; Gated DeltaNet ("linear_attention") ``[Hv, Dk, Dv]`` over ``q |
    k | v`` (``gated_delta.state_shapes``); "mamba1" ``[N, Di]``. Not paged
    nor shareable by prefix: a slot's row is installed by its prefill
    (a later CHUNK of a linear-attention prompt reads the row the chunk
    before it wrote, and overwrites it), advanced by every tick, and
    simply overwritten by the slot's next prefill (a freed slot's row
    computes garbage that nothing reads)."""

    ssm: jnp.ndarray
    conv: jnp.ndarray

    @classmethod
    def create(cls, config: llama.LlamaConfig,
               num_slots: int) -> "StateCache":
        c = config
        if c.mamba_dt_rank or "linear_attention" in c.layer_types:
            from ray_tpu.models import gated_delta, mamba1
            state, tail = (mamba1 if c.mamba_dt_rank
                           else gated_delta).state_shapes(c)
        else:
            from ray_tpu.ops.ssm import packed_shape

            state = packed_shape(c.mamba_n_heads, c.mamba_d_head,
                                 c.mamba_d_state)
            tail = (c.mamba_d_conv - 1, c.mamba_dims[1])
        return cls(
            ssm=jnp.zeros((c.state_layers, num_slots) + state, jnp.float32),
            conv=jnp.zeros((c.state_layers, num_slots) + tail, c.dtype))

    @property
    def nbytes(self) -> int:
        return int(self.ssm.nbytes + self.conv.nbytes)


class TailCache(NamedTuple):
    """What the layers of a compressed-convolutional-attention model
    (``layer_types`` "cca_attention", ``models/cca.py``) keep for each
    slot BESIDE their K/V in the arena: ``tail [L, slots, W]``, one row
    a slot a layer in the model's dtype, the last token's ``[u | a |
    v2]``: the two convolutions' last inputs and the half of the next
    token's value that is this token's. No recurrent state (a
    :class:`StateCache` would carry an empty one through every program).
    Lives and dies as a state cache's row does: a prompt's first chunk
    starts from zeros, a later chunk from the row the one before it
    wrote, every tick advances it, the slot's next prefill overwrites
    it."""

    tail: jnp.ndarray

    @classmethod
    def create(cls, config: llama.LlamaConfig,
               num_slots: int) -> "TailCache":
        from ray_tpu.models.cca import tail_width

        return cls(tail=jnp.zeros(
            (config.cca_layers, num_slots, tail_width(config)),
            config.dtype))

    @property
    def nbytes(self) -> int:
        return int(self.tail.nbytes)


def ring_blocks(window: int, block_size: int) -> int:
    """Entries of a slot's ring for a window of ``window`` keys: the
    ``window // bs + 1`` blocks a query's keys can span, and one more,
    so that the block a prefill chunk or a tick writes next never holds
    a key some query of the same program still sees."""
    return -(-window // block_size) + 2


class RingKVCache(NamedTuple):
    """What the SLIDING-WINDOW attention layers keep, beside the arena:
    k/v ``[L_win, 1 + slots * ring, KVH, bs, D]``, the arena's block
    layout (so ``paged_kv_write`` and ``paged_decode_attn`` take it as
    they take the arena), but every slot owns a fixed RING of ``ring``
    blocks and logical block ``b`` of its context lives in entry ``b %
    ring``: a block is overwritten once every key in it is more than
    ``sliding_window`` positions behind the slot's query, so a slot's
    window layers hold ``ring x bs`` tokens whatever its context
    (:func:`ring_blocks`). Block 0 is the garbage block, as in the
    arena. No allocator and no sharing: slot ``s`` owns blocks ``1 + s *
    ring ...``, from construction; nothing in it outlives its request."""

    k: jnp.ndarray
    v: jnp.ndarray

    @classmethod
    def create(cls, config: llama.LlamaConfig, num_slots: int,
               block_size: int) -> "RingKVCache":
        ring = ring_blocks(config.sliding_window, block_size)
        shape = (config.window_layers, 1 + num_slots * ring,
                 config.num_kv_heads, block_size, config.head_dim)
        return cls(k=jnp.zeros(shape, config.dtype),
                   v=jnp.zeros(shape, config.dtype))

    @staticmethod
    def tables(slots, ring: int):
        """Each of ``slots``' ring as a block table ``[len(slots), ring]``
        (entry r = the block that holds logical blocks ``b % ring == r``):
        a function of the slot alone, so programs make it from an iota
        and the host uploads nothing."""
        return (1 + slots.astype(jnp.int32)[:, None] * ring
                + jnp.arange(ring, dtype=jnp.int32)[None, :])

    @property
    def nbytes(self) -> int:
        return int(self.k.nbytes + self.v.nbytes)

    def token_bytes(self) -> int:
        """Bytes one token in the window occupies across the window layers."""
        layers, _, kvh, _, d = self.k.shape
        return 2 * layers * kvh * d * jnp.dtype(self.k.dtype).itemsize


class BlockAllocator:
    """Host-side free-list over arena block ids. Block 0 (GARBAGE_BLOCK)
    is never handed out: freed slots keep scattering their masked-lane
    garbage there. LIFO reuse keeps hot blocks hot."""

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError("paged arena needs >= 2 blocks "
                             "(block 0 is reserved)")
        self.num_blocks = num_blocks
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        self._allocated: set = set()   # O(1) double-free detection

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return len(self._allocated)

    def alloc(self, n: int) -> Optional[List[int]]:
        """n blocks, or None (all-or-nothing) when the arena can't cover
        them — the caller leaves the request queued."""
        if n <= 0:
            return []      # [-0:] would slice (and drain) the whole list
        if n > len(self._free):
            return None
        taken = self._free[-n:][::-1]
        del self._free[-n:]
        self._allocated.update(taken)
        return taken

    def free(self, blocks: List[int]) -> None:
        for b in blocks:
            if b == GARBAGE_BLOCK:
                raise ValueError("cannot free the reserved garbage block")
            if b not in self._allocated:
                raise ValueError(f"double free / bad block id {b}")
        self._allocated.difference_update(blocks)
        self._free.extend(reversed(blocks))

    def reset(self) -> None:
        self._free = list(range(self.num_blocks - 1, 0, -1))
        self._allocated.clear()


class _RadixNode:
    """One block-aligned chunk in the prefix tree. ``refs`` counts the
    slots currently reading this block through their tables; 0 parks the
    node in the index LRU (block content stays valid in the arena)."""

    __slots__ = ("chunk", "block", "parent", "children", "refs")

    def __init__(self, chunk: Optional[Tuple[int, ...]], block: int,
                 parent: Optional["_RadixNode"]):
        self.chunk = chunk
        self.block = block
        self.parent = parent
        self.children: Dict[Tuple[int, ...], "_RadixNode"] = {}
        self.refs = 0


class RadixBlockIndex:
    """Radix index over block-aligned token-id chunks → arena block ids.

    Keys are EXACT token tuples (dict equality, no lossy hashing — a
    hash collision would silently serve another prompt's K/V), chained
    parent→child so chunk ``i``'s node is reachable only through the
    full token prefix ``[0, (i+1)·bs)`` that determines its K/V content
    (causal attention: position ``p`` depends on tokens ``[0..p]``).

    Refcount/eviction rules (the engine's shared-block contract):

    * :meth:`match` pins every matched node (``refs += 1``; revived out
      of the LRU) — matched blocks are spliced into a slot's table
      READ-ONLY and must never be reclaimed or written while pinned;
    * :meth:`insert` indexes a slot's newly-prefilled full-prompt blocks
      (pinned, refs=1); a chunk already indexed under a different block
      — two cold twins racing one admission round — stops the walk and
      leaves the loser's remaining blocks exclusive (freed on release);
    * :meth:`release` unpins; refs==0 parks the node at the LRU's young
      end instead of freeing its block;
    * :meth:`evict` reclaims parked blocks LEAF-FIRST in LRU order, so
      a popular prefix's root chunks outlive its cold tails. Every
      slot pins a contiguous root-chain, so a parked node can never
      have a pinned descendant — leaf-first eviction never strands a
      live reader.
    """

    def __init__(self):
        self._root = _RadixNode(None, GARBAGE_BLOCK, None)
        self._lru: "OrderedDict[_RadixNode, None]" = OrderedDict()
        self._live = 0          # nodes with refs >= 1
        self._by_block: Dict[int, _RadixNode] = {}

    # ------------------------------------------------------------ stats
    @property
    def cached_count(self) -> int:
        """Parked refcount-0 blocks the arena can reclaim."""
        return len(self._lru)

    @property
    def shared_count(self) -> int:
        """Indexed blocks currently pinned by at least one slot."""
        return self._live

    @property
    def indexed_count(self) -> int:
        return len(self._by_block)

    # ------------------------------------------------------------- read
    def match_nodes(self,
                    chunks: Sequence[Tuple[int, ...]]) -> List[_RadixNode]:
        """Longest indexed prefix, read-only (NO pinning): the
        admission-feasibility probe inspects the nodes' refcounts — a
        parked (refs==0) matched block covers part of the request's
        need, but pinning it revives it from the LRU without freeing
        anything, so the probe must not also count it as evictable."""
        node, out = self._root, []
        for chunk in chunks:
            node = node.children.get(chunk)
            if node is None:
                break
            out.append(node)
        return out

    def match_len(self, chunks: Sequence[Tuple[int, ...]]) -> int:
        """Longest indexed prefix, in blocks — read-only (no pinning)."""
        return len(self.match_nodes(chunks))

    # ------------------------------------------------------------ write
    def match(self, chunks: Sequence[Tuple[int, ...]]) -> List[_RadixNode]:
        """Longest indexed prefix, PINNED: each matched node's refcount
        rises (reviving it from the LRU), so the caller may splice the
        blocks into a live table. Pair with :meth:`release`."""
        node, out = self._root, []
        for chunk in chunks:
            node = node.children.get(chunk)
            if node is None:
                break
            self._pin(node)
            out.append(node)
        return out

    def insert(self, chunks: Sequence[Tuple[int, ...]],
               blocks: Sequence[int], start: int = 0) -> List[_RadixNode]:
        """Index ``blocks[start:]`` under ``chunks[start:]`` (the chunks
        before ``start`` were matched — their nodes already exist and are
        pinned by this caller). Returns the nodes CREATED (pinned,
        refs=1). A chunk already indexed under a *different* block stops
        the walk: the caller's remaining blocks stay exclusive."""
        node = self._root
        for chunk in chunks[:start]:
            node = node.children[chunk]   # matched path must exist
        created: List[_RadixNode] = []
        for i in range(start, len(chunks)):
            child = node.children.get(chunks[i])
            if child is not None:
                if child.block != blocks[i]:
                    break                 # cold twin lost the race
                node = child
                continue
            child = _RadixNode(chunks[i], blocks[i], node)
            node.children[chunks[i]] = child
            self._by_block[blocks[i]] = child
            self._pin(child)
            created.append(child)
            node = child
        return created

    def release(self, nodes: Sequence[_RadixNode]) -> None:
        """Unpin (slot released its table): refcount 0 parks the node at
        the LRU young end — the block stays resident until reclaimed."""
        for node in nodes:
            node.refs -= 1
            assert node.refs >= 0, "prefix node over-released"
            if node.refs == 0:
                self._live -= 1
                self._lru[node] = None

    def evict(self, want: int) -> List[int]:
        """Reclaim up to ``want`` parked blocks, leaf-first in LRU order;
        returns their ids (the caller hands them back to the
        allocator's free list). Pinned nodes are untouchable — a parked
        node never has pinned descendants (contiguous root-chain pins),
        so every parked block is reachable leaf-first. A parent joins
        the candidate queue the moment its last child drops, keeping a
        deep parked chain O(evicted) instead of one full LRU rescan per
        tree level (this runs synchronously on the admission path)."""
        out: List[int] = []
        ready = deque(nd for nd in self._lru if not nd.children)
        while len(out) < want and ready:
            node = ready.popleft()
            if node.children or node not in self._lru:
                continue                  # defensive: invariant violated
            parent = node.parent
            self._drop(node)
            out.append(node.block)
            if (parent is not None and not parent.children
                    and parent in self._lru):
                ready.append(parent)
        return out

    def clear(self) -> None:
        self._root = _RadixNode(None, GARBAGE_BLOCK, None)
        self._lru.clear()
        self._by_block.clear()
        self._live = 0

    # ---------------------------------------------------------- helpers
    def _pin(self, node: _RadixNode) -> None:
        if node.refs == 0:
            self._live += 1
            self._lru.pop(node, None)
        node.refs += 1

    def _drop(self, node: _RadixNode) -> None:
        self._lru.pop(node, None)
        self._by_block.pop(node.block, None)
        if node.parent is not None:
            node.parent.children.pop(node.chunk, None)


def unpack_staging(staging, layout):
    """Reconstruct the per-array views of a gather_blocks staging
    buffer: ``{name: ndarray}``, each a zero-copy view into
    ``staging``. The buffer may have crossed a process boundary (shm
    channel read) — only the bytes moved, never a per-array pickle."""
    import numpy as np

    buf = np.frombuffer(memoryview(staging), np.uint8) \
        if not isinstance(staging, np.ndarray) else staging
    out = {}
    for name, dtype, shape, off, nbytes in layout:
        out[name] = buf[off:off + nbytes].view(np.dtype(dtype)) \
            .reshape(shape)
    return out


def prompt_chunks(prompt_tokens: Sequence[int],
                  block_size: int) -> List[Tuple[int, ...]]:
    """Block-aligned chunk keys for the SHAREABLE region of a prompt:
    only blocks filled entirely by prompt tokens are deterministic
    across requests (the tail block mixes prompt and generated tokens),
    and a matcher must leave ≥1 prompt token to prefill — the first
    token is sampled from the last prompt position's logits, which the
    KV cache does not store — so matching is additionally capped at
    ``(len(prompt) - 1) // block_size`` by the engine."""
    n = len(prompt_tokens) // block_size
    return [tuple(prompt_tokens[i * block_size:(i + 1) * block_size])
            for i in range(n)]
