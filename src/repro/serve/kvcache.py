"""KV-cache utilities: sizing arithmetic, the slot API the continuous-
batching engine is built on, and the **paged KV pool** (block table +
``BlockManager``) the paged engine is built on.

Cache construction lives with the model (transformer._cache_from_prefill)
so layouts stay next to the attention code; this module adds the
serving-side pieces:

* ``kv_cache_bytes``        — footprint arithmetic (estimator/server).
* ``kv_block_size``         — the KV block granularity (canonical home;
                              the kernels, both engines' capacity
                              rounding, and the paged pool's physical
                              block size all share this one helper).
* ``alloc_decode_cache``    — zero-filled slot-addressed decode cache of
                              ``slots`` rows × ``capacity`` KV entries,
                              position arrays initialised to -1 (invalid).
* ``slot_batch_axes`` / ``take_slot`` / ``put_slot`` — the slot-view API
  chunked pad-free prefill is built on: slice one slot's row out of the
  big cache (a batch-1 sub-cache), run a prefill chunk against it, and
  splice it back.  Admission resets a slot by ``put_slot``-ing an empty
  batch-1 cache in (positions −1, SSM state zeroed).
* ``release_slot``          — invalidate a slot row's positions so stale
                              KV can never be attended (the free path).
* ``abstract_decode_cache`` — ShapeDtypeStructs of the above, for AOT
                              export (eon_compiler.compile_serve_decode).

Paged layout (docs/paged_kv.md): the full-attention KV leaves trade
their per-slot ``capacity`` rectangle for a global pool of ``num_blocks``
fixed-size blocks — leaf (*L, B, S, Hkv, D) becomes (*L, NB, BS, Hkv,
D), positions move to a (NB, BS) ``pool_pos`` pool — addressed through a
per-slot **block table** (B, capacity // BS).  Sliding-window ring
caches and SSM state stay slot-addressed (they are O(window)/O(state)
per slot — there is no capacity tail to reclaim).  ``BlockManager`` owns
allocation: free-list, per-block refcounts, hash-chain prefix caching
(identical prompt prefixes share physical blocks at block granularity),
and LRU reclaim of cached-but-unreferenced blocks; preempt-and-recompute
lives in the scheduler/server on top of it.

Validity is decided by stored positions (−1 = empty) plus the
scheduler's per-slot ``kv_len`` bound, so a slot row can be recycled
between decode steps without touching the K/V bytes — and, in the paged
layout, so a physical block can be handed to a new tenant without being
scrubbed (the new tenant's writes precede its ``kv_len``).

Every entry point is precision-aware (``PrecisionPolicy``): an int8
policy makes the KV leaves ``Int8KV`` pairs — int8 values plus one f32
scale per (entry, head) — and the slot API splices/releases/sizes the
paired pytree; ``decode_cache_nbytes`` measures the HBM delta.
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.arch import ArchConfig, ShapeConfig
from repro.core.quantize import PrecisionPolicy
# canonical block-granularity helper (defined next to the kernels it
# must agree with; this module is its serving-side home)
from repro.kernels.flash_decode import check_kv_block, kv_block_size


def kv_cache_bytes(cfg: ArchConfig, batch: int, seq_len: int,
                   dtype_bytes: int = 2, *,
                   precision: str = "float") -> int:
    """Global KV/state cache footprint for one decode session.

    ``precision="int8"`` prices the Int8KV layout: 1 byte per value plus
    one f32 scale per (entry, head) vector of ``head_dim`` values —
    attention KV only; SSM recurrent state stays float either way.
    """
    hd = cfg.resolved_head_dim
    # bytes per stored attention-KV scalar; the int8 layout adds one f32
    # scale per head-vector of hd values.  SSM conv/recurrent state stays
    # float under every precision.
    kv_bytes = (hd + 4) / hd if precision == "int8" else dtype_bytes
    if cfg.family == "ssm":
        conv = batch * (cfg.d_conv - 1) * cfg.d_inner * dtype_bytes
        h = batch * cfg.d_inner * cfg.ssm_state * 4
        return int(cfg.n_layers * (conv + h))
    if cfg.family == "hybrid":
        nh = cfg.resolved_ssm_heads
        hp = cfg.d_inner // nh
        conv = batch * (cfg.d_conv - 1) * cfg.d_inner * dtype_bytes
        h = batch * nh * hp * cfg.ssm_state * 4
        n_attn = cfg.n_layers // max(cfg.attn_every, 1)
        kv = n_attn * 2 * batch * seq_len * cfg.n_kv_heads * hd * kv_bytes
        return int(cfg.n_layers * (conv + h) + kv)
    per_layer_kv = 2 * batch * cfg.n_kv_heads * hd * kv_bytes
    if cfg.sliding_window and cfg.local_global_ratio:
        r = cfg.local_global_ratio
        n_global = cfg.n_layers // (r + 1)
        n_local = cfg.n_layers - n_global
        return int(n_global * per_layer_kv * seq_len
                   + n_local * per_layer_kv * min(cfg.sliding_window, seq_len))
    # Enc-dec: encoder layers hold no decode-time cache (the encoder runs
    # once; its output *is* the cross KV).  The decoder holds self-attn KV
    # over seq_len plus cross-attn KV over the subsampled encoder length.
    total = cfg.n_layers * per_layer_kv * seq_len
    if cfg.is_encdec:
        total += cfg.n_layers * per_layer_kv * (seq_len // cfg.enc_seq_divisor)
    return int(total)


# ---------------------------------------------------------------------------
# Slot-addressed decode cache (continuous batching)
# ---------------------------------------------------------------------------
def abstract_decode_cache(cfg: ArchConfig, slots: int, capacity: int,
                          policy: Optional[PrecisionPolicy] = None):
    """ShapeDtypeStructs of a ``slots`` × ``capacity`` decode cache.
    With an int8 ``policy`` the KV leaves come back as Int8KV pairs."""
    from repro.models.api import abstract_cache
    shape = ShapeConfig("serve_alloc", seq_len=capacity, global_batch=slots,
                        kind="prefill")
    return abstract_cache(cfg, shape, policy)


def decode_cache_nbytes(cache) -> int:
    """HBM footprint of a (concrete or abstract) decode-cache pytree —
    every leaf: KV values, Int8KV scales, position bookkeeping."""
    return sum(int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
               for leaf in jax.tree.leaves(cache))


def _concrete_empty(abs_cache):
    """Zeros everywhere, −1 in position leaves (the empty marker)."""
    def init(key_path, sds):
        name = key_path[0].key if hasattr(key_path[0], "key") else None
        if name is not None and name.endswith("_pos"):
            return jnp.full(sds.shape, -1, sds.dtype)
        return jnp.zeros(sds.shape, sds.dtype)

    return jax.tree_util.tree_map_with_path(init, abs_cache)


def alloc_decode_cache(cfg: ArchConfig, slots: int, capacity: int,
                       policy: Optional[PrecisionPolicy] = None):
    """Concrete all-empty decode cache: zeros, positions −1 (invalid)."""
    return _concrete_empty(abstract_decode_cache(cfg, slots, capacity,
                                                 policy))


def _first_diff_axis(big_shape, small_shape) -> int:
    """Axis where a batch-1 sub-cache differs from the full cache (the
    batch axis — it always precedes any seq-length difference)."""
    for i, (b, s) in enumerate(zip(big_shape, small_shape)):
        if b != s:
            return i
    return -1  # identical shapes: slots == 1, write in place


def slot_batch_axes(cfg: ArchConfig, slots: int, capacity: int,
                    policy: Optional[PrecisionPolicy] = None):
    """Per-leaf batch-axis pytree of the decode cache, inferred by
    diffing the ``slots``-row abstract cache against its batch-1 twin —
    robust to every layout (stacked-layer KV, Int8KV value/scale pairs,
    nested SSM state).  Computed once per server; closed over (static)
    by the jitted slot-view steps.  −1 marks a leaf with no batch axis
    (only possible when ``slots == 1``: slice/splice in place)."""
    big = abstract_decode_cache(cfg, slots, capacity, policy)
    small = abstract_decode_cache(cfg, 1, capacity, policy)
    return jax.tree.map(lambda b, s: _first_diff_axis(b.shape, s.shape),
                        big, small)


def take_slot(big_cache, axes, slot):
    """Slice slot ``slot``'s row out of the big cache as a batch-1
    sub-cache (``axes`` from ``slot_batch_axes``, closed over — the axis
    choice must be static under jit; ``slot`` may be traced)."""
    def take(big, axis):
        if axis < 0:
            return big
        starts = [0] * big.ndim
        starts[axis] = slot
        sizes = list(big.shape)
        sizes[axis] = 1
        return lax.dynamic_slice(big, tuple(starts), tuple(sizes))
    return jax.tree.map(take, big_cache, axes)


def put_slot(big_cache, small_cache, axes, slot):
    """Splice a batch-1 sub-cache back into row ``slot`` — the inverse
    of ``take_slot``.  Splicing a fresh ``alloc_decode_cache(cfg, 1, …)``
    resets the slot (positions −1, SSM state zeroed) for admission."""
    def put(big, small, axis):
        starts = [0] * big.ndim
        if axis >= 0:
            starts[axis] = slot
        return lax.dynamic_update_slice(big, small.astype(big.dtype),
                                        tuple(starts))
    return jax.tree.map(put, big_cache, small_cache, axes)


def release_slot(big_cache: Dict[str, Any], slot) -> Dict[str, Any]:
    """Invalidate a slot row: set its position entries to −1.  K/V bytes
    stay in place — they are unreachable once no position marks them.
    (``pool_pos`` is pool-addressed, not per-slot, and is skipped: paged
    reuse is fenced by ``kv_len``, not by scrubbing — see
    docs/paged_kv.md.)"""
    out = dict(big_cache)
    for key, big in big_cache.items():
        if key.endswith("_pos") and key != "pool_pos":
            row = jnp.full((1, big.shape[1]), -1, big.dtype)
            out[key] = lax.dynamic_update_slice(big, row, (slot, 0))
    return out


# ---------------------------------------------------------------------------
# Paged KV pool (block table + BlockManager) — docs/paged_kv.md
# ---------------------------------------------------------------------------
_PAGED_KEYS = {
    "uniform_dense": ("k", "v"),
    "uniform_moe": ("k", "v"),
    "local_global": ("global_k", "global_v"),
    "hybrid": ("attn_k", "attn_v"),
    "uniform_ssm": (),
}


def paged_cache_keys(cfg: ArchConfig) -> Tuple[str, ...]:
    """Cache keys that live in the paged pool for this architecture:
    exactly the full-attention KV leaves.  Sliding-window rings and SSM
    state stay slot-addressed (fixed O(window)/O(state) per slot), and a
    pure-SSM family pages nothing at all."""
    from repro.models.params import layer_pattern
    return _PAGED_KEYS[layer_pattern(cfg)["kind"]]


def abstract_paged_cache(cfg: ArchConfig, slots: int, capacity: int,
                         num_blocks: int,
                         policy: Optional[PrecisionPolicy] = None,
                         block_size: Optional[int] = None):
    """ShapeDtypeStructs of a paged decode cache: full-attention KV
    leaves as (*L, num_blocks, BS, Hkv, D) pools + an (num_blocks, BS)
    ``pool_pos`` position pool, everything else (ring caches, SSM state,
    ``local_pos``) as the usual ``slots``-row slot leaves.  BS defaults
    to ``kv_block_size(capacity)`` (the kernel tile — maximum DMA
    efficiency) and may be overridden by any divisor of ``capacity``
    the kernel accepts (``check_kv_block``) for finer-grained pooling;
    the block table itself is host state (a (slots, capacity // BS)
    int32 operand, not a cache leaf)."""
    bs = block_size or kv_block_size(capacity)
    check_kv_block(bs, capacity)
    slot_abs = abstract_decode_cache(cfg, slots, capacity, policy)
    keys = paged_cache_keys(cfg)
    cache = {k: v for k, v in slot_abs.items()
             if k not in keys and k != "full_pos"}
    if keys:
        # a pool is structurally a "cache of num_blocks slots of BS rows"
        pool_abs = abstract_decode_cache(cfg, num_blocks, bs, policy)
        for k in keys:
            cache[k] = pool_abs[k]
        cache["pool_pos"] = pool_abs["full_pos"]
    return cache


def alloc_paged_cache(cfg: ArchConfig, slots: int, capacity: int,
                      num_blocks: int,
                      policy: Optional[PrecisionPolicy] = None,
                      block_size: Optional[int] = None):
    """Concrete all-empty paged decode cache (zeros, positions −1)."""
    return _concrete_empty(abstract_paged_cache(cfg, slots, capacity,
                                                num_blocks, policy,
                                                block_size))


def paged_slot_axes(cfg: ArchConfig, slots: int, capacity: int,
                    num_blocks: int,
                    policy: Optional[PrecisionPolicy] = None,
                    block_size: Optional[int] = None):
    """Per-leaf batch-axis pytree for the *paged* cache, consumed by
    ``take_slot``/``put_slot``: slot-addressed leaves carry their batch
    axis as in ``slot_batch_axes``; pool leaves (and ``pool_pos``) carry
    −1 — "no slot axis", which those helpers already treat as take-whole
    / splice-whole, exactly what a globally shared pool needs."""
    cache = abstract_paged_cache(cfg, slots, capacity, num_blocks, policy,
                                 block_size)
    small = abstract_decode_cache(cfg, 1, capacity, policy)
    shared = set(paged_cache_keys(cfg)) | {"pool_pos"}
    axes: Dict[str, Any] = {}
    for key, leaf in cache.items():
        if key in shared:
            axes[key] = jax.tree.map(lambda _: -1, leaf)
        else:
            axes[key] = jax.tree.map(
                lambda b, s: _first_diff_axis(b.shape, s.shape),
                leaf, small[key])
    return axes


def kv_pool_block_bytes(cfg: ArchConfig, capacity: int,
                        policy: Optional[PrecisionPolicy] = None,
                        block_size: Optional[int] = None) -> int:
    """HBM bytes one physical KV block occupies across all paged leaves
    (KV values, Int8KV scales, its ``pool_pos`` row) — the per-block
    price the pool's live-block accounting multiplies out."""
    keys = paged_cache_keys(cfg)
    if not keys:
        return 0
    bs = block_size or kv_block_size(capacity)
    # pass bs as the explicit block size too: a one-block pool of
    # capacity bs would otherwise re-derive kv_block_size(bs), which
    # differs whenever bs > 128 (kv_block_size(256) == 128)
    pool = abstract_paged_cache(cfg, 1, bs, 1, policy, bs)
    leaves = [pool[k] for k in keys] + [pool["pool_pos"]]
    return decode_cache_nbytes(leaves)


class PoolExhausted(RuntimeError):
    """Raised by ``BlockManager.alloc`` when the pool cannot satisfy an
    allocation even after reclaiming cached blocks — the server's cue to
    preempt (or, at admission, to keep the request queued)."""


class BlockManager:
    """Host-side allocator for the paged KV pool.

    * **Free-list allocation** — O(1) alloc/free of fixed-size physical
      blocks; every live block has refcount ≥ 1.
    * **Prefix caching** — finished prefills register their full prompt
      blocks under a chain hash (``h_i = hash((h_{i-1}, tokens of block
      i))``); a later request whose prompt starts with the same token
      blocks shares the physical blocks (refcount++), skipping both the
      HBM and the prefill compute for the shared prefix.  The registry
      holds one reference per cached block, so cached blocks survive
      their writer's release and are reclaimed LRU only under pool
      pressure.  Shared blocks are never written: the engine starts
      chunked prefill at the shared boundary and decode writes land past
      the prompt, which is what makes block-granular sharing safe
      without copy-on-write copies (docs/paged_kv.md).
    * **Accounting** — ``live_blocks``/``free_blocks`` and hit/reclaim
      counters feed the serve-bench pool-utilization report.

    The device never sees this object: it only materializes as the
    (slots, n_blocks) int32 block-table operand the kernels' index maps
    read.
    """

    def __init__(self, num_blocks: int, block_size: int, *,
                 prefix_cache: bool = True):
        assert num_blocks > 0 and block_size > 0
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.prefix_cache = prefix_cache
        self.refcount = np.zeros(self.num_blocks, np.int32)
        self._free: deque = deque(range(self.num_blocks))
        self._cached: "OrderedDict[bytes, int]" = OrderedDict()  # digest→blk
        self._hash_of: Dict[int, bytes] = {}                     # blk→digest
        self.stats: Dict[str, int] = {
            "allocated": 0, "freed": 0, "reclaimed": 0,
            "prefix_queries": 0, "prefix_hit_blocks": 0,
        }

    # -- accounting -----------------------------------------------------
    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def live_blocks(self) -> int:
        """Blocks referenced by at least one slot or the prefix cache."""
        return self.num_blocks - len(self._free)

    def _reclaimable(self) -> int:
        return sum(1 for b in self._cached.values()
                   if self.refcount[b] == 1)

    def can_alloc(self, n: int) -> bool:
        return self.free_blocks + self._reclaimable() >= n

    # -- alloc / free ---------------------------------------------------
    def alloc(self, n: int) -> List[int]:
        """Take ``n`` blocks (refcount 1 each); reclaims LRU cached
        blocks under pressure; raises ``PoolExhausted`` if the pool
        genuinely cannot cover the request."""
        if n == 0:
            return []
        while self.free_blocks < n and self._reclaim_one():
            pass
        if self.free_blocks < n:
            raise PoolExhausted(
                f"need {n} KV blocks, {self.free_blocks} free of "
                f"{self.num_blocks} (live {self.live_blocks})")
        out = [self._free.popleft() for _ in range(n)]
        for b in out:
            self.refcount[b] = 1
        self.stats["allocated"] += n
        return out

    def free(self, blocks: Sequence[int]) -> None:
        """Drop one reference per block; a block returns to the free
        list when nothing references it (prefix-cache entries hold their
        own reference, so cached blocks survive their writer)."""
        for b in blocks:
            assert self.refcount[b] > 0, f"double free of block {b}"
            self.refcount[b] -= 1
            if self.refcount[b] == 0:
                self._free.append(b)
                self.stats["freed"] += 1

    def _reclaim_one(self) -> bool:
        for h, b in self._cached.items():
            if self.refcount[b] == 1:       # only the cache holds it
                del self._cached[h]
                del self._hash_of[b]
                self.refcount[b] = 0
                self._free.append(b)
                self.stats["reclaimed"] += 1
                return True
        return False

    # -- prefix caching -------------------------------------------------
    def block_hashes(self, tokens: np.ndarray) -> List[bytes]:
        """Chain digests of the token blocks fully covered by ``tokens``
        — ``h_i`` commits to the whole prefix through block ``i``, so a
        single-digest match implies the entire chain matches.  SHA-256
        over (parent digest ‖ canonical int64 token bytes): a match IS
        the content check — Python's randomized 64-bit ``hash()`` would
        make a silent cross-request KV collision merely improbable and
        unreproducible, not impossible."""
        bs = self.block_size
        h = b""
        out: List[bytes] = []
        toks = np.asarray(tokens, np.int64)
        for i in range(len(toks) // bs):
            h = hashlib.sha256(h + toks[i * bs:(i + 1) * bs].tobytes()) \
                .digest()
            out.append(h)
        return out

    def match_prefix(self, tokens: np.ndarray) -> List[int]:
        """Longest cached chain matching the prompt's leading full
        blocks, **capped at len(tokens) − 1** (the last prompt token
        must be recomputed — its logits seed generation).  Matched
        blocks come back refcounted for the caller; a caller that ends
        up not using some or all of them must hand those back through
        ``unmatch`` so references AND hit accounting stay exact."""
        self.stats["prefix_queries"] += 1
        if not self.prefix_cache:
            return []
        usable = (len(tokens) - 1) // self.block_size
        out: List[int] = []
        for h in self.block_hashes(tokens)[:usable]:
            b = self._cached.get(h)
            if b is None:
                break
            out.append(b)
            self._cached.move_to_end(h)     # LRU touch
        for b in out:
            self.refcount[b] += 1
        self.stats["prefix_hit_blocks"] += len(out)
        return out

    def unmatch(self, blocks: Sequence[int], *,
                whole_query: bool = False) -> None:
        """Exactly reverse (part of) a ``match_prefix`` the caller did
        not use: drop the references and the hit accounting, and with
        ``whole_query`` the query count too (the match never led to an
        admission).  Keeps the stat/refcount invariant inside the
        manager instead of making callers hand-reverse counters."""
        self.free(blocks)
        self.stats["prefix_hit_blocks"] -= len(blocks)
        if whole_query:
            self.stats["prefix_queries"] -= 1

    def registry_size(self) -> int:
        """Number of cached prefix blocks — with ``free_blocks``/
        ``live_blocks`` this fingerprints every state a repeated
        ``match_prefix`` could answer differently from."""
        return len(self._cached)

    def register_prefix(self, tokens: np.ndarray,
                        blocks: Sequence[int]) -> None:
        """Publish a *fully prefilled* prompt's full blocks to the
        prefix cache (one cache reference each).  Must only be called
        once the blocks' contents are final — the engine calls it when a
        prefill completes, never mid-flight, so a shared block can never
        be half-written."""
        if not self.prefix_cache:
            return
        for h, b in zip(self.block_hashes(tokens), blocks):
            if h in self._cached or b in self._hash_of:
                continue                     # first writer wins
            self._cached[h] = b
            self._hash_of[b] = h
            self.refcount[b] += 1
