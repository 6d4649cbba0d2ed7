"""The serving engine — the "EIM process runner" analogue (paper §4.6):
a deployed artifact behind a queue-driven I/O interface.

``PagedBatchServer`` is continuous batching over a **paged KV pool**
(docs/paged_kv.md).  Full-attention KV lives in fixed-size physical
blocks addressed through per-slot block tables, so HBM is priced at live
tokens rather than at a worst-case ``slots × capacity`` rectangle.
Identical prompt prefixes share blocks, and when the pool runs dry the
youngest slot is preempted and later recomputed.

Prompts enter through **chunked pad-free prefill**: a prompt of length S
is consumed in ceil(S / C) fixed-size chunk steps interleaved with decode
under a per-step token budget, each chunk written unpadded at positions
``[p, p + C)``.  Finished sequences release their slot *between decode
steps* and waiting requests are admitted into freed slots; per-request
``max_new_tokens`` is honored in-step.  One chunk shape compiles once.
Decode step n+1 is launched before step n's tokens are pulled, so the
host's loop runs while the device runs a step (docs/serving.md,
"One-step decode lookahead").  Optionally the decode hot loop runs a
``CompiledArtifact`` (``core/eon_compiler.compile_serve_decode``), so
serving executes the same AOT executable we "deploy" (paper C4).

The engine accepts ``precision="float" | "int8"`` (paper C5 threaded
end-to-end) and puts the weights in their at-rest form once at
construction (``quantize.serving_params``): float holds the projections
and the embedding tables in the activation dtype, int8 wraps the
projections in QTensor, serves through the quant-aware matmul entry
point, and keeps the decode cache as Int8KV — ≥2× KV HBM, token-exact
against the fake-quant float reference (docs/quantization.md).

The decode step gets a per-slot ``kv_len`` — with pad-free admission
this is the *exact* live fill (``position + 1``; 0 for idle or
mid-prefill slots, whose rows the step neither reads nor writes) — so
the flash-decode kernel reads only each slot's live blocks, and int8
decode dequantizes inside the kernel tile, never materializing a float
cache (docs/serving.md, "Flash-decode kernel").

No pad row ever enters the KV cache or an SSM recurrence, so batched
serving is token-exact versus an unpadded single-request decode for
every supported architecture family — attention, sliding-window ring,
and SSM/hybrid alike (docs/scheduling.md).  Prompts that cannot fit a
slot's capacity are rejected at ``submit`` with an explicit error;
nothing is silently truncated.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.arch import ArchConfig
from repro.core.quantize import policy_for, serving_params
from repro.kernels.flash_decode import BLOCK_K, check_kv_block
from repro.serve.kvcache import (BlockManager, PoolExhausted,
                                 alloc_decode_cache, alloc_paged_cache,
                                 decode_cache_nbytes, kv_block_size,
                                 kv_pool_block_bytes, paged_cache_keys,
                                 paged_slot_axes, put_slot)
from repro.serve.scheduler import SlotScheduler
from repro.serve.serve_step import (make_paged_chunk_prefill_step,
                                    make_paged_decode_step)

@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray              # (S,) int32
    max_new_tokens: int = 16
    submitted_at: float = 0.0
    tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    admitted_at: Optional[float] = None   # host clock at first admission
    # host clock per emitted token: one stamp per decode step, taken
    # after the token pull and shared by that step's tokens
    token_times: List[float] = dataclasses.field(default_factory=list)
    admitted_step: Optional[int] = None   # decode-step clock at admission
    finished_step: Optional[int] = None
    preemptions: int = 0            # times evicted and recomputed


def _check_supported(cfg: ArchConfig) -> None:
    if cfg.is_encdec or cfg.frontend:
        raise NotImplementedError(
            f"{cfg.name}: serving engine requires a token-input decoder-only"
            " architecture (enc-dec / embedding-frontend archs need a"
            " modality runner in front)")


def _chunk_rows(prompt_len: int, chunk: int) -> int:
    """Cache rows a chunked prefill touches: whole chunks, so the ragged
    final chunk's pad tail (written invalid, overwritten by decode)
    still needs rows up to the chunk boundary."""
    return -(-prompt_len // chunk) * chunk


def _summarize(served: List[Request], wall: float, *,
               decode_steps: int, prefills: int,
               occupancy: Optional[List[int]] = None,
               n_slots: int = 0) -> Dict[str, float]:
    ttfts = np.array([r.first_token_at - r.submitted_at for r in served])
    gen = sum(len(r.tokens) for r in served)
    m: Dict[str, float] = {
        "engine": "paged",
        "requests": len(served),
        "wall_s": wall,
        "ttft_mean_s": float(ttfts.mean()) if len(ttfts) else 0.0,
        "ttft_p50_s": float(np.percentile(ttfts, 50)) if len(ttfts) else 0.0,
        "ttft_p95_s": float(np.percentile(ttfts, 95)) if len(ttfts) else 0.0,
        "tokens_generated": gen,
        "tokens_per_s": gen / max(wall, 1e-9),
        "decode_steps": decode_steps,
        "prefill_chunks": prefills,
    }
    if occupancy and n_slots:
        m["mean_active_slots"] = float(np.mean(occupancy))
        m["slot_utilization"] = float(np.mean(occupancy)) / n_slots
    return m


class PagedBatchServer:
    """Continuous batching over a **paged KV pool** (docs/paged_kv.md).

    ``slots`` decode rows share one jitted decode step; prompts are
    consumed ``prefill_chunk`` tokens at a time (one compiled chunk
    shape, pad-free cache rows) under ``prefill_token_budget`` prefill
    tokens per decode step, so a long prompt cannot head-of-line-block
    the active slots' next tokens.

    A ``slots × capacity`` rectangle per slot would hold its dead tail
    in HBM even though kv_len bounding never *reads* it, pricing
    concurrency at the worst case.  Instead the full-attention KV lives
    in a global pool of fixed-size physical blocks (block == the
    flash-decode KV block), each slot maps logical KV positions to
    physical blocks through a **block table** that rides the decode
    signature into the kernels' index maps, and a host-side
    ``BlockManager`` owns the pool:

    * admission gates on the free-block watermark (prompt blocks must be
      coverable), not merely on a free slot;
    * identical prompt prefixes **share physical blocks** at block
      granularity via hash-chain prefix caching (refcounted, never
      written — chunked prefill starts at the shared boundary);
    * when the pool runs dry mid-decode the youngest slot is
      **preempted**: blocks freed, request re-queued at the FCFS front,
      re-prefilled over ``prompt ++ generated`` through the ordinary
      chunked-prefill path (preempt-and-recompute; greedy decoding makes
      the recompute token-exact).

    Ring (sliding-window) caches and SSM state stay slot-addressed —
    they are already minimal (O(window)/O(state) per slot, no capacity
    tail), so paging them buys nothing; a pure-SSM family degenerates to
    plain continuous batching with pool bookkeeping disabled.  Prefix
    sharing is enabled only where *all* persistent state lives in the
    pool (uniform full-attention families); preemption works everywhere
    because recompute rebuilds slot-local state from scratch.
    """

    def __init__(self, cfg: ArchConfig, params, *,
                 slots: Optional[int] = None,
                 max_prompt: Optional[int] = None,
                 prefill_chunk: int = 8,
                 prefill_token_budget: Optional[int] = None,
                 max_new_tokens: int = 16,
                 max_new_cap: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 use_artifact: bool = False,
                 pool_blocks: Optional[int] = None,
                 block_size: Optional[int] = None,
                 prefix_cache: bool = True,
                 precision: str = "float"):
        _check_supported(cfg)
        self.cfg = cfg
        self.precision = precision
        self.prec = policy_for(precision)
        # the weights' at-rest form, made once: the steps never cast a
        # weight, and no reference to the f32 leaves it replaced is kept
        self.params = serving_params(params, self.prec, cfg.activation_dtype)
        self._weight_bytes = sum(leaf.nbytes
                                 for leaf in jax.tree.leaves(self.params))
        self._next_rid = 0
        self.requests: Dict[int, Request] = {}
        self.metrics: Dict[str, float] = {}
        self.n_slots = int(slots or 4)
        self.max_prompt = int(max_prompt or 32)
        self.chunk = int(prefill_chunk)
        # fairness knob: prefill tokens spent per decode step once any
        # slot is actively decoding (floored at one chunk so admission
        # always progresses); see docs/scheduling.md for the trade-off.
        self.prefill_budget = int(prefill_token_budget or self.chunk)
        self.max_new = int(max_new_tokens)
        self.max_new_cap = int(max_new_cap or max(self.max_new, 1))
        self.capacity = self._slot_capacity()
        # pool block: the kernel tile by default (maximum DMA width);
        # any smaller divisor of capacity the kernel accepts trades DMA
        # width for allocation granularity / prefix-hit resolution
        self.block_size = int(block_size or kv_block_size(self.capacity))
        check_kv_block(self.block_size, self.capacity)
        if self.capacity % self.chunk:
            raise ValueError(
                f"prefill_chunk {self.chunk} must divide the rounded "
                f"capacity {self.capacity} (paged blocks may not "
                f"overflow the table)")
        self.n_table = self.capacity // self.block_size
        # default pool == the slots × capacity rectangle's block count
        # (no preemption possible); size it below that to trade
        # HBM for occasional preempt-and-recompute.  A pool smaller than
        # one worst-case request is permitted (real requests may be
        # smaller); an individually unservable request raises at
        # admission time instead of deadlocking.
        self.pool_blocks = int(pool_blocks or self.n_slots * self.n_table)
        if self.pool_blocks < 1:
            raise ValueError("pool_blocks must be >= 1")
        self.eos_id = eos_id
        self.paged_keys = paged_cache_keys(cfg)
        # prefix reuse requires every layer's persistent decode state to
        # be (a) a function of the shared tokens alone and (b) resident
        # in the paged pool: uniform full-attention families only —
        # ring windows and SSM recurrences are slot-local and must be
        # rebuilt by an actual prefill.
        from repro.models.params import layer_pattern
        kind = layer_pattern(cfg)["kind"]
        share = bool(prefix_cache and self.paged_keys
                     and kind in ("uniform_dense", "uniform_moe"))
        self.manager = BlockManager(self.pool_blocks, self.block_size,
                                    prefix_cache=share)
        self._block_bytes = kv_pool_block_bytes(cfg, self.capacity,
                                                self.prec,
                                                self.block_size)
        self.sched = SlotScheduler(self.n_slots)
        self._init_paged_steps()
        self.preemptions = 0
        self._prompt_blocks_seen = 0
        # (rid, pool fingerprint) of the last admission that failed the
        # free-block watermark — suppresses per-step re-matching
        self._blocked_state = None
        self.artifact = None
        if use_artifact:
            from repro.core.eon_compiler import compile_serve_decode
            self.artifact = compile_serve_decode(
                cfg, self.params, slots=self.n_slots,
                capacity=self.capacity, policy=self.prec,
                pool_blocks=self.pool_blocks,
                block_size=self.block_size)
            self.decode = self.artifact.rehydrate()
        else:
            self.decode = jax.jit(
                make_paged_decode_step(cfg, policy=self.prec),
                donate_argnums=(1,))

    # ------------------------------------------------------------------
    def _init_paged_steps(self) -> None:
        axes = paged_slot_axes(self.cfg, self.n_slots, self.capacity,
                               self.pool_blocks, self.prec,
                               self.block_size)
        self._chunk_step = jax.jit(
            make_paged_chunk_prefill_step(self.cfg, axes=axes,
                                          policy=self.prec),
            donate_argnums=(1,))
        self.cache = alloc_paged_cache(self.cfg, self.n_slots,
                                       self.capacity, self.pool_blocks,
                                       self.prec, self.block_size)
        # slot-addressed leaves (ring caches, SSM state, local_pos) are
        # reset per admission by splicing in an empty batch-1 row; pool
        # leaves need no scrub — a new tenant's writes precede its kv_len
        shared = set(self.paged_keys) | {"pool_pos"}
        slot_keys = tuple(k for k in self.cache if k not in shared)
        if slot_keys:
            slot_axes = {k: axes[k] for k in slot_keys}
            full_empty = alloc_decode_cache(self.cfg, 1, self.capacity,
                                            self.prec)
            self._empty_row = {k: full_empty[k] for k in slot_keys}

            def reset(cache, empty, slot):
                out = dict(cache)
                out.update(put_slot({k: cache[k] for k in slot_keys},
                                    empty, slot_axes, slot))
                return out

            self._reset = jax.jit(reset, donate_argnums=(0,))
        else:
            self._reset = None
        self._cur = np.zeros((self.n_slots,), np.int32)
        # the last decode step's token output, whose form (committed to
        # its device or not) a host-built token operand takes
        self._tok_out = None
        # host mirror of the device block-table operand (0 = unmapped:
        # always a valid physical block; dead entries are fenced by
        # kv_len, not by the table)
        self.block_table = np.zeros((self.n_slots, self.n_table), np.int32)

    # ------------------------------------------------------------------
    def _slot_capacity(self) -> int:
        """Per-slot KV rows: prompt + generation budget, with headroom
        for a ragged final chunk's pad tail at max_prompt, rounded up to
        the flash-decode KV tile (``BLOCK_K``) so the kernel runs at its
        full tile and never pads the cache per step; the tail is dead
        capacity the per-slot kv_len bound skips without reading.
        ``_check_fits`` shares this."""
        need = max(self.max_prompt + self.max_new_cap,
                   _chunk_rows(self.max_prompt, self.chunk))
        return -(-need // BLOCK_K) * BLOCK_K

    def _check_fits(self, prompt: np.ndarray, max_new: int) -> None:
        """Explicit capacity check at submit — any prompt that fits is
        served exactly; anything else errors instead of being silently
        truncated (the old bucket policy's failure mode)."""
        s = len(prompt)
        if s < 1:
            raise ValueError("empty prompt")
        need = max(s + max_new, _chunk_rows(s, self.chunk))
        if need > self.capacity:
            raise ValueError(
                f"prompt of {s} tokens + {max_new} new needs {need} cache"
                f" rows > slot capacity {self.capacity}; raise max_prompt/"
                f"max_new_cap (or shorten the prompt)")

    def _make_requests(self, prompts: List[np.ndarray],
                       max_new_tokens) -> List[Request]:
        if max_new_tokens is None:
            max_new_tokens = self.max_new
        if isinstance(max_new_tokens, int):
            max_new_tokens = [max_new_tokens] * len(prompts)
        assert len(max_new_tokens) == len(prompts)
        # validate the whole batch before registering anything, so a
        # rejected prompt leaves no orphaned half-submitted requests
        checked = []
        for p, mn in zip(prompts, max_new_tokens):
            p = np.asarray(p, np.int32)
            mn = max(1, min(int(mn), self.max_new_cap))
            self._check_fits(p, mn)
            checked.append((p, mn))
        now = time.perf_counter()
        reqs = []
        for p, mn in checked:
            r = Request(rid=self._next_rid, prompt=p, max_new_tokens=mn,
                        submitted_at=now)
            self._next_rid += 1
            self.requests[r.rid] = r
            reqs.append(r)
        return reqs

    def submit(self, prompts: List[np.ndarray],
               max_new_tokens: Union[int, Sequence[int], None] = None
               ) -> List[Request]:
        with TraceAnnotation("serve.submit", rid=self._next_rid,
                             n=len(prompts)):
            reqs = self._make_requests(prompts, max_new_tokens)
            for r in reqs:
                self.sched.enqueue(r)
        return reqs

    # ------------------------------------------------------------------
    def _set_table_row(self, slot) -> None:
        self.block_table[slot.index, :] = 0
        if slot.blocks:
            self.block_table[slot.index, :len(slot.blocks)] = slot.blocks

    def _free_slot(self, slot) -> None:
        """FREE path: return block references (prefix-cached blocks
        survive via the registry's own reference); no device-side scrub
        — kv_len == 0 fences the slot until re-admission."""
        self.manager.free(slot.blocks)
        slot.release()
        self._set_table_row(slot)

    def _preempt(self, slot) -> None:
        """PREEMPTED: evict ``slot`` and re-queue its request at the
        FCFS front; re-admission re-prefills ``prompt ++ generated``
        (the request keeps every token already emitted)."""
        req = self.requests[slot.rid]
        self.manager.free(slot.blocks)
        slot.release()
        self._set_table_row(slot)
        req.preemptions += 1
        self.preemptions += 1
        self.sched.requeue_front(req)

    def _admit(self, decode_steps: int) -> None:
        """Admission by free-block watermark, FCFS: the queue head is
        admitted when a slot is free AND the pool covers its prefill
        rows beyond any prefix-cache hit; otherwise it (and everything
        behind it) waits."""
        while self.sched.waiting:
            free = self.sched.free_slots()
            if not free:
                return
            req = self.sched.waiting[0]
            seq = (np.concatenate([req.prompt,
                                   np.asarray(req.tokens, np.int32)])
                   if req.tokens else req.prompt)
            if not self.paged_keys:
                # pure-SSM family: no pooled leaves, no block accounting
                shared, start, need = [], 0, 0
            else:
                # a blocked head request is retried every scheduler
                # iteration: skip the (hashing + LRU-touching) prefix
                # match outright unless the pool or registry changed
                # since it last failed the watermark
                state = (req.rid, self.manager.free_blocks,
                         self.manager.live_blocks,
                         self.manager.registry_size())
                if state == self._blocked_state:
                    return
                shared = self.manager.match_prefix(seq)
                start = len(shared) * self.block_size
                # chunk-rounded prefill rows must fit the table; drop
                # shared blocks if a misaligned chunk boundary overflows
                # (dropped blocks are not used → not hits)
                while shared and (start + _chunk_rows(len(seq) - start,
                                                      self.chunk)
                                  > self.capacity):
                    self.manager.unmatch(shared[-1:])
                    shared = shared[:-1]
                    start -= self.block_size
                rows = start + _chunk_rows(len(seq) - start, self.chunk)
                need = -(-rows // self.block_size) - len(shared)
                if not self.manager.can_alloc(need):
                    # undo the match exactly — refcounts AND accounting;
                    # nothing was admitted, so nothing is counted
                    self.manager.unmatch(shared, whole_query=True)
                    if all(s.free for s in self.sched.slots):
                        # nothing running that could ever free blocks:
                        # this request is individually unservable
                        raise PoolExhausted(
                            f"request rid={req.rid} needs {need} KV "
                            f"blocks of {self.block_size} but the pool "
                            f"holds only {self.pool_blocks}")
                    self._blocked_state = state
                    return
                self._prompt_blocks_seen += max(
                    (len(seq) - 1) // self.block_size, 0)
            self._blocked_state = None
            slot = free[0]
            self.sched.waiting.popleft()
            blocks = shared + self.manager.alloc(need)
            if self._reset is not None:
                self.cache = self._reset(self.cache, self._empty_row,
                                         slot.index)
            slot.occupy(req.rid, seq, req.max_new_tokens)
            slot.blocks = blocks
            slot.chunk_pos = start          # prefill starts past the hit
            self._set_table_row(slot)
            if req.admitted_step is None:
                req.admitted_step = decode_steps
                req.admitted_at = time.perf_counter()

    def _grow_for_decode(self, active, preempt: bool):
        """Ensure every active slot owns the block this step's write
        lands in, preempting the youngest occupied slot (LIFO, vLLM-
        style) whenever the pool runs dry.  Oldest slots grow first, so
        under pressure service order degenerates gracefully to FCFS.
        With ``preempt`` False a dry pool returns None instead, having
        preempted nothing (the blocks already grown stay)."""
        if not self.paged_keys:
            return active                   # pure-SSM: nothing paged
        for s in sorted(active, key=lambda x: x.rid):
            while not s.free and s.position // self.block_size \
                    >= len(s.blocks):
                try:
                    s.blocks.extend(self.manager.alloc(1))
                    self.block_table[s.index,
                                     len(s.blocks) - 1] = s.blocks[-1]
                except PoolExhausted:
                    if not preempt:
                        return None
                    victim = self.sched.preemption_victim()
                    self._preempt(victim)
                    if victim is s:
                        break
        return [s for s in active if s.active]

    def _decode_ready(self) -> list:
        """ACTIVE slots with a token still to come: a slot whose last
        token a launched step already computes sits the next step out."""
        return [s for s in self.sched.active_slots()
                if s.generated < s.max_new]

    def _grow(self, preempt: bool):
        """``_grow_for_decode`` over the ready slots, in its span."""
        with TraceAnnotation("serve.grow") as span:
            before = self.preemptions
            active = self._grow_for_decode(self._decode_ready(), preempt)
            span.set_metadata(preempted=self.preemptions - before)
        return active

    def _host_tokens(self) -> jax.Array:
        """The host token mirror as a decode token operand, in the form
        the step gives its own token output: an ahead step passes that
        output on as it is, so both key the one compiled program."""
        out = self._tok_out
        if out is not None and out.committed:
            return jax.device_put(self._cur.copy(), out.sharding)
        return jnp.asarray(self._cur.copy())

    def _run_chunk(self, slot, step_clock: int) -> None:
        """One prefill chunk for ``slot``; flips it ACTIVE (and emits the
        next token) when the prompt is exhausted.  For a fresh request
        that token is its first; for a preempted request re-prefilling
        ``prompt ++ generated`` it is a continuation — the bookkeeping
        below is resume-aware."""
        c = self.chunk
        prompt = slot.prompt
        p = slot.chunk_pos
        r = min(c, len(prompt) - p)
        with TraceAnnotation("serve.chunk", rid=slot.rid, pos=p, rows=r):
            with TraceAnnotation("serve.chunk.prepare"):
                toks = np.zeros((1, c), np.int32)
                poss = np.full((1, c), -1, np.int32)
                toks[0, :r] = prompt[p:p + r]
                poss[0, :r] = np.arange(p, p + r, dtype=np.int32)
                # a copy of the row: on the CPU an array made from numpy
                # may share its memory, and the host table changes while
                # the step is queued
                args = (jnp.asarray(toks), jnp.asarray(poss), slot.index,
                        jnp.asarray([p + c], jnp.int32),
                        jnp.asarray(self.block_table[slot.index:
                                                     slot.index + 1].copy()))
            with TraceAnnotation("serve.chunk.launch"):
                ntok, _, self.cache = self._chunk_step(self.params,
                                                       self.cache, *args)
            slot.chunk_pos += r
            if slot.chunk_pos < len(prompt):
                return
            # final chunk: its last real row's logits are the next token
            req = self.requests[slot.rid]
            # publish the fully-written prompt blocks to the prefix
            # cache (a no-op unless sharing is enabled for this family)
            self.manager.register_prefix(prompt, slot.blocks)
            with TraceAnnotation("serve.chunk.pull"):
                tok0 = int(np.asarray(ntok)[0, r - 1])
            now = time.perf_counter()
            req.tokens.append(tok0)
            req.token_times.append(now)
            if req.first_token_at is None:
                req.first_token_at = now
            slot.begin_decode()
            slot.generated = len(req.tokens)
            if slot.generated >= slot.max_new or tok0 == self.eos_id:
                self._finish(req, step_clock)
                self._free_slot(slot)
            else:
                self._cur[slot.index] = tok0

    def _pull(self, step) -> None:
        """Pull a launched decode step's tokens and emit them: append
        each token to its request, stamped with one host time for the
        step, then finish and free the slots whose request is done.  A
        slot whose request finished at the step before (its ``eos``
        came after this step was launched) has moved on: its token is
        dropped."""
        launched, ntok, step_clock = step
        with TraceAnnotation("serve.decode.pull"):
            ntok = np.asarray(ntok)
        with TraceAnnotation("serve.decode.emit") as span:
            now = time.perf_counter()
            finished = 0
            for s, rid in launched:
                if s.rid != rid:
                    continue
                req = self.requests[rid]
                t = int(ntok[s.index])
                req.tokens.append(t)
                req.token_times.append(now)
                self._cur[s.index] = t
                if len(req.tokens) >= s.max_new or t == self.eos_id:
                    self._finish(req, step_clock)
                    self._free_slot(s)
                    finished += 1
            span.set_metadata(finished=finished)

    def _finish(self, req: Request, step_clock: int) -> None:
        req.done = True
        req.finished_at = time.perf_counter()
        req.finished_step = step_clock
        self._served.append(req)

    # ------------------------------------------------------------------
    def run(self) -> Dict[str, float]:
        """Serve until queue and slots drain; returns latency metrics
        plus pool accounting (utilization, prefix hits, preemptions)."""
        t0 = time.perf_counter()
        self._served: List[Request] = []
        decode_steps = 0
        ahead_steps = 0
        prefill_chunks = 0
        occupancy: List[int] = []
        kv_fill: List[int] = []
        kv_raw: List[int] = []
        live_hist: List[int] = []
        # the decode step launched and not yet pulled: ([(slot, rid)],
        # its token output on the device, its decode-step clock)
        pending = None

        # Decode step n+1 is launched before step n's tokens are pulled,
        # its token operand step n's output on the device, so the host's
        # work of an iteration runs while the device runs step n+1.  The
        # loop drains (pulls step n first) only where it needs step n's
        # tokens on the host: a slot enters decode, or growth must
        # preempt (docs/serving.md, "One-step decode lookahead").  Host
        # spans are on the profiler's clock.
        while self.sched.busy or pending is not None:
            with TraceAnnotation("serve.iteration", step=decode_steps):
                with TraceAnnotation("serve.admit") as span:
                    waiting = len(self.sched.waiting)
                    self._admit(decode_steps)
                    span.set_metadata(
                        admitted=waiting - len(self.sched.waiting),
                        waiting=len(self.sched.waiting))

                spent = 0
                for slot in sorted(self.sched.prefilling_slots(),
                                   key=lambda s: s.rid):
                    while slot.prefilling and spent < self.prefill_budget:
                        self._run_chunk(slot, decode_steps)
                        prefill_chunks += 1
                        spent += self.chunk
                    if spent >= self.prefill_budget:
                        break

                if pending is not None:
                    inflight = {(s.index, rid) for s, rid in pending[0]}
                    if any((s.index, s.rid) not in inflight
                           for s in self._decode_ready()):
                        # a slot entered decode: its token is on the host
                        self._pull(pending)
                        pending = None
                active = self._grow(preempt=pending is None)
                if active is None:
                    # the pool is dry, and a preempted request is
                    # re-prefilled over prompt ++ generated: step n's
                    # tokens first
                    self._pull(pending)
                    pending = None
                    active = self._grow(preempt=True)

                step = None
                if active:
                    ahead = pending is not None
                    with TraceAnnotation("serve.decode.prepare",
                                         active=len(active)):
                        # every slot of an ahead step was in step n, so
                        # step n's output is its token operand as it is
                        tok = pending[1] if ahead else self._host_tokens()
                        pos = np.zeros((self.n_slots,), np.int32)
                        kvl = np.zeros((self.n_slots,), np.int32)
                        for s in active:
                            pos[s.index] = s.position
                            kvl[s.index] = s.position + 1
                        # a copy: the host table changes while the step
                        # is queued (see ``_run_chunk``)
                        table = jnp.asarray(self.block_table.copy())
                    with TraceAnnotation("serve.decode.launch",
                                         ahead=int(ahead)):
                        ntok, _, self.cache = self.decode(
                            self.params, self.cache, tok, pos, kvl, table)
                    self._tok_out = ntok
                    for s in active:
                        s.advance()
                    decode_steps += 1
                    ahead_steps += ahead
                    occupancy.append(len(active))
                    live_hist.append(self.manager.live_blocks)
                    blocks = np.maximum(-(-kvl // self.block_size), 1)
                    kv_fill.append(int(blocks.sum()) * self.block_size)
                    kv_raw.append(int(kvl.sum()))
                    step = ([(s, s.rid) for s in active], ntok,
                            decode_steps)
                if pending is not None:
                    self._pull(pending)
                pending = step

        served = self._served
        wall = time.perf_counter() - t0
        self.metrics = _summarize(served, wall, decode_steps=decode_steps,
                                  prefills=prefill_chunks,
                                  occupancy=occupancy,
                                  n_slots=self.n_slots)
        # decode steps launched while the step before was still unpulled
        self.metrics["decode_ahead_share"] = (
            ahead_steps / decode_steps if decode_steps else 0.0)
        self.metrics["precision"] = self.precision
        self.metrics["prefill_chunk"] = self.chunk
        self.metrics["kv_cache_bytes"] = decode_cache_nbytes(self.cache)
        self.metrics["weight_bytes"] = self._weight_bytes
        self.metrics["kv_block_bytes"] = self._block_bytes
        self.metrics["block_size"] = self.block_size
        self.metrics["pool_blocks"] = self.pool_blocks
        self.metrics["preemptions"] = self.preemptions
        st = self.manager.stats
        self.metrics["prefix_hit_blocks"] = st["prefix_hit_blocks"]
        self.metrics["prefix_hit_rate"] = (
            st["prefix_hit_blocks"] / self._prompt_blocks_seen
            if self._prompt_blocks_seen else 0.0)
        if live_hist:
            self.metrics["pool_live_blocks_peak"] = int(np.max(live_hist))
            self.metrics["pool_utilization"] = (
                float(np.mean(live_hist)) / self.pool_blocks)
            self.metrics["kv_live_bytes_peak"] = (
                int(np.max(live_hist)) * self._block_bytes)
        if kv_fill:
            # fraction of the slots × capacity rectangle the bounded
            # decode kernel reads per step, block-granular (even an idle
            # slot's clamped index map fetches one block), and the exact
            # live fill: pad-free, so it counts only real tokens
            denom = self.n_slots * self.capacity
            self.metrics["kv_read_frac"] = float(np.mean(kv_fill) / denom)
            self.metrics["kv_fill_frac"] = float(np.mean(kv_raw) / denom)
        if self.artifact is not None:
            self.metrics["artifact_bytes"] = self.artifact.artifact_bytes
        return self.metrics

