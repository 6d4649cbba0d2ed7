"""Serving engines — the "EIM process runner" analogue (paper §4.6):
a deployed artifact behind a queue-driven I/O interface.

Three schedulers over the same model serve steps:

* ``PagedBatchServer`` — continuous batching over a **paged KV pool**:
  fixed-size physical KV blocks addressed through per-slot block
  tables, with hash-based prefix sharing and preempt-and-recompute when
  the pool runs dry (docs/paged_kv.md).  Live-token HBM replaces
  worst-case-rectangle HBM; the two rectangle engines below remain the
  measured baselines.

* ``ContinuousBatchServer`` (the default ``BatchServer``) — slot-based
  continuous batching with **chunked pad-free prefill**: a prompt of
  length S is consumed in ceil(S / C) fixed-size chunk steps interleaved
  with decode under a per-step token budget, each chunk written unpadded
  into the slot's cache rows ``[p, p + C)``.  Finished sequences release
  their KV-cache slot *between decode steps* and waiting requests are
  admitted into freed slots; per-request ``max_new_tokens`` is honored
  in-step.  One chunk shape compiles once (instead of one shape per
  padded bucket); optionally the decode hot loop runs a
  ``CompiledArtifact`` (``core/eon_compiler.compile_serve_decode``) so
  serving executes the same AOT executable we "deploy" (paper C4).
* ``StaticBatchServer`` — the classic baseline: a batch is formed once,
  prefilled to completion (same pad-free chunk steps, no interleaving),
  and decodes until its slowest member finishes; short requests block
  behind long ones.  Kept as the benchmark control.

Every engine accepts ``precision="float" | "int8"`` (paper C5 threaded
end-to-end) and puts the weights in their at-rest form once at
construction (``quantize.serving_params``): float holds the projections
and the embedding tables in the activation dtype, int8 wraps the
projections in QTensor, serves through the quant-aware matmul entry
point, and keeps the decode cache as Int8KV — ≥2× KV HBM, token-exact
against the fake-quant float reference (docs/quantization.md).

Both feed the decode step a per-slot ``kv_len`` — with pad-free
admission this is the *exact* live fill (``position + 1``; 0 for idle or
mid-prefill slots, whose rows the step neither reads nor writes) — so
the flash-decode kernel reads only each slot's live prefix of the
capacity rectangle, and int8 decode dequantizes inside the kernel tile,
never materializing a float cache (docs/serving.md, "Flash-decode
kernel").

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
                                 paged_slot_axes, put_slot, release_slot,
                                 slot_batch_axes)
from repro.serve.scheduler import SlotScheduler
from repro.serve.serve_step import (make_chunk_prefill_step,
                                    make_paged_chunk_prefill_step,
                                    make_paged_decode_step,
                                    make_slot_decode_step)

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
    preemptions: int = 0            # paged engine: times evicted/recomputed


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


def _summarize(served: List[Request], wall: float, *, engine: str,
               decode_steps: int, prefills: int,
               occupancy: Optional[List[int]] = None,
               n_slots: int = 0) -> Dict[str, float]:
    ttfts = np.array([r.first_token_at - r.submitted_at for r in served])
    gen = sum(len(r.tokens) for r in served)
    m: Dict[str, float] = {
        "engine": engine,
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


class _ServerBase:
    def __init__(self, cfg: ArchConfig, params, precision: str = "float"):
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

    def _footprint_metrics(self) -> None:
        """What every engine's ``run`` reports of its precision, chunk
        and HBM: the KV cache and the params tree the steps read."""
        self.metrics["precision"] = self.precision
        self.metrics["prefill_chunk"] = self.chunk
        self.metrics["kv_cache_bytes"] = decode_cache_nbytes(self.cache)
        self.metrics["weight_bytes"] = self._weight_bytes

    def _slot_capacity(self) -> int:
        """Per-slot KV rows: prompt + generation budget, with headroom
        for a ragged final chunk's pad tail at max_prompt, rounded up to
        the flash-decode KV tile (``BLOCK_K``) so the kernel runs at its
        full tile and never pads the cache per step; the tail is dead
        capacity the per-slot kv_len bound skips without reading.  Both
        engines and ``_check_fits`` share this."""
        need = max(self.max_prompt + self.max_new_cap,
                   _chunk_rows(self.max_prompt, self.chunk))
        return -(-need // BLOCK_K) * BLOCK_K

    def _init_slot_steps(self, n_slots: int) -> None:
        """Chunk-prefill / decode / reset steps over an ``n_slots`` ×
        ``self.capacity`` cache (shared by both engines)."""
        axes = slot_batch_axes(self.cfg, n_slots, self.capacity, self.prec)
        # the cache is dead after every call (immediately reassigned):
        # donate it so steps update rows in place instead of copying the
        # whole KV allocation per token
        self._chunk_step = jax.jit(
            make_chunk_prefill_step(self.cfg, axes=axes, policy=self.prec),
            donate_argnums=(1,))
        self._reset = jax.jit(
            lambda cache, empty, slot: put_slot(cache, empty, axes, slot),
            donate_argnums=(0,))
        self._release = jax.jit(release_slot, donate_argnums=(0,))
        self._empty_row = alloc_decode_cache(self.cfg, 1, self.capacity,
                                             self.prec)
        self.cache = alloc_decode_cache(self.cfg, n_slots, self.capacity,
                                        self.prec)
        # host mirror of the last emitted token per slot (decode feed)
        self._cur = np.zeros((n_slots,), np.int32)

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

    def _chunk_table(self, slot) -> tuple:
        """Operands a chunk step takes after ``kv_len`` (the paged
        engine's block-table row of ``slot``)."""
        return ()

    def _register_prefill(self, slot, prompt) -> None:
        """Hook at prefill completion (paged: publish prefix blocks)."""

    def _release_finished(self, slot) -> None:
        """Free a slot whose request finished (paged: refcount blocks)."""
        self.cache = self._release(self.cache, slot.index)
        slot.release()

    def _run_chunk(self, slot, step_clock: int) -> None:
        """One prefill chunk for ``slot``; flips it ACTIVE (and emits the
        next token) when the prompt is exhausted.  For a fresh request
        that token is its first; for a preempted request re-prefilling
        ``prompt ++ generated`` (paged engine) it is a continuation —
        the bookkeeping below is resume-aware so one implementation
        serves every engine."""
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
                args = (jnp.asarray(toks), jnp.asarray(poss), slot.index,
                        jnp.asarray([p + c], jnp.int32),
                        *self._chunk_table(slot))
            with TraceAnnotation("serve.chunk.launch"):
                ntok, _, self.cache = self._chunk_step(self.params,
                                                       self.cache, *args)
            slot.chunk_pos += r
            if slot.chunk_pos < len(prompt):
                return
            # final chunk: its last real row's logits are the next token
            req = self.requests[slot.rid]
            self._register_prefill(slot, prompt)
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
                self._release_finished(slot)
            else:
                self._cur[slot.index] = tok0

    def _emit(self, active, ntok, step_clock: int) -> None:
        """Append each active slot's token from the pulled decode output
        ``ntok``, stamped with one host time for the step, then finish
        and free the slots whose request is done."""
        with TraceAnnotation("serve.decode.emit") as span:
            now = time.perf_counter()
            finished = 0
            for s in active:
                req = self.requests[s.rid]
                t = int(ntok[s.index])
                req.tokens.append(t)
                req.token_times.append(now)
                s.advance()
                self._cur[s.index] = t
                if s.generated >= s.max_new or t == self.eos_id:
                    self._finish(req, step_clock)
                    self._release_finished(s)
                    finished += 1
            span.set_metadata(finished=finished)

    def _finish(self, req: Request, step_clock: int) -> None:
        req.done = True
        req.finished_at = time.perf_counter()
        req.finished_step = step_clock
        self._served.append(req)


class ContinuousBatchServer(_ServerBase):
    """Continuous batching: slot recycling between decode steps, with
    prefill chunks scheduled *inside* the decode loop.

    ``slots`` decode rows share one jitted decode step; prompts are
    consumed ``prefill_chunk`` tokens at a time (one compiled chunk
    shape, pad-free cache rows) under ``prefill_token_budget`` prefill
    tokens per decode step, so a long prompt cannot head-of-line-block
    the active slots' next tokens.  ``batch_size`` / ``prompt_len`` are
    accepted as aliases so existing callers keep working.
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
                 batch_size: Optional[int] = None,
                 prompt_len: Optional[int] = None,
                 precision: str = "float"):
        super().__init__(cfg, params, precision)
        self.n_slots = int(slots or batch_size or 4)
        self.max_prompt = int(max_prompt or prompt_len or 32)
        self.chunk = int(prefill_chunk)
        # fairness knob: prefill tokens spent per decode step once any
        # slot is actively decoding (floored at one chunk so admission
        # always progresses); see docs/scheduling.md for the trade-off.
        self.prefill_budget = int(prefill_token_budget or self.chunk)
        self.max_new = int(max_new_tokens)
        self.max_new_cap = int(max_new_cap or max(self.max_new, 1))
        self.capacity = self._slot_capacity()
        # effective flash-decode block at this capacity — the HBM-read
        # metric quantizes to it (same helper the kernels use)
        self._kv_block = kv_block_size(self.capacity)
        self.eos_id = eos_id
        self.sched = SlotScheduler(self.n_slots)
        self._init_slot_steps(self.n_slots)
        self.artifact = None
        if use_artifact:
            from repro.core.eon_compiler import compile_serve_decode
            self.artifact = compile_serve_decode(
                cfg, self.params, slots=self.n_slots, capacity=self.capacity,
                policy=self.prec)
            self.decode = self.artifact.rehydrate()
        else:
            self.decode = jax.jit(
                make_slot_decode_step(cfg, policy=self.prec),
                donate_argnums=(1,))

    # ------------------------------------------------------------------
    def submit(self, prompts: List[np.ndarray],
               max_new_tokens: Union[int, Sequence[int], None] = None
               ) -> List[Request]:
        reqs = self._make_requests(prompts, max_new_tokens)
        for r in reqs:
            self.sched.enqueue(r)
        return reqs

    # ------------------------------------------------------------------
    def run(self) -> Dict[str, float]:
        """Serve until queue and slots drain; returns latency metrics."""
        t0 = time.perf_counter()
        self._served: List[Request] = []
        decode_steps = 0
        prefill_chunks = 0
        occupancy: List[int] = []
        kv_fill: List[int] = []   # Σ block-rounded kv_len per decode step
        kv_raw: List[int] = []    # Σ kv_len per decode step (exact fill)

        while self.sched.busy:
            # Admission: freed slots pick up waiting requests *now*, not
            # at the end of a batch — the continuous-batching invariant.
            # One slot-row reset on device; the prefill compute itself
            # is chunked below.
            for slot, req in self.sched.admissions():
                self.cache = self._reset(self.cache, self._empty_row,
                                         slot.index)
                slot.occupy(req.rid, req.prompt, req.max_new_tokens)
                req.admitted_step = decode_steps
                req.admitted_at = time.perf_counter()

            # Budgeted chunk prefill, oldest request first: at most
            # prefill_budget prompt tokens per decode step (always at
            # least one chunk), so active slots keep emitting while long
            # prompts stream in.
            spent = 0
            for slot in sorted(self.sched.prefilling_slots(),
                               key=lambda s: s.rid):
                while slot.prefilling and spent < self.prefill_budget:
                    self._run_chunk(slot, decode_steps)
                    prefill_chunks += 1
                    spent += self.chunk
                if spent >= self.prefill_budget:
                    break

            active = self.sched.active_slots()
            if not active:
                continue

            tok = np.array(self._cur)
            pos = np.zeros((self.n_slots,), np.int32)
            # per-slot fill: pad-free, so fill == position + 1 exactly
            # (0 = idle or mid-prefill slot: skipped outright, and the
            # step suppresses its writes)
            kvl = np.zeros((self.n_slots,), np.int32)
            for s in active:
                pos[s.index] = s.position
                kvl[s.index] = s.position + 1
            ntok, _, self.cache = self.decode(self.params, self.cache,
                                              tok, pos, kvl)
            decode_steps += 1
            occupancy.append(len(active))
            # block-granular: the kernel fetches whole KV blocks, and
            # even an idle slot's clamped index map fetches one
            blocks = np.maximum(-(-kvl // self._kv_block), 1)
            kv_fill.append(int(blocks.sum()) * self._kv_block)
            kv_raw.append(int(kvl.sum()))
            self._emit(active, np.asarray(ntok), decode_steps)

        served = self._served
        wall = time.perf_counter() - t0
        self.metrics = _summarize(served, wall, engine="continuous",
                                  decode_steps=decode_steps,
                                  prefills=prefill_chunks,
                                  occupancy=occupancy,
                                  n_slots=self.n_slots)
        self._footprint_metrics()
        if kv_fill:
            # fraction of the slots × capacity rectangle the bounded
            # decode kernel reads per step (1.0 = no bounding).  Block-
            # granular at the kernel's effective block, and exact only
            # for the kv_len-bounded full-attention leaves — ring/local
            # caches carry their own position-based bound.
            # kv_fill_frac is the exact live fill (entries) — pad-free,
            # so it counts only real prompt/generated tokens — the floor
            # the read fraction approaches as capacity / block grows.
            denom = self.n_slots * self.capacity
            self.metrics["kv_read_frac"] = float(np.mean(kv_fill) / denom)
            self.metrics["kv_fill_frac"] = float(np.mean(kv_raw) / denom)
        if self.artifact is not None:
            self.metrics["artifact_bytes"] = self.artifact.artifact_bytes
        return self.metrics


class StaticBatchServer(_ServerBase):
    """Static batching baseline: the queue is drained in fixed batches
    and every batch decodes until its *slowest* member finishes — slots
    are never recycled mid-flight.  Prefill uses the same pad-free chunk
    steps as the continuous engine (run to completion up front, no
    interleaving), so token-for-token the two engines match on every
    architecture family; only scheduling differs.
    """

    def __init__(self, cfg: ArchConfig, params, *, batch_size: int = 4,
                 max_prompt: Optional[int] = None,
                 prefill_chunk: int = 8,
                 prompt_len: Optional[int] = None,
                 max_new_tokens: int = 16,
                 precision: str = "float"):
        super().__init__(cfg, params, precision)
        self.batch_size = int(batch_size)
        self.max_prompt = int(max_prompt or prompt_len or 32)
        self.chunk = int(prefill_chunk)
        self.max_new = int(max_new_tokens)
        self.max_new_cap = self.max_new
        self.eos_id = None
        self.capacity = self._slot_capacity()
        self.queue: List[Request] = []
        self._init_slot_steps(self.batch_size)
        self.decode = jax.jit(
            make_slot_decode_step(cfg, policy=self.prec),
            donate_argnums=(1,))

    def submit(self, prompts: List[np.ndarray],
               max_new_tokens: Union[int, Sequence[int], None] = None
               ) -> List[Request]:
        reqs = self._make_requests(prompts, max_new_tokens)
        self.queue.extend(reqs)
        return reqs

    def run(self) -> Dict[str, float]:
        from repro.serve.scheduler import Slot
        t0 = time.perf_counter()
        self._served: List[Request] = []
        decode_steps = 0
        prefill_chunks = 0
        while self.queue:
            batch = self.queue[:self.batch_size]
            self.queue = self.queue[self.batch_size:]
            slots = []
            for i, r in enumerate(batch):
                self.cache = self._reset(self.cache, self._empty_row, i)
                slot = Slot(i)
                slot.occupy(r.rid, r.prompt, r.max_new_tokens)
                r.admitted_step = decode_steps
                r.admitted_at = time.perf_counter()
                while slot.prefilling:      # full prefill, no interleave
                    self._run_chunk(slot, decode_steps)
                    prefill_chunks += 1
                slots.append(slot)
            horizon = max(r.max_new_tokens for r in batch) - 1
            # the batch decodes as one unit until its slowest member
            # drains; finished rows keep stepping (outputs discarded)
            for _ in range(horizon):
                if not any(s.active for s in slots):
                    break
                tok = np.array(self._cur)
                pos = np.zeros((self.batch_size,), np.int32)
                kvl = np.zeros((self.batch_size,), np.int32)
                for s in slots:
                    if s.active:
                        pos[s.index] = s.position
                        kvl[s.index] = s.position + 1
                ntok, _, self.cache = self.decode(self.params, self.cache,
                                                  tok, pos, kvl)
                decode_steps += 1
                ntok_h = np.asarray(ntok)
                now = time.perf_counter()
                for s in slots:
                    if not s.active:
                        continue
                    r = self.requests[s.rid]
                    t = int(ntok_h[s.index])
                    s.advance()
                    self._cur[s.index] = t
                    if not r.done:
                        r.tokens.append(t)
                        r.token_times.append(now)
                        if len(r.tokens) >= r.max_new_tokens:
                            self._finish(r, decode_steps)

        served = self._served
        wall = time.perf_counter() - t0
        self.metrics = _summarize(served, wall, engine="static",
                                  decode_steps=decode_steps,
                                  prefills=prefill_chunks)
        self._footprint_metrics()
        return self.metrics


class PagedBatchServer(_ServerBase):
    """Continuous batching over a **paged KV pool** (docs/paged_kv.md).

    The contiguous engine holds a ``slots × capacity`` rectangle per
    slot: after kv_len bounding the dead tail is never *read*, but it is
    still *held* in HBM, so concurrency is priced at the worst case.
    Here the full-attention KV lives in a global pool of fixed-size
    physical blocks (block == the flash-decode KV block), each slot maps
    logical KV positions to physical blocks through a **block table**
    that rides the decode signature into the kernels' index maps, and a
    host-side ``BlockManager`` owns the pool:

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
                 batch_size: Optional[int] = None,
                 prompt_len: Optional[int] = None,
                 precision: str = "float"):
        super().__init__(cfg, params, precision)
        self.n_slots = int(slots or batch_size or 4)
        self.max_prompt = int(max_prompt or prompt_len or 32)
        self.chunk = int(prefill_chunk)
        self.prefill_budget = int(prefill_token_budget or self.chunk)
        self.max_new = int(max_new_tokens)
        self.max_new_cap = int(max_new_cap or max(self.max_new, 1))
        self.capacity = self._slot_capacity()
        # pool block: the kernel tile by default (maximum DMA width);
        # any smaller divisor of capacity the kernel accepts trades DMA
        # width for allocation granularity / prefix-hit resolution
        self.block_size = self._kv_block = int(
            block_size or kv_block_size(self.capacity))
        check_kv_block(self.block_size, self.capacity)
        if self.capacity % self.chunk:
            raise ValueError(
                f"prefill_chunk {self.chunk} must divide the rounded "
                f"capacity {self.capacity} (paged blocks may not "
                f"overflow the table)")
        self.n_table = self.capacity // self.block_size
        # default pool == the contiguous rectangle's block count (no
        # preemption possible); size it below slots × capacity to trade
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
        # reset per admission exactly as in the contiguous engine; pool
        # leaves need no scrub — a new tenant's writes precede its kv_len
        shared = set(self.paged_keys) | {"pool_pos"}
        slot_keys = tuple(k for k in self.cache if k not in shared)
        self._slot_keys = slot_keys
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
        # host mirror of the device block-table operand (0 = unmapped:
        # always a valid physical block; dead entries are fenced by
        # kv_len, not by the table)
        self.block_table = np.zeros((self.n_slots, self.n_table), np.int32)

    # ------------------------------------------------------------------
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

    def _chunk_table(self, slot) -> tuple:
        return (jnp.asarray(self.block_table[slot.index:slot.index + 1]),)

    def _register_prefill(self, slot, prompt) -> None:
        """Publish the fully-written prompt blocks to the prefix cache
        (a no-op unless sharing is enabled for this family)."""
        self.manager.register_prefix(prompt, slot.blocks)

    def _release_finished(self, slot) -> None:
        self._free_slot(slot)

    def _grow_for_decode(self, active) -> list:
        """Ensure every active slot owns the block this step's write
        lands in, preempting the youngest occupied slot (LIFO, vLLM-
        style) whenever the pool runs dry.  Oldest slots grow first, so
        under pressure service order degenerates gracefully to FCFS."""
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
                    victim = self.sched.preemption_victim()
                    self._preempt(victim)
                    if victim is s:
                        break
        return [s for s in active if s.active]

    # ------------------------------------------------------------------
    def run(self) -> Dict[str, float]:
        """Serve until queue and slots drain; returns latency metrics
        plus pool accounting (utilization, prefix hits, preemptions)."""
        t0 = time.perf_counter()
        self._served: List[Request] = []
        decode_steps = 0
        prefill_chunks = 0
        occupancy: List[int] = []
        kv_fill: List[int] = []
        kv_raw: List[int] = []
        live_hist: List[int] = []

        # Host spans on the profiler's clock (docs/serving.md,
        # "Observability"): the loop is synchronous, so the host waits
        # on the device only inside a ``*.pull`` span, and every device
        # idle gap falls inside a named span.
        while self.sched.busy:
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

                with TraceAnnotation("serve.grow") as span:
                    preempted = self.preemptions
                    active = self._grow_for_decode(self.sched.active_slots())
                    span.set_metadata(preempted=self.preemptions - preempted)
                if not active:
                    continue

                with TraceAnnotation("serve.decode.prepare",
                                     active=len(active)):
                    tok = np.array(self._cur)
                    pos = np.zeros((self.n_slots,), np.int32)
                    kvl = np.zeros((self.n_slots,), np.int32)
                    for s in active:
                        pos[s.index] = s.position
                        kvl[s.index] = s.position + 1
                    table = jnp.asarray(self.block_table)
                with TraceAnnotation("serve.decode.launch"):
                    ntok, _, self.cache = self.decode(
                        self.params, self.cache, tok, pos, kvl, table)
                decode_steps += 1
                occupancy.append(len(active))
                live_hist.append(self.manager.live_blocks)
                blocks = np.maximum(-(-kvl // self._kv_block), 1)
                kv_fill.append(int(blocks.sum()) * self._kv_block)
                kv_raw.append(int(kvl.sum()))
                with TraceAnnotation("serve.decode.pull"):
                    ntok_h = np.asarray(ntok)
                self._emit(active, ntok_h, decode_steps)

        served = self._served
        wall = time.perf_counter() - t0
        self.metrics = _summarize(served, wall, engine="paged",
                                  decode_steps=decode_steps,
                                  prefills=prefill_chunks,
                                  occupancy=occupancy,
                                  n_slots=self.n_slots)
        self._footprint_metrics()
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
            denom = self.n_slots * self.capacity
            self.metrics["kv_read_frac"] = float(np.mean(kv_fill) / denom)
            self.metrics["kv_fill_frac"] = float(np.mean(kv_raw) / denom)
        if self.artifact is not None:
            self.metrics["artifact_bytes"] = self.artifact.artifact_bytes
        return self.metrics


# Default engine: continuous batching (what the old name promised).
BatchServer = ContinuousBatchServer
