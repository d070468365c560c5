"""Batched inference engines: bucketed pad-aware prefill + continuous-batch
decode, one execution path from models to both cache layouts.

Two engines back the serving tiers:

* ``InferenceEngine`` (v1, dense): slots hold independent sequences over a
  fixed ``max_slots x max_len`` cache — every admitted sequence reserves a
  full ``max_len`` stripe up front (Orca-style continuous batching).

* ``PagedInferenceEngine`` (v2, paged): the KV cache is a shared pool of
  fixed-size pages (serving/paging.py); sequences own page lists, admission
  is gated on *free pages* rather than free slots, and page exhaustion
  preempts the newest sequence back to the waiting queue (recompute-style
  resume, vLLM-like). Prefill is *truly paged*: attention K/V scatter
  through the sequence's block-table row inside each layer
  (``model.prefill_paged``) — no dense per-length staging cache exists.

Bounded compilation (shared ``_EngineBase`` bucketing): every prompt — and
every preemption-resume context, which otherwise multiplies distinct
lengths — is right-padded to a power-of-two multiple of the page/bucket
unit, capped at the engine's length cap. Prefill therefore compiles at most
``num_buckets(unit, cap)`` = ceil(log2(cap/unit)) + 1 times regardless of
the traffic mix, instead of once per distinct context length; padding is
masked out of attention writes, logits, and the recurrent-state updates of
mamba/xlstm mixers (pad steps are identity), so bucketed serving is
token-for-token identical to unbucketed. ``compile_events`` — the number of
distinct prefill shapes executed — is exported through ``capacity_now()``
so the placer and telemetry can see warm-up state.

Chunked prefill + the per-step token budget: with ``chunk_tokens > 0`` a
prompt is no longer absorbed in one device call. Admission merely reserves
capacity (a slot; for the paged engine also the full context's pages) and
puts the slot in the PREFILLING state — a chunk cursor, the context being
absorbed, and an OFF-CACHE recurrent carry (models/api.py
``prefill_chunk``/``prefill_chunk_paged``). Each ``step()`` then shares one
token budget (``step_token_budget``, auto ``2*chunk_tokens``) between the
decode batch (one token per decoding slot) and at-most-a-few prefill
chunks, served FIFO by admission stamp with a one-chunk-per-step progress
guarantee — so a 4k-token prompt is absorbed over many iterations while
every decoding slot keeps emitting a token EVERY iteration, instead of
stalling behind the whole prefill (and ``_admit`` running up to max_slots
back-to-back full prefills). The final chunk installs the carry into the
decode cache, emits the same greedy token the whole-prompt prefill would,
and flips the slot to decoding under the unchanged stop conditions. With
chunking OFF the same budget still caps full-prefill admissions per step
(the first admission of each step is unconditional so nothing starves). Chunks reuse the bucket geometry capped at ``chunk_tokens``, so
compilation stays bounded (the shape bound only shrinks); PREFILLING slots
and remaining backlog tokens are exported through ``capacity_now()``
(``prefilling_slots`` / ``prefill_backlog_tokens``) so the placer can see a
tier digesting a long prompt.

Warm-up: ``prewarm(buckets)`` compiles the prefill path for every bucket
length (or a chosen subset) before traffic arrives, so the first real
request of each shape pays a warm dispatch instead of an XLA compile.
Pre-warmed shapes count toward ``compile_events``, and ``capacity_now()``
additionally exports ``total_buckets`` so the placer can compute a warm
fraction (``compile_events / total_buckets``) and steer traffic toward
warmed-up tiers while another is still compiling.

Thread-safety contract (loop-owned stepping): each engine owns a reentrant
``lock`` covering ALL state-mutating entry points — ``submit``, ``step``,
``generate``, ``fork``, ``prewarm`` — i.e. the host-side bookkeeping
(waiting queue, slots, page allocator/tables, compile-shape set) **and** the
jitted device calls, which donate their cache buffers and therefore must
never run concurrently. The intended serving topology is *one stepper, many
submitters*: a single ``serving.scheduler.EngineLoop`` background thread
owns all ``step()`` calls, while any number of threads call ``submit`` —
each step admits whatever has been submitted and decodes every active slot
in ONE batched device call, so concurrent requests interleave inside the
decode batch instead of serializing whole generations. There must be at
most ONE stepper at a time: the lock keeps concurrent ``step`` /
``generate`` calls memory-safe, but a ``step()`` returns finished sequences
only to ITS caller — a second stepper (e.g. ``generate`` racing a running
EngineLoop) can pop the other's completions, which then never reach that
stepper's bookkeeping. ``generate`` is the synchronous convenience and the
serialized benchmark baseline for an engine NOT owned by a loop, not the
serving path. The read-only telemetry — ``capacity_now``,
``admission_capacity``, ``free_slots``, ``compile_events`` — is deliberately
lock-free: it returns instantaneous, possibly-stale snapshots. Callers must
NOT assume a capacity probe still holds by the time their request reaches
the engine (admission re-checks under the lock), and must not touch engine
internals (``waiting``, ``slot_seq``, ``allocator``, ``cache``, the
``_chunk*`` PREFILLING state) without holding ``lock``. The chunked-prefill
state machine lives entirely inside ``step()`` under the engine lock — the
EngineLoop needs no new entry points to interleave chunk work with decode.

Warm-up cost: every prefill-shape compile (bucket miss or ``prewarm``) is
wall-timed into ``compile_ema_s``, an EMA exported via ``capacity_now()`` —
the placer weighs warm-up gaps against it (a one-bucket gap on a tiny model
is not worth a tier hop).

Cross-request prefix cache (paged engine, ``prefix_cache=True``): finished
sequences no longer free their pages — they retire them into a radix tree
(serving/prefix_cache.py) keyed by token-id page runs, and admission
matches every prompt against that tree first. The slot lifecycle contract
changes from *release == free* to **release-to-cache vs free**:

* RELEASE-TO-CACHE (the sequence finished normally): the full pages holding
  its prompt + output K/V transfer their allocator reference to the tree
  (duplicates of already-cached prefixes are freed); only the trailing
  partial page returns to the free list. The pages stay warm for the next
  request sharing the prefix.
* FREE (preemption, or cache off): every page reference is dropped as
  before — but pages shared with the tree survive under the tree's own
  reference, so preempting a prefix-hit sequence never invalidates the
  cache it was reading.

On a prefix HIT, admission attaches the matched pages to the front of the
new sequence's ``PageTable`` via the same ref-count machinery ``fork``
uses, pins the matched tree path, and enters the PREFILLING state with the
chunk cursor AT THE MATCH BOUNDARY — prefill runs only for the unmatched
suffix (the match is capped one token short of the context so the final
chunk always has a token to emit logits from). Preemption of a prefix-hit
sequence drops the pin; re-admission re-matches from scratch, so a resume
restarts at the *re-validated* boundary (the tree may have grown or evicted
in between), never blindly at the old one. ``fork`` of a cache-attached
sequence pins the tree path once more, keeping path pins == attached
sequences. Cached pages are "free-ish" capacity, not occupancy: whenever an
allocation would fail, cold (unpinned) tree leaves are evicted LRU-first
BEFORE any live sequence is preempted, and ``capacity_now()`` exports
``cached_pages`` / ``evictable_pages`` / ``prefix_hit_rate`` so the placer
counts evictable cache as reclaimable. The cache requires an attention-only
decoder: recurrent mixers (mamba/xlstm) carry per-slot state that cached
pages cannot restore.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tracing import span
from repro.kernels.paged_attention.kernel import live_pages
from repro.models import get_model
from repro.serving.paging import (
    NULL_PAGE,
    BlockAllocator,
    ChainedTables,
    OutOfPages,
    PageTable,
    bucket_lengths,
    bucket_tokens,
    num_buckets,
)
from repro.serving.prefix_cache import PrefixCache


def _apply_cache_dtype(cfg, choice: str):
    """Resolve an engine-level KV-cache storage choice onto the model config:
    "" inherits the model's own settings, "f32"/"bf16" set the non-quantized
    storage dtype, "int8" turns on KV quantization (values + per-token-head
    scales). The engine owns this knob because cache layout is a serving
    decision — the same checkpoint serves at any storage width."""
    if not choice:
        return cfg
    if choice == "int8":
        return cfg.replace(kv_quant=True)
    dt = {"f32": jnp.float32, "bf16": jnp.bfloat16}.get(choice)
    if dt is None:
        raise ValueError(f"cache_dtype must be '', 'f32', 'bf16' or 'int8', got {choice!r}")
    return cfg.replace(kv_quant=False, kv_cache_dtype=dt)


def _kv_dtype_name(cfg) -> str:
    """The KV-cache storage dtype as telemetry sees it."""
    return "int8" if cfg.kv_quant else jnp.dtype(cfg.kv_dtype).name


def _kv_bytes_per_token(cfg, cache, token_slots: int) -> float:
    """KV-cache bytes per cached-token slot across every attention layer —
    values plus scales for int8, so the placer converts free tokens to real
    bytes whatever the storage format. Recurrent-mixer state is per-slot,
    not per-token, and stays out of the ratio."""
    total = 0
    for i, kind in enumerate(cfg.block_pattern):
        if kind != "attn":
            continue
        for leaf in jax.tree.leaves(cache["blocks"][f"l{i}_mixer"]):
            total += leaf.size * jnp.dtype(leaf.dtype).itemsize
    return total / max(1, token_slots)


@dataclass
class EngineConfig:
    max_slots: int = 4
    max_len: int = 256
    max_new_tokens: int = 32
    eos_id: int = -1            # -1: never stop early
    bucket_unit: int = 16       # prefill pad quantum (the dense "page unit")
    bucket_prefill: bool = True # False: one prefill compile per distinct length
    chunk_tokens: int = 0       # >0: chunked prefill, tokens per chunk (snapped
                                # to a bucket_unit multiple; must divide max_len)
    step_token_budget: int = 0  # per-step prefill+decode token budget
                                # (0 = auto: 2*chunk_tokens chunked, max_len not)
    spec_tokens: int = 0        # >0: n-gram speculative decoding, proposal
                                # tokens per slot per step (attention-only
                                # decoders; greedy-token-identical)
    spec_ngram: int = 3         # prompt-lookup match length for the proposer
    cache_dtype: str = ""       # KV-cache storage: "" inherit model config,
                                # "f32" | "bf16" | "int8" (int8 = quantized)


@dataclass
class Sequence:
    sid: int
    prompt: List[int]
    out: List[int] = field(default_factory=list)
    done: bool = False
    preemptions: int = 0
    # tokens of this sequence's context served from the prefix cache at its
    # most recent admission (0 = cold prefill / cache off); re-validated on
    # every preemption-resume, recorded into the prefix_matched_tokens
    # histogram by EngineLoop when the sequence finishes
    cached_tokens: int = 0
    # observability: submit timestamp + one monotonic stamp per emitted
    # token (TTFT = token_times[0] - submit_t; inter-token gaps = diffs).
    # Always recorded — one float append per token, noise next to a device
    # step — so latency histograms exist even without a tracer attached.
    submit_t: float = 0.0
    token_times: List[float] = field(default_factory=list)
    # first admission into a slot (chunked or whole prefill), kept across
    # preemption: admit_t - submit_t is the time spent in the engine's queue
    admit_t: Optional[float] = None
    # lifecycle trace context (core/tracing.Trace) carried from the router
    # through EngineLoop.submit; None = untraced (zero-cost path)
    trace: Optional[object] = field(default=None, repr=False, compare=False)

    def context_tokens(self) -> List[int]:
        """Tokens that must be in cache to resume decoding (recompute)."""
        return list(self.prompt) + list(self.out)

    @property
    def lane(self) -> str:
        """Trace lane for this sequence's engine-side spans (a hedged
        request's two sids give two parallel lanes in one trace)."""
        return f"engine-sid{self.sid}"


class _EngineBase:
    """Shared continuous-batching scaffolding: submission bookkeeping, the
    stop conditions (applied identically at admission and after decode so
    the dense/paged engines stay token-for-token interchangeable), prefill
    length bucketing with its compile-event accounting, bucket pre-warming,
    the chunked-prefill (PREFILLING) state machine with its per-step token
    budget, and the synchronous generate loop. Subclasses provide ``step()``
    / ``_prewarm_shape()`` / ``_run_chunk_device()`` / ``_release_slot()``
    and set ``_max_new`` / ``_eos`` / ``_len_cap`` / ``_bucket_unit`` /
    ``_bucket_on`` / ``_chunk_tokens`` / ``_step_budget`` plus the per-slot
    chunk state (``_chunking`` / ``_chunk_pos`` / ``_chunk_ctx`` /
    ``_chunk_carry`` / ``_stamp``) and the reentrant ``lock`` (see the
    module docstring for the thread-safety contract)."""

    name = "engine"      # labels this engine's step spans; EngineLoop sets it

    def free_slots(self) -> int:
        return sum(1 for s in self.slot_seq if s is None)

    def submit(self, prompt: List[int], trace=None) -> int:
        # stamped before the wait: step() holds the lock for a whole step
        submit_t = time.monotonic()
        waiting = span("serve.engine.lock_wait").__enter__()
        with self.lock:
            waiting.__exit__()
            seq = Sequence(self._sid, list(prompt), submit_t=submit_t, trace=trace)
            self._sid += 1
            self.waiting.append(seq)
            if trace is not None:
                trace.event("engine_submit", lane=seq.lane, t=seq.submit_t,
                            sid=seq.sid, prompt_tokens=len(prompt))
            return seq.sid

    # -- bucketed prefill shapes ---------------------------------------------
    def _bucket_len(self, n: int, cap: int = 0) -> int:
        if not self._bucket_on:
            return n
        return bucket_tokens(n, self._bucket_unit, cap or self._len_cap)

    def _pad_context(self, ctx_toks: List[int], cap: int = 0):
        """Right-pad a context to its bucket (capped at ``cap`` — the chunk
        size for chunked prefill, the length cap otherwise); returns
        (tokens, n_valid, Lp, fresh) where ``fresh`` marks a shape not
        executed before — the caller wall-times that prefill into the
        compile-cost EMA. Records the shape so ``compile_events`` tracks
        distinct prefill compilations (jit caches per shape, so #shapes ==
        #compiles)."""
        n = len(ctx_toks)
        Lp = self._bucket_len(n, cap)
        fresh = Lp not in self._prefill_shapes
        self._prefill_shapes.add(Lp)
        toks = np.zeros(Lp, np.int32)
        toks[:n] = ctx_toks
        return toks, n, Lp, fresh

    def _note_compile(self, dt_s: float) -> None:
        """Fold one measured compile wall time into the EMA the placer reads
        (``compile_ema_s`` in ``capacity_now()``)."""
        prev = self._compile_ema_s
        self._compile_ema_s = dt_s if prev is None else 0.5 * prev + 0.5 * dt_s

    @property
    def compile_ema_s(self) -> float:
        """EMA of prefill-compile wall time; 0.0 until a compile is measured
        (consumers treat 0 as unknown)."""
        return self._compile_ema_s or 0.0

    @property
    def compile_events(self) -> int:
        """Distinct prefill shapes executed so far — the engine's warm-up
        state. Placer/telemetry read it via ``capacity_now()``."""
        return len(self._prefill_shapes)

    @property
    def _shape_cap(self) -> int:
        """Largest prefill shape this engine executes: the chunk size when
        chunked prefill is on (whole prompts are absorbed chunk by chunk),
        the length cap otherwise."""
        return self._chunk_tokens or self._len_cap

    @property
    def total_buckets(self) -> int:
        """How many distinct prefill shapes bucketing can produce (0 when
        bucketing is off — the shape count is then unbounded, so no warm
        fraction exists). With chunked prefill on, shapes are capped at the
        chunk size, so the bound only shrinks."""
        return num_buckets(self._bucket_unit, self._shape_cap) if self._bucket_on else 0

    @property
    def step_budget(self) -> int:
        """Per-step token budget shared by the decode batch and prefill
        work. Auto (config 0): two chunks' worth when chunked prefill is on
        (one chunk + headroom keeps decode gaps bounded at ~one chunk), one
        max-length prefill's worth otherwise (caps back-to-back full
        prefills per step without deferring moderate admissions)."""
        if self._step_budget:
            return self._step_budget
        return 2 * self._chunk_tokens if self._chunk_tokens else self._len_cap

    # -- chunked prefill state machine -----------------------------------------
    def _resolve_chunking(self, cfg, chunk_tokens: int, unit: int, cap: int,
                          require_divisible: bool) -> int:
        """Validate + snap the chunk size: a positive multiple of the bucket
        unit/page size, capped at the length cap. The dense engine requires
        the cap to be a chunk multiple (its stripe writes would otherwise
        clamp at the edge); the paged engine's tail overruns are absorbed by
        the null page. Chunked prefill is decoder-only."""
        if not chunk_tokens:
            return 0
        if getattr(cfg, "encoder", None) is not None:
            raise ValueError("chunked prefill is decoder-only (no enc-dec support)")
        ct = min(-(-chunk_tokens // unit) * unit, cap)
        if require_divisible and cap % ct != 0:
            raise ValueError(
                f"chunk_tokens={ct} must divide the length cap {cap} "
                f"(dense stripe writes cannot overrun the cache edge)"
            )
        return ct

    def _init_chunk_slots(self, B: int) -> None:
        """Per-slot PREFILLING state: chunk cursor, the full context being
        absorbed, the off-cache recurrent carry (single owner for the field
        group — both engines init and clear through here)."""
        self._chunking = [False] * B
        self._chunk_pos = np.zeros(B, np.int32)
        self._chunk_ctx = [None] * B
        self._chunk_carry = [None] * B

    def _clear_chunk_slot(self, slot: int) -> None:
        self._chunking[slot] = False
        self._chunk_pos[slot] = 0
        self._chunk_ctx[slot] = None
        self._chunk_carry[slot] = None

    def _begin_chunked(self, slot: int, seq: Sequence, start: int = 0) -> None:
        """Move ``seq`` into ``slot`` in the PREFILLING state: no device work
        happens here — the budget-gated chunk phase (``_run_chunks``) absorbs
        the context over the following steps. ``slot_len`` tracks the chunk
        cursor so the batched decode's garbage write for this slot always
        lands on a position the next chunk (or the first decode) rewrites.

        ``start`` > 0 (paged engine, prefix-cache hit) begins the cursor at
        the match boundary: positions below ``start`` are already in cache
        on pages SHARED with the prefix tree, so no chunk may rewrite them —
        and since ``start`` is page-aligned, the garbage decode write at the
        cursor lands on the sequence's first exclusively-owned page."""
        self.slot_seq[slot] = seq
        self.slot_len[slot] = start
        if seq.admit_t is None:
            seq.admit_t = time.monotonic()
        self._chunking[slot] = True
        self._chunk_pos[slot] = start
        self._chunk_ctx[slot] = seq.context_tokens()
        self._chunk_carry[slot] = self.model.init_chunk_state()
        self._stamp[slot] = self._stamp_next
        self._stamp_next += 1
        if seq.trace is not None:
            seq.trace.event(
                "admitted", lane=seq.lane, slot=slot, chunked=True,
                ctx_tokens=len(self._chunk_ctx[slot]), resume=seq.preemptions,
                cached_tokens=start,
            )

    def _prefilling_slots(self) -> List[int]:
        """PREFILLING slots in admission order (FIFO chunk service)."""
        return sorted(
            (i for i in range(len(self.slot_seq)) if self._chunking[i]),
            key=lambda i: self._stamp[i],
        )

    @property
    def _chunk_unit(self) -> int:
        """Tokens absorbed per chunk step: the chunk size, or the full length
        cap when chunked prefill is off but the chunk machinery still runs
        (paged engine with the prefix cache on — a whole unmatched suffix is
        then one "chunk")."""
        return self._chunk_tokens or self._len_cap

    def _next_chunk_cost(self, slot: int) -> int:
        """Padded length of the slot's next chunk (budget accounting)."""
        remaining = len(self._chunk_ctx[slot]) - int(self._chunk_pos[slot])
        return self._bucket_len(min(remaining, self._chunk_unit), self._chunk_unit)

    def _run_chunks(self, spent: int, budget: int) -> int:
        """Budget-gated chunk phase: serve PREFILLING slots in admission
        order, at most ``budget - spent`` further prefill tokens this step —
        but ALWAYS at least one chunk when any slot is mid-prefill, so
        prefill can never starve behind a saturated decode batch (and a
        too-small budget degrades to one chunk per step, the design point:
        decode gaps bounded at ~one chunk of work)."""
        first = True
        for slot in self._prefilling_slots():
            while self._chunking[slot]:
                cost = self._next_chunk_cost(slot)
                if not first and spent + cost > budget:
                    return spent
                spent += cost
                self._chunk_step(slot)
                first = False
        return spent

    def _chunk_step(self, slot: int) -> None:
        """Run ONE prefill chunk for a PREFILLING slot. The final chunk
        installs the recurrent carry into the decode cache, emits the
        prefill token (from the chunk's last valid position — identical to
        the whole-prompt prefill's token) and transitions the slot to
        decoding, applying the same stop conditions as unchunked
        admission."""
        seq = self.slot_seq[slot]
        ctx = self._chunk_ctx[slot]
        pos = int(self._chunk_pos[slot])
        piece = ctx[pos : pos + self._chunk_unit]
        toks, n, _, fresh = self._pad_context(piece, cap=self._chunk_unit)
        with span("serve.engine.prefill", seq.trace, lane=seq.lane, tokens=n,
                  padded=len(toks), offset=pos, fresh_compile=fresh):
            t0 = time.perf_counter()
            nxt = self._run_chunk_device(slot, toks, pos, n)
            if fresh:
                with span("serve.engine.device_wait"):
                    jax.block_until_ready(nxt)
                self._note_compile(time.perf_counter() - t0)
            new_pos = pos + n
            self._chunk_pos[slot] = new_pos
            self.slot_len[slot] = new_pos
            if new_pos < len(ctx):
                return                                # mid-prefill: token is garbage
            self.cache = self._install_carry(
                self.cache, self._chunk_carry[slot], jnp.asarray(slot))
            with span("serve.engine.device_wait"):
                tok = int(nxt)
        self._clear_chunk_slot(slot)              # PREFILLING -> decoding
        self._last[slot] = tok
        seq.out.append(tok)
        seq.token_times.append(time.monotonic())  # the prefill-emitted token
        self.tokens_emitted += 1
        if self._stop_hit(seq, tok, int(self.slot_len[slot])):
            # the prefill-emitted token can already cross a stop condition
            seq.done = True
            self._just_finished.append(seq)
            self._release_slot(slot)

    def prefill_backlog_tokens(self) -> int:
        """Tokens of prompt context not yet absorbed: remaining chunk work
        across PREFILLING slots plus queued (unadmitted) contexts. Lock-free
        and possibly stale, like every capacity gauge."""
        backlog = 0
        for i in range(len(self.slot_seq)):
            ctx = self._chunk_ctx[i]             # snapshot: the stepper may
            if ctx is None:                      # null it out concurrently
                continue
            backlog += max(0, len(ctx) - int(self._chunk_pos[i]))
        try:
            backlog += sum(len(s.prompt) + len(s.out) for s in list(self.waiting))
        except RuntimeError:
            pass          # deque mutated mid-iteration: skip the stale part
        return backlog

    def prewarm(self, buckets: Optional[List[int]] = None) -> List[int]:
        """Compile the prefill path for the given bucket lengths (default:
        every bucket this engine can produce) before traffic arrives, so no
        real request pays an XLA compile. Each shape compiles at most once
        and counts toward ``compile_events``. Returns the lengths compiled.

        The warm-up prefill runs a zero prompt through an idle slot (paged:
        an all-null block-table row, so K/V writes land on the reserved
        garbage page); no live sequence state is disturbed. When every slot
        is busy the remaining shapes are skipped — prewarm is a startup
        API, not a mid-traffic one."""
        with self.lock:
            if buckets is None:
                if not self._bucket_on:
                    return []
                buckets = bucket_lengths(self._bucket_unit, self._shape_cap)
            warmed: List[int] = []
            for Lp in sorted({int(b) for b in buckets}):
                Lp = self._bucket_len(max(1, Lp), self._shape_cap)  # snap to a real bucket
                if Lp in self._prefill_shapes:
                    continue
                slot = next((i for i, s in enumerate(self.slot_seq) if s is None), None)
                if slot is None:
                    break
                t0 = time.perf_counter()
                self._prewarm_shape(Lp, slot)
                self._note_compile(time.perf_counter() - t0)
                self._prefill_shapes.add(Lp)
                warmed.append(Lp)
            return warmed

    def _stop_hit(self, seq: Sequence, tok: int, cache_len: int) -> bool:
        return (
            len(seq.out) >= self._max_new
            or tok == self._eos
            or cache_len >= self._len_cap - 1
        )

    # -- speculative decoding (n-gram / prompt-lookup proposer) -----------------
    def _resolve_spec(self, cfg, spec_tokens: int) -> int:
        """Validate the speculative-decoding config. The verify pass re-runs
        k+1 positions statelessly against the KV cache — recurrent mixers
        carry per-slot state a rolled-back verify cannot restore, so (like
        the prefix cache) speculation is attention-only."""
        if not spec_tokens:
            return 0
        if getattr(cfg, "encoder", None) is not None or any(
            kind != "attn" for kind in cfg.block_pattern
        ):
            raise ValueError(
                "spec_tokens requires an attention-only decoder: the verify "
                "pass replays positions statelessly, which recurrent mixers "
                "(mamba/xlstm) and enc-dec models cannot"
            )
        return spec_tokens

    def _init_spec(self) -> None:
        """Speculation + throughput accounting, read lock-free by
        ``capacity_now()`` and drained per step by ``EngineLoop``:
        ``tokens_emitted`` counts EVERY emitted token (prefill-emitted,
        decoded, speculative) so tokens-per-step is a pure delta;
        ``spec_runs`` holds this step's accepted-run lengths (proposal
        tokens accepted per verify, cleared at step start); the cumulative
        ``spec_proposed`` / ``spec_accepted`` give the lifetime acceptance
        rate."""
        self.tokens_emitted = 0
        self.spec_runs: List[int] = []
        self.spec_proposed = 0
        self.spec_accepted = 0

    def _propose(self, seq: Sequence) -> Optional[List[int]]:
        """Prompt-lookup proposal for one decoding slot: match the context's
        last ``spec_ngram`` tokens against their most recent earlier
        occurrence and propose the continuation, padded to ``spec_tokens``
        with 0s (padding is safe — acceptance only ever keeps tokens that
        EQUAL the model's greedy choice, wherever the proposal came from).
        Deterministic in the context alone, so a preempted-and-resumed
        sequence re-proposes identically. Returns None when no match — the
        slot degrades to plain batched decode this step."""
        k, n = self._spec_tokens, self._spec_ngram
        ctx = seq.context_tokens()
        L = len(ctx)
        if L < n + 1:
            return None
        tail = ctx[-n:]
        for i in range(L - n - 1, -1, -1):
            if ctx[i : i + n] == tail:
                cont = ctx[i + n : i + n + k]
                return cont + [0] * (k - len(cont))
        return None

    def _accept_verified(self, slot: int, seq: Sequence, proposal: List[int],
                         toks, k_eff: int):
        """Accept the longest matching run of a verify pass and advance the
        slot's write-head. ``toks[j]`` is the model's greedy token after
        verify position offset+j (position 0 re-ran the pending last token,
        1..k_eff the proposal) — token j+1 is trustworthy iff every proposal
        token before it matched the greedy chain, so we emit tokens until
        the first mismatch, always at least one (the plain-decode token) and
        at most k_eff+1 (all proposals plus the free bonus token). The final
        emitted token becomes the slot's new pending ``_last`` — NOT yet in
        cache, exactly the batched-decode convention — which is what makes
        the cache provably valid: positions L..L+m-1 hold the previous
        pending token plus accepted proposals, all equal to the greedy
        stream. Stop conditions apply per accepted token (EOS mid-run ends
        the run). Returns (m, done): tokens emitted, stop hit."""
        L0 = int(self.slot_len[slot])
        m = 0
        done = False
        tok_t = time.monotonic()          # one stamp per verify pass
        while True:
            tok = int(toks[m])
            m += 1
            seq.out.append(tok)
            seq.token_times.append(tok_t)
            self._last[slot] = tok
            self.tokens_emitted += 1
            if self._stop_hit(seq, tok, L0 + m):
                done = True
                break
            if m > k_eff or proposal[m - 1] != tok:
                break
        self.slot_len[slot] = L0 + m
        accepted = m - 1
        self.spec_proposed += k_eff
        self.spec_accepted += accepted
        self.spec_runs.append(accepted)
        if seq.trace is not None:
            seq.trace.event(
                "spec_accept" if accepted else "spec_reject", lane=seq.lane,
                slot=slot, proposed=k_eff, accepted=accepted,
            )
        return m, done

    def generate(self, prompts: List[List[int]], max_steps: int = 10000) -> List[Sequence]:
        """Synchronous convenience AND the serialized benchmark baseline:
        runs until all prompts finish while holding the engine lock
        end-to-end, so concurrent callers serialize whole generations. The
        serving path is ``serving.scheduler.EngineLoop`` — submit into its
        shared step loop and concurrent requests interleave in one decode
        batch instead (benchmarks/continuous_batching.py measures the gap)."""
        with self.lock:
            done: List[Sequence] = []
            for p in prompts:
                self.submit(p)
            for _ in range(max_steps):
                done.extend(self.step())
                if not self.waiting and all(s is None for s in self.slot_seq):
                    break
            return sorted(done, key=lambda s: s.sid)


class InferenceEngine(_EngineBase):
    def __init__(self, cfg, ecfg: EngineConfig, ctx=None, params=None, seed: int = 0):
        cfg = _apply_cache_dtype(cfg, ecfg.cache_dtype)
        self.cfg = cfg
        self.ecfg = ecfg
        self.ctx = ctx
        self.model = get_model(cfg)
        self.params = params if params is not None else self.model.init(jax.random.PRNGKey(seed))
        self._max_new, self._eos, self._len_cap = ecfg.max_new_tokens, ecfg.eos_id, ecfg.max_len
        self._bucket_unit, self._bucket_on = ecfg.bucket_unit, ecfg.bucket_prefill
        self._chunk_tokens = self._resolve_chunking(
            cfg, ecfg.chunk_tokens, ecfg.bucket_unit, ecfg.max_len, require_divisible=True
        )
        self._spec_tokens = self._resolve_spec(cfg, ecfg.spec_tokens)
        self._spec_ngram = max(1, ecfg.spec_ngram)
        self._init_spec()
        self._step_budget = ecfg.step_token_budget
        self._prefill_shapes = set()
        self._compile_ema_s: Optional[float] = None
        self.lock = threading.RLock()  # locklint: blocking-ok one stepper owns the donated buffers
        B, L = ecfg.max_slots, ecfg.max_len
        self.cache = self.model.init_cache(B, L)
        self._kv_bytes_per_token = _kv_bytes_per_token(cfg, self.cache, B * L)
        self.slot_len = np.zeros(B, np.int32)        # tokens in cache per slot
        self.slot_seq: List[Optional[Sequence]] = [None] * B
        self.waiting: Deque[Sequence] = deque()
        self._sid = 0
        self._just_finished: List[Sequence] = []
        self._init_chunk_slots(B)
        self._stamp = np.zeros(B, np.int64)   # admission order (chunk FIFO)
        self._stamp_next = 1
        self._build()

    # -- jitted steps ---------------------------------------------------------
    def _build(self):
        model, ctx = self.model, self.ctx
        B, L = self.ecfg.max_slots, self.ecfg.max_len

        @jax.named_scope("prefill")
        def prefill_slot(params, cache, tokens, slot, n_valid):
            """Prefill a single slot with a right-padded prompt of length L_p;
            positions >= n_valid are bucket padding, masked out of every
            stateful update and of the emitted logits."""
            tok2 = tokens[None, :]                                   # (1, Lp)
            next_tok, mini = model.prefill(
                ctx, params, {"tokens": tok2, "n_valid": n_valid[None]}, cap=L
            )

            def write(full, part):
                # every cache leaf is (n_sb, B, ...); part has B=1 at axis 1
                return jax.lax.dynamic_update_slice_in_dim(
                    full, part.astype(full.dtype), slot, axis=1
                )

            cache = jax.tree.map(write, cache, mini)
            return next_tok[0], cache

        @jax.named_scope("decode_all")
        def decode_all(params, cache, last_tokens, lens):
            """One decode step for every slot; per-slot lengths drive the
            cache writes, masks and positions."""
            batch = {"token": last_tokens[:, None], "cache_index": jnp.max(lens), "lengths": lens}
            return model.decode(ctx, params, cache, batch)

        @jax.named_scope("prefill_chunk")
        def prefill_chunk_slot(params, cache, tokens, slot, offset, n_valid, carry):
            """One chunked-prefill step against the slot's stripe: slice the
            mini cache out, run the resumable chunk (K/V written at
            ``offset``, recurrent state rides ``carry``), write the stripe
            back. Compiles once per chunk bucket — offset/slot/n_valid are
            all dynamic."""
            mini = jax.tree.map(
                lambda full: jax.lax.dynamic_slice_in_dim(full, slot, 1, axis=1), cache
            )
            batch = {"tokens": tokens[None, :], "n_valid": n_valid[None], "offset": offset}
            nxt, mini, carry = model.prefill_chunk(ctx, params, batch, mini, carry)

            def write(full, part):
                return jax.lax.dynamic_update_slice_in_dim(
                    full, part.astype(full.dtype), slot, axis=1
                )

            return nxt[0], jax.tree.map(write, cache, mini), carry

        @jax.named_scope("verify")
        def verify_slot(params, cache, tokens, slot, offset):
            """Speculative verify against the slot's stripe: slice the mini
            cache out, write all k+1 verify tokens at ``offset`` and read
            the greedy token at EVERY position in one pass (a verify step is
            a chunk — same stripe write + absolute-position masking as
            ``prefill_chunk_slot``, no recurrent carry). Compiles once per
            k_eff (at most spec_tokens shapes)."""
            mini = jax.tree.map(
                lambda full: jax.lax.dynamic_slice_in_dim(full, slot, 1, axis=1), cache
            )
            toks, mini = model.verify(
                ctx, params, {"tokens": tokens[None, :], "offset": offset}, mini
            )

            def write(full, part):
                return jax.lax.dynamic_update_slice_in_dim(
                    full, part.astype(full.dtype), slot, axis=1
                )

            return toks[0], jax.tree.map(write, cache, mini)

        self._prefill = jax.jit(prefill_slot)
        self._decode = jax.jit(decode_all, donate_argnums=(1,))
        self._prefill_chunk = jax.jit(prefill_chunk_slot, donate_argnums=(1, 6))
        self._verify = jax.jit(verify_slot, donate_argnums=(1,))
        self._install_carry = jax.jit(model.install_chunk_state, donate_argnums=(0,))
        self._last = np.zeros(B, np.int32)

    def _run_chunk_device(self, slot: int, toks, offset: int, n: int):
        nxt, self.cache, self._chunk_carry[slot] = self._prefill_chunk(
            self.params,
            self.cache,
            jnp.asarray(toks),
            jnp.asarray(slot),
            jnp.asarray(offset),
            jnp.asarray(n),
            self._chunk_carry[slot],
        )
        return nxt

    # -- capacity telemetry ------------------------------------------------------
    def capacity_now(self) -> Dict[str, int]:
        """Live capacity snapshot for the placer (core/telemetry.py gauge).
        The dense engine reserves max_len cache tokens per admitted slot."""
        free = self.free_slots()
        return {
            "free_slots": free,
            "num_slots": self.ecfg.max_slots,
            "free_cache_tokens": free * self.ecfg.max_len,
            "cache_tokens": self.ecfg.max_slots * self.ecfg.max_len,
            "kv_cache_dtype": _kv_dtype_name(self.cfg),
            "kv_bytes_per_token": self._kv_bytes_per_token,
            "waiting": len(self.waiting),
            "compile_events": self.compile_events,
            "total_buckets": self.total_buckets,
            "compile_ema_s": self.compile_ema_s,
            "prefilling_slots": sum(self._chunking),
            "prefill_backlog_tokens": self.prefill_backlog_tokens(),
            "chunk_tokens": self._chunk_tokens,
            "spec_tokens": self._spec_tokens,
            "tokens_emitted": self.tokens_emitted,
            "spec_proposed": self.spec_proposed,
            "spec_accepted": self.spec_accepted,
        }

    def admission_capacity(self, est_tokens: int = 0) -> int:
        """How many more requests this engine can admit right now."""
        return self.free_slots()

    # -- public API -------------------------------------------------------------
    def _prewarm_shape(self, Lp: int, slot: int) -> None:
        """Compile (and discard) a prefill at shape ``Lp``. With chunked
        prefill on, traffic runs the CHUNK path, so that is what gets
        compiled — its stray writes land in a free slot's stripe, which is
        causally masked for any future occupant. The plain dense prefill
        does not donate its cache argument, so dropping the returned cache
        leaves engine state untouched."""
        toks = np.zeros(Lp, np.int32)
        if self._chunk_tokens:
            _, self.cache, _ = self._prefill_chunk(
                self.params, self.cache, jnp.asarray(toks), jnp.asarray(slot),
                jnp.asarray(0), jnp.asarray(1), self.model.init_chunk_state(),
            )
            return
        self._prefill(
            self.params, self.cache, jnp.asarray(toks), jnp.asarray(slot), jnp.asarray(1)
        )

    def _release_slot(self, slot: int) -> None:
        self.slot_seq[slot] = None
        self.slot_len[slot] = 0
        self._clear_chunk_slot(slot)
        self._stamp[slot] = 0

    def _admit(self, spent: int = 0, budget: int = 0) -> int:
        """Budget-gated admission. Chunked: free slots become PREFILLING at
        no device cost (the chunk phase spends the budget). Unchunked: the
        FIRST prefill of a step is always admitted (progress guarantee — a
        single long prompt must not starve behind a busy decode batch), but
        every further one must fit ``budget`` — a queue burst can no longer
        run up to max_slots full back-to-back device prefills in one
        iteration while every active sequence stalls. Returns the updated
        spend. (Called bare — budget 0 — it resolves ``step_budget``.)"""
        budget = budget or self.step_budget
        admitted = False
        for i in range(self.ecfg.max_slots):
            if self.slot_seq[i] is not None or not self.waiting:
                continue
            if self._chunk_tokens:
                self._begin_chunked(i, self.waiting.popleft())
                continue
            Lp = self._bucket_len(len(self.waiting[0].prompt))
            if admitted and spent + Lp > budget:
                break                        # over budget: stays queued
            seq = self.waiting.popleft()
            toks, n, _, fresh = self._pad_context(seq.prompt)
            with span("serve.engine.prefill", seq.trace, lane=seq.lane, tokens=n,
                      padded=len(toks), offset=0, slot=i, fresh_compile=fresh):
                t0 = time.perf_counter()
                nxt, self.cache = self._prefill(
                    self.params, self.cache, jnp.asarray(toks), jnp.asarray(i), jnp.asarray(n)
                )
                if fresh:
                    with span("serve.engine.device_wait"):
                        jax.block_until_ready(nxt)
                    self._note_compile(time.perf_counter() - t0)
                with span("serve.engine.device_wait"):
                    nxt = int(nxt)
            spent += Lp
            admitted = True
            self.slot_seq[i] = seq
            self.slot_len[i] = n
            if seq.admit_t is None:
                seq.admit_t = time.monotonic()
            self._last[i] = nxt
            seq.out.append(nxt)
            seq.token_times.append(time.monotonic())
            self.tokens_emitted += 1
            if self._stop_hit(seq, nxt, int(self.slot_len[i])):
                # the prefill-emitted token can already cross a stop
                # condition (max_new_tokens=1, or greedy EOS on prompt)
                seq.done = True
                self._just_finished.append(seq)
                self._release_slot(i)
        return spent

    def _spec_phase(self, active: List[int], spent: int, budget: int):
        """Speculate on decoding slots at the decode frontier: per slot with
        a proposal and budget headroom, one verify pass (k_eff+1 positions)
        replaces this step's plain decode token with the accepted run.
        Rollback is trivial for the dense engine — the write-head
        (``slot_len``) simply stops at the accepted length; rejected stripe
        positions are hidden by the length masks and overwritten by the
        next write at that position. Returns (speculated slots, spent)."""
        sped: List[int] = []
        for slot in active:
            seq = self.slot_seq[slot]
            L = int(self.slot_len[slot])
            k_eff = min(self._spec_tokens, self._len_cap - 1 - L)
            if k_eff < 1 or spent + k_eff > budget:
                continue
            proposal = self._propose(seq)
            if proposal is None:
                continue
            toks, self.cache = self._verify(
                self.params,
                self.cache,
                jnp.asarray(
                    np.asarray([int(self._last[slot])] + proposal[:k_eff], np.int32)
                ),
                jnp.asarray(slot),
                jnp.asarray(L),
            )
            spent += k_eff
            with span("serve.engine.device_wait"):
                toks = np.asarray(toks)
            _, done = self._accept_verified(slot, seq, proposal, toks, k_eff)
            sped.append(slot)
            if done:
                seq.done = True
                self._just_finished.append(seq)
                self._release_slot(slot)
        return sped, spent

    def step(self) -> List[Sequence]:
        """Admit (budget-gated) + chunk work + speculation + one decode
        step; returns sequences finished this step. PREFILLING slots are
        excluded from the host-side decode bookkeeping — the batched device
        decode still sweeps them, but its writes land on the chunk cursor
        (rewritten by the next chunk) and the authoritative recurrent state
        rides the off-cache carry until install. Speculated slots are
        likewise excluded: the sweep's write of their pending token at the
        new write-head is idempotent with the next step's decode write
        (same token, same position), so only the host bookkeeping skips
        them."""
        with self.lock, span("serve.engine.step", engine=self.name):
            budget = self.step_budget
            self.spec_runs = []
            spent = sum(
                1 for i, s in enumerate(self.slot_seq)
                if s is not None and not self._chunking[i]
            )
            with span("serve.engine.admit") as sp:
                queued = len(self.waiting)
                spent = self._admit(spent, budget)
                sp.set(admitted=queued - len(self.waiting))
            if self._chunk_tokens:
                spent = self._run_chunks(spent, budget)
            active = [
                i for i in range(self.ecfg.max_slots)
                if self.slot_seq[i] is not None and not self._chunking[i]
            ]
            if self._spec_tokens and active:
                with span("serve.engine.spec"):
                    sped, spent = self._spec_phase(active, spent, budget)
                active = [i for i in active if i not in set(sped)]
            finished, self._just_finished = self._just_finished, []
            if active:
                with span("serve.engine.decode", rows=self.ecfg.max_slots, live=len(active)):
                    lens = jnp.asarray(self.slot_len)
                    nxt, self.cache = self._decode(
                        self.params, self.cache, jnp.asarray(self._last), lens
                    )
                with span("serve.engine.device_wait"):
                    nxt = np.asarray(nxt)
                tok_t = time.monotonic()      # one stamp per batched decode step
                for i in active:
                    seq = self.slot_seq[i]
                    self.slot_len[i] += 1
                    self._last[i] = nxt[i]
                    seq.out.append(int(nxt[i]))
                    seq.token_times.append(tok_t)
                    self.tokens_emitted += 1
                    if self._stop_hit(seq, int(nxt[i]), int(self.slot_len[i])):
                        seq.done = True
                        finished.append(seq)
                        self._release_slot(i)
            return finished


# ---------------------------------------------------------------------------
# Paged engine (v2)
# ---------------------------------------------------------------------------


@dataclass
class PagedEngineConfig:
    page_size: int = 16
    num_pages: int = 64          # pool size, incl. the reserved null page 0
    max_slots: int = 8           # decode batch width
    max_seq_len: int = 256       # block-table width = ceil(max_seq_len / page_size)
    max_new_tokens: int = 32
    eos_id: int = -1
    bucket_prefill: bool = True  # pad prefill to power-of-two page buckets
    chunk_tokens: int = 0        # >0: chunked prefill, tokens per chunk
                                 # (snapped to a page multiple)
    step_token_budget: int = 0   # per-step prefill+decode token budget
                                 # (0 = auto: 2*chunk_tokens chunked, cap not)
    prefix_cache: bool = False   # cross-request prefix cache: finished
                                 # sequences retire their pages into a radix
                                 # tree; new prompts skip prefill for cached
                                 # prefixes (attention-only decoders). Off by
                                 # default: release-to-cache retains pages, a
                                 # semantic change callers must opt into.
    spec_tokens: int = 0         # >0: n-gram speculative decoding, proposal
                                 # tokens per slot per step (attention-only
                                 # decoders; greedy-token-identical)
    spec_ngram: int = 3          # prompt-lookup match length for the proposer
    cache_dtype: str = ""        # KV-pool storage: "" inherit model config,
                                 # "f32" | "bf16" | "int8" (int8 = quantized
                                 # pool + per-(page-slot, head) scales)
    chained_tables: bool = False # two-level block tables: per-slot first-level
                                 # rows of table-page ids resolve through a
                                 # shared second-level pool — lifts the
                                 # num_pages >= table_width coupling, so
                                 # max_seq_len can exceed what a flat row
                                 # over this pool could address
    table_page_entries: int = 0  # chained: physical pages per second-level
                                 # row (0 = page_size)

    @property
    def table_width(self) -> int:
        return -(-self.max_seq_len // self.page_size)

    @property
    def cache_tokens(self) -> int:
        """Usable cache budget in tokens (null page excluded)."""
        return (self.num_pages - 1) * self.page_size


class PagedInferenceEngine(_EngineBase):
    """Continuous batching over a paged KV cache.

    Differences from the dense engine:
      * a sequence holds ceil(len/page_size) pages, not a max_len stripe —
        short sequences leave the rest of the pool for others;
      * admission is gated on the free list (pages for prompt + 1 token);
      * when a growing sequence needs a page and the pool is dry, the newest
        admitted sequence is preempted back to the waiting queue; on
        re-admission its full context (prompt + generated tokens) is
        re-prefilled, which under greedy decoding reproduces the identical
        continuation;
      * ``fork()`` clones a running sequence sharing its full prefix pages
        (ref-counted) — only the trailing partial page is copied.
    """

    def __init__(self, cfg, pcfg: PagedEngineConfig, ctx=None, params=None, seed: int = 0):
        cfg = _apply_cache_dtype(cfg, pcfg.cache_dtype)
        self.cfg = cfg
        self.pcfg = pcfg
        self.ctx = ctx
        if not pcfg.chained_tables and pcfg.num_pages - 1 < pcfg.table_width:
            # one max-length sequence must always fit, else admission can
            # stall forever and the sole active sequence can never grow.
            # Chained tables drop this coupling: the admission cap is
            # re-derived from pool capacity instead (see _len_cap below).
            raise ValueError(
                f"num_pages={pcfg.num_pages} cannot hold one max_seq_len={pcfg.max_seq_len} "
                f"sequence ({pcfg.table_width} pages + reserved null page)"
            )
        if pcfg.prefix_cache and (
            any(kind != "attn" for kind in cfg.block_pattern)
            or getattr(cfg, "encoder", None) is not None
        ):
            raise ValueError(
                "prefix_cache requires an attention-only decoder: recurrent "
                "mixers carry per-slot state that cached pages cannot restore"
            )
        self.model = get_model(cfg)
        self.params = params if params is not None else self.model.init(jax.random.PRNGKey(seed))
        self._max_new, self._eos = pcfg.max_new_tokens, pcfg.eos_id
        # Chained tables decouple max_seq_len from the pool: the admission
        # length cap is then whatever the POOL can hold (one sequence can
        # never exceed cache_tokens without self-deadlocking on growth) —
        # with flat tables the constructor check above already guarantees
        # max_seq_len <= cache_tokens.
        self._len_cap = (
            min(pcfg.max_seq_len, pcfg.cache_tokens)
            if pcfg.chained_tables else pcfg.max_seq_len
        )
        self._bucket_unit, self._bucket_on = pcfg.page_size, pcfg.bucket_prefill
        self._chunk_tokens = self._resolve_chunking(
            cfg, pcfg.chunk_tokens, pcfg.page_size, self._len_cap,
            require_divisible=False,   # tail overruns land on the null page
        )
        self._spec_tokens = self._resolve_spec(cfg, pcfg.spec_tokens)
        self._spec_ngram = max(1, pcfg.spec_ngram)
        self._init_spec()
        self._step_budget = pcfg.step_token_budget
        self._prefill_shapes = set()
        self._compile_ema_s: Optional[float] = None
        self.lock = threading.RLock()  # locklint: blocking-ok one stepper owns the donated buffers
        B = pcfg.max_slots
        if pcfg.chained_tables:
            # Second-level geometry: a sequence can hold at most
            # min(table_width, num_pages - 1) data pages, so the flat row a
            # chain encodes is that many entries rounded up to whole table
            # pages. The flat ``block_tab`` is STILL maintained (write-side
            # paths — prefill scatter, context gather, verify — take
            # host-flattened rows); only the batched decode walks the chain.
            tpp = pcfg.table_page_entries or pcfg.page_size
            max_pages = min(pcfg.table_width, pcfg.num_pages - 1)
            self.chain: Optional[ChainedTables] = ChainedTables(B, -(-max_pages // tpp), tpp)
            self._row_width = self.chain.width1 * tpp
        else:
            self.chain = None
            self._row_width = pcfg.table_width
        self.cache = self.model.init_paged_cache(B, pcfg.num_pages, pcfg.page_size)
        self._kv_bytes_per_token = _kv_bytes_per_token(
            cfg, self.cache, pcfg.num_pages * pcfg.page_size
        )
        self.allocator = BlockAllocator(pcfg.num_pages, pcfg.page_size)
        self.prefix_cache: Optional[PrefixCache] = (
            PrefixCache(self.allocator, pcfg.page_size) if pcfg.prefix_cache else None
        )
        self._cache_nodes: List[Optional[object]] = [None] * B  # pinned tree path per slot
        self.tables: List[Optional[PageTable]] = [None] * B
        self.slot_len = np.zeros(B, np.int32)
        self.slot_seq: List[Optional[Sequence]] = [None] * B
        self.block_tab = np.full((B, self._row_width), NULL_PAGE, np.int32)
        self.waiting: Deque[Sequence] = deque()
        self.preemptions = 0
        self.peak_active = 0
        self._sid = 0
        self._stamp = np.zeros(B, np.int64)   # admission order, newest = max
        self._stamp_next = 1
        self._just_finished: List[Sequence] = []
        self._init_chunk_slots(B)
        self._build()

    # -- jitted steps ---------------------------------------------------------
    def _build(self):
        model, ctx, cfg = self.model, self.ctx, self.cfg

        @jax.named_scope("prefill")
        def prefill_paged(params, cache, tokens, tab_row, slot, n_valid):
            """Prefill one bucket-padded sequence through the model's paged
            path: attention K/V scatter through the block-table row inside
            each layer (pads land on the null page), recurrent mixers run
            from zero state into ``slot`` — no dense staging cache."""
            batch = {
                "tokens": tokens[None, :],                            # (1, Lp)
                "n_valid": n_valid[None],
                "tab_row": tab_row,
                "slot": slot,
            }
            next_tok, cache = model.prefill_paged(ctx, params, batch, cache)
            return next_tok[0], cache

        if self.chain is not None:
            @jax.named_scope("decode_all")
            def decode_all(params, cache, last_tokens, lens, tab, l2):
                # chained decode: tab is the (B, W1) first-level table, l2
                # the shared second-level pool — the kernel resolves pages
                # through both scalar-prefetched levels.
                batch = {
                    "token": last_tokens[:, None], "lengths": lens,
                    "block_tab": tab, "l2_tab": l2,
                }
                return model.decode(ctx, params, cache, batch)
        else:
            @jax.named_scope("decode_all")
            def decode_all(params, cache, last_tokens, lens, tab):
                batch = {"token": last_tokens[:, None], "lengths": lens, "block_tab": tab}
                return model.decode(ctx, params, cache, batch)

        def copy_fork(cache, src_pages, dst_pages, src_slot, dst_slot):
            """Device-side copy-on-write for fork(): duplicate the trailing
            partial pages and the per-slot recurrent state."""
            out_blocks = dict(cache["blocks"])
            for i, kind in enumerate(cfg.block_pattern):
                key = f"l{i}_mixer"
                if kind == "attn":
                    out_blocks[key] = jax.tree.map(
                        lambda pool: pool.at[:, dst_pages].set(pool[:, src_pages]),
                        cache["blocks"][key],
                    )
                else:
                    def copy_slot(leaf):
                        row = jax.lax.dynamic_slice_in_dim(leaf, src_slot, 1, axis=1)
                        return jax.lax.dynamic_update_slice_in_dim(leaf, row, dst_slot, axis=1)

                    out_blocks[key] = jax.tree.map(copy_slot, cache["blocks"][key])
            return {"blocks": out_blocks}

        @jax.named_scope("prefill_chunk")
        def prefill_chunk_paged(params, cache, tokens, tab_row, slot, offset, n_valid, carry):
            """One chunked-prefill step straight into the page pool: the
            chunk's K/V scatters through the row at its page-aligned offset
            and the recurrent state rides ``carry``. Compiles once per chunk
            bucket — tab_row/slot/offset/n_valid are all dynamic."""
            batch = {
                "tokens": tokens[None, :],
                "n_valid": n_valid[None],
                "tab_row": tab_row,
                "slot": slot,
                "offset": offset,
            }
            nxt, cache, carry = model.prefill_chunk_paged(ctx, params, batch, cache, carry)
            return nxt[0], cache, carry

        @jax.named_scope("verify")
        def verify_paged(params, cache, tokens, tab_row, offset):
            """Speculative verify straight against the page pool: the k+1
            verify tokens scatter through the row at the (mid-page)
            write-head and the greedy token is read at every position —
            ``prefill_chunk_paged``'s scatter+gather+absolute-mask shape
            with per-token page indexing instead of a page-shifted row.
            Compiles once per k_eff (at most spec_tokens shapes)."""
            batch = {"tokens": tokens[None, :], "tab_row": tab_row, "offset": offset}
            toks, cache = model.verify_paged(ctx, params, batch, cache)
            return toks[0], cache

        self._prefill = jax.jit(prefill_paged, donate_argnums=(1,))
        self._decode = jax.jit(decode_all, donate_argnums=(1,))
        self._copy_fork = jax.jit(copy_fork, donate_argnums=(0,))
        self._prefill_chunk = jax.jit(prefill_chunk_paged, donate_argnums=(1, 7))
        self._verify = jax.jit(verify_paged, donate_argnums=(1,))
        self._install_carry = jax.jit(model.install_chunk_state, donate_argnums=(0,))
        self._last = np.zeros(self.pcfg.max_slots, np.int32)

    def _run_chunk_device(self, slot: int, toks, offset: int, n: int):
        nxt, self.cache, self._chunk_carry[slot] = self._prefill_chunk(
            self.params,
            self.cache,
            jnp.asarray(toks),
            jnp.asarray(self.block_tab[slot]),
            jnp.asarray(slot),
            jnp.asarray(offset),
            jnp.asarray(n),
            self._chunk_carry[slot],
        )
        return nxt

    # -- capacity telemetry ------------------------------------------------------
    def free_pages(self) -> int:
        return self.allocator.free_pages

    def capacity_now(self) -> Dict[str, int]:
        """Live capacity snapshot: what the StraightLine placer consumes
        instead of a static ``capacity`` constant. With the prefix cache on
        it additionally exports ``cached_pages`` / ``evictable_pages`` /
        ``prefix_hit_rate`` / ``prefix_cached_tokens`` — evictable cache is
        reclaimable capacity the placer may count as free-ish (the keys are
        absent when the cache is off, and StraightLinePolicy stays
        byte-faithful to Algorithm 1 without them)."""
        snap = {
            "free_slots": self.free_slots(),
            "num_slots": self.pcfg.max_slots,
            "free_pages": self.allocator.free_pages,
            "num_pages": self.pcfg.num_pages - 1,
            "free_cache_tokens": self.allocator.free_pages * self.pcfg.page_size,
            "cache_tokens": self.pcfg.cache_tokens,
            "kv_cache_dtype": _kv_dtype_name(self.cfg),
            "kv_bytes_per_token": self._kv_bytes_per_token,
            "waiting": len(self.waiting),
            "compile_events": self.compile_events,
            "total_buckets": self.total_buckets,
            "compile_ema_s": self.compile_ema_s,
            "prefilling_slots": sum(self._chunking),
            "prefill_backlog_tokens": self.prefill_backlog_tokens(),
            "chunk_tokens": self._chunk_tokens,
            "spec_tokens": self._spec_tokens,
            "tokens_emitted": self.tokens_emitted,
            "spec_proposed": self.spec_proposed,
            "spec_accepted": self.spec_accepted,
        }
        pc = self.prefix_cache
        if pc is not None:
            snap["cached_pages"] = pc.cached_pages
            snap["evictable_pages"] = pc.evictable_pages()
            snap["prefix_hit_rate"] = pc.hit_rate
            snap["prefix_cached_tokens"] = pc.matched_tokens_total
        return snap

    def admission_capacity(self, est_tokens: int = 0) -> int:
        """How many requests of ~est_tokens context the engine can admit now
        (page- and slot-bounded). est_tokens=0 assumes a one-page sequence.
        Evictable prefix-cache pages count as free: admission reclaims them
        before it would ever report the pool full."""
        est = max(1, est_tokens)
        per_seq = PageTable.pages_needed(est + 1, self.pcfg.page_size)
        pages = self.allocator.free_pages
        if self.prefix_cache is not None:
            pages += self.prefix_cache.evictable_pages()
        return min(self.free_slots(), pages // per_seq)

    # -- public API -------------------------------------------------------------
    def _null_prefill(self, Lp: int, slot: int):
        """The prefill program traffic runs, with its arguments for a zero
        prompt of shape ``Lp`` through an all-null block-table row. With
        chunked prefill on — or the prefix cache, whose admissions all ride
        the chunk machinery — that is the CHUNK program. Returns (jitted
        fn, args); the fn's second output is the new cache."""
        toks = jnp.zeros(Lp, jnp.int32)
        row = jnp.full(self._row_width, NULL_PAGE, jnp.int32)
        if self._chunk_tokens or self.prefix_cache is not None:
            return self._prefill_chunk, (
                self.params, self.cache, toks, row, jnp.asarray(slot),
                jnp.asarray(0), jnp.asarray(1), self.model.init_chunk_state(),
            )
        return self._prefill, (
            self.params, self.cache, toks, row, jnp.asarray(slot), jnp.asarray(1),
        )

    def _decode_args(self) -> tuple:
        """Arguments of the batched decode step: the slot state plus the
        flat block table, or both levels of the chained one."""
        tabs = (self.chain.l1, self.chain.l2) if self.chain is not None else (self.block_tab,)
        return (
            self.params, self.cache, jnp.asarray(self._last), jnp.asarray(self.slot_len),
            *(jnp.asarray(t) for t in tabs),
        )

    def _decode_counts(self, active: List[int]) -> Dict[str, int]:
        """What one batched decode sweeps: every slot's row (``rows``), of
        which ``live`` decode; the kernel copies the pages below each row's
        length — every row's, flat or chained alike — (``pages_walked``, by
        the kernel's own rule), while the live rows hold ``pages_live``
        pages of the tokens they attend over (cache plus the token being
        written)."""
        ps, width = self.pcfg.page_size, self._row_width
        walked = live_pages(self.slot_len + 1, ps, width)
        return {"rows": self.pcfg.max_slots, "live": len(active),
                "pages_walked": int(walked.sum()), "pages_live": int(walked[active].sum())}

    def _prewarm_shape(self, Lp: int, slot: int) -> None:
        """Compile a paged prefill at shape ``Lp`` through an all-null
        block-table row: K/V writes land on the reserved null page (garbage
        by design) and the idle slot's recurrent state is rewritten from
        zero on any real install. The cache is reassigned because the paged
        prefill donates its buffer."""
        fn, args = self._null_prefill(Lp, slot)
        self.cache = fn(*args)[1]

    def lowered_programs(self) -> Dict[str, str]:
        """StableHLO text of the decode step and of the largest prefill
        program, lowered (not compiled, not run) for this engine's shapes:
        what a caller reads to check which kernels the served programs
        hold (a Pallas kernel compiled for the TPU is a
        ``tpu_custom_call``)."""
        with self.lock:
            fn, args = self._null_prefill(self._bucket_len(self._shape_cap, self._shape_cap), 0)
            return {
                "decode": self._decode.lower(*self._decode_args()).as_text(),
                "prefill": fn.lower(*args).as_text(),
            }

    def submit(self, prompt: List[int], trace=None) -> int:
        # Gate on the engine's RESOLVED length cap, not raw max_seq_len: in
        # chained mode the cap is re-derived from pool capacity (a prompt the
        # pool can hold is admissible however max_seq_len relates to the flat
        # table geometry), and in flat mode the two are identical anyway.
        if len(prompt) + self.pcfg.max_new_tokens > self._len_cap:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens exceeds the length "
                f"cap {self._len_cap} (max_seq_len={self.pcfg.max_seq_len}, "
                f"pool={self.pcfg.cache_tokens} tokens)"
            )
        return super().submit(prompt, trace=trace)

    def _free_slot(self) -> Optional[int]:
        for i in range(self.pcfg.max_slots):
            if self.slot_seq[i] is None:
                return i
        return None

    def _sync_row(self, slot: int) -> None:
        """Single owner of the host block-table views after ANY page-list
        change (install, growth, spec grow/trim, fork, release): rewrites the
        slot's flat row and, in chained mode, re-chains its first/second
        -level entries — so the two views can never disagree."""
        table = self.tables[slot]
        pages = table.pages if table is not None else []
        self.block_tab[slot, :] = (
            table.row(self._row_width) if pages else NULL_PAGE
        )
        if self.chain is not None:
            self.chain.set_row(slot, pages)

    def _install(self, slot: int, seq: Sequence, table: PageTable) -> int:
        """Prefill seq's full context (bucket-padded) through ``table`` into
        slot; returns the emitted next token. Pad positions past the
        allocated pages map to the null page via the padded table row."""
        ctx_toks = seq.context_tokens()
        table.num_tokens = len(ctx_toks)
        self.tables[slot] = table
        self._sync_row(slot)
        toks, n, _, fresh = self._pad_context(ctx_toks)
        with span("serve.engine.prefill", seq.trace, lane=seq.lane, tokens=n,
                  padded=len(toks), offset=0, slot=slot, fresh_compile=fresh,
                  resume=seq.preemptions):
            t0 = time.perf_counter()
            nxt, self.cache = self._prefill(
                self.params,
                self.cache,
                jnp.asarray(toks),
                jnp.asarray(self.block_tab[slot]),
                jnp.asarray(slot),
                jnp.asarray(n),
            )
            if fresh:
                with span("serve.engine.device_wait"):
                    jax.block_until_ready(nxt)
                self._note_compile(time.perf_counter() - t0)
            with span("serve.engine.device_wait"):
                nxt = int(nxt)
        self.slot_seq[slot] = seq
        self.slot_len[slot] = n
        if seq.admit_t is None:
            seq.admit_t = time.monotonic()
        self._last[slot] = nxt
        self._stamp[slot] = self._stamp_next
        self._stamp_next += 1
        return nxt

    def _reserve_pages(self, n: int, seq: Optional[Sequence] = None) -> bool:
        """Make ``n`` pages allocatable, reclaiming cold prefix-cache leaves
        (LRU-first) before the caller has to preempt any live sequence —
        cached pages are reclaimable capacity, not occupancy. Returns whether
        ``alloc(n)`` can now succeed."""
        if self.allocator.can_alloc(n):
            return True
        if self.prefix_cache is not None:
            freed = self.prefix_cache.evict(n - self.allocator.free_pages)
            if freed and seq is not None and seq.trace is not None:
                seq.trace.event("prefix_evict", lane=seq.lane,
                                freed_pages=freed, need_pages=n)
        return self.allocator.can_alloc(n)

    def _release(self, slot: int, to_cache: bool = True) -> None:
        """Tear down a slot. With the prefix cache on and ``to_cache`` (the
        sequence FINISHED — not preempted), its full pages retire into the
        radix tree instead of the free list: the tree either adopts the
        sequence's page reference or, for prefixes it already holds, frees
        the duplicate. Only the trailing partial page is actually freed. A
        preemption (``to_cache=False``) drops every reference as before —
        pages shared with the tree survive under the tree's own reference."""
        seq = self.slot_seq[slot]
        table = self.tables[slot]
        node = self._cache_nodes[slot]
        self._cache_nodes[slot] = None
        if node is not None:
            self.prefix_cache.release(node)   # unpin the matched path
        if (self.prefix_cache is not None and to_cache
                and seq is not None and not self._chunking[slot]):
            toks = seq.context_tokens()[: int(self.slot_len[slot])]
            n_full = len(toks) // self.pcfg.page_size
            self.prefix_cache.insert(toks, table.pages[:n_full])
            self.allocator.free(table.pages[n_full:])    # partial tail only
            table.pages = []
            table.num_tokens = 0
        else:
            table.release(self.allocator)
        self.tables[slot] = None
        self.slot_seq[slot] = None
        self.slot_len[slot] = 0
        self._sync_row(slot)
        self._stamp[slot] = 0
        # a preempted PREFILLING slot drops its chunk progress: re-admission
        # restarts the chunked prefill from scratch with a fresh zero carry
        # (and re-matches the prefix cache, re-validating the boundary)
        self._clear_chunk_slot(slot)

    _release_slot = _release          # shared _chunk_step hook (see _EngineBase)

    def _admit(self, spent: int = 0, budget: int = 0) -> int:
        """Budget-gated page-gated admission (see the dense engine's
        ``_admit`` for the budget contract — called bare, budget 0 resolves
        ``step_budget``). Chunked: the new sequence's FULL context pages are
        reserved up front (the growth-before-admission invariant still
        holds — a decode token mid-prefill always lands on an allocated
        page) and the slot enters PREFILLING; the chunk phase spends the
        budget. With the prefix cache on, the context is matched against the
        radix tree BEFORE chunking: matched pages go to the front of the
        page table (one extra allocator reference each), only the suffix is
        freshly allocated, and the chunk cursor starts at the match boundary
        — every admission then rides the chunk machinery (a whole unmatched
        suffix is one chunk when chunking is off). Returns the updated
        spend."""
        budget = budget or self.step_budget
        admitted = False
        while self.waiting:
            slot = self._free_slot()
            if slot is None:
                break
            seq = self.waiting[0]
            ctx_toks = seq.context_tokens()
            ctx_len = len(ctx_toks)
            need = PageTable.pages_needed(ctx_len + 1, self.pcfg.page_size)
            if self.prefix_cache is not None:
                hit_pages, hit_node, hit_tokens = self.prefix_cache.acquire(ctx_toks)
                if not self._reserve_pages(need - len(hit_pages), seq):
                    self.prefix_cache.cancel(hit_pages, hit_node)
                    break                                # page-gated admission
                self.waiting.popleft()
                if seq.trace is not None:
                    seq.trace.event(
                        "prefix_hit" if hit_tokens else "prefix_miss",
                        lane=seq.lane, matched_tokens=hit_tokens,
                        ctx_tokens=ctx_len,
                    )
                seq.cached_tokens = hit_tokens
                table = PageTable(
                    self.pcfg.page_size,
                    hit_pages + self.allocator.alloc(need - len(hit_pages)),
                )
                table.num_tokens = ctx_len
                self.tables[slot] = table
                self._sync_row(slot)
                self._cache_nodes[slot] = hit_node
                self._begin_chunked(slot, seq, start=hit_tokens)
                continue
            if not self.allocator.can_alloc(need):
                break                                    # page-gated admission
            if self._chunk_tokens:
                self.waiting.popleft()
                table = PageTable(self.pcfg.page_size, self.allocator.alloc(need))
                table.num_tokens = ctx_len
                self.tables[slot] = table
                self._sync_row(slot)
                self._begin_chunked(slot, seq)
                continue
            Lp = self._bucket_len(ctx_len)
            if admitted and spent + Lp > budget:
                break                                    # over budget: stays queued
            self.waiting.popleft()
            table = PageTable(self.pcfg.page_size, self.allocator.alloc(need))
            nxt = self._install(slot, seq, table)
            spent += Lp
            admitted = True
            seq.out.append(nxt)
            seq.token_times.append(time.monotonic())
            self.tokens_emitted += 1
            if self._stop_hit(seq, nxt, int(self.slot_len[slot])):
                # the (re-)prefill-emitted token can already cross a stop
                # condition: a resumed sequence near max_new_tokens, or a
                # fresh prompt whose greedy next token is EOS
                seq.done = True
                self._just_finished.append(seq)
                self._release(slot)
        return spent

    def _preempt_newest(self, active: List[int]) -> int:
        """Evict the most recently admitted active sequence back to the
        waiting queue (front), releasing its pages. Returns the slot."""
        victim = max(active, key=lambda i: self._stamp[i])
        seq = self.slot_seq[victim]
        seq.preemptions += 1
        self.preemptions += 1
        if seq.trace is not None:
            seq.trace.event("preempted", lane=seq.lane, slot=victim,
                            n_out=len(seq.out), preemptions=seq.preemptions)
        self.waiting.appendleft(seq)
        self._release(victim, to_cache=False)
        active.remove(victim)
        return victim

    def _ensure_growth(self, active: List[int]) -> None:
        """Every active slot writes one token at position slot_len this step;
        allocate the page that position lands in. When the pool is dry, cold
        prefix-cache leaves are evicted FIRST (reclaimable capacity); only
        when nothing evictable remains is the newest sequence preempted."""
        for slot in sorted(active, key=lambda i: self._stamp[i]):
            if slot not in active:
                continue
            while self.tables[slot].capacity_tokens <= self.slot_len[slot]:
                if not self._reserve_pages(1, self.slot_seq[slot]):
                    if active == [slot]:
                        raise RuntimeError(
                            "page pool too small to grow the only active sequence; "
                            "increase num_pages"
                        )
                    preempted = self._preempt_newest(active)
                    if preempted == slot:
                        break
                    continue
                self.tables[slot].append_pages(self.allocator.alloc(1))
                self._sync_row(slot)

    def _spec_phase(self, active: List[int], spent: int, budget: int):
        """Speculate on decoding slots at the decode frontier (see the dense
        engine's ``_spec_phase`` for the budget/acceptance contract). The
        paged twist is the write-head's page coverage: the verify pass
        writes positions L..L+k_eff, so the pages covering them are
        allocated up front — through ``_reserve_pages``, which may evict
        cold prefix-cache leaves but NEVER preempts a live sequence for
        speculation (a failed reservation degrades the slot to plain
        decode). On rejection the speculative tail pages are rolled back:
        every page past max(pre-speculation count, accepted coverage) was
        freshly allocated this attempt — exclusively owned, never a
        prefix-cache or CoW-shared page (those sit at the table's front) —
        so ``PageTable.trim`` returns them to the free list whole."""
        ps = self.pcfg.page_size
        sped: List[int] = []
        for slot in active:
            seq = self.slot_seq[slot]
            L = int(self.slot_len[slot])
            k_eff = min(self._spec_tokens, self._len_cap - 1 - L)
            if k_eff < 1 or spent + k_eff > budget:
                continue
            proposal = self._propose(seq)
            if proposal is None:
                continue
            table = self.tables[slot]
            n0 = len(table.pages)
            need = PageTable.pages_needed(L + k_eff + 1, ps) - n0
            if need > 0:
                if not self._reserve_pages(need, seq):
                    continue               # pool dry: degrade to plain decode
                table.append_pages(self.allocator.alloc(need))
                self._sync_row(slot)
            toks, self.cache = self._verify(
                self.params,
                self.cache,
                jnp.asarray(
                    np.asarray([int(self._last[slot])] + proposal[:k_eff], np.int32)
                ),
                jnp.asarray(self.block_tab[slot]),
                jnp.asarray(L),
            )
            spent += k_eff
            with span("serve.engine.device_wait"):
                toks = np.asarray(toks)
            m, done = self._accept_verified(slot, seq, proposal, toks, k_eff)
            keep = max(n0, PageTable.pages_needed(L + m, ps))
            if table.trim(keep, self.allocator):
                self._sync_row(slot)
            table.num_tokens = L + m
            sped.append(slot)
            if done:
                seq.done = True
                self._just_finished.append(seq)
                self._release(slot)
        return sped, spent

    def step(self) -> List[Sequence]:
        """Grow + admit (budget-gated) + chunk work + speculation + one
        decode step; returns sequences finished. Growth runs first so
        admission can't grab the last pages only for the freshly prefilled
        sequence to be preempted in the same step — admitted sequences are
        already growth-covered (ceil((ctx+1)/ps)), PREFILLING ones trivially
        so (their full-context pages are reserved at admission, and they are
        preemption candidates like any other occupant). PREFILLING slots
        are excluded from the host-side decode bookkeeping; the batched
        device decode still sweeps them, but its scatter lands on the chunk
        cursor's (allocated) page and is rewritten by the next chunk, and
        the authoritative recurrent state rides the off-cache carry until
        install. Speculated slots are excluded the same way: the sweep
        writes their pending token at the new write-head — idempotent with
        the next step's decode write when that page is allocated, absorbed
        by the null page when it is not."""
        with self.lock, span("serve.engine.step", engine=self.name):
            budget = self.step_budget
            self.spec_runs = []
            occupied = [i for i in range(self.pcfg.max_slots) if self.slot_seq[i] is not None]
            with span("serve.engine.grow") as sp:
                preempted = self.preemptions
                self._ensure_growth(occupied)
                sp.set(preempted=self.preemptions - preempted)
            spent = sum(
                1 for i, s in enumerate(self.slot_seq)
                if s is not None and not self._chunking[i]
            )
            with span("serve.engine.admit") as sp:
                queued = len(self.waiting)
                spent = self._admit(spent, budget)
                sp.set(admitted=queued - len(self.waiting))
            if self._chunk_tokens or self.prefix_cache is not None:
                spent = self._run_chunks(spent, budget)
            active = [
                i for i in range(self.pcfg.max_slots)
                if self.slot_seq[i] is not None and not self._chunking[i]
            ]
            self.peak_active = max(self.peak_active, len(active))
            if self._spec_tokens and active:
                with span("serve.engine.spec"):
                    sped, spent = self._spec_phase(active, spent, budget)
                active = [i for i in active if i not in set(sped)]
            finished, self._just_finished = self._just_finished, []
            if active:
                with span("serve.engine.decode", **self._decode_counts(active)):
                    nxt, self.cache = self._decode(*self._decode_args())
                with span("serve.engine.device_wait"):
                    nxt = np.asarray(nxt)
                tok_t = time.monotonic()      # one stamp per batched decode step
                for i in active:
                    seq = self.slot_seq[i]
                    self.slot_len[i] += 1
                    self.tables[i].num_tokens = int(self.slot_len[i])
                    self._last[i] = nxt[i]
                    seq.out.append(int(nxt[i]))
                    seq.token_times.append(tok_t)
                    self.tokens_emitted += 1
                    if self._stop_hit(seq, int(nxt[i]), int(self.slot_len[i])):
                        seq.done = True
                        finished.append(seq)
                        self._release(i)
            return finished

    def fork(self, sid: int) -> Optional[int]:
        """Clone a running sequence (hedged/retried copy): full prefix pages
        are shared via ref-counting, the trailing partial page is copied on
        device, and the clone continues decoding independently. Returns the
        new sid, or None if no free slot / pages."""
        with self.lock:
            src = next(
                (i for i, s in enumerate(self.slot_seq) if s is not None and s.sid == sid), None
            )
            dst = self._free_slot()
            if src is None or dst is None:
                return None
            if self._chunking[src]:
                # mid-prefill: the authoritative recurrent state is in the
                # off-cache carry, not the slot — nothing coherent to clone
                return None
            src_table = self.tables[src]
            cow_pages = len(src_table.pages) - src_table.num_tokens // self.pcfg.page_size
            if not self._reserve_pages(cow_pages, self.slot_seq[src]):
                return None                   # even evicting cache can't cover CoW
            try:
                new_table = src_table.fork(self.allocator)
            except OutOfPages:
                return None
            seq = self.slot_seq[src]
            now = time.monotonic()
            clone = Sequence(self._sid, list(seq.prompt), out=list(seq.out),
                             submit_t=now, admit_t=now, trace=seq.trace,
                             cached_tokens=seq.cached_tokens)
            if self._cache_nodes[src] is not None:
                # the clone shares the source's cache-attached pages: it must
                # hold the tree path too, or the source finishing would leave
                # the path evictable under the still-running clone
                self._cache_nodes[dst] = self.prefix_cache.pin(self._cache_nodes[src])
            self._sid += 1
            n_full = new_table.num_tokens // self.pcfg.page_size
            src_part = self.tables[src].pages[n_full:]
            dst_part = new_table.pages[n_full:]
            self.cache = self._copy_fork(
                self.cache,
                jnp.asarray(src_part or [NULL_PAGE], jnp.int32),
                jnp.asarray(dst_part or [NULL_PAGE], jnp.int32),
                jnp.asarray(src),
                jnp.asarray(dst),
            )
            self.tables[dst] = new_table
            self._sync_row(dst)
            self.slot_seq[dst] = clone
            self.slot_len[dst] = self.slot_len[src]
            self._last[dst] = self._last[src]
            self._stamp[dst] = self._stamp_next
            self._stamp_next += 1
            return clone.sid
