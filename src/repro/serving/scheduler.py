"""Token-budget continuous-batching scheduler over one fused mixed step.

Real serving stacks (vLLM/JetStream/Sarathi-style) do not run prefill and
decode as separate phases: every engine tick assembles ONE forward pass of
up to ``token_budget`` tokens in which decoding rows contribute 1 token
each and admitted-but-unfinished prompts contribute a prefill *chunk* —
several chunks from different requests batched together, interleaved with
the decode rows. This module is the jax-native equivalent:

  * a fixed-shape slot pool (batch B rows) holds all request state;
  * each tick carves chunks (``PrefillState`` cursors + budget accounting),
    left-aligns every row's contribution into a ``(B, T)`` token block
    (T = the bucketed max contribution), and runs one jitted
    ``step_rows`` forward: per-row ``pos`` vectors place each row at its
    own absolute position, a per-token ``active`` mask drops the padding
    tail's cache writes, and only each row's LAST real token's logits are
    consumed (chunk-aware sampling — a non-final chunk discards them, a
    final chunk samples the request's first token, a decode row its next);
  * there is no separate admission prefill: admission just binds a slot,
    resets its row state, and lets the tick stream the prompt in — so
    decode rows keep advancing while prompts prefill, and a prompt longer
    than a ``local_attn`` window is admissible (chunks are capped at the
    window; the ring read path handles multi-token chunks — the seed's
    one-shot ring prefill limit is gone).

Admission is (priority, arrival)-ordered — ``Request.priority`` (higher
first), FIFO among equals, so equal-priority traffic cannot starve — and
gated by a free-block *watermark* in paged mode (``admit_watermark``:
admit only while ``free_blocks >= watermark``), replacing the seed's bare
FIFO head-of-line.

Two KV-cache backends, selected by ``paged``:

  * dense (default) — every row reserves ``max_len`` KV positions up front
    (``init_cache``). Admission is gated by free *slots*; memory scales with
    B * max_len regardless of how long requests actually are.
  * paged — a global block pool of ``num_blocks`` blocks of ``block_size``
    tokens per layer plus per-row block tables (``init_paged_cache``).
    ``BlockAllocator`` is the host-side free list; blocks are allocated as
    chunks and decode writes land in them (a chunk shrinks to the blocks it
    can get — partial prefill progress is fine) and freed at retirement.
    When the pool is exhausted and NO row can advance, the most recently
    admitted stalled row is preempted vLLM-style: its blocks are freed and
    the request is re-queued (keeping its original arrival rank) for
    recompute-resume. The resume is just a longer prompt re-entering the
    SAME chunked-prefill path — greedy decode, and position-keyed sampling
    where the token at position p is drawn with ``fold_in(request_seed,
    p)``, make the resumed continuation exact, and chunking makes rows past
    a ``local_attn`` window preemptable too (the seed had to refuse them).

The decode tick samples with ``GenerateConfig`` parity: pass ``gen=`` for
temperature/top-k (greedy by default) and ``Request.seed`` for per-request
reproducibility. In paged mode each tick passes a bucketed *live width* —
the max blocks any row holds, rounded to a power of two — as a static
argument plus a per-row live-width vector, so the paged attention read
(Pallas kernel on TPU, XLA gather elsewhere; see
``core.attention.paged_attention``) only visits the allocated block-table
prefix and each row's read is masked at its own block count.

Models with recurrent blocks (griffin/xlstm) cannot express ragged rows
(a recurrence has no per-token write index to mask), so for those configs
the engine splits each tick into a decode sub-step and a uniform-length
prefill sub-step instead of one mixed ragged step — still chunked, still
non-stalling, just not interleaved within a single forward.

The per-row ``pos`` vector / masked per-token scatter contract the step
relies on is documented in ``repro.models.transformer.model_apply`` and
``repro.core.attention``; the architecture narrative lives in
``docs/serving.md``.

Slot and block bookkeeping is host-side python (cheap, O(B) per step); all
tensor work stays jitted with static shapes — (T, live_width) pairs are
bucketed to powers of two so at most O(log(budget) * log(W)) step
specializations exist.

INT8 serving (the paper's payoff, live): ``qconfig=`` turns the tick into
a W8A8 forward — activation ranges are PTQ-calibrated ONCE at engine
construction against a few synthetic batches (``quant.ptq.calibrate``),
the matmul weights are pre-quantized onto the params tree
(``quant.int8_weights.attach_int8_weights``) and every linear routes
through the int8 MXU kernel with those static ranges (see
``nn.layers.linear_apply``); the calibrated context is captured by the
jitted step as closure constants, so the tick compiles exactly like the
fp one. ``kv_int8=`` (default: on whenever ``qconfig`` is given with
``paged=True``) stores the paged KV pools as int8 with per-slot scale
vectors — quantize fused into the cache scatter, dequant into both paged
read backends (``init_paged_cache(kv_int8=True)``). KV block memory drops
~3.5x for typical head shapes, so an equal-byte pool admits proportionally
more concurrent rows; serving stays bitwise invariant to chunking, slot
assignment and preemption-resume because each token is quantized exactly
once at write (see ``quant.kv_cache``). ``kv_int8=True`` alone (no
``qconfig``) is allowed: fp matmuls over a quantized cache.

Robustness layer (SLO-aware scheduling, swapped preemption, degradation —
see ``docs/serving.md`` "Traffic, SLOs, and failure handling"):

  * ``step(now=...)`` threads a caller-owned clock (the open-loop workload
    harness in ``serving.workload`` drives a deterministic virtual clock;
    ``now`` defaults to an internal tick counter). ``Request`` grows
    ``deadline`` (absolute, same clock) and ``timeout`` (relative to
    submission): expired/timed-out requests are cancelled the same tick —
    queued, mid-prefill or decoding — with their blocks released, and land
    in ``self.failed`` with a status string. Queued requests whose minimum
    remaining work provably cannot meet their deadline are shed early
    (``shed_infeasible``), and deadline-bearing requests are admitted and
    prefill-carved earliest-deadline-first within a priority level.
    ``prefill_budget`` caps the prefill share of each tick's token budget
    so a burst of arrivals cannot inflate decode-tick p99.
  * swapped preemption: with ``swap_break_even_tokens`` set, a preemption
    victim whose cached context is long copies its live pool blocks (and
    int8 scale vectors) plus its batch-led row state out to host memory
    (``SwappedState``) and copies them back in on resume — bit-exact, no
    recompute. Short victims keep the recompute-resume path: swap cost
    scales with the row's KV *bytes* (linear in tokens) while recompute
    re-runs the model over all cached tokens (much more expensive per
    token), so the bytes-vs-recompute rule reduces to a token threshold.
    Swap-in is all-or-nothing; after ``swap_retry_limit`` failed attempts
    (pool pressure or an injected denial) the request degrades to
    recompute-resume, which can always make incremental progress.
  * fault tolerance: every block release goes through one audited
    ``_release_blocks`` helper, ``BlockAllocator.free`` rejects double
    frees and foreign ids, and ``audit()`` checks the full invariant
    (every block exactly one of free / owned-by-live-row; tables mirror
    slot state; swapped requests hold zero device blocks) —
    ``debug_audit=True`` runs it after every tick. A spurious allocation
    failure (the allocator denies despite free blocks — ``serving.chaos``
    injects these) is treated as transient: the tick stalls and retries
    instead of preempting; once the fault persists past
    ``fault_shed_after`` ticks the engine degrades by policy, shedding
    exactly one victim per tick in strict priority order (lowest first,
    newest arrival among equals). ``on_pool_exhausted="shed"`` converts
    the one remaining hard failure (a single request larger than the whole
    pool) into a shed as well.

Prefix sharing + parallel sampling (see docs/serving.md "Prefix sharing
& copy-on-write" and ``serving.prefix_cache``): ``BlockAllocator`` is
refcounted — blocks are owned, not merely held, and return to the free
list only at zero owners. ``prefix_cache=True`` (paged, all-attn
configs) consults a token-ids-keyed trie at admission: a prompt whose
prefix is cached maps those FULL blocks straight into its table
(acquiring refs) and prefills only the divergent tail, so cached-prefix
TTFT collapses to ~one tick; completed prefills publish their prompt
blocks back, and LRU eviction of sole-owner nodes keeps the cache from
ever blocking a live allocation. ``Request(n=k)`` admits once and fans
into k branches (branch i seeded ``base + i``): the leader prefills,
siblings attach to a snapshot of its prompt blocks at refcount k and
diverge via copy-on-write — the first write into a still-shared block
remaps the row to a fresh block with a device-side content copy
(``copy_pool_blocks``), jitted separately so the decode tick's compile
budget is untouched. ``audit()``'s invariant generalizes to: every
block's refcount equals its owner count across slot tables + trie +
group snapshots. Sharing is bitwise-invisible: KV bits (fp, or int8
with its per-token scale) are pure functions of (token, position), so a
shared read equals the cold prefill the sharing replaced.

Speculative decoding (``spec=SpecConfig(k=...)`` — see
``serving.speculate`` and docs/serving.md "Speculative decoding"): each
decode row feeds its last token PLUS up to k model-free n-gram drafts
into the same fused tick; ``make_spec_step`` returns the full (B, T)
target matrix and the host accepts the longest draft prefix matching it,
advancing the row 1..k+1 tokens per tick — bitwise identical to the
non-speculative stream because the accept test IS position-keyed
sampling. Scheduler-side that means: ``_plan`` grows a decode row's
block table to cover 1+k writes (possibly crossing several block
boundaries in one tick — ``_grow_blocks`` already handles multi-block
growth and cursor-block CoW, and a short grant just truncates the
draft), rejected drafts leave stale-but-causally-hidden cache entries
that the row's own later writes overwrite (swap copies them harmlessly;
recompute-resume never rebuilds them), and accounting splits into
``last_tick_tokens`` (FED tokens — the compute the tick paid, what the
virtual clock charges) vs ``last_tick_new_tokens`` (tokens actually
banked into outputs — what goodput/TPOT count). Speculation requires an
all-'attn' pattern (ring/recurrent writes cannot be causally hidden)
and composes with paged/dense, fp/int8, prefix sharing, ``Request(n)``
branches, swap and preemption — all equivalence-tested.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention import blocks_per_step, live_steps
from repro.models.transformer import (
    ModelConfig,
    copy_pool_blocks,
    init_cache,
    init_paged_cache,
    model_apply,
)
from repro.quant.int8_weights import attach_int8_weights
from repro.quant.ptq import calibrate
from repro.quant.qconfig import NO_QUANT, QConfig
from repro.serving import telemetry
from repro.serving.decode import (
    GenerateConfig,
    make_mixed_step,
    make_spec_step,
)
from repro.serving.prefix_cache import PrefixCache
from repro.serving.speculate import NGramDrafter, SpecConfig

Array = jax.Array

_TABLE_KEY = jax.tree_util.DictKey("block_table")
_GROUPS_KEY = jax.tree_util.DictKey("groups")
_RECURRENT_KINDS = ("griffin", "mlstm", "slstm")


class AllocatorAuditError(RuntimeError):
    """A block-accounting invariant was violated (leak, double free,
    foreign id, stale table mirror). Raised by ``BlockAllocator.free`` and
    ``ContinuousBatcher.audit`` — the chaos harness asserts this never
    fires under any fault plan."""


# eq=False (here and _SampleGroup): live requests are identity objects —
# parallel-sampling branches share uid AND prompt, so a field-wise ==
# would compare ndarray prompts and raise instead of answering, breaking
# list membership (queue, _groups) on the first same-uid pair
@dataclasses.dataclass(eq=False)
class Request:
    uid: int
    prompt: np.ndarray               # (T,) int32
    max_new_tokens: int = 32
    # admission priority: HIGHER is served first; FIFO (arrival order)
    # among equal priorities, so equal-priority traffic cannot starve
    priority: int = 0
    # per-request sampling seed (used when the batcher's GenerateConfig has
    # temperature > 0); None derives a deterministic default from uid
    seed: Optional[int] = None
    # parallel sampling: n completions of the same prompt. The request
    # admits once — internally it expands into n branch requests where
    # branch i samples with seed base+i (base = seed or uid), so the
    # result is bitwise what n independent Requests with those seeds
    # would produce; on shareable engines (paged, all-attn) the branches
    # share the prompt's blocks at refcount n and diverge via
    # copy-on-write. Results aggregate into ``outputs`` (index order);
    # ``output`` aliases outputs[0].
    n: int = 1
    # --- SLOs (see step(now=...): all times share the caller's clock) ---
    # absolute completion deadline: past it the request is cancelled
    # ("expired") and its tokens no longer count toward goodput; queued
    # requests that provably cannot meet it are shed early
    deadline: Optional[float] = None
    # relative cap on time since submission ("timeout" when exceeded)
    timeout: Optional[float] = None
    # filled by the scheduler
    output: Optional[np.ndarray] = None
    # parallel sampling (n > 1): per-branch outputs in branch order
    outputs: Optional[List[np.ndarray]] = None
    # lifecycle: queued -> running -> done | cancelled | expired | timeout
    # | shed (failed statuses land the request in batcher.failed)
    status: str = "queued"
    submit_time: Optional[float] = None
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    # internal: host-side copy-out of a swap-preempted row (swap-resume)
    swapped: Optional["SwappedState"] = None
    # internal: tokens generated before a preemption (recompute-resume state)
    resume_generated: Optional[List[int]] = None
    # internal: submission sequence number (admission tie-break; a preempted
    # request keeps its original arrival, so re-queueing cannot demote it
    # behind later arrivals of the same priority)
    arrival: Optional[int] = None
    # internal: parallel-sampling bookkeeping (set on the expanded branch
    # requests, never on the parent the caller submitted)
    group: Optional["_SampleGroup"] = None
    branch: int = 0


@dataclasses.dataclass(eq=False)
class _SampleGroup:
    """Bookkeeping for one ``Request(n=k)`` parallel-sampling group.

    The parent request never enters the queue; it expands into ``n``
    branch requests sharing its uid. On shareable engines the branches'
    admission is staged: the LEADER (lowest live branch) prefills the
    prompt normally; when its prefill completes the group snapshots the
    prompt's blocks (one extra allocator reference each, ``shared``) and
    flips ``ready`` — only then do the siblings become admissible, each
    binding with its position cursor at ``len(prompt) - 1``: it acquires
    the snapshot blocks, re-feeds just the LAST prompt token (one-token
    prefill, so its first sample sees the same logits the leader's did),
    and its first divergent write copy-on-writes the shared tail block.
    ``unshared`` tracks which branches still get to take the snapshot
    (one admission each — a preempted branch resumes through the normal
    recompute/trie path); the snapshot refs drop as soon as every branch
    has taken (or terminally lost) its turn. Terminal branches collect in
    ``results``; the last one landing folds the group into the parent."""
    parent: Request
    n: int
    prompt_len: int
    leader: int = 0
    ready: bool = False
    shared: List[int] = dataclasses.field(default_factory=list)
    unshared: set = dataclasses.field(default_factory=set)
    branches: List[Request] = dataclasses.field(default_factory=list)
    results: Dict[int, Request] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class PrefillState:
    """Chunked-prefill cursor of one admitted request.

    ``feed`` is everything that must stream through the model before the
    request can decode: the prompt, plus — for a recompute-resume after
    preemption — all but the last of its previously generated tokens (the
    last one becomes the first decode input again). ``done`` tokens of it
    are already written to the cache; each tick the scheduler carves the
    next chunk ``feed[done:done+c]`` against the token budget."""
    feed: np.ndarray                 # (T,) int32
    done: int = 0
    # recompute-resume: the previously generated tokens, restored verbatim
    # when the prefill completes (the final chunk's sample is discarded —
    # position-keyed sampling would reproduce it exactly anyway)
    resume: Optional[List[int]] = None

    @property
    def remaining(self) -> int:
        return len(self.feed) - self.done


@dataclasses.dataclass
class SwappedState:
    """Host-side copy-out of a swap-preempted row's live device state.

    ``pool`` maps cache-leaf paths of the batch-free pool leaves (the K/V
    block pools and, for int8 KV, their per-slot scale vectors) to the
    victim's block rows in block-table order; ``row`` maps batch-led leaf
    paths (ring KV / pos_ids, recurrent h/conv/cell) to the victim's row
    slice. Together with the slot bookkeeping below, a swap-in restores
    the row bit-exactly into freshly allocated blocks — no recompute.
    The copied blocks themselves are FREED at swap-out: a swapped request
    holds zero device blocks (the allocator audit checks this)."""
    pool: Dict[Tuple, np.ndarray]
    row: Dict[Tuple, np.ndarray]
    n_blocks: int
    pos: int
    generated: List[int]
    prefill: Optional[PrefillState]
    key: Optional[np.ndarray]
    nbytes: int
    attempts: int = 0        # failed swap-in tries (bounded retry)


@dataclasses.dataclass
class _Slot:
    req: Optional[Request] = None
    pos: int = 0                     # next cache position (= tokens written)
    generated: List[int] = dataclasses.field(default_factory=list)
    blocks: List[int] = dataclasses.field(default_factory=list)  # paged only
    order: int = 0                   # admission sequence number
    key: Optional[np.ndarray] = None  # (2,) uint32 request PRNG key
    prefill: Optional[PrefillState] = None   # None once fully prefilled


class BlockAllocator:
    """Host-side REFCOUNTED free list over the global KV block pool.

    Physical block ids are plain ints in [0, num_blocks); the pool tensors
    live on device, only the *mapping* is host state. Every block carries
    an ownership count: ``alloc`` hands out blocks at refcount 1,
    ``acquire`` adds an owner to a live block (prefix-trie publication, a
    sampling-group snapshot, a row mapping a cached prefix), ``release``
    drops one — the block returns to the free list only when its LAST
    owner lets go, which is what makes prefix sharing, copy-on-write
    divergence and swap-out of shared rows ("copy, don't free, while
    another owner holds it") all fall out of one rule.

    A single ``alloc`` call is all-or-nothing, but callers may take less
    than they ultimately want: ``_grow_blocks`` claims
    ``min(need, available)`` so a prefill chunk shrinks to partial
    progress instead of stalling — a row CAN hold blocks for writes it
    has not made yet (they are used on a later tick, or returned
    wholesale at preemption/retirement)."""

    def __init__(self, num_blocks: int) -> None:
        self.num_blocks = num_blocks
        self._free = list(range(num_blocks - 1, -1, -1))
        self._refs = [0] * num_blocks

    @property
    def available(self) -> int:
        return len(self._free)

    def refcount(self, block: int) -> int:
        """Current owner count of ``block`` (0 = free)."""
        self._check(block)
        return self._refs[block]

    def _check(self, b: int) -> None:
        if not 0 <= b < self.num_blocks:
            raise AllocatorAuditError(f"foreign block id {b} "
                                      f"(pool has {self.num_blocks})")

    def alloc(self, n: int) -> Optional[List[int]]:
        """Pop ``n`` blocks at refcount 1, or None (and no side effect)
        if not enough are free."""
        if n > len(self._free):
            return None
        got = [self._free.pop() for _ in range(n)]
        for b in got:
            self._refs[b] = 1
        return got

    def acquire(self, blocks: List[int]) -> None:
        """Add one owner to each (already-live) block. Acquiring a FREE
        block raises — ownership can only be shared from an existing
        owner, never conjured."""
        for b in blocks:
            self._check(b)
            if self._refs[b] == 0:
                raise AllocatorAuditError(
                    f"acquire of free block {b} (no existing owner)")
            self._refs[b] += 1

    def release(self, blocks: List[int]) -> None:
        """Drop one owner per block; a block whose count hits zero
        returns to the free list. Over-release (the refcount edition of a
        double free) and foreign ids raise ``AllocatorAuditError`` instead
        of silently corrupting the pool — every release path goes through
        the scheduler's audited ``_release_blocks`` (or the trie/group
        teardown, which the audit also counts), so a violation here is a
        real bug."""
        for b in blocks:
            self._check(b)
            if self._refs[b] == 0:
                raise AllocatorAuditError(f"double free of block {b}")
            self._refs[b] -= 1
            if self._refs[b] == 0:
                self._free.append(b)

    # historical name: pre-refcount callers (and tests) say "free";
    # with ownership counts a free is exactly a release
    free = release

    def free_list(self) -> List[int]:
        """Snapshot of the free block ids (audit surface)."""
        return list(self._free)


def _table_leaf(leaf, table: Array):
    """Fit a host-owned (B, W) block table onto a cache table leaf,
    broadcasting over the leading layer-group axis of scanned caches."""
    if leaf.ndim == table.ndim + 1:                  # scanned: (G, B, W)
        return jnp.broadcast_to(table, (leaf.shape[0],) + table.shape)
    return table


def _with_tables(cache, table: Array):
    """Return ``cache`` with every block_table leaf set to ``table`` (B, W)."""
    def set_leaf(path, leaf):
        if path and path[-1] == _TABLE_KEY:
            return _table_leaf(leaf, table)
        return leaf
    return jax.tree_util.tree_map_with_path(set_leaf, cache)


def _bucket(n: int) -> int:
    """Round up to a power of two (bounds jit specializations)."""
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def _calibrate_engine(params, cfg: ModelConfig, qconfig: QConfig,
                      max_len: int, num_batches: int):
    """PTQ-calibrate activation ranges for the W8A8 serving tick.

    Runs ONCE at engine construction: a few synthetic uniform-token batches
    stream through the UN-jitted forward in 'collect' mode
    (``quant.ptq.calibrate``), the estimators close into static per-site
    (s, z), and the context flips to 'int8' — from then on the calibrated
    ranges are python-float closure constants of the jitted tick. Synthetic
    calibration is exactly the deployment-friendly protocol the paper
    argues the outlier-free models tolerate: per-tensor static ranges with
    no data-dependent tuning."""
    t = max(1, min(32, max_len, cfg.max_seq_len))
    key = jax.random.PRNGKey(0)
    batches = [
        {"tokens": jax.random.randint(jax.random.fold_in(key, i),
                                      (2, t), 0, cfg.vocab_size)}
        for i in range(num_batches)
    ]

    def apply_fn(p, batch, ctx):
        return model_apply(p, cfg, batch, ctx=ctx)[0]

    ctx = calibrate(apply_fn, params, batches, qconfig,
                    num_batches=num_batches)
    ctx.use_int8_runtime()
    return ctx


class ContinuousBatcher:
    """Token-budget slot-pool scheduler over a shared static KV cache
    (dense or paged).

    Device state per slot row: KV cache (dense row or block-table view into
    the pool), next position and last sampled token; one jitted mixed step
    advances every runnable row per tick — decode rows by one token,
    prefilling rows by a prompt chunk — regardless of their (generally
    different) positions and phase."""

    def __init__(self, params, cfg: ModelConfig, batch_size: int,
                 max_len: int, eos_id: Optional[int] = None,
                 paged: bool = False, block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 gen: Optional[GenerateConfig] = None,
                 token_budget: int = 256,
                 prefill_chunk: Optional[int] = None,
                 admit_watermark: int = 0,
                 qconfig: Optional[QConfig] = None,
                 kv_int8: Optional[bool] = None,
                 calib_batches: int = 4,
                 prefill_budget: Optional[int] = None,
                 swap_break_even_tokens: Optional[int] = None,
                 swap_pool_bytes: Optional[int] = None,
                 swap_retry_limit: int = 3,
                 shed_infeasible: bool = True,
                 fault_shed_after: int = 8,
                 on_pool_exhausted: str = "raise",
                 prefix_cache: bool = False,
                 spec: Optional[SpecConfig] = None,
                 debug_audit: bool = False) -> None:
        # ---- INT8 serving (W8A8 tick + quantized paged KV) -------------
        if kv_int8 is None:
            kv_int8 = qconfig is not None and paged
        if kv_int8 and not paged:
            raise ValueError(
                "kv_int8 requires paged=True: the int8 KV layout is the "
                "block pool + per-slot scale vectors (init_paged_cache)")
        self.kv_int8 = bool(kv_int8)
        self.qconfig = qconfig
        self._qctx = NO_QUANT
        if qconfig is not None:
            # W8A8 needs per-layer calibration sites and per-layer int8
            # weight slices, so the engine runs the unrolled layer path
            # (functionally identical — stacked scanned params are
            # tree_slice'd per group by model_apply's unrolled branch)
            if cfg.scan_layers:
                cfg = dataclasses.replace(cfg, scan_layers=False)
            self._qctx = _calibrate_engine(params, cfg, qconfig, max_len,
                                           calib_batches)
            params = attach_int8_weights(params, skip=qconfig.skip_patterns)
        self.params = params
        self.cfg = cfg
        self.B = batch_size
        self.L = max_len
        # sampling config for the fused tick (greedy by default — parity
        # with GenerateConfig's temperature/top-k knobs; per-request seeds
        # come from Request.seed). eos_id arg wins over gen.eos_id.
        self._gen = gen if gen is not None else GenerateConfig()
        self.eos_id = eos_id if eos_id is not None else self._gen.eos_id
        self.paged = paged
        if token_budget < 1:
            raise ValueError("token_budget must be >= 1")
        self.token_budget = token_budget
        self.admit_watermark = admit_watermark
        self.slots = [_Slot() for _ in range(batch_size)]
        self.queue: List[Request] = []
        self.done: List[Request] = []
        # requests that left the engine without completing: cancelled,
        # expired (deadline), timeout, or shed (infeasible / persistent
        # faults / pool exhaustion under on_pool_exhausted="shed")
        self.failed: List[Request] = []
        self._order = 0
        self._arrival = 0
        # ---- SLO / robustness knobs ------------------------------------
        # per-tick cap on PREFILL tokens (None = whole remaining budget):
        # bounds the mixed tick's size when arrivals burst, protecting
        # decode-tick p99 at a TTFT cost
        if prefill_budget is not None and prefill_budget < 1:
            raise ValueError("prefill_budget must be >= 1 (or None)")
        self.prefill_budget = prefill_budget
        # swap-vs-recompute cost rule threshold (None = swap disabled):
        # victims with >= this many cached tokens copy out, shorter ones
        # recompute (see _swap_eligible for the bytes-vs-recompute story)
        self.swap_break_even_tokens = swap_break_even_tokens
        self.swap_pool_bytes = swap_pool_bytes   # host swap capacity cap
        self.swap_retry_limit = swap_retry_limit
        self.shed_infeasible = shed_infeasible
        self.fault_shed_after = fault_shed_after
        if on_pool_exhausted not in ("raise", "shed"):
            raise ValueError("on_pool_exhausted must be 'raise' or 'shed'")
        self.on_pool_exhausted = on_pool_exhausted
        self.debug_audit = debug_audit
        # caller-owned clock (step(now=...)); defaults to a tick counter
        self.now = 0.0
        self._tick_ewma: Optional[float] = None   # est. virtual tick cost
        self._prev_advanced = False
        self._alloc_fault = False      # spurious alloc denial seen this tick
        self._fault_streak = 0         # consecutive faulted no-progress ticks
        self._swap_bytes = 0           # host bytes currently held by swaps
        # chaos hook: called before each swap-in; returning False denies it
        # (counts as a retry attempt -> bounded degradation to recompute)
        self._swap_in_gate: Optional[Callable[[Request], bool]] = None
        # total REAL tokens processed by the most recent step() across all
        # sub-steps — the workload harness's virtual-clock cost input.
        # With speculation this counts FED tokens (drafts included,
        # accepted or not): it is the tick's compute cost, not its yield
        self.last_tick_tokens = 0
        # tokens BANKED into request outputs by the most recent step():
        # decode advances (1..k+1 per row under speculation) plus each
        # completed prefill's first token — the goodput/TPOT numerator
        self.last_tick_new_tokens = 0
        # counts vector of the most recent sub-step (observability + tests:
        # a mixed tick shows >= 2 entries > 1 next to entries == 1)
        self.last_counts: Optional[np.ndarray] = None
        # host spans of every tick (serving/telemetry.py): ``serve.step``
        # carries this engine's id and the tick index, its children the
        # tick's phases
        self.telemetry = telemetry.RECORDER
        self.engine_id = next(telemetry.ENGINE_IDS)
        self.ticks = 0
        if paged:
            self.block_size = block_size
            n_entries = -(-max_len // block_size)
            # default pool = dense-equivalent memory (B rows of max_len)
            self.num_blocks = num_blocks if num_blocks is not None \
                else batch_size * n_entries
            self.allocator = BlockAllocator(self.num_blocks)
            self.tables = np.full((batch_size, n_entries), -1, np.int32)
            # host tables are mirrored into the device cache lazily: only
            # ticks after an admit/alloc/retire/preempt pay the re-upload
            self._tables_dirty = True
            make_cache = lambda b: init_paged_cache(  # noqa: E731
                cfg, b, max_len, self.num_blocks, block_size,
                kv_int8=self.kv_int8)
        else:
            make_cache = lambda b: init_cache(cfg, b, max_len)  # noqa: E731
        self.cache = make_cache(batch_size)
        # fresh batch-1 state template: admission resets the slot's
        # batch-led rows (ring pos_ids, recurrent states, dense KV) from it
        # so the previous occupant cannot leak into the new request's
        # prefill. In paged mode only its batch-led leaves are ever read —
        # build it with a 1-block pool so the template does not duplicate
        # the real pool's device memory
        self._row_template = init_paged_cache(cfg, 1, max_len, 1, block_size,
                                              kv_int8=self.kv_int8) \
            if paged else make_cache(1)
        kinds = cfg.pattern + cfg.tail_pattern
        # recurrent states have no per-token write index to mask, so ragged
        # mixed steps are not expressible — such configs run split
        # decode/uniform-prefill sub-steps instead (see module docstring)
        self._uniform = any(k in _RECURRENT_KINDS for k in kinds)
        # ---- prefix sharing / parallel sampling ------------------------
        # sharing rides on the paged attn pools only: ring (local_attn)
        # and recurrent layers keep batch-led PER-ROW state that a shared
        # block cannot carry, so those configs run sampling branches
        # independently and cannot cache prefixes
        self._can_share = paged and all(k == "attn" for k in kinds)
        # ---- speculative decoding --------------------------------------
        # sound only for global-attn KV (dense or paged): a rejected
        # draft's cache write is causally hidden (every read path masks
        # keys at positions > q) and overwritten by the row's own next
        # writes before its position passes it — but a local_attn RING
        # write at pos % window clobbers live in-window history, and a
        # recurrent state has no per-token position to hide behind
        self.spec = spec
        self._drafter: Optional[NGramDrafter] = None
        self._tick_drafts: Dict[int, List[int]] = {}
        # observability: drafted vs accepted totals (accept rate =
        # spec_accepted / spec_drafted), read by tests + the benchmark
        self.spec_drafted = 0
        self.spec_accepted = 0
        if spec is not None:
            if not all(k == "attn" for k in kinds):
                raise ValueError(
                    "spec=SpecConfig(...) requires an all-'attn' layer "
                    "pattern: rejected draft writes are only causally "
                    "hidden in a global-attn KV cache — a local_attn "
                    "ring write clobbers in-window history and "
                    "recurrent states have no per-token write to mask")
            self._drafter = NGramDrafter(spec)
        self.prefix_cache: Optional[PrefixCache] = None
        if prefix_cache:
            if not self._can_share:
                raise ValueError(
                    "prefix_cache=True requires paged=True and an "
                    "all-'attn' layer pattern: ring/recurrent layers keep "
                    "per-row state a shared block cannot carry")
            self.prefix_cache = PrefixCache(block_size, self.allocator)
        self._groups: List[_SampleGroup] = []     # live sampling groups
        # observability: copy-on-write block copies performed, admissions
        # that mapped a shared prefix, and prompt tokens skipped that way
        self.cow_copies = 0
        self.shared_admissions = 0
        self.shared_tokens = 0
        if paged:
            # device half of copy-on-write (see transformer.copy_pool_blocks):
            # jitted separately from the decode tick so CoW adds zero
            # specializations to the tick's compile budget; (n,) index pairs
            # are pow-2 padded by _copy_blocks so this fn compiles O(log B)
            # times at most
            self._cow_fn = jax.jit(copy_pool_blocks, donate_argnums=(0,))
        # a prefill chunk on a local_attn layer must fit the ring, and its
        # own writes must not collide inside it
        ring_cap = min(max_len, cfg.window) \
            if (any(k == "local_attn" for k in kinds) and cfg.window) \
            else token_budget
        self._chunk_cap = min(prefill_chunk or token_budget, token_budget,
                              ring_cap)
        # which leaves are batch-free (the paged global pools, shared by all
        # rows) vs batch-led (dense/ring KV, recurrent states, block
        # tables): exactly the leaves whose shape ignores the batch argument
        spec1, spec2 = (jax.eval_shape(lambda b=b: make_cache(b))
                        for b in (1, 2))
        self._batch_free = jax.tree_util.tree_map(
            lambda a, b: a.shape == b.shape, spec1, spec2)

        # the jitted fused tick lives with the other serving programs in
        # decode.py; calibrated int8 ranges ride along as closure
        # constants. A speculative engine runs make_spec_step for EVERY
        # tick (it subsumes the mixed step: a draft-free decode row is the
        # T=1 case and a prefill chunk's first token is tgt[b, c-1]), so
        # spec adds one program family, not two
        make_step = make_mixed_step if spec is None else make_spec_step
        self._step_fn = make_step(cfg, self._gen, self._qctx)

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        """Enqueue a request, rejecting impossible ones up front — a lazy
        admit-time failure would wedge the queue head and strand every
        queued request behind it. (Preemption re-queues bypass this:
        resume lengths are bounded by construction.)"""
        t = len(req.prompt)
        if t == 0:
            raise ValueError(
                f"request uid={req.uid}: empty prompt (there is no logits "
                f"position to sample a first token from)")
        if t > self.L - 1:
            raise ValueError(
                f"request uid={req.uid}: {t} prompt tokens do not fit a "
                f"max_len={self.L} {'row' if self.paged else 'slot'} "
                f"(>= 1 position must remain for decode)")
        if self.paged and self._blocks_for(t + 1) > self.num_blocks:
            raise ValueError(
                f"request uid={req.uid} needs {self._blocks_for(t + 1)} "
                f"blocks; the pool only has {self.num_blocks}")
        if req.n < 1:
            raise ValueError(f"request uid={req.uid}: n must be >= 1")
        if req.n > 1 and req.group is None:
            self._submit_group(req)
            return
        if req.arrival is None:
            req.arrival = self._arrival
            self._arrival += 1
        if req.submit_time is None:
            req.submit_time = self.now
        req.status = "queued"
        self.queue.append(req)

    def _submit_group(self, req: Request) -> None:
        """Expand ``Request(n=k)`` into k branch requests sharing the
        parent's uid. Branch i samples with seed ``base + i`` (base =
        the parent's seed, or its uid by default) — exactly the seeds k
        independent single requests would need to reproduce it, which is
        what the equivalence tests assert. The parent itself never
        queues; it lands in done/failed when its last branch does."""
        g = _SampleGroup(parent=req, n=req.n, prompt_len=len(req.prompt),
                         unshared=set(range(1, req.n)))
        base = req.seed if req.seed is not None else req.uid
        req.status = "queued"
        if req.submit_time is None:
            req.submit_time = self.now
        self._groups.append(g)
        for i in range(req.n):
            br = Request(uid=req.uid,
                         prompt=np.asarray(req.prompt, np.int32).copy(),
                         max_new_tokens=req.max_new_tokens,
                         priority=req.priority, seed=base + i,
                         deadline=req.deadline, timeout=req.timeout,
                         group=g, branch=i)
            g.branches.append(br)
            self.submit(br)

    def cancel(self, uid: int, status: str = "cancelled") -> bool:
        """Cancel a request by uid — queued, mid-prefill, or decoding —
        the same tick: its blocks are released immediately, queued prefill
        chunks are dropped with the cursor, and any generated tokens are
        delivered as a partial ``output``. A parallel-sampling request
        cancels ALL of its branches (they share the parent's uid).
        Returns False if the uid is not live (already finished or
        unknown)."""
        hit = False
        while True:
            found = False
            for j, req in enumerate(self.queue):
                if req.uid == uid:
                    self.queue.pop(j)
                    self._fail(req, status)
                    hit = found = True
                    break
            if found:
                continue
            for i, s in enumerate(self.slots):
                if s.req is not None and s.req.uid == uid:
                    self._evict(i, status)
                    hit = found = True
                    break
            if not found:
                return hit

    def _fail(self, req: Request, status: str,
              output: Optional[List[int]] = None) -> None:
        """Terminal non-success: stamp status/finish time, release any swap
        bytes, deliver a (possibly partial) output, move to ``failed``."""
        if req.swapped is not None:
            self._swap_bytes -= req.swapped.nbytes
            if output is None and req.swapped.generated:
                output = req.swapped.generated
            req.swapped = None
        if output is None and req.resume_generated:
            output = req.resume_generated
        req.output = np.asarray(output if output is not None else [],
                                np.int32)
        req.status = status
        req.finish_time = self.now
        self._land(req)

    def _land(self, req: Request) -> None:
        """Route a terminal request (status already stamped) to
        done/failed. Parallel-sampling branches aggregate into their
        parent instead of landing individually: the group's last terminal
        branch folds all branch outputs into ``parent.outputs`` and lands
        the PARENT once."""
        g = req.group
        if g is None:
            (self.done if req.status == "done" else self.failed).append(req)
            return
        g.results[req.branch] = req
        if not g.ready and req.branch == g.leader:
            # the prefilling leader died before publishing the prompt:
            # promote the next live branch so the group cannot deadlock
            # (the promoted leader is admissible and prefills cold)
            live = sorted(br.branch for br in g.branches
                          if br.branch not in g.results)
            if live:
                g.leader = live[0]
        if req.branch in g.unshared:
            # a branch that died before taking its snapshot turn
            g.unshared.discard(req.branch)
            self._maybe_drop_share(g)
        if len(g.results) == g.n:
            self._finalize_group(g)

    def _maybe_drop_share(self, g: _SampleGroup) -> None:
        """Release the group's prompt-block snapshot once every branch
        has taken (or terminally lost) its turn against it."""
        if g.shared and not g.unshared:
            self.allocator.release(g.shared)
            g.shared = []

    def _finalize_group(self, g: _SampleGroup) -> None:
        """All n branches are terminal: fold them into the parent.
        ``outputs`` keeps branch order; the parent is 'done' only if
        every branch finished, else it carries the first failing branch's
        status (individual branch outcomes stay readable per entry)."""
        if g.shared:
            self.allocator.release(g.shared)
            g.shared = []
        if g in self._groups:
            self._groups.remove(g)
        p = g.parent
        branches = [g.results[i] for i in range(g.n)]
        p.outputs = [br.output for br in branches]
        p.output = p.outputs[0]
        bad = [br.status for br in branches if br.status != "done"]
        p.status = "done" if not bad else bad[0]
        fts = [br.first_token_time for br in branches
               if br.first_token_time is not None]
        p.first_token_time = min(fts) if fts else None
        p.finish_time = self.now
        (self.done if p.status == "done" else self.failed).append(p)

    def _evict(self, i: int, status: str) -> None:
        """Terminally remove slot ``i``'s occupant (cancel/expire/shed):
        blocks released through the audited path, partial tokens kept."""
        s = self.slots[i]
        out = (s.prefill.resume if s.prefill is not None and s.prefill.resume
               else s.generated)
        self._release_blocks(i)
        self._fail(s.req, status, output=list(out))
        self.slots[i] = _Slot()

    def _release_blocks(self, i: int) -> None:
        """The ONE path blocks travel back to the free list (retire,
        preempt, cancel, shed all route here): frees the slot's blocks,
        clears its table row, marks the device mirror dirty. Keeping a
        single audited release point is what makes the allocator audit's
        no-leak/no-double-free invariant cheap to uphold."""
        s = self.slots[i]
        if not self.paged:
            return
        if s.blocks:
            self.allocator.release(s.blocks)
            s.blocks = []
        self.tables[i] = -1
        self._tables_dirty = True

    def _free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s.req is None]

    def _blocks_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.block_size)

    def _reset_row(self, i: int) -> None:
        """Reset slot ``i``'s batch-led device state (dense/ring KV rows,
        ring pos_ids, recurrent h/conv/cell) to the fresh template before a
        new occupant starts prefilling: stale ring position ids or
        recurrent state from the previous occupant would otherwise leak
        into the new request. Paged pool leaves are shared by all rows and
        left alone (newly allocated blocks are fully overwritten before any
        causally reachable read), and block tables stay host-owned."""
        def pick(path, batch_free, live_leaf, tmpl_leaf):
            if (path and path[-1] == _TABLE_KEY) or batch_free:
                return live_leaf
            # scanned caches stack layer groups in front: (G, B, ...)
            ax = 1 if path and path[0] == jax.tree_util.DictKey("groups") \
                else 0
            dst = (slice(None),) * ax + (i,)
            src = (slice(None),) * ax + (0,)
            return live_leaf.at[dst].set(tmpl_leaf[src])

        self.cache = jax.tree_util.tree_map_with_path(
            pick, self._batch_free, self.cache, self._row_template)

    def _admit_key(self, j: int):
        """Admission order: priority desc, then earliest deadline first
        among equals (deadline-free requests sort last within their
        priority), then arrival — so SLO-bearing traffic is both
        prioritized by tier and EDF-scheduled inside a tier."""
        r = self.queue[j]
        d = r.deadline if r.deadline is not None else float("inf")
        return (-r.priority, d, r.arrival)

    def _admit(self) -> None:
        """Bind queued requests to free slots in ``_admit_key`` order.
        Admission does NOT prefill — it resets the slot row and hands the
        prompt to the chunked tick — so its only gates are a free slot
        and, in paged mode, the free-block watermark (admission stops
        while ``free_blocks < admit_watermark``, keeping headroom for the
        rows already decoding instead of thrashing the pool). A swapped
        request instead restores its copied-out state into freshly
        allocated blocks (all-or-nothing); while its swap-in is denied it
        is deferred for the tick rather than blocking the queue head."""
        deferred: set = set()
        for i in self._free_slots():
            while True:
                cands = [j for j, r in enumerate(self.queue)
                         if id(r) not in deferred and self._admissible(r)]
                if not cands:
                    return
                if self.paged and \
                        self._avail() < self.admit_watermark:
                    return
                j = min(cands, key=self._admit_key)
                req = self.queue[j]
                if req.swapped is not None:
                    ok = self._try_swap_in(i, j)
                    if ok is None:       # degraded to recompute: re-pick
                        continue
                    if not ok:           # denied this tick: try next cand
                        deferred.add(id(req))
                        continue
                    break                # restored into slot i
                self.queue.pop(j)
                self._bind_slot(i, req)
                break

    def _admissible(self, r: Request) -> bool:
        """Sampling-group siblings wait for their leader's prefill (the
        shared prompt blocks) on engines that can share; on engines that
        cannot, the branches are plain independent requests."""
        g = r.group
        if g is None or not self._can_share:
            return True
        return g.ready or r.branch == g.leader

    def _bind_slot(self, i: int, req: Request) -> None:
        """Fresh (or recompute-resume) admission into slot ``i``."""
        resume = req.resume_generated
        req.resume_generated = None
        if resume:
            feed = np.concatenate(
                [np.asarray(req.prompt, np.int32),
                 np.asarray(resume[:-1], np.int32)])
        else:
            feed = np.asarray(req.prompt, np.int32)
        self._reset_row(i)
        key = np.asarray(jax.random.PRNGKey(
            req.seed if req.seed is not None else req.uid))
        self.slots[i] = _Slot(
            req=req, pos=0, generated=[], blocks=[], order=self._order,
            key=key,
            prefill=PrefillState(feed=feed,
                                 resume=list(resume) if resume else None))
        self._order += 1
        req.status = "running"
        if self.paged:
            self._attach_prefix(i, resumed=bool(resume))

    def _attach_prefix(self, i: int, resumed: bool) -> None:
        """Map the longest shareable prefix of slot ``i``'s feed onto
        EXISTING physical blocks, acquiring one reference per block, and
        advance the prefill cursor past the whole span — the engine runs
        zero prefill chunks for it. Two sources, tried in order:

          * sampling-group snapshot (fresh sibling admissions only): the
            leader's prompt blocks through token ``len(prompt) - 1``; the
            last prompt token is re-fed as a one-token prefill so the
            sibling's first sample sees the same logits the leader's did,
            and its first write (position len(prompt) - 1, inside the
            shared tail block) triggers copy-on-write;
          * prefix trie: full cached prompt blocks only (see
            ``serving.prefix_cache``), so a trie hit starts writing
            strictly AFTER the shared span and never copies.

        Shared KV reads are bitwise-equal to a cold prefill because KV
        bits (fp or int8 + per-token scale) are pure functions of (token,
        position) — the same invariance that already makes chunk size,
        slot assignment and preemption unobservable. Stale slots past the
        cursor inside a snapshot tail block are never read: causal
        masking hides positions > q, and position q itself is rewritten
        (identically) by the re-fed token's own scatter before use."""
        s = self.slots[i]
        req = s.req
        g = req.group
        blocks: List[int] = []
        start = 0
        if (self._can_share and g is not None and not resumed
                and req.branch in g.unshared and g.shared):
            blocks = list(g.shared)
            start = g.prompt_len - 1
            self.allocator.acquire(blocks)
            g.unshared.discard(req.branch)
            self._maybe_drop_share(g)
        elif self.prefix_cache is not None:
            blocks = self.prefix_cache.match(s.prefill.feed)
            start = len(blocks) * self.block_size
            if blocks:
                self.allocator.acquire(blocks)
        if not blocks or start <= 0:
            if blocks and start <= 0:    # 1-token prompt: nothing to skip
                self.allocator.release(blocks)
            return
        s.blocks = list(blocks)
        self.tables[i, :len(blocks)] = blocks
        self._tables_dirty = True
        s.pos = start
        s.prefill.done = start
        self.shared_admissions += 1
        self.shared_tokens += start

    # ---- swapped preemption ------------------------------------------
    def _swap_eligible(self, s: _Slot) -> bool:
        """The bytes-vs-recompute cost rule, reduced to a token threshold:
        swap-out cost is the row's live KV *bytes* — linear in cached
        tokens, a pure copy — while recompute-resume re-runs the model
        over every cached token (attention makes it superlinear, and even
        the linear term is a full forward per token, orders of magnitude
        more work per token than a memcpy). Both costs scale with the same
        token count, so 'swap when bytes beat recompute' is 'swap when
        the cached context is longer than a break-even token count'."""
        if self.swap_break_even_tokens is None or not self.paged:
            return False
        if s.pos < self.swap_break_even_tokens:
            return False
        if self.swap_pool_bytes is not None and \
                self._swap_bytes >= self.swap_pool_bytes:
            return False        # host swap pool full: fall back to recompute
        return True

    def _swap_out(self, i: int) -> SwappedState:
        """Copy slot ``i``'s live device state to host: its pool blocks
        (K/V and, for int8 KV, the per-slot scale vectors travel together
        — a block's scales are meaningless without it) in table order from
        every batch-free pool leaf, plus its row slice of every batch-led
        leaf (ring KV/pos_ids, recurrent states). The blocks themselves
        are released by the caller — a swapped request holds none."""
        s = self.slots[i]
        idx = jnp.asarray(s.blocks, jnp.int32)
        pool: Dict[Tuple, np.ndarray] = {}
        row: Dict[Tuple, np.ndarray] = {}

        def grab(path, batch_free, leaf):
            if path and path[-1] == _TABLE_KEY:
                return
            ax = 1 if path and path[0] == _GROUPS_KEY else 0
            if batch_free:
                pool[path] = np.asarray(jnp.take(leaf, idx, axis=ax))
            else:
                row[path] = np.asarray(leaf[(slice(None),) * ax + (i,)])

        jax.tree_util.tree_map_with_path(grab, self._batch_free, self.cache)
        st = s.prefill
        nbytes = sum(a.nbytes for a in pool.values()) \
            + sum(a.nbytes for a in row.values())
        return SwappedState(
            pool=pool, row=row, n_blocks=len(s.blocks), pos=s.pos,
            generated=list(s.generated),
            prefill=None if st is None else PrefillState(
                feed=st.feed, done=st.done,
                resume=list(st.resume) if st.resume else None),
            key=None if s.key is None else np.array(s.key),
            nbytes=nbytes)

    def _try_swap_in(self, i: int, j: int) -> Optional[bool]:
        """Attempt to restore queued request ``j`` into slot ``i``.
        Returns True on success, False when denied this tick (pool cannot
        hand out the blocks, or the chaos gate says no — bounded retry),
        and None when the retry budget is exhausted and the request
        degraded to recompute-resume (graceful degradation: recompute can
        always make incremental progress)."""
        req = self.queue[j]
        sw = req.swapped
        denied = self._swap_in_gate is not None and \
            not self._swap_in_gate(req)
        blocks = None if denied else self._alloc(sw.n_blocks)
        if blocks is None:
            sw.attempts += 1
            if sw.attempts > self.swap_retry_limit:
                self._drop_swap(req)
                return None
            return False
        self.queue.pop(j)
        idx = jnp.asarray(blocks, jnp.int32)

        def put(path, batch_free, leaf):
            if path and path[-1] == _TABLE_KEY:
                return leaf
            ax = 1 if path and path[0] == _GROUPS_KEY else 0
            if batch_free:
                sel = (slice(None),) * ax + (idx,)
                return leaf.at[sel].set(jnp.asarray(sw.pool[path],
                                                    leaf.dtype))
            sel = (slice(None),) * ax + (i,)
            return leaf.at[sel].set(jnp.asarray(sw.row[path], leaf.dtype))

        self.cache = jax.tree_util.tree_map_with_path(
            put, self._batch_free, self.cache)
        self.tables[i, :len(blocks)] = blocks
        self.tables[i, len(blocks):] = -1
        self._tables_dirty = True
        self.slots[i] = _Slot(req=req, pos=sw.pos,
                              generated=list(sw.generated),
                              blocks=list(blocks), order=self._order,
                              key=sw.key, prefill=sw.prefill)
        self._order += 1
        self._swap_bytes -= sw.nbytes
        req.swapped = None
        req.status = "running"
        return True

    def _drop_swap(self, req: Request) -> None:
        """Degrade a swapped request to recompute-resume (swap-in kept
        failing): reconstruct the recompute state from the host copy and
        release the swap bytes. Outputs stay exact — recompute-resume and
        swap-resume are bitwise equivalent by construction."""
        sw = req.swapped
        req.swapped = None
        self._swap_bytes -= sw.nbytes
        if sw.prefill is not None and sw.prefill.resume:
            req.resume_generated = list(sw.prefill.resume)
        elif sw.generated:
            req.resume_generated = list(sw.generated)

    def _preempt(self, i: int) -> None:
        """Evict slot ``i`` on pool pressure and re-queue it (the original
        arrival rank keeps it ahead of later equal-priority arrivals).
        Victims past the swap break-even copy their live state out to host
        (``SwappedState``: resume is a copy-in, no recompute); short
        victims stash their generated tokens for recompute-resume. Either
        way the blocks go back through the audited release path."""
        s = self.slots[i]
        req = s.req
        if self._swap_eligible(s):
            req.swapped = self._swap_out(i)
            self._swap_bytes += req.swapped.nbytes
            req.resume_generated = None
        elif s.prefill is not None and s.prefill.resume:
            req.resume_generated = list(s.prefill.resume)
        else:
            req.resume_generated = list(s.generated)
        self._release_blocks(i)
        req.status = "queued"
        self.queue.append(req)
        self.slots[i] = _Slot()

    def preempt_slot(self, i: int) -> None:
        """Force-preempt live slot ``i`` (chaos storms, tests): exactly the
        pool-pressure eviction path, including the swap-vs-recompute
        choice."""
        if self.slots[i].req is None:
            raise ValueError(f"slot {i} is not occupied")
        self._preempt(i)

    # ------------------------------------------------------------------
    def _avail(self) -> int:
        """Blocks an allocation could obtain right now: the free list
        plus whatever LRU trie eviction could release. Admission and
        growth gate on this, not raw ``available`` — the prefix cache
        must never block a live row."""
        n = self.allocator.available
        if self.prefix_cache is not None:
            n += self.prefix_cache.evictable()
        return n

    def _alloc(self, n: int) -> Optional[List[int]]:
        """Allocate through LRU trie eviction. Eviction runs only on a
        GENUINE shortage (``available < n``): a transient fault denial
        while free blocks exist must NOT flush the cache — the denial
        path still returns None and the caller's fault handling engages."""
        if n <= 0:
            return []
        if self.prefix_cache is not None and self.allocator.available < n:
            self.prefix_cache.evict(n - self.allocator.available)
        return self.allocator.alloc(n)

    def _copy_blocks(self, pairs: List[Tuple[int, int]]) -> None:
        """Flush copy-on-write block copies device-side, BEFORE any of
        this tick's forward writes land. Pairs are pow-2 padded by
        repeating the first pair (a duplicate copy writes the same bytes
        twice — idempotent), so the jitted copy compiles at most
        O(log B) times and the decode tick's own compile budget is
        untouched."""
        self.cow_copies += len(pairs)
        n = _bucket(len(pairs))
        pairs = pairs + [pairs[0]] * (n - len(pairs))
        src = jnp.asarray([p[0] for p in pairs], jnp.int32)
        dst = jnp.asarray([p[1] for p in pairs], jnp.int32)
        self.cache = self._cow_fn(self.cache, src, dst)

    def _grow_blocks(self, i: int, n_tokens: int) -> int:
        """Paged: try to grow slot ``i``'s block list to cover its next
        ``n_tokens`` writes; allocates as many of the missing blocks as the
        pool can give. Returns how many of the ``n_tokens`` writes are now
        covered (possibly 0).

        Copy-on-write: if the block the next write lands in is still
        referenced by another owner (prefix trie, sampling-group snapshot
        or a sibling row), the row's entry is remapped to a fresh block
        and the content copied device-side first. Only the entry holding
        ``pos`` can ever be shared — shared spans sit strictly before the
        cursor and growth appends fresh blocks — so one check suffices."""
        s = self.slots[i]
        e = s.pos // self.block_size
        if e < len(s.blocks) and self.allocator.refcount(s.blocks[e]) > 1:
            got = self._alloc(1)
            if got is None:
                if self.allocator.available >= 1:
                    # denied despite a free block: transient fault, see below
                    self._alloc_fault = True
                return 0
            old, new = s.blocks[e], got[0]
            # copy first, then hand back our reference: the copy is
            # flushed immediately so no later device write (swap-in
            # restore, this tick's forward) can race it
            self._copy_blocks([(old, new)])
            self.allocator.release([old])
            s.blocks[e] = new
            self.tables[i, e] = new
            self._tables_dirty = True
        need = self._blocks_for(s.pos + n_tokens) - len(s.blocks)
        if need > 0:
            take = min(need, self._avail())
            got = self._alloc(take) if take > 0 else None
            if take > 0 and got is None:
                # the allocator denied a request its own 'available' said
                # it could serve: a transient fault (chaos injection), not
                # pool pressure — flag it so _plan stalls instead of
                # preempting (freeing blocks cannot cure a denial)
                self._alloc_fault = True
            if got:
                self.tables[i, len(s.blocks):len(s.blocks) + len(got)] = got
                s.blocks.extend(got)
                self._tables_dirty = True
        return max(0, min(n_tokens, len(s.blocks) * self.block_size - s.pos))

    def _plan(self, want_decode: bool, want_prefill: bool,
              allow_preempt: bool) -> np.ndarray:
        """Carve this sub-step's per-row token counts against the budget,
        allocating paged blocks as needed. Decode rows come first (1 token
        each — inter-token latency is the knob the budget must never
        starve), then prefill chunks: earliest deadline first among
        deadline-bearing rows, admission order after them, against the
        smaller of the remaining budget and ``prefill_budget`` (the p99
        guard: a burst of admissions cannot inflate the tick past the
        prefill cap). If the pool is exhausted and NO row can advance,
        preempt the most recently admitted stalled row and retry — unless
        the failure was a transient allocator fault, which stalls the tick
        instead (preemption cannot cure a denial). A single stalled row
        holding the whole pool means the pool is simply too small for the
        request: raise, or shed it under ``on_pool_exhausted='shed'``."""
        while True:
            counts = np.zeros(self.B, np.int32)
            stalled: List[int] = []
            budget = self.token_budget
            pleft = self.prefill_budget if self.prefill_budget is not None \
                else self.token_budget
            self._tick_drafts = {}
            if want_decode:
                for i, s in enumerate(self.slots):
                    if s.req is None or s.prefill is not None:
                        continue
                    drafts: List[int] = []
                    if self.spec is not None:
                        # per-row draft length: the SpecConfig cap, then
                        # the cache row's write bounds (k+1 writes at
                        # pos..pos+k must stay < L-1 so the row can still
                        # retire cleanly), then the tokens the request
                        # can still USE (accepting past max_new_tokens
                        # is wasted verification), then leftover budget
                        # (the base decode token stays budget-exempt,
                        # like the non-speculative tick)
                        k_cap = min(self.spec.k,
                                    self.L - 2 - s.pos,
                                    s.req.max_new_tokens
                                    - len(s.generated) - 1,
                                    budget - 1)
                        if k_cap > 0:
                            drafts = self._drafter.propose(
                                s.req.prompt, s.generated, k_cap)
                    c = 1 + len(drafts)
                    if self.paged:
                        # one tick may cross several block boundaries;
                        # a short grant truncates the draft instead of
                        # stalling the row
                        c = self._grow_blocks(i, c)
                        if c < 1:
                            stalled.append(i)
                            continue
                        drafts = drafts[:c - 1]
                    counts[i] = c
                    budget -= c
                    if drafts:
                        self._tick_drafts[i] = drafts
            if want_prefill:
                def edf(i):
                    s = self.slots[i]
                    d = s.req.deadline if s.req.deadline is not None \
                        else float("inf")
                    return (d, s.order)
                pre = sorted(
                    (i for i, s in enumerate(self.slots)
                     if s.req is not None and s.prefill is not None),
                    key=edf)
                uniform_c = None
                if self._uniform and pre:
                    uniform_c = min(min(self.slots[i].prefill.remaining
                                        for i in pre),
                                    self._chunk_cap, max(budget, 0),
                                    max(pleft, 0))
                for i in pre:
                    if budget <= 0 or pleft <= 0:
                        break
                    s = self.slots[i]
                    if uniform_c is not None:
                        if uniform_c > min(budget, pleft):
                            break
                        c = uniform_c
                    else:
                        c = min(s.prefill.remaining, self._chunk_cap,
                                budget, pleft)
                    if c > 0 and self.paged:
                        c = self._grow_blocks(i, c)
                        if self._uniform and 0 < c < uniform_c:
                            # a short chunk would make the step ragged;
                            # recurrent rows sit this tick out instead
                            c = 0
                    if c <= 0:
                        stalled.append(i)
                        continue
                    counts[i] = c
                    budget -= c
                    pleft -= c
            if counts.any() or not stalled:
                return counts
            if not allow_preempt:
                return counts
            if self._alloc_fault:
                # transient fault: stall the tick and retry next step();
                # step() bounds the streak with priority-ordered shedding
                return counts
            occupied = sum(s.req is not None for s in self.slots)
            if occupied == 1:
                if self._drop_group_shares():
                    continue      # snapshot refs released: retry the plan
                s = self.slots[stalled[0]]
                if self.on_pool_exhausted == "shed":
                    self._evict(stalled[0], "shed")
                    continue
                raise RuntimeError(
                    f"block pool too small: request uid={s.req.uid} holds "
                    f"{len(s.blocks)}/{self.num_blocks} blocks and still "
                    f"needs more; increase num_blocks")
            self._preempt(max(stalled, key=lambda i: self.slots[i].order))

    def _drop_group_shares(self) -> bool:
        """Last-resort pool relief when a single row holds everything it
        can get and still stalls: release every sampling group's prompt
        snapshot (branches that have not taken their turn will re-prefill
        via the trie or from scratch — slower, never incorrect). Returns
        True if anything was released."""
        hit = False
        for g in self._groups:
            if g.shared:
                self.allocator.release(g.shared)
                g.shared = []
                g.unshared.clear()
                hit = True
        return hit

    def _live_width(self) -> Optional[int]:
        """Static block-table read width for this tick: the max blocks any
        occupied slot holds, rounded up to a power of two (so at most
        log2(W)+1 distinct jit specializations exist). Allocation is
        prefix-dense — tables fill from entry 0 — so every live token of
        every row sits inside the first ``live_width`` entries and slicing
        the READ path there is exact. Returns None in dense mode."""
        if not self.paged:
            return None
        held = max((len(s.blocks) for s in self.slots if s.req is not None),
                   default=1)
        return min(_bucket(held), self.tables.shape[1])

    def _retire(self) -> None:
        for i, s in enumerate(self.slots):
            if s.req is None or s.prefill is not None:
                continue
            out_len = len(s.generated)
            hit_eos = self.eos_id is not None and s.generated and \
                s.generated[-1] == self.eos_id
            if out_len >= s.req.max_new_tokens or hit_eos or s.pos >= self.L - 1:
                s.req.output = np.asarray(s.generated, np.int32)
                s.req.status = "done"
                s.req.finish_time = self.now
                self._release_blocks(i)
                self._land(s.req)
                self.slots[i] = _Slot()

    def _substep(self, want_decode: bool = True, want_prefill: bool = True,
                 allow_preempt: bool = True) -> int:
        """Plan, assemble and run ONE fused forward; apply its results to
        the slots. Returns the number of rows that advanced."""
        rec = self.telemetry
        with rec.span("serve.plan"):
            counts = self._plan(want_decode, want_prefill, allow_preempt)
        run = np.flatnonzero(counts)
        if run.size == 0:
            return 0
        with rec.span("serve.assemble"):
            args, final, (steps, grid) = self._assemble(run, counts)
        tokens, width = args[0], args[4]
        with rec.span("serve.dispatch", T=tokens.shape[1], width=width,
                      B=self.B,
                      blocks=sum(len(self.slots[i].blocks) for i in run),
                      steps=steps, grid=grid):
            nxt, self.cache = self._step_fn(self.params, self.cache, *args)
        with rec.span("serve.wait"):
            nt = np.asarray(nxt)
        with rec.span("serve.apply"):
            self._apply(run, counts, nt, final)
        return int(run.size)

    def _assemble(self, run: np.ndarray, counts: np.ndarray):
        """The tick's arguments after ``params`` and ``cache`` for the rows
        ``run`` — ``(tokens (B, T), pos, counts, keys, live_width,
        live_widths)`` —, per prefill row whether its chunk ends its feed,
        and the paged-attention grid steps that fetch and in all
        (``_attn_grid``). Uploads the block tables when they changed."""
        self.last_counts = counts.copy()
        # recurrent rows would feed any padding tail into their recurrence
        # (no per-token write index to mask), so uniform mode uses the
        # exact chunk length — one compile per distinct prompt-chunk size,
        # the same specialization behavior as a one-shot prefill engine
        # repro: ignore[R002] uniform recurrent rows need the exact chunk length
        t_step = int(counts.max()) if self._uniform \
            else _bucket(int(counts.max()))
        tokens = np.zeros((self.B, t_step), np.int32)
        pos = np.zeros((self.B,), np.int32)
        final = {}
        for i in run:
            s = self.slots[i]
            c = int(counts[i])
            pos[i] = s.pos
            if s.prefill is None:
                tokens[i, 0] = s.generated[-1] if s.generated else 0
                drafts = self._tick_drafts.get(i)
                if drafts:
                    tokens[i, 1:c] = drafts
            else:
                st = s.prefill
                tokens[i, :c] = st.feed[st.done:st.done + c]
                final[i] = st.done + c == len(st.feed)
        keys = np.stack([s.key if s.key is not None
                         else np.zeros((2,), np.uint32) for s in self.slots])
        if self.paged and self._tables_dirty:
            self.cache = _with_tables(self.cache, jnp.asarray(self.tables))
            self._tables_dirty = False
        held = np.asarray([len(s.blocks) for s in self.slots], np.int32) \
            if self.paged else None
        width = self._live_width()
        # the step returns its block tables unchanged, so in steady state
        # (no admissions/retirements) the paged tick is as cheap as the
        # dense one: no table upload, no tree surgery
        return (jnp.asarray(tokens), jnp.asarray(pos), jnp.asarray(counts),
                jnp.asarray(keys), width,
                None if held is None else jnp.asarray(held)), final, \
            self._attn_grid(pos, t_step, held, width)

    def _attn_grid(self, pos: np.ndarray, t: int,
                   held: Optional[np.ndarray],
                   width: Optional[int]) -> Tuple[int, int]:
        """The paged-attention kernel's grid along rows and the table, for
        the tick's own ``pos``, T and per-row held blocks: the steps that
        fetch (each row's ``live_steps``, summed) and all of them
        (B x ceil(width / P)). (0, 0) in dense mode."""
        if not self.paged:
            return 0, 0
        steps = live_steps(pos, t, held, block_size=self.block_size,
                           width=width, causal=self.cfg.causal)
        p = blocks_per_step(self.block_size, width)
        return int(steps.sum()), self.B * -(-width // p)

    def _apply(self, run: np.ndarray, counts: np.ndarray, nt: np.ndarray,
               final: Dict[int, bool]) -> None:
        """Bank one forward's sampled tokens ``nt`` into the rows ``run``
        fed; ``final`` marks the prefill rows whose chunk ends their feed."""
        spec_on = self.spec is not None
        self.last_tick_tokens += int(counts.sum())
        for i in run:
            s = self.slots[i]
            c = int(counts[i])
            if s.prefill is None:
                if spec_on:
                    self._apply_spec_decode(i, nt[i], c)
                else:
                    s.generated.append(int(nt[i]))
                    s.pos += 1
                    self.last_tick_new_tokens += 1
            else:
                st = s.prefill
                st.done += c
                s.pos += c
                if final[i]:
                    # chunk-aware sampling: only the final chunk's last-token
                    # logits produce a token — the request's first generated
                    # token at position len(feed), drawn under the same
                    # position-keyed rule as every decode tick. A resumed
                    # request restores its stashed continuation instead.
                    # (A spec step returns the (T,) target row; entry c-1
                    # is exactly the mixed step's last-token sample.)
                    first = int(nt[i, c - 1]) if spec_on else int(nt[i])
                    s.generated = list(st.resume) if st.resume else [first]
                    s.prefill = None
                    if not st.resume:
                        self.last_tick_new_tokens += 1
                    self._on_prefill_done(i)
            if s.generated and s.req.first_token_time is None:
                s.req.first_token_time = self.now

    def _apply_spec_decode(self, i: int, tgt: np.ndarray, c: int) -> None:
        """Verify slot ``i``'s drafts against the (T,) target row of the
        speculative tick and bank 1..c tokens: the longest draft prefix
        with ``draft[j] == tgt[j]`` plus the bonus token ``tgt[n_acc]``
        (always valid — it was sampled conditioned only on the accepted
        prefix). EOS / max_new_tokens truncate the banked run, in which
        case the row retires this very tick and its over-written cache
        tail is never read. ``pos`` advances by the banked count, so
        rejected drafts' cache entries sit at positions >= the new pos:
        causally invisible to every read, and overwritten (with identical
        bits) by the row's own future writes before pos passes them."""
        s = self.slots[i]
        drafts = self._tick_drafts.pop(i, [])
        n_acc = 0
        while n_acc < len(drafts) and drafts[n_acc] == int(tgt[n_acc]):
            n_acc += 1
        self.spec_drafted += len(drafts)
        self.spec_accepted += n_acc
        banked = drafts[:n_acc] + [int(tgt[n_acc])]
        room = s.req.max_new_tokens - len(s.generated)
        kept: List[int] = []
        for tok in banked:
            kept.append(tok)
            if self.eos_id is not None and tok == self.eos_id:
                break
            if len(kept) >= room:
                break
        s.generated.extend(kept)
        s.pos += len(kept)
        self.last_tick_new_tokens += len(kept)

    def _on_prefill_done(self, i: int) -> None:
        """Prefill-completion hooks for slot ``i``:

          * publish the row's FULL prompt blocks into the prefix trie
            (insert dedupes, so a trie-hit row republishing its matched
            span is a no-op and only genuinely new blocks gain a ref);
          * for a sampling-group leader, snapshot the blocks covering the
            prompt (one extra ref each) and flip ``ready`` — the siblings
            become admissible against the snapshot."""
        s = self.slots[i]
        req = s.req
        plen = len(req.prompt)
        if self.prefix_cache is not None:
            n_full = plen // self.block_size
            if n_full > 0:
                prompt = np.asarray(req.prompt, np.int32)
                self.prefix_cache.insert(prompt[:n_full * self.block_size],
                                         s.blocks[:n_full])
        g = req.group
        if (g is not None and self._can_share and not g.ready
                and req.branch == g.leader):
            g.ready = True
            if g.unshared:
                shared = s.blocks[:self._blocks_for(plen)]
                self.allocator.acquire(shared)
                g.shared = list(shared)

    # ---- SLO enforcement / degradation -------------------------------
    def _min_ticks_left(self, req: Request) -> int:
        """Optimistic lower bound on ticks to finish a QUEUED request:
        prefill chunks at the full chunk cap plus one decode tick per
        remaining token. Used only to shed provably-late requests, so it
        must underestimate, never overestimate."""
        if req.swapped is not None:
            sw = req.swapped
            feed_left = sw.prefill.remaining if sw.prefill is not None else 0
            dec = max(0, req.max_new_tokens - len(sw.generated))
        else:
            resume = req.resume_generated or []
            feed_left = len(req.prompt) + max(0, len(resume) - 1)
            dec = max(0, req.max_new_tokens - len(resume))
        cap = min(self._chunk_cap,
                  self.prefill_budget or self.token_budget)
        if self.spec is not None:
            # a speculative tick can bank up to k+1 decode tokens; the
            # bound must stay OPTIMISTIC (shedding on an overestimate
            # would drop feasible requests), so assume full acceptance
            dec = -(-dec // (self.spec.k + 1))
        return -(-feed_left // max(cap, 1)) + dec

    def _enforce_slos(self) -> None:
        """Same-tick cancellation of requests past their deadline or
        timeout — queued, mid-prefill or decoding — plus early shedding of
        queued requests whose optimistic remaining work already overruns
        their deadline (only once a tick-cost estimate exists)."""
        now = self.now
        for req in list(self.queue):
            late = req.deadline is not None and now > req.deadline
            timed = req.timeout is not None and req.submit_time is not None \
                and now - req.submit_time > req.timeout
            if late or timed:
                self.queue.remove(req)
                self._fail(req, "expired" if late else "timeout")
            elif (self.shed_infeasible and req.deadline is not None
                  and self._tick_ewma is not None
                  and now + self._min_ticks_left(req) * self._tick_ewma
                  > req.deadline):
                self.queue.remove(req)
                self._fail(req, "shed")
        for i, s in enumerate(self.slots):
            if s.req is None:
                continue
            req = s.req
            late = req.deadline is not None and now > req.deadline
            timed = req.timeout is not None and req.submit_time is not None \
                and now - req.submit_time > req.timeout
            if late or timed:
                self._evict(i, "expired" if late else "timeout")

    def _shed_one(self) -> None:
        """Persistent-fault degradation: drop exactly ONE victim, in
        strict priority order — lowest priority first, newest arrival
        among equals — preferring queued requests over running rows (a
        running row may still drain what it holds)."""
        if self.queue:
            j = min(range(len(self.queue)),
                    key=lambda j: (self.queue[j].priority,
                                   -(self.queue[j].arrival or 0)))
            req = self.queue.pop(j)
            self._fail(req, "shed")
            return
        live = [i for i, s in enumerate(self.slots) if s.req is not None]
        if live:
            i = min(live, key=lambda i: (self.slots[i].req.priority,
                                         -self.slots[i].order))
            self._evict(i, "shed")

    def audit(self) -> None:
        """Block-accounting invariant, refcount edition: every physical
        block's refcount equals its OWNER COUNT summed across slot block
        tables, the prefix trie, and sampling-group snapshots — and free
        blocks are exactly the zero-ref ones. Plus: host tables mirror
        slot state (a block appears at most once per row), the trie owns
        each of its blocks once, swapped requests hold zero device
        blocks, swap-byte accounting balances. Raises
        ``AllocatorAuditError`` on any violation — the chaos harness
        calls this after every step, and ``debug_audit=True`` makes the
        engine self-check every tick."""
        if not self.paged:
            return
        owners: Dict[int, int] = {}
        for i, s in enumerate(self.slots):
            if s.req is None:
                if s.blocks:
                    raise AllocatorAuditError(
                        f"empty slot {i} holds blocks {s.blocks}")
                if not (self.tables[i] == -1).all():
                    raise AllocatorAuditError(
                        f"empty slot {i} has stale table entries")
                continue
            row_seen = set()
            for b in s.blocks:
                if b in row_seen:
                    raise AllocatorAuditError(
                        f"slot {i} maps block {b} twice")
                row_seen.add(b)
                owners[b] = owners.get(b, 0) + 1
            w = len(s.blocks)
            if list(self.tables[i, :w]) != s.blocks or \
                    not (self.tables[i, w:] == -1).all():
                raise AllocatorAuditError(
                    f"slot {i} table row {self.tables[i].tolist()} does "
                    f"not mirror its blocks {s.blocks}")
        if self.prefix_cache is not None:
            cached = self.prefix_cache.cached_blocks()
            if len(cached) != len(set(cached)):
                raise AllocatorAuditError(
                    "prefix trie owns a block through two nodes")
            for b in cached:
                owners[b] = owners.get(b, 0) + 1
        for g in self._groups:
            for b in g.shared:
                owners[b] = owners.get(b, 0) + 1
        free = self.allocator.free_list()
        if len(free) != len(set(free)):
            raise AllocatorAuditError("free list repeats a block id")
        free_set = set(free)
        for b in range(self.num_blocks):
            rc = self.allocator.refcount(b)
            own = owners.get(b, 0)
            if rc != own:
                raise AllocatorAuditError(
                    f"block {b}: refcount {rc} != owner count {own} "
                    f"(slots + trie + sampling groups)")
            if (rc == 0) != (b in free_set):
                raise AllocatorAuditError(
                    f"block {b}: refcount {rc} inconsistent with free-"
                    f"list membership {b in free_set}")
        swap_bytes = sum(r.swapped.nbytes for r in self.queue
                         if r.swapped is not None)
        if swap_bytes != self._swap_bytes:
            raise AllocatorAuditError(
                f"swap byte accounting broken: held={self._swap_bytes} "
                f"but queued swaps sum to {swap_bytes}")

    def step(self, now: Optional[float] = None) -> int:
        """One scheduler tick: enforce SLOs, retire, admit, run the mixed
        token-budget step (or the split decode/uniform-prefill sub-steps
        for recurrent configs), retire again. ``now`` is the caller's
        clock (virtual or wall — deadlines/timeouts are compared against
        it); omitted, it advances an internal tick counter by 1. Returns
        the number of rows advanced (0 = stalled or idle, never an
        exception under transient faults)."""
        self.ticks += 1
        with self.telemetry.span("serve.step", tick=self.ticks,
                                 engine=self.engine_id):
            return self._tick(now)

    def _tick(self, now: Optional[float]) -> int:
        rec = self.telemetry
        now = self.now + 1.0 if now is None else float(now)
        dt = now - self.now
        if dt > 0 and self._prev_advanced:
            # per-tick cost estimate for infeasibility shedding; only
            # ticks that did work count (idle clock jumps would bloat it)
            self._tick_ewma = dt if self._tick_ewma is None \
                else 0.8 * self._tick_ewma + 0.2 * dt
        self.now = now
        self._alloc_fault = False
        self.last_tick_tokens = 0
        self.last_tick_new_tokens = 0
        with rec.span("serve.retire"):
            self._retire()
        with rec.span("serve.slo"):
            self._enforce_slos()
        with rec.span("serve.admit"):
            self._admit()
        if self._uniform:
            has_pre = any(s.req is not None and s.prefill is not None
                          for s in self.slots)
            n = self._substep(want_prefill=False,
                              allow_preempt=not has_pre)
            if has_pre:
                n += self._substep(want_decode=False,
                                   allow_preempt=(n == 0))
        else:
            n = self._substep()
        with rec.span("serve.retire"):
            self._retire()
        self._prev_advanced = n > 0
        if self._alloc_fault and n == 0:
            self._fault_streak += 1
            if self._fault_streak > self.fault_shed_after:
                # the fault is persistent: degrade by policy instead of
                # queueing unboundedly — one victim per tick, lowest
                # priority first
                self._shed_one()
        elif not self._alloc_fault:
            self._fault_streak = 0
        if self.debug_audit:
            self.audit()
        return n

    def run(self, max_ticks: int = 10_000) -> List[Request]:
        ticks = 0
        while (self.queue or any(s.req for s in self.slots)) and ticks < max_ticks:
            self.step()
            ticks += 1
        return self.done
