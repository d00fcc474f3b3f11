"""Continuous-batching streaming engine (docs/serving.md).

:class:`~repro.runtime.serve.Server` batches a request *list*: compose a
fixed-size group, prefill it once, decode every row to the group's max —
padded tail rows and short requests ride along as waste, and a request that
arrives mid-batch waits for the whole batch to finish.  This module replaces
that with the engine shape every production LLM server converged on
(Orca-style iteration-level scheduling, vLLM-style paged KV):

* an **admission queue** consumes :class:`~repro.data.pipeline.ServingRequest`
  with open-loop ``arrival_s`` timestamps (``bursty_open_loop_trace``);
* an **iteration-level scheduler** composes every step from interleaved
  prefill and decode work and retires a finished request *that step* — no
  row ever decodes past its own ``max_new_tokens``;
* a **paged KV cache**: a block pool with a free-list
  :class:`BlockAllocator` and a ``block_table`` (rid → block).  Blocks here
  are sequence-granular — one block holds one request's whole KV row at
  fixed capacity, the honest granularity for a cache dict whose layout the
  model owns — so decode batches compose by *index* into the pool, which
  every step updates in place, instead of the
  ``_cache_chunk``/``_cache_concat`` copy round-trips.

The paper's posture carries over intact.  Prefill groups and decode gathers
dispatch through registry ops (``engine_prefill`` / ``engine_decode``) whose
candidate family is the chunking **degree**, bracketed by the
:class:`~repro.core.degree.DegreeController`'s set-on-entry/restore-on-exit
protocol.  New here: the *scheduler itself* is a tuned kernel
(``serve_scheduler``) — prefill chunk size, prefill/decode interleave ratio,
admission policy, max in-flight and (when the queue is bounded) the shed
policy form a :class:`~repro.core.params.ParamSpace` keyed per
:class:`~repro.core.traffic.TrafficClass` of the *queue state* (phase
``stream``), searched off the hot path by the
:class:`~repro.runtime.background_tuner.BackgroundTuner` with a measured
shadow replay as the cost.  The DegreeController is thereby demoted from
"the serving policy" to one policy among the scheduler's knobs.

Decode composes heterogeneous positions by ``jax.vmap`` of the batch-1
layer math over the pool's rows (:func:`_make_decode_rows`: attention KV
reads each layer's rows inside the layer scan and writes one slot per row;
recurrent state gathers and scatters whole rows): ``cache["len"]`` is
scalar per row, so every request advances at its own position, and
:func:`~repro.models.attention.decode_attention` masks unwritten slots with
``-inf`` — extra pool capacity is numerically inert, which is what makes the
engine bit-match the one-request-at-a-time reference (the conformance test).
MoE is the one asymmetry: capacity-bounded dispatch couples rows *within a
prefill group* (prefill chunk pins to 1), but vmapped batch-1 decode rows
are independent, so MoE decode chunks freely — a capability the static
server never had.

**Hardening** (PR 8, docs/serving.md failure-mode table).  By default
(``hardened=True``) no input trace, resource state, or per-request failure
crashes or wedges the engine; every request retires exactly once with a
:class:`RequestResult` status in ``{ok, timed_out, shed, error}``:

* **deadlines** — a request past its ``deadline_s`` (or the engine-level
  ``default_ttl_s``) retires ``timed_out``, queued or in flight, instead of
  holding a KV block;
* **preemption with recompute** — when the pool is exhausted and a strictly
  higher-priority admission is blocked, the lowest-priority in-flight
  request is evicted: block released, requeued at the queue front with its
  already-generated tokens as *replay* state.  On re-admission the prompt
  prefills again and the replay tokens force the decode trajectory, so the
  final output is bit-identical to the uninterrupted run; ``max_preemptions``
  bounds re-eviction of the same request (anti-livelock);
* **load shedding** — with ``queue_limit`` set, the queue is bounded by a
  shed policy (``reject-new`` | ``drop-oldest`` | ``deadline-aware``) that
  joins the tuned scheduler knobs;
* **fault isolation** — a prefill/decode step that raises is retried one
  request at a time; a request that still raises retires ``error`` (block
  released) and the engine continues.  The pool is donated to every step,
  so a fault after a call consumed it retires the step's rows instead
  (:meth:`StreamingEngine._decode_step` states the contract).  A watchdog
  counts scheduler iterations with no retire/admit/decode progress and
  raises :class:`EngineStalled` with a state dump after ``watchdog_limit``
  of them — loud failure instead of a silent spin;
* **chaos** — :class:`~repro.runtime.chaos.ChaosInjector` hooks (step
  faults, pool pressure, virtual delays) make every path above a
  deterministic CI test.

``hardened=False`` restores the pre-hardening contract (validation errors
and step faults raise to the caller) — the overload benchmark runs that
configuration against the same adversarial trace to demonstrate the crash
the hardened engine survives.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import (
    ATRegion,
    AutotunedOp,
    BasicParams,
    DegreeController,
    KernelSpec,
    ParamSpace,
    PerfParam,
    TrafficClass,
    TuningDB,
    bucket_pow2,
    register_kernel,
)
from repro.core.autotuned import OpState
from repro.data.pipeline import ServingRequest
from repro.obs.trace import DeferredRegion, Region, current_tracer
from repro.distributed.sharding import mesh_bp_entries
from repro.models import cache_batch_axis, decode_fn, init_cache, prefill_fn
from repro.models.transformer import (
    decode_attn_layer,
    decode_inputs,
    decode_logits,
)
from repro.models.config import ModelConfig
from repro.runtime.background_tuner import BackgroundTuner
from repro.runtime.serve import (
    _batch_chunk,
    _cache_concat,
    build_batch_inputs,
    check_unique_rids,
)


# ---------------------------------------------------------------------------
# Typed engine failures
# ---------------------------------------------------------------------------


class KVPoolExhausted(RuntimeError):
    """The block pool has no free block.

    Subclasses ``RuntimeError`` so pre-hardening callers (and tests) that
    catch the bare exhaustion error keep working; carries the pool stats the
    scheduler needs to decide between waiting, shedding, and preempting.
    """

    def __init__(self, n_blocks: int, in_use: int) -> None:
        super().__init__(
            f"KV block pool exhausted ({in_use}/{n_blocks} blocks in use); "
            "the scheduler must bound admissions by allocator.free"
        )
        self.n_blocks = int(n_blocks)
        self.in_use = int(in_use)

    @property
    def free(self) -> int:
        return self.n_blocks - self.in_use


class EngineStalled(RuntimeError):
    """Watchdog: no retire/admit/decode progress for ``watchdog_limit``
    consecutive scheduler iterations — fail loudly with a state dump
    instead of spinning forever."""


# ---------------------------------------------------------------------------
# Paged KV cache
# ---------------------------------------------------------------------------


class BlockAllocator:
    """Free-list allocator over a fixed pool of KV blocks."""

    def __init__(self, n_blocks: int) -> None:
        if n_blocks < 1:
            raise ValueError("n_blocks must be >= 1")
        self.n_blocks = int(n_blocks)
        self._free: List[int] = list(range(n_blocks - 1, -1, -1))
        self.peak_in_use = 0

    @property
    def free(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.n_blocks - len(self._free)

    def allocate(self) -> int:
        if not self._free:
            raise KVPoolExhausted(self.n_blocks, self.in_use)
        block = self._free.pop()
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        return block

    def release(self, block: int) -> None:
        # the allocator stays strict (double-free of a *block* is always a
        # bookkeeping bug); rid-level idempotence lives in PagedKVCache
        if not (0 <= block < self.n_blocks) or block in self._free:
            raise ValueError(f"release of invalid or free block {block}")
        self._free.append(block)


class PagedKVCache:
    """A block pool of per-request KV rows plus the rid → block table.

    Every leaf of the model's cache dict for batch 1 at fixed ``capacity``
    is stacked under a leading ``(n_blocks,)`` axis; the scalar ``len`` leaf
    becomes ``(n_blocks,)`` so each block carries its own position.  The
    pool is updated in place: the row insert and the engine's decode step
    both donate it and hand back the pool that replaces it.  Insert
    scatters prefilled rows into allocated blocks.  Decode, for attention
    KV, reads each layer's rows inside its layer scan and writes one slot
    per row and layer; for recurrent state it gathers the rows, steps them
    and scatters them back (:func:`_make_decode_rows`).  A donated pool is
    deleted, so whoever calls a donating program rebinds :attr:`pool` to its
    output at once; :meth:`lost` tells a fault handler that the pool was
    consumed with nothing to replace it, and :meth:`reset` empties it.
    """

    def __init__(self, cfg: ModelConfig, n_blocks: int, capacity: int) -> None:
        self.cfg = cfg
        self.capacity = int(capacity)
        self.allocator = BlockAllocator(n_blocks)
        self.block_table: Dict[int, int] = {}
        self.pool: Dict[str, jnp.ndarray] = self.empty_pool()

    def empty_pool(self) -> Dict[str, jnp.ndarray]:
        """A zeroed pool of this cache's shape."""
        row = jax.eval_shape(lambda: init_cache(self.cfg, 1, self.capacity))
        return {
            k: jnp.zeros((self.n_blocks,) + tuple(v.shape), v.dtype)
            for k, v in row.items()
        }

    @property
    def n_blocks(self) -> int:
        return self.allocator.n_blocks

    @property
    def free(self) -> int:
        return self.allocator.free

    def allocate(self, rid: int) -> int:
        if rid in self.block_table:
            raise ValueError(f"rid {rid} already holds block {self.block_table[rid]}")
        block = self.allocator.allocate()
        self.block_table[rid] = block
        return block

    def release(self, rid: int) -> None:
        """Release ``rid``'s block.  Idempotent: releasing a rid that holds
        no block is a no-op, so every retirement path (finish, timeout,
        shed, error, preempt) can release unconditionally without tracking
        who already did."""
        block = self.block_table.pop(rid, None)
        if block is not None:
            self.allocator.release(block)

    def block_of(self, rid: int) -> int:
        return self.block_table[rid]

    def insert(self, rids: Sequence[int], cache: Dict[str, Any]) -> None:
        """Scatter the rows of a freshly prefilled group cache into blocks.

        ``cache`` has batch ``len(rids)`` and this pool's exact capacity;
        row ``i`` lands in ``rids[i]``'s allocated block.
        """
        slots = jnp.asarray([self.block_table[r] for r in rids], jnp.int32)
        self.pool = _INSERT_ROWS(self.pool, cache, slots)

    def lost(self) -> bool:
        """Whether a donating call consumed :attr:`pool` without returning
        the pool that replaces it (its buffers are deleted)."""
        return any(v.is_deleted() for v in self.pool.values())

    def reset(self) -> None:
        """Replace the pool with an empty one; every row's KV is gone."""
        self.pool = self.empty_pool()


def _insert_rows(pool, cache, slots):
    """pool[slots[i]] <- row i of the batched group cache (per leaf)."""
    out = {}
    B = slots.shape[0]
    for k, v in pool.items():
        if k == "len":
            ln = jnp.broadcast_to(cache["len"], (B,)).astype(v.dtype)
            out[k] = v.at[slots].set(ln)
            continue
        ax = cache_batch_axis(k, cache[k].ndim)
        rows = jnp.moveaxis(cache[k], ax, 0)
        # restore the inner batch-1 axis the pool rows keep (row = the
        # model's own batch-1 cache layout, so decode_fn applies unchanged)
        rows = jnp.expand_dims(rows, ax + 1)
        out[k] = v.at[slots].set(rows.astype(v.dtype))
    return out


#: the row insert, with the pool donated: it writes the rows in place
_INSERT_ROWS = jax.jit(_insert_rows, donate_argnums=0)


# ---------------------------------------------------------------------------
# Engine stats
# ---------------------------------------------------------------------------


@dataclass
class StreamStats:
    tokens_out: int = 0          # tokens delivered to real requests, only
    prefill_steps: int = 0       # scheduler iterations that ran a prefill
    decode_steps: int = 0        # scheduler iterations' decode micro-steps
    decode_inplace_steps: int = 0  # decode steps that wrote one slot per row
    prefill_calls: int = 0       # underlying jitted prefill invocations
    decode_calls: int = 0        # underlying jitted decode-step invocations
    prefill_s: float = 0.0
    decode_s: float = 0.0
    idle_s: float = 0.0          # virtual-clock time with nothing runnable
    makespan_s: float = 0.0      # arrival of first request -> last retire
    peak_in_flight: int = 0
    ttft_s: Dict[int, float] = field(default_factory=dict)
    finish_s: Dict[int, float] = field(default_factory=dict)
    # hardening counters (all zero on a clean trace)
    timeouts: int = 0            # requests retired past deadline
    sheds: int = 0               # requests shed by admission control
    errors: int = 0              # requests retired by fault isolation
    duplicates: int = 0          # duplicate-rid arrivals ignored
    preempted: int = 0           # KV-block evictions for priority admissions
    step_faults: int = 0         # prefill/decode steps that raised
    knob_faults: int = 0         # scheduler-knob resolutions that raised
    # the serve loop on the measurement timer, summed over its regions
    iterations: int = 0          # passes of the serve loop (engine.iter)
    host_s: float = 0.0          # engine.iter time outside *.device regions
    schedule_s: float = 0.0      # engine.schedule
    prepare_s: float = 0.0       # engine.prefill.prepare + decode.prepare
    commit_s: float = 0.0        # engine.prefill.commit + decode.commit
    queue_wait_s: float = 0.0    # engine.queue: admission to first prefill
    queue_waits: int = 0
    decode_rows_live: int = 0    # requests in each engine.decode.device
    decode_rows_run: int = 0     # the pow2 bucket each one ran as

    @property
    def tok_per_s(self) -> float:
        return self.tokens_out / self.makespan_s if self.makespan_s else 0.0

    def ttft_percentile(self, q: float) -> float:
        if not self.ttft_s:
            return 0.0
        return float(np.percentile(np.asarray(list(self.ttft_s.values())), q))

    def as_metrics(self) -> Dict[str, float]:
        """Flat numeric snapshot for the metrics registry
        (:func:`repro.obs.metrics.snapshot_stats` protocol)."""
        return {
            "tokens_out": self.tokens_out,
            "prefill_steps": self.prefill_steps,
            "decode_steps": self.decode_steps,
            "decode_inplace_steps": self.decode_inplace_steps,
            "prefill_calls": self.prefill_calls,
            "decode_calls": self.decode_calls,
            "prefill_s": self.prefill_s,
            "decode_s": self.decode_s,
            "peak_in_flight": self.peak_in_flight,
            "requests_finished": len(self.finish_s),
            "timeouts": self.timeouts,
            "sheds": self.sheds,
            "errors": self.errors,
            "duplicates": self.duplicates,
            "preempted": self.preempted,
            "step_faults": self.step_faults,
            "knob_faults": self.knob_faults,
            "iterations": self.iterations,
            "host_s": self.host_s,
            "schedule_s": self.schedule_s,
            "prepare_s": self.prepare_s,
            "commit_s": self.commit_s,
            "queue_wait_s": self.queue_wait_s,
            "queue_waits": self.queue_waits,
            "decode_rows_live": self.decode_rows_live,
            "decode_rows_run": self.decode_rows_run,
        }


@dataclass
class RequestResult:
    """Terminal record of one request — exactly one per admitted rid."""

    rid: int
    status: str  # "ok" | "timed_out" | "shed" | "error"
    tokens: List[int] = field(default_factory=list)  # delivered (may be partial)
    detail: str = ""


#: terminal statuses a request can retire with (the property-test alphabet)
REQUEST_STATUSES = ("ok", "timed_out", "shed", "error")


@dataclass
class _Waiting:
    """One queued request plus its hardening state."""

    req: ServingRequest
    # tokens already delivered before a preemption: on re-admission they
    # force the decode trajectory (recompute), so output stays bit-identical
    resume: List[int] = field(default_factory=list)
    preemptions: int = 0
    deadline: Optional[float] = None  # absolute virtual-clock deadline
    # engine.queue, open from admission until the first prefill serves it
    queue: Optional[Region] = None


@dataclass
class _Active:
    """One in-flight request: its block, generated tokens, current context."""

    req: ServingRequest
    block: int
    gen: List[int]
    last_tok: int
    ctx: int  # tokens currently in the row's KV (plen + decodes done)
    replay: List[int] = field(default_factory=list)  # forced recompute tokens
    preemptions: int = 0
    deadline: Optional[float] = None


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------

# scheduler-knob vocabulary: max requests per prefill group, decode
# micro-steps per scheduler iteration, queue ordering, admission ceiling,
# bounded-queue shed policy
SCHED_KNOBS = (
    "prefill_chunk", "interleave", "admission", "max_in_flight", "shed_policy",
)

#: bounded-queue shed policies (the `shed_policy` knob's full domain)
SHED_POLICIES = ("reject-new", "drop-oldest", "deadline-aware")

# virtual-clock advance per no-progress iteration while the watchdog counts
_STALL_TICK_S = 1e-3
# shadow-replay cost penalty per shed request (keeps "shed everything"
# from looking like a great makespan)
_SHED_COST_S = 0.05


class StreamingEngine:
    """Continuous-batching server over a paged KV pool.

    ``serve(requests)`` replays an open-loop trace on a virtual clock: the
    clock advances by each step's *measured* wall time and jumps over idle
    gaps, so time-to-first-token percentiles are deterministic-shaped and
    CI-safe (no sleeps) while still reflecting real step costs.

    After ``serve`` returns, ``self.results`` maps every admitted rid to its
    :class:`RequestResult`; the return value stays rid → tokens for the
    ``ok`` subset (the pre-hardening contract).
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        n_blocks: int = 8,
        max_len: int = 128,
        tuning_db: Optional[TuningDB] = None,
        mesh: Any = None,
        background_tuner: Optional[BackgroundTuner] = None,
        inline_tune: bool = False,
        device_key: bool = False,
        hardened: bool = True,
        queue_limit: Optional[int] = None,
        shed_policy: Optional[str] = None,
        default_ttl_s: Optional[float] = None,
        max_preemptions: int = 3,
        watchdog_limit: int = 200,
        chaos: Any = None,
        timer: Any = None,
        tracer: Any = None,
    ) -> None:
        if shed_policy is not None and shed_policy not in SHED_POLICIES:
            raise ValueError(
                f"shed_policy must be one of {SHED_POLICIES}, got {shed_policy!r}"
            )
        if queue_limit is not None and queue_limit < 1:
            raise ValueError("queue_limit must be >= 1")
        self.cfg = cfg
        self.params = params
        self.max_len = int(max_len)
        self.db = tuning_db or TuningDB()
        self.mesh = mesh
        self.background = background_tuner
        self.inline_tune = inline_tune
        self.device_key = device_key
        self.hardened = bool(hardened)
        self.queue_limit = queue_limit
        self.shed_policy = shed_policy  # pin; None lets the tuner choose
        self.default_ttl_s = default_ttl_s
        self.max_preemptions = int(max_preemptions)
        self.watchdog_limit = int(watchdog_limit)
        self.chaos = chaos
        # observability: ``timer`` is the *measurement* clock — it stamps
        # every engine span and event, and the step times it measures feed
        # the virtual clock; inject e.g. a TickTimer for byte-identical
        # deterministic traces.  ``tracer`` pins a Tracer to this engine
        # (falls back to the process-wide current_tracer())
        self._timer = timer if timer is not None else time.perf_counter
        self.tracer = tracer
        # the regions of the current serve-loop pass (its id, its children),
        # and those of ended passes whose events wait for the next device step
        self._iter = 0
        self._spans: List[Region] = []
        self._ended: List[Region] = []
        self.cache = PagedKVCache(cfg, n_blocks, self.max_len)
        self.degree = DegreeController(max_degree=max(2, n_blocks))
        self.stats = StreamStats()
        self.results: Dict[int, RequestResult] = {}
        self.duplicate_rids: List[int] = []
        self._delivered: Set[int] = set()
        self._hot_tuned: set = set()

        # raw jitted primitives (shared by hot path, candidates, and the
        # scheduler's shadow replay); counted wrappers feed the stats the
        # regression tests assert on.  capacity is pinned so prefilled group
        # caches always match the pool's row layout.  Named functions, so
        # the profiler names the programs jit_engine_prefill/_decode.
        cap = self.max_len

        def engine_prefill(p, b):
            return prefill_fn(p, b, cfg, capacity=cap)

        self._prefill_raw = jax.jit(engine_prefill)
        # donates the pool: whoever calls it rebinds the pool to its output
        self._decode_raw = decode_program(cfg)
        self.inplace = slot_layout(self.cache.pool)  # the slot-write path
        # tuner trials step a pool of their own (made on the first trial):
        # the live one is donated by every step, and a background job's
        # arguments outlive it
        self._trial_pool: Optional[Dict[str, jnp.ndarray]] = None
        self._trial_lock = threading.Lock()
        # set once a decode call has returned the pool that replaces the
        # live one (the step's fault contract, _decode_step)
        self._pool_stepped = False

        def counted_prefill(p, b):
            self.stats.prefill_calls += 1
            return self._prefill_raw(p, b)

        def counted_decode(p, pool, idx, toks):
            self.stats.decode_calls += 1
            return self._decode_raw(p, pool, idx, toks)

        self._prefill = counted_prefill
        self._decode = counted_decode
        self.prefill_op = self._make_prefill_op()
        self.decode_op = self._make_decode_op()
        self.sched_op = self._make_sched_op()
        # last-resort knobs when the tuning path itself fails (hardened):
        # sequential admission, full pool, no reordering, shed newest
        self._fallback_knobs: Dict[str, Any] = {
            "prefill_chunk": 1,
            "interleave": 1,
            "admission": "fcfs",
            "max_in_flight": self.cache.n_blocks,
            "shed_policy": self.shed_policy or "reject-new",
        }

    def _tr(self):
        """Active tracer for engine events (pinned beats process-global)."""
        return self.tracer if self.tracer is not None else current_tracer()

    def _region(self, name: str, **attrs: Any) -> Region:
        """Open a child region of the current serve-loop pass, stamped by
        the measurement timer; its event and its share of the stats wait
        for :meth:`_emit_regions`."""
        r = DeferredRegion(name, self.tracer, self._timer, cat="engine",
                           track="engine", iter=self._iter, **attrs)
        self._spans.append(r)
        return r

    def _close_iter(self, it: Region) -> None:
        """End one pass's ``engine.iter``; it follows its children into the
        regions :meth:`_emit_regions` writes."""
        it.end()
        self.stats.iterations += 1
        self._spans.append(it)
        self._ended += self._spans
        self._spans = []

    def _emit_regions(self) -> None:
        """Write the ended passes' regions to the tracer and add them to the
        stats: host time is a pass outside its device regions.  Called
        between a device step's dispatch and its ``block_until_ready``, so
        the work overlaps the device's and adds nothing to the host time
        between two steps, and when a serve ends."""
        ended = self._ended
        if not ended:
            return
        self._ended = []
        s = self.stats
        device = 0.0
        for r in ended:
            r.emit()
            name = r.name
            d = r.t1 - r.t0
            if name == "engine.iter":  # the last region of its pass
                s.host_s += d - device
                device = 0.0
            elif name.endswith(".device"):
                device += d
                if name == "engine.decode.device":
                    s.decode_rows_live += r.attrs["batch"]
                    s.decode_rows_run += r.attrs["bucket"]
            elif name == "engine.schedule":
                s.schedule_s += d
            elif name.endswith(".prepare"):
                s.prepare_s += d
            elif name.endswith(".commit"):
                s.commit_s += d

    def _open_queue(self, rid: int) -> Region:
        """``engine.queue`` of one admitted request; the first prefill that
        serves it ends it (:meth:`_close_queue`)."""
        return DeferredRegion("engine.queue", self.tracer, self._timer,
                              cat="engine", track="engine", rid=rid)

    def _close_queue(self, w: "_Waiting") -> None:
        if w.queue is not None:
            self.stats.queue_wait_s += w.queue.end(iter=self._iter)
            self.stats.queue_waits += 1
            self._spans.append(w.queue)
            w.queue = None

    # -- registry ops --------------------------------------------------------

    def _degree_domain(self, n: int, moe_pins: bool) -> Tuple[int, ...]:
        if moe_pins and self.cfg.family == "moe":
            return (1,)
        return tuple(d for d in (1, 2, 4) if d <= n and n % d == 0)

    def _make_prefill_op(self) -> AutotunedOp:
        cfg, mesh, cap = self.cfg, self.mesh, self.max_len
        prefill = self._prefill

        def instantiate(point):
            d = int(point.get("degree", 1))
            if d == 1:
                return lambda params, batch: prefill(params, batch)

            def chunked(params, batch):
                outs = [prefill(params, _batch_chunk(batch, i, d)) for i in range(d)]
                logits = jnp.concatenate([o[0] for o in outs], axis=0)
                return logits, _cache_concat([o[1] for o in outs])

            return chunked

        def shape_class(params, batch) -> BasicParams:
            # the exact group size keys the class (degree validity: chunk
            # counts must divide it); capacity keys the pool row layout
            return BasicParams.make(
                kernel="engine_prefill", arch=cfg.name,
                batch=int(batch["tokens"].shape[0]), capacity=cap,
                backend=jax.default_backend(), **mesh_bp_entries(mesh),
            )

        def traffic_class(params, batch) -> TrafficClass:
            B, plen = batch["tokens"].shape
            return TrafficClass.of("prefill", int(B), int(plen))

        def make_region(bp: BasicParams) -> ATRegion:
            # MoE prefill pins degree 1: capacity dispatch couples the group
            space = ParamSpace([
                PerfParam("degree", self._degree_domain(int(bp["batch"]), True))
            ])
            return ATRegion("engine_prefill", space, instantiate)

        spec = register_kernel(
            KernelSpec(
                name=f"engine_prefill/{cfg.name}",
                make_region=make_region,
                shape_class=shape_class,
                tags=("runtime", "serve", "engine"),
                traffic_class=traffic_class,
            ),
            replace=True,
        )
        return AutotunedOp(
            spec, db=self.db, tune=self.inline_tune, warm=False, monitor=False,
            device_key=self.device_key,
        )

    def _make_decode_op(self) -> AutotunedOp:
        """The ``engine_decode`` op.  Its candidates are what the tuner
        measures and warms: the same donating program at the same shapes
        as the hot path, on :meth:`_trial_decode`'s pool, never on the live
        pool they are handed.  The hot path runs the selected degree on the
        live pool itself (:meth:`_decode_exec`)."""
        cfg, mesh, cap = self.cfg, self.mesh, self.max_len

        def instantiate(point):
            d = int(point.get("degree", 1))

            # len_hint is scheduler metadata for the traffic class only
            def trial(params, pool, idx, toks, len_hint=0):
                return self._trial_decode(d, params, idx, toks)

            return trial

        def shape_class(params, pool, idx, toks, len_hint=0) -> BasicParams:
            return BasicParams.make(
                kernel="engine_decode", arch=cfg.name,
                bucket=int(idx.shape[0]), capacity=cap,
                backend=jax.default_backend(), **mesh_bp_entries(mesh),
            )

        def traffic_class(params, pool, idx, toks, len_hint=0) -> TrafficClass:
            # context bucketed on the scheduler's python-tracked max row
            # length: no device sync on the hot path
            return TrafficClass.of("decode", int(idx.shape[0]), max(1, int(len_hint)))

        def make_region(bp: BasicParams) -> ATRegion:
            # vmapped batch-1 rows are independent even for MoE: decode
            # chunks freely at any degree (unlike grouped prefill)
            space = ParamSpace([
                PerfParam("degree", self._degree_domain(int(bp["bucket"]), False))
            ])
            return ATRegion("engine_decode", space, instantiate)

        spec = register_kernel(
            KernelSpec(
                name=f"engine_decode/{cfg.name}",
                make_region=make_region,
                shape_class=shape_class,
                tags=("runtime", "serve", "engine"),
                traffic_class=traffic_class,
            ),
            replace=True,
        )
        return AutotunedOp(
            spec, db=self.db, tune=self.inline_tune, warm=False, monitor=False,
            device_key=self.device_key,
        )

    def _make_sched_op(self) -> AutotunedOp:
        cfg, mesh = self.cfg, self.mesh
        n_blocks = self.cache.n_blocks

        chunk_domain: Tuple[int, ...] = tuple(
            c for c in (2, 4, 1) if c <= n_blocks
        )
        if cfg.family == "moe":
            chunk_domain = (1,)  # grouped MoE prefill couples rows
        if self.shed_policy is not None:
            shed_domain: Tuple[str, ...] = (self.shed_policy,)
        elif self.queue_limit is not None:
            shed_domain = SHED_POLICIES
        else:
            # unbounded queue never sheds: a 1-point domain keeps the
            # search product (and the measured shadow replays) small
            shed_domain = ("reject-new",)
        space = ParamSpace([
            PerfParam("prefill_chunk", chunk_domain),
            PerfParam("interleave", (1, 2)),
            PerfParam("admission", ("fcfs", "sjf")),
            # dict.fromkeys dedupes while keeping order (a 1-block pool
            # would otherwise produce the duplicate domain (1, 1))
            PerfParam("max_in_flight",
                      tuple(dict.fromkeys((n_blocks, max(1, n_blocks // 2))))),
            PerfParam("shed_policy", shed_domain),
        ])

        def instantiate(point):
            # the "kernel body" is just the knob assignment — selection is
            # the product; tuning measures it through the shadow replay
            knobs = dict(point)
            return lambda snapshot: knobs

        def shape_class(snapshot) -> BasicParams:
            return BasicParams.make(
                kernel="serve_scheduler", arch=cfg.name, pool=n_blocks,
                capacity=self.max_len, backend=jax.default_backend(),
                **mesh_bp_entries(mesh),
            )

        def traffic_class(snapshot) -> TrafficClass:
            # the *queue state* is the traffic: waiting depth × prompt scale
            return TrafficClass.of(
                "stream",
                max(1, int(snapshot["waiting"])),
                max(1, int(snapshot["mean_plen"])),
            )

        def cost_factory(region, bp, args, kwargs):
            snapshot = args[0]

            def cost(point) -> float:
                # best-of-2 (the paper's repeat-and-take-stable methodology):
                # the first replay of a point can pay jit compiles for group
                # shapes no other point has produced yet, and the worker
                # thread shares the device with the live serve loop — a
                # single sample would hand the win to whichever point
                # happened to measure on a quiet step
                return min(
                    self._shadow_replay(snapshot, dict(point))
                    for _ in range(2)
                )

            return cost

        spec = register_kernel(
            KernelSpec(
                name=f"serve_scheduler/{cfg.name}",
                make_region=lambda bp: ATRegion("serve_scheduler", space, instantiate),
                shape_class=shape_class,
                cost_factory=cost_factory,
                tags=("runtime", "serve", "engine", "scheduler"),
                traffic_class=traffic_class,
            ),
            replace=True,
        )
        return AutotunedOp(
            spec, db=self.db, tune=self.inline_tune, warm=False, monitor=False,
            device_key=self.device_key,
        )

    def _trial_decode(self, d: int, params, idx, toks) -> jnp.ndarray:
        """One tuner trial of degree ``d``: the rows ``idx`` of the trial
        pool, donated and rebound like the live pool; returns the tokens."""
        with self._trial_lock:
            if self._trial_pool is None or any(
                    v.is_deleted() for v in self._trial_pool.values()):
                self._trial_pool = self.cache.empty_pool()
            new_tok, self._trial_pool = _decode_degree(
                self._decode, d, params, self._trial_pool, idx, toks)
        return new_tok

    # -- tuning hand-off (same contract as Server._resolve) ------------------

    def _resolve(self, op: AutotunedOp, *args: Any) -> OpState:
        if self.background is not None:
            # scheduler knobs jump the tuning queue: a tuned scheduler
            # reshapes every later batch, kernel degrees only their own class
            pri = 1 if op is self.sched_op else 0
            state = self.background.submit(
                op, *args, on_complete=self._on_tuned, priority=pri
            )
        else:
            before = op.states() if self.inline_tune else None
            state = op.resolve(*args)
            if (before is not None and state.tuned
                    and state.bp.fingerprint() not in before):
                self._hot_tuned.add(state.bp.fingerprint())
        if state.tuned or state.from_cache:
            self._on_tuned(state)
        return state

    def _on_tuned(self, state: OpState) -> None:
        """Mirror a degree winner into the DegreeController (the scheduler's
        demoted ``omp_set_num_threads`` policy); scheduler-knob states carry
        no degree and pass through untouched."""
        deg = state.region.selected.get("degree")
        if deg is not None and state.traffic is not None:
            self.degree.set_tuned(state.traffic.label, int(deg))

    @property
    def hot_path_cost_evaluations(self) -> int:
        total = 0
        for op in (self.prefill_op, self.decode_op, self.sched_op):
            for st in op.states().values():
                if st.bp.fingerprint() in self._hot_tuned:
                    total += st.cost_evaluations
        return total

    @property
    def traffic_classes_seen(self) -> List[str]:
        labels = set()
        for op in (self.prefill_op, self.decode_op, self.sched_op):
            for st in op.states().values():
                if st.traffic is not None:
                    labels.add(st.traffic.label)
        return sorted(labels)

    @property
    def tuned_scheduler_classes(self) -> List[str]:
        return sorted(
            st.traffic.label
            for st in self.sched_op.states().values()
            if st.traffic is not None and (st.tuned or st.from_cache)
        )

    # -- scheduling ----------------------------------------------------------

    def _knobs(
        self, waiting: Sequence[_Waiting], active: Dict[int, _Active]
    ) -> Dict[str, Any]:
        pool = [w.req for w in waiting] or [a.req for a in active.values()]
        mean_plen = int(np.mean([len(r.prompt) for r in pool])) if pool else 1
        mean_mnt = int(np.mean([r.max_new_tokens for r in pool])) if pool else 1
        snapshot = {
            "waiting": max(1, len(waiting)),
            "mean_plen": max(1, mean_plen),
            "mean_mnt": max(1, mean_mnt),
        }
        state = self._resolve(self.sched_op, snapshot)
        return dict(state.region.selected)

    def _safe_knobs(
        self, waiting: Sequence[_Waiting], active: Dict[int, _Active]
    ) -> Dict[str, Any]:
        """Hardened knob resolution: a raising or incomplete tuning path
        degrades to the conservative fallback knobs, never crashes serving."""
        if not self.hardened:
            return self._knobs(waiting, active)
        try:
            knobs = self._knobs(waiting, active)
        except Exception:
            self.stats.knob_faults += 1
            return dict(self._fallback_knobs)
        if all(k in knobs for k in
               ("prefill_chunk", "interleave", "admission", "max_in_flight")):
            return knobs
        self.stats.knob_faults += 1
        return dict(self._fallback_knobs)

    def _pick_group(
        self,
        waiting: List[_Waiting],
        active: Dict[int, _Active],
        knobs: Dict[str, Any],
    ) -> List[_Waiting]:
        """Pop the next prefill group: same exact prompt length (no padding
        → reference-exact logits), bounded by the chunk knob, the in-flight
        ceiling, and the allocator's free blocks.  Higher priority admits
        first; at equal priority the admission knob (fcfs/sjf) orders —
        all-zero priorities reduce to the pre-hardening order exactly."""
        room = min(
            int(knobs["prefill_chunk"]),
            int(knobs["max_in_flight"]) - len(active),
            self.cache.free,
        )
        if room < 1 or not waiting:
            return []
        if knobs["admission"] == "sjf":
            order = sorted(
                range(len(waiting)),
                key=lambda i: (-waiting[i].req.priority,
                               waiting[i].req.max_new_tokens,
                               waiting[i].req.arrival_s,
                               waiting[i].req.rid),
            )
        else:  # fcfs — stable sort keeps queue order within a priority level
            order = sorted(
                range(len(waiting)), key=lambda i: -waiting[i].req.priority
            )
        lead_plen = len(waiting[order[0]].req.prompt)
        chosen = []
        for i in order:
            if len(chosen) >= room:
                break
            if len(waiting[i].req.prompt) == lead_plen:
                chosen.append(i)
        group = [waiting[i] for i in chosen]
        for i in sorted(chosen, reverse=True):
            del waiting[i]
        return group

    # -- hardening helpers ---------------------------------------------------

    def _deadline_of(self, r: ServingRequest) -> Optional[float]:
        dl = getattr(r, "deadline_s", None)
        if dl is not None:
            return float(dl)
        if self.default_ttl_s is not None:
            return float(r.arrival_s) + float(self.default_ttl_s)
        return None

    def _retire(
        self,
        rid: int,
        status: str,
        tokens: Sequence[int],
        now: float,
        out: Dict[int, List[int]],
        detail: str = "",
    ) -> bool:
        """Terminal bookkeeping for one request — idempotent: the first
        retirement wins, every later attempt is a no-op.  Always releases
        the rid's block (cache.release is rid-idempotent)."""
        if rid in self.results:
            return False
        self.results[rid] = RequestResult(
            rid=rid, status=status, tokens=list(tokens), detail=detail
        )
        tr = self._tr()
        if tr is not None:
            # exactly one terminal instant per admitted rid (the
            # retire-uniqueness property test keys on this)
            tr.instant(
                "engine.retire", t=self._timer(), cat="engine", track="engine",
                rid=rid, status=status, tokens=len(tokens),
            )
        self.cache.release(rid)
        if status == "ok":
            out[rid] = list(tokens)
            self.stats.finish_s[rid] = now
        elif status == "timed_out":
            self.stats.timeouts += 1
        elif status == "shed":
            self.stats.sheds += 1
        elif status == "error":
            self.stats.errors += 1
        return True

    def _admit(
        self,
        r: ServingRequest,
        seen: Set[int],
        waiting: List[_Waiting],
        out: Dict[int, List[int]],
        now: float,
    ) -> None:
        """Hardened admission: malformed requests retire ``error`` on the
        spot; duplicate rids are counted and ignored (the first occurrence
        owns the rid's result slot)."""
        rid = r.rid
        if rid in seen:
            self.duplicate_rids.append(rid)
            self.stats.duplicates += 1
            return
        seen.add(rid)
        plen = len(r.prompt)
        mnt = int(r.max_new_tokens)
        if plen < 1:
            self._retire(rid, "error", [], now, out, detail="malformed: empty prompt")
            return
        if mnt < 1:
            self._retire(
                rid, "error", [], now, out,
                detail=f"malformed: max_new_tokens {mnt} < 1",
            )
            return
        need = plen + mnt - 1
        if need > self.max_len:
            self._retire(
                rid, "error", [], now, out,
                detail=(f"malformed: prompt {plen} + {mnt} new tokens needs "
                        f"{need} KV slots > capacity {self.max_len}"),
            )
            return
        tr = self._tr()
        if tr is not None:
            tr.instant(
                "engine.admit", t=self._timer(), cat="engine", track="engine",
                rid=rid, plen=plen, max_new_tokens=mnt,
            )
        waiting.append(_Waiting(req=r, deadline=self._deadline_of(r),
                                queue=self._open_queue(rid)))

    def _expire_deadlines(
        self,
        waiting: List[_Waiting],
        active: Dict[int, _Active],
        out: Dict[int, List[int]],
        now: float,
    ) -> None:
        for w in list(waiting):
            if w.deadline is not None and now >= w.deadline:
                waiting.remove(w)
                self._retire(
                    w.req.rid, "timed_out", w.resume, now, out,
                    detail=f"deadline {w.deadline:.4f}s passed in queue",
                )
        for rid in list(active.keys()):
            a = active[rid]
            if a.deadline is not None and now >= a.deadline:
                del active[rid]
                self._retire(
                    rid, "timed_out", a.gen, now, out,
                    detail=f"deadline {a.deadline:.4f}s passed in flight",
                )

    def _shed(
        self,
        waiting: List[_Waiting],
        out: Dict[int, List[int]],
        now: float,
        policy: str,
    ) -> None:
        while len(waiting) > self.queue_limit:
            if policy == "drop-oldest":
                i = 0
            elif policy == "deadline-aware":
                # least slack first: about to miss its deadline anyway;
                # undeadlined requests (infinite slack) shed newest-first
                i = min(
                    range(len(waiting)),
                    key=lambda j: (
                        waiting[j].deadline if waiting[j].deadline is not None
                        else float("inf"),
                        -waiting[j].req.arrival_s,
                        -waiting[j].req.rid,
                    ),
                )
            else:  # reject-new
                i = len(waiting) - 1
            w = waiting.pop(i)
            self._retire(
                w.req.rid, "shed", w.resume, now, out,
                detail=f"queue over limit {self.queue_limit} ({policy})",
            )

    def _maybe_preempt(
        self, waiting: List[_Waiting], active: Dict[int, _Active],
    ) -> bool:
        """Evict the lowest-priority in-flight request when the pool is
        exhausted and a strictly higher-priority admission is blocked.  The
        victim requeues at the front with its generated tokens as replay
        state; ``max_preemptions`` evictions make it non-evictable
        (anti-livelock)."""
        if not waiting or not active or self.cache.free > 0:
            return False
        cand_pri = max(int(w.req.priority) for w in waiting)
        eligible = [
            a for a in active.values() if a.preemptions < self.max_preemptions
        ]
        if not eligible:
            return False
        victim = min(
            eligible,
            key=lambda a: (int(a.req.priority), -a.req.arrival_s, -a.req.rid),
        )
        if cand_pri <= int(victim.req.priority):
            return False
        rid = victim.req.rid
        tr = self._tr()
        if tr is not None:
            tr.instant(
                "engine.preempt", t=self._timer(), cat="engine", track="engine",
                rid=rid, priority=int(victim.req.priority),
                preemptions=victim.preemptions + 1,
            )
        del active[rid]
        self.cache.release(rid)
        waiting.insert(0, _Waiting(
            req=victim.req,
            resume=list(victim.gen),
            preemptions=victim.preemptions + 1,
            deadline=victim.deadline,
        ))
        self.stats.preempted += 1
        return True

    def _idle_advance(
        self,
        now: float,
        reqs: Sequence[ServingRequest],
        cursor: int,
        waiting: Sequence[_Waiting],
        active: Dict[int, _Active],
    ) -> float:
        """No progress this iteration (hardened): jump the virtual clock to
        the nearest future event (arrival or deadline) so timeouts and
        admissions stay reachable; a fixed tick when there is none."""
        targets: List[float] = []
        if cursor < len(reqs):
            targets.append(reqs[cursor].arrival_s)
        targets.extend(w.deadline for w in waiting if w.deadline is not None)
        targets.extend(
            a.deadline for a in active.values() if a.deadline is not None
        )
        future = [t for t in targets if t > now]
        nxt = max(min(future) if future else now, now + _STALL_TICK_S)
        self.stats.idle_s += nxt - now
        return nxt

    def _state_dump(
        self,
        waiting: Sequence[_Waiting],
        active: Dict[int, _Active],
        now: float,
        idle_iters: int,
    ) -> str:
        return (
            f"engine stalled: no progress for {idle_iters} iterations "
            f"(watchdog_limit={self.watchdog_limit}) at t={now:.4f}s | "
            f"waiting={[w.req.rid for w in waiting]} "
            f"active={sorted(active)} "
            f"free_blocks={self.cache.free}/{self.cache.n_blocks} "
            f"block_table={dict(self.cache.block_table)} "
            f"retired={len(self.results)} "
            f"chaos_holding={getattr(self.chaos, 'holding', 0)}"
        )

    # -- serve ---------------------------------------------------------------

    def serve(self, requests: Sequence[ServingRequest]) -> Dict[int, List[int]]:
        """Greedy-decode an open-loop trace; returns rid → generated tokens
        for the ``ok`` requests (``self.results`` has every terminal
        status)."""
        self.results = {}
        self.duplicate_rids = []
        self._delivered = set()
        if not self.hardened:
            # pre-hardening contract: malformed input raises to the caller
            check_unique_rids(requests)
            for r in requests:
                need = len(r.prompt) + r.max_new_tokens - 1
                if need > self.max_len:
                    raise ValueError(
                        f"request {r.rid}: prompt {len(r.prompt)} + "
                        f"{r.max_new_tokens} new tokens needs {need} KV slots "
                        f"> capacity {self.max_len}"
                    )
        reqs = sorted(requests, key=lambda r: (r.arrival_s, r.rid))
        out: Dict[int, List[int]] = {}
        if not reqs:
            return out
        serve_region = Region("engine.serve", self.tracer, self._timer,
                              cat="engine", track="engine", requests=len(reqs))
        try:
            self._serve_loop(reqs, out)
        finally:
            self._emit_regions()
            serve_region.close(retired=len(self.results),
                               tokens_out=self.stats.tokens_out)
        return out

    def _serve_loop(
        self, reqs: List[ServingRequest], out: Dict[int, List[int]]
    ) -> None:
        now = reqs[0].arrival_s
        t_start = now
        cursor = 0
        waiting: List[_Waiting] = []
        active: Dict[int, _Active] = {}
        seen: Set[int] = set()
        idle_iters = 0

        while cursor < len(reqs) or waiting or active:
            self._iter = self.stats.iterations
            it = DeferredRegion("engine.iter", self.tracer, self._timer,
                                cat="engine", track="engine", iter=self._iter,
                                waiting=len(waiting), active=len(active))
            try:
                with self._region("engine.schedule"):
                    while cursor < len(reqs) and reqs[cursor].arrival_s <= now:
                        r = reqs[cursor]
                        cursor += 1
                        if self.hardened:
                            self._admit(r, seen, waiting, out, now)
                        else:
                            waiting.append(_Waiting(
                                req=r, queue=self._open_queue(r.rid)))
                    if self.chaos is not None:
                        self.chaos.tick(self.cache)
                    if self.hardened:
                        self._expire_deadlines(waiting, active, out, now)
                    if not waiting and not active:
                        if cursor < len(reqs):
                            # nothing runnable: the open-loop clock jumps to
                            # the next arrival instead of sleeping
                            self.stats.idle_s += reqs[cursor].arrival_s - now
                            now = reqs[cursor].arrival_s
                            continue
                        break  # everything retired; chaos may still hold blocks
                    n_retired = len(self.results)
                    knobs = self._safe_knobs(waiting, active)
                    if self.hardened and self.queue_limit is not None:
                        policy = self.shed_policy or str(
                            knobs.get("shed_policy", "reject-new")
                        )
                        self._shed(waiting, out, now, policy)
                    if self.hardened:
                        self._maybe_preempt(waiting, active)
                    group = self._pick_group(waiting, active, knobs)

                progressed = False
                if group:
                    now = self._prefill_step(group, active, waiting, out, now)
                    progressed = True
                for _ in range(int(knobs["interleave"])):
                    if not active:
                        break
                    now = self._decode_step(active, out, now)
                    progressed = True
                if len(self.results) > n_retired:
                    progressed = True  # sheds/timeouts/errors are retirements
                self.stats.peak_in_flight = max(
                    self.stats.peak_in_flight, len(active)
                )
                if progressed:
                    idle_iters = 0
                else:
                    if not self.hardened:
                        # waiting but no admission room and nothing decoding
                        # can only mean a stuck ceiling; active==∅ implies
                        # room ≥ 1
                        raise RuntimeError("scheduler stalled: no admissible work")
                    idle_iters += 1
                    if idle_iters > self.watchdog_limit:
                        raise EngineStalled(
                            self._state_dump(waiting, active, now, idle_iters)
                        )
                    now = self._idle_advance(now, reqs, cursor, waiting, active)
            finally:
                self._close_iter(it)
        if self.chaos is not None:
            self.chaos.drain(self.cache)
        self.stats.makespan_s += now - t_start

    # -- prefill -------------------------------------------------------------

    def _prefill_step(
        self,
        group: List[_Waiting],
        active: Dict[int, _Active],
        waiting: List[_Waiting],
        out: Dict[int, List[int]],
        now: float,
    ) -> float:
        if not self.hardened:
            return self._prefill_exec(group, active, waiting, out, now)
        try:
            return self._prefill_exec(group, active, waiting, out, now)
        except Exception as exc:
            self.stats.step_faults += 1
            # a row insert that consumed the pool took every in-flight
            # row's KV with it (the contract of _decode_step)
            self._after_dispatch(active, [], out, now, exc, step="prefill")
            # undo partial state: blocks allocated to members that never
            # activated (cache.release is rid-idempotent)
            for w in group:
                if w.req.rid not in active:
                    self.cache.release(w.req.rid)
            # isolate: retry each not-yet-settled member on its own; a
            # member that raises again is the implicated request
            for w in group:
                rid = w.req.rid
                if (rid in self.results or rid in active
                        or any(q.req.rid == rid for q in waiting)):
                    continue
                try:
                    now = self._prefill_exec([w], active, waiting, out, now)
                except Exception as exc:
                    self._retire(
                        rid, "error", w.resume, now, out,
                        detail=f"prefill fault: {type(exc).__name__}: {exc}",
                    )
            return now

    def _prefill_exec(
        self,
        group: List[_Waiting],
        active: Dict[int, _Active],
        waiting: List[_Waiting],
        out: Dict[int, List[int]],
        now: float,
    ) -> float:
        reqs = [w.req for w in group]
        plen = len(reqs[0].prompt)
        with self._region("engine.prefill.prepare"):
            batch = build_batch_inputs(self.cfg, reqs, plen)
            pstate = self._resolve(self.prefill_op, self.params, batch)
            label = pstate.traffic.label if pstate.traffic else "prefill"
            if self.chaos is not None:
                self.chaos.before_step("prefill", [r.rid for r in reqs])
        for w in group:
            self._close_queue(w)
        # the device region's two timer reads bracket dispatch through
        # block_until_ready: they are the step's dt, nothing else ticks
        with self._region("engine.prefill.device", batch=len(reqs),
                          plen=plen) as dev:
            with self.degree.region(label):
                logits, cache = pstate.region(self.params, batch)
                self._emit_regions()  # while the device runs the step
                logits.block_until_ready()
        dt = dev.t1 - dev.t0
        self.stats.prefill_s += dt
        self.stats.prefill_steps += 1
        now += dt
        if self.chaos is not None:
            now += self.chaos.step_delay()
        tr = self._tr()
        if tr is not None:
            # emitted as the step's tokens exist, before any commit work
            tr.complete(
                "engine.prefill", dev.t0, dev.t1, cat="engine",
                track="engine", rids=[r.rid for r in reqs], batch=len(reqs),
                plen=plen, label=label,
            )
        with self._region("engine.prefill.commit"):
            if pstate.selector is not None and pstate.selector.observe(dt):
                self._on_tuned(pstate)
            toks = np.asarray(jnp.argmax(logits, axis=-1)).astype(np.int32)
            # a resumed (preempted) request forces its first delivered token:
            # greedy decode reproduces it anyway, forcing guarantees bit-match
            first_toks: Dict[int, int] = {}
            for i, w in enumerate(group):
                r = w.req
                tok0 = int(w.resume[0]) if w.resume else int(toks[i])
                first_toks[r.rid] = tok0
                if r.rid not in self._delivered:
                    self._delivered.add(r.rid)
                    self.stats.ttft_s[r.rid] = now - r.arrival_s
                    self.stats.tokens_out += 1
                if r.max_new_tokens <= 1:
                    # done at first token: never allocates a block
                    self._retire(r.rid, "ok", [tok0], now, out)
            keep_idx: List[int] = []
            activated: List[_Waiting] = []
            for i, w in enumerate(group):
                if w.req.max_new_tokens <= 1:
                    continue
                try:
                    self.cache.allocate(w.req.rid)
                except KVPoolExhausted:
                    if not self.hardened:
                        raise
                    # pool raced away (e.g. chaos squeeze between pick and
                    # allocate): requeue at the front with recompute state
                    resume = list(w.resume) if w.resume else [first_toks[w.req.rid]]
                    waiting.insert(0, _Waiting(
                        req=w.req, resume=resume,
                        preemptions=w.preemptions, deadline=w.deadline,
                    ))
                    continue
                keep_idx.append(i)
                activated.append(w)
            if activated:
                if len(keep_idx) < len(group):
                    # drop the retired/deferred rows before scattering
                    cache = _take_rows(cache, np.asarray(keep_idx, np.int32))
                self.cache.insert([w.req.rid for w in activated], cache)
                for w in activated:
                    r = w.req
                    tok0 = first_toks[r.rid]
                    active[r.rid] = _Active(
                        req=r, block=self.cache.block_of(r.rid),
                        gen=[tok0], last_tok=tok0, ctx=plen,
                        replay=list(w.resume[1:]),
                        preemptions=w.preemptions, deadline=w.deadline,
                    )
        return now

    # -- decode --------------------------------------------------------------

    def _decode_step(
        self, active: Dict[int, _Active], out: Dict[int, List[int]], now: float
    ) -> float:
        """One decode step of every active row, hardened by this contract.

        * A fault before the step's call has consumed the pool (the chaos
          injector's ``before_step``, preparation, a call that raises before
          running) leaves the pool as it was: the rows are stepped again one
          at a time, and a row that raises again retires ``error``.
        * The call donates the pool.  Once it has consumed it, a fault never
          touches the deleted buffers: the rows of the step still in flight
          retire ``error`` with the tokens they delivered.  If the call
          returned, the pool is its output and the other rows keep their
          KV; if it consumed the pool and returned nothing, the pool is made
          anew, empty, and every in-flight request retires ``error``.
        """
        if not self.hardened:
            return self._decode_exec(active, out, now)
        try:
            return self._decode_exec(active, out, now)
        except Exception as exc:
            self.stats.step_faults += 1
            if self._after_dispatch(active, list(active), out, now, exc):
                return now
            # isolate: step each row on its own; a row that raises again is
            # the implicated request
            for rid in list(active.keys()):
                if rid not in active:
                    continue
                try:
                    now = self._decode_exec(active, out, now, only=[rid])
                except Exception as exc:
                    if self._after_dispatch(active, [rid], out, now, exc):
                        continue
                    a = active.pop(rid)
                    self._retire(
                        rid, "error", a.gen, now, out,
                        detail=f"decode fault: {type(exc).__name__}: {exc}",
                    )
            return now

    def _after_dispatch(
        self,
        active: Dict[int, _Active],
        rids: Sequence[int],
        out: Dict[int, List[int]],
        now: float,
        exc: Exception,
        step: str = "decode",
    ) -> bool:
        """The fault contract once a step's call has consumed the pool
        (:meth:`_decode_step`); False, doing nothing, if it has not."""
        lost = self.cache.lost()
        if not (lost or (step == "decode" and self._pool_stepped)):
            return False
        if lost:
            self.cache.reset()
            rids = list(active)
        for rid in rids:
            a = active.pop(rid, None)
            if a is not None:
                self._retire(
                    rid, "error", a.gen, now, out,
                    detail=(f"{step} fault after dispatch"
                            f"{' (pool lost)' if lost else ''}: "
                            f"{type(exc).__name__}: {exc}"),
                )
        return True

    def _decode_exec(
        self,
        active: Dict[int, _Active],
        out: Dict[int, List[int]],
        now: float,
        only: Optional[Sequence[int]] = None,
    ) -> float:
        self._pool_stepped = False
        rids = [
            r for r in (list(active.keys()) if only is None else only)
            if r in active
        ]
        act = [active[r] for r in rids]
        A = len(act)
        if A == 0:
            return now
        bucket = bucket_pow2(A)
        with self._region("engine.decode.prepare"):
            # pad to the pow2 bucket by replicating row 0: replicas compute
            # the identical update, so duplicate scatter indices write equal
            # values (well-defined) and the compile cache stays per-bucket
            idx = [a.block for a in act] + [act[0].block] * (bucket - A)
            toks = [a.last_tok for a in act] + [act[0].last_tok] * (bucket - A)
            idx_arr = jnp.asarray(idx, jnp.int32)
            tok_arr = jnp.asarray(toks, jnp.int32)
            len_hint = max(a.ctx for a in act)
            dstate = self._resolve(
                self.decode_op, self.params, self.cache.pool, idx_arr, tok_arr,
                len_hint,
            )
            label = dstate.traffic.label if dstate.traffic else "decode"
            if self.chaos is not None:
                self.chaos.before_step("decode", rids)
        degree = int(dstate.region.selected.get("degree", 1))
        with self._region("engine.decode.device", batch=A, bucket=bucket,
                          path="slot" if self.inplace else "rows") as dev:
            with self.degree.region(label):
                new_tok, self.cache.pool = _decode_degree(
                    self._decode, degree, self.params, self.cache.pool,
                    idx_arr, tok_arr,
                )
                self._pool_stepped = True
                self._emit_regions()  # while the device runs the step
                new_tok.block_until_ready()
        dt = dev.t1 - dev.t0
        self.stats.decode_s += dt
        self.stats.decode_steps += 1
        self.stats.decode_inplace_steps += int(self.inplace)
        now += dt
        if self.chaos is not None:
            now += self.chaos.step_delay()
        tr = self._tr()
        if tr is not None:
            # emitted as the step's tokens exist, before any commit work
            tr.complete(
                "engine.decode", dev.t0, dev.t1, cat="engine", track="engine",
                rids=rids, batch=A, bucket=bucket, label=label,
            )
        with self._region("engine.decode.commit"):
            if dstate.selector is not None and dstate.selector.observe(dt):
                self._on_tuned(dstate)
            new_np = np.asarray(new_tok)[:A]
            for a, t in zip(act, new_np):
                if a.replay:
                    # recompute of an already-delivered token (post-
                    # preemption): force the original trajectory, don't
                    # re-count delivery
                    tok = int(a.replay.pop(0))
                else:
                    tok = int(t)
                    self.stats.tokens_out += 1
                a.gen.append(tok)
                a.last_tok = tok
                a.ctx += 1
                if len(a.gen) >= a.req.max_new_tokens:
                    self._retire(a.req.rid, "ok", a.gen, now, out)
                    del active[a.req.rid]
        return now

    # -- scheduler-knob cost: measured shadow replay -------------------------

    def _shadow_replay(self, snapshot: Dict[str, int], knobs: Dict[str, Any]) -> float:
        """Cost of one knob assignment: replay a deterministic mini-trace
        shaped like the snapshot's traffic class through the raw jitted
        primitives (no op dispatch, no degree bracket, fresh pool) on a
        virtual clock.  Runs on the BackgroundTuner's worker thread; cost =
        virtual makespan + p99 TTFT + a fixed penalty per shed request, so
        knobs that starve admissions, waste decode slots, or shed their way
        to a short makespan all lose.
        """
        plen = max(1, min(int(snapshot["mean_plen"]), self.max_len - 6))
        n = int(min(max(2, snapshot["waiting"]), 4))
        rng = np.random.default_rng(
            np.random.SeedSequence([plen, n, 0x5C4ED])
        )
        mini: List[ServingRequest] = []
        for i in range(n):
            mnt = max(1, min(int(snapshot["mean_mnt"]) + 2 * (i % 2), 5))
            prompt = rng.integers(
                0, self.cfg.vocab_size - 1, size=plen
            ).astype(np.int32)
            # alternating finite deadlines give the deadline-aware shed
            # policy something to distinguish itself on
            mini.append(ServingRequest(
                rid=i, prompt=prompt, max_new_tokens=mnt,
                deadline_s=0.05 * (i + 1) if i % 2 else None,
            ))

        shadow = PagedKVCache(self.cfg, self.cache.n_blocks, self.max_len)
        waiting = list(mini)
        shed = 0
        if self.queue_limit is not None:
            # bound the shadow queue below the mini-trace size so the shed
            # policies produce genuinely different traces (and costs)
            limit = max(1, min(int(self.queue_limit), n - 1))
            policy = str(knobs.get("shed_policy", "reject-new"))
            while len(waiting) > limit:
                if policy == "drop-oldest":
                    j = 0
                elif policy == "deadline-aware":
                    j = min(
                        range(len(waiting)),
                        key=lambda q: (
                            waiting[q].deadline_s
                            if waiting[q].deadline_s is not None
                            else float("inf"),
                            -waiting[q].rid,
                        ),
                    )
                else:  # reject-new
                    j = len(waiting) - 1
                waiting.pop(j)
                shed += 1
        active: Dict[int, _Active] = {}
        now = 0.0
        ttft: List[float] = []
        while waiting or active:
            room = min(
                int(knobs["prefill_chunk"]),
                int(knobs["max_in_flight"]) - len(active),
                shadow.free,
            )
            if waiting and room >= 1:
                if knobs["admission"] == "sjf":
                    waiting.sort(key=lambda r: (r.max_new_tokens, r.rid))
                group, waiting = waiting[:room], waiting[room:]
                batch = build_batch_inputs(self.cfg, group, plen)
                t0 = time.perf_counter()
                logits, cache = self._prefill_raw(self.params, batch)
                logits.block_until_ready()
                now += time.perf_counter() - t0
                toks = np.asarray(jnp.argmax(logits, axis=-1)).astype(np.int32)
                survivors = [r for r in group if r.max_new_tokens > 1]
                ttft.extend(now for _ in group)
                if survivors:
                    for r in survivors:
                        shadow.allocate(r.rid)
                    if len(survivors) < len(group):
                        keep = np.asarray(
                            [i for i, r in enumerate(group)
                             if r.max_new_tokens > 1], np.int32,
                        )
                        cache = _take_rows(cache, keep)
                    shadow.insert([r.rid for r in survivors], cache)
                    for i, r in enumerate(group):
                        if r.max_new_tokens > 1:
                            active[r.rid] = _Active(
                                req=r, block=shadow.block_of(r.rid),
                                gen=[int(toks[i])], last_tok=int(toks[i]),
                                ctx=plen,
                            )
            for _ in range(int(knobs["interleave"])):
                if not active:
                    break
                act = list(active.values())
                A = len(act)
                bucket = bucket_pow2(A)
                idx = [a.block for a in act] + [act[0].block] * (bucket - A)
                tk = [a.last_tok for a in act] + [act[0].last_tok] * (bucket - A)
                t0 = time.perf_counter()
                new_tok, shadow.pool = self._decode_raw(
                    self.params, shadow.pool,
                    jnp.asarray(idx, jnp.int32), jnp.asarray(tk, jnp.int32),
                )
                new_tok.block_until_ready()
                now += time.perf_counter() - t0
                new_np = np.asarray(new_tok)[:A]
                for a, t in zip(act, new_np):
                    a.gen.append(int(t))
                    a.last_tok = int(t)
                    if len(a.gen) >= a.req.max_new_tokens:
                        shadow.release(a.req.rid)
                        del active[a.req.rid]
        p99 = float(np.percentile(np.asarray(ttft), 99)) if ttft else 0.0
        return now + p99 + _SHED_COST_S * shed


# ---------------------------------------------------------------------------
# The decode program: vmapped batch-1 rows over the donated pool
# ---------------------------------------------------------------------------


def slot_layout(pool: Dict[str, Any]) -> bool:
    """Whether a pool holds attention KV alone: leaves ``k``, ``v`` laid out
    ``(n_blocks, L, 1, cap, kv, hd)`` and ``len``, so one decode step
    changes one slot per layer of each row (the dense, vlm and moe caches).
    Recurrent state (``conv``/``h``, ring buffers) has no such slot."""
    return set(pool) == {"k", "v", "len"} and pool["k"].ndim == 6


def _make_decode_rows(cfg: ModelConfig):
    """The engine's decode step ``(params, pool, idx, toks) -> (new_tok,
    pool)`` over the rows ``idx`` of the pool, which the engine donates, so
    the compiled step updates it in place.

    Every row is the model's batch-1 cache with its own scalar ``len``, and
    the per-row work is ``jax.vmap`` of the batch-1 layer math, so rows at
    different positions advance independently (and MoE rows route alone).
    Two paths, by the pool's layout (:func:`slot_layout`):

    * **slot** (attention KV): the layer scan runs outside and the rows
      inside it.  Layer ``i`` reads its ``(bucket, 1, cap, kv, hd)`` K/V of
      the ``idx`` rows from the pool, puts the new K/V at slot ``len`` and
      attends over it (:func:`~repro.models.transformer.decode_attn_layer`,
      the body ``decode_step`` runs too); the scan emits only the new slot.
      After the scan one scatter writes ``(bucket, L, kv, hd)`` values each
      into K and V at ``len``, and ``len + 1``; whole rows are never written.
    * **rows** (recurrent state): gather the rows, ``vmap(decode_fn)``,
      scatter the whole updated rows back.

    Pow2 padding repeats row 0's index; its replicas compute identical
    values, so the duplicate scatter indices write equal values.
    """

    def engine_decode(params, pool, idx, toks):
        if slot_layout(pool):
            return _decode_slots(cfg, params, pool, idx, toks)
        return _decode_rows(cfg, params, pool, idx, toks)

    return engine_decode


def _decode_slots(cfg: ModelConfig, params, pool, idx, toks):
    lens = pool["len"][idx]
    L, cap = pool["k"].shape[1], pool["k"].shape[3]

    x, positions = jax.vmap(
        lambda tok, ln: decode_inputs(params, tok[None, None], ln, cfg)
    )(toks, lens)

    def rows_of(leaf, i):
        # layer i of each row, a dynamic slice a row: the TPU compiler
        # would split one gather of them into copies of the whole pool
        return jnp.stack([leaf[idx[b], i] for b in range(idx.shape[0])])

    def layer(h, inputs):
        lp, i = inputs

        def row(h1, ck, cv, pos, ln):
            h1, _, kv = decode_attn_layer(h1, lp, ck, cv, cfg, pos, ln)
            return h1, kv

        return jax.vmap(row)(h, rows_of(pool["k"], i), rows_of(pool["v"], i),
                             positions, lens)

    x, (ks, vs) = lax.scan(layer, x, (params["layers"], jnp.arange(L)))
    logits = jax.vmap(lambda h: decode_logits(params, h, cfg)[0])(x)
    new_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    # slot len of layer l of row idx[b]; clamped as the in-row update is
    at = (idx[:, None], jnp.arange(L)[None, :], 0,
          jnp.minimum(lens, cap - 1)[:, None])
    new_pool = {
        "k": pool["k"].at[at].set(jnp.swapaxes(ks[:, :, 0, 0], 0, 1)),
        "v": pool["v"].at[at].set(jnp.swapaxes(vs[:, :, 0, 0], 0, 1)),
        "len": pool["len"].at[idx].set(lens + 1),
    }
    return new_tok, new_pool


def _decode_rows(cfg: ModelConfig, params, pool, idx, toks):
    rows = {k: v[idx] for k, v in pool.items()}

    def body(tok, row):
        logits, new_row = decode_fn(params, {"tokens": tok[None, None]}, row, cfg)
        return logits[0], new_row

    logits, new_rows = jax.vmap(body)(toks, rows)
    new_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    new_pool = {k: pool[k].at[idx].set(new_rows[k]) for k in pool}
    return new_tok, new_pool


def decode_program(cfg: ModelConfig):
    """The jitted decode step the engine runs: the pool (argument 1) is
    donated, so the program's pool output aliases its input."""
    return jax.jit(_make_decode_rows(cfg), donate_argnums=1)


def _decode_degree(decode, d: int, params, pool, idx, toks):
    """Decode the rows ``idx`` in ``d`` chunks in turn (the ``degree``
    candidate), each chunk stepping the pool the one before returned."""
    if d == 1:
        return decode(params, pool, idx, toks)
    n = idx.shape[0] // d
    outs = []
    for i in range(d):
        sl = slice(i * n, (i + 1) * n)
        tok_i, pool = decode(params, pool, idx[sl], toks[sl])
        outs.append(tok_i)
    return jnp.concatenate(outs, axis=0), pool


def _take_rows(cache: Dict[str, Any], keep: np.ndarray) -> Dict[str, Any]:
    """Select a row subset of a batched cache dict along each leaf's batch
    axis (scalar leaves pass through)."""
    out = {}
    for k, v in cache.items():
        ax = cache_batch_axis(k, getattr(v, "ndim", 0))
        out[k] = v if ax is None else jnp.take(v, jnp.asarray(keep), axis=ax)
    return out
