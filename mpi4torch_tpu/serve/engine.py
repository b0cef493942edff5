"""The continuous-batching serving engine.

One fixed-capacity **slot table** (``ServeConfig.slots`` concurrent
sequences), ONE cache manager (a pool of pages behind a per-slot block
table, :mod:`.paging`; ``ServeConfig.block_size=0`` is that pool at one
page of ``max_seq`` tokens a slot), one compiled decode-step program, a
host-driven loop:

* **admission** — each step starts by filling free slots from the
  request queue (the active :data:`POLICIES` entry picks the order).
  A request is admitted by a per-request TP prefill at its TRUE prompt
  length (exactly what ``generate()`` does — the engine's first token
  and the oracle's come from the same batched-prefill logits), whose
  cache rows are installed into the slot's pages.  Prefill compiles per
  distinct prompt length, like ``generate`` itself; the DECODE loop
  never retraces.
* **decode** — one :func:`~mpi4torch_tpu.serve.decode_step_paged` call
  over the whole slot table per step: static shapes, per-slot
  positions, free slots riding along unmapped and masked
  (ops/ragged masks; see kv.py).  The step ends in
  :func:`select_rows`: every slot's token is chosen where the logits
  are, with ``models/transformer.select_token`` under the exact
  per-request key discipline of ``generate()`` — engine tokens equal
  per-request ``generate()`` tokens by construction — and the host
  fetches ``(slots,)`` tokens, never the ``(slots, vocab)`` table.
  The slot state the step reads (tokens, positions, live mask, block
  table, keys) stays on the device and the step hands it back
  advanced; the host's arrays stay the truth, and a step uploads only
  the arrays in which they differ from what the device holds
  (:meth:`Engine._step_inputs`): a decode step behind a decode step
  sends nothing and fetches one array.
* **eviction** — a slot finishes on EOS or its token budget; its pages
  are unmapped and the slot returns to the free pool, ready
  for the next admission in the SAME step loop — no batch barrier,
  which is the whole point of continuous batching.

Two execution modes behind one engine:

* **eager / Mode B** — construct the engine inside a ``run_ranks``
  rank thread (or on the plain single-device world): collectives run
  through the eager rendezvous, so PR 7 fault plans compose at the
  chokepoints — a ``rank_death`` mid-decode surfaces as an attributed
  ``RankFailedError`` on every survivor, never a hang.
* **SPMD / Mode A** — ``Engine(..., spmd=True, nranks=4)`` (or
  ``mesh=``/``axis_name=``): the decode step is ONE ``run_spmd``
  program; per-rank KV shards and the slot state ride between steps
  as a stacked ``(size, ...)`` leading axis (sliced by rank in-trace,
  re-stacked by the rank-major output convention — on the CPU harness
  this means each device holds the full stacked cache; a production
  deployment would pin the axis sharded, which changes none of the
  semantics here).  :meth:`Engine.lower_step` exposes the lowered step
  for the deterministic exposure/latency censuses.
"""

from __future__ import annotations

import functools
import math
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from ..comm import COMM_WORLD
from ..models.transformer import GQA, TransformerConfig, select_token
from ..ops import paged_attention as _paged_attn
from ..runtime import CommError
from ..utils import profiling as _prof
from . import kv as _kv
from . import paging as _paging

__all__ = ["ServeConfig", "Request", "Engine", "POLICIES",
           "SHED_POLICIES", "QueueFullError", "select_rows",
           "STATUS_OK", "STATUS_EXPIRED", "STATUS_SHED"]

# Typed result statuses (ISSUE 15): every finished rid carries one.
# ``expired`` = the request's deadline passed (queued requests return
# the bare prompt; slotted ones keep the tokens emitted so far — a
# bitwise PREFIX of the per-request generate() oracle).  ``shed`` =
# evicted from the queue by the overload shed policy to admit newer
# traffic.
STATUS_OK = "ok"
STATUS_EXPIRED = "deadline_expired"
STATUS_SHED = "shed"

# The spans of one :meth:`Engine.step` (``ServeStats.span``; the names
# are an API — doc/serving.md says what each covers and whether it ends
# in a device sync).  One span per phase, never one per page, cache
# leaf or token: ten a step that decodes (``dispatch`` and ``fetch``
# each with two children) plus four per admitted request or chunk.
SPAN_STEP = _prof.STEP_SPAN
SPAN_EXPIRE = SPAN_STEP + ".expire"
SPAN_ADMIT = SPAN_STEP + ".admit"
SPAN_PLAN = SPAN_ADMIT + ".plan"
SPAN_PREFILL = SPAN_ADMIT + ".prefill"
SPAN_INSTALL = SPAN_ADMIT + ".install"
SPAN_FIRST_TOKEN = SPAN_ADMIT + ".first_token"
SPAN_DISPATCH = SPAN_STEP + ".decode.dispatch"
SPAN_DISPATCH_INPUTS = SPAN_DISPATCH + ".inputs"
SPAN_DISPATCH_CALL = SPAN_DISPATCH + ".call"
SPAN_FETCH = SPAN_STEP + ".decode.fetch"
SPAN_FETCH_TOKENS = SPAN_FETCH + ".tokens"
SPAN_FETCH_COUNTERS = SPAN_FETCH + ".counters"
SPAN_SELECT = SPAN_STEP + ".decode.select"
# Outside a step (profiling.setup_spans): the constructor's phases, and
# the compilations program_texts() makes to read the programs' texts.
SPAN_CONSTRUCT = "mpi4torch.serve.construct"
SPAN_SHARD = SPAN_CONSTRUCT + ".shard"
SPAN_SHARD_TOP = SPAN_SHARD + ".top"
SPAN_SHARD_TAKE = SPAN_SHARD + ".take"
SPAN_SHARD_LAYER = SPAN_SHARD + ".layer"
SPAN_POOL = SPAN_CONSTRUCT + ".pool"
SPAN_BUILD_INSTALL = SPAN_CONSTRUCT + ".install"
SPAN_PROGRAM_TEXTS = "mpi4torch.serve.program_texts"


def select_rows(logits, keys, temperature: float, top_k: int):
    """Every slot's decoding choice from the ``(slots, vocab)`` logits
    of one decode step, inside the program that computed them (or in
    one call behind an eager step): to each row exactly what
    :meth:`Engine._select` applies to one — split the slot's key,
    ``select_token(row[None, :], sub, temperature, top_k, int32)`` —
    under ``jax.vmap`` over the ``(slots, ...)`` per-slot ``keys``.
    Returns ``(tokens (slots,) int32, new_keys)``, bit for bit what the
    per-slot calls give (first maximum on ties).  ``keys=None`` is the
    greedy engine's form: greedy reads no key, so none goes in and
    ``None`` comes back.

    The table is held as it was computed, rounded to its dtype, before
    anything is chosen from it (``optimization_barrier``): fused into
    the unembedding product a TPU compares the product's unrounded
    accumulators, and the near-ties of bfloat16 logits then fall
    otherwise than for ``_select`` on the fetched table."""
    logits = jax.lax.optimization_barrier(logits)
    if keys is None:
        return select_token(logits, None, temperature, top_k,
                            jnp.int32), None

    def one(row, key):
        key, sub = jax.random.split(key)
        return select_token(row[None, :], sub, temperature, top_k,
                            jnp.int32)[0], key

    return jax.vmap(one)(logits, keys)


def _advanced(xp, state: dict, toks, keys) -> dict:
    """The slot state behind a decode step that chose ``toks`` (and
    ``keys``, None where the engine is greedy): a live slot takes its
    chosen token, the next position and its new key; a free one keeps
    what it had; the mask and the table stay as they came.  Written
    once for both sides of the boundary: the compiled step ends in it
    (``xp`` is ``jax.numpy``), and the host applies it to its memory of
    what the device holds (``numpy``)."""
    live = state["live"]
    new = dict(state,
               tokens=xp.where(live, toks, state["tokens"]),
               pos=state["pos"] + live.astype(state["pos"].dtype))
    if keys is not None:
        new["keys"] = xp.where(live[:, None], keys, state["keys"])
    return new


class QueueFullError(CommError):
    """Raised by :meth:`Engine.submit` when the engine is at capacity
    (every slot occupied AND the bounded queue full) — the serving
    backpressure signal a front-end turns into HTTP 429/503.  With a
    ``ServeConfig.shed_policy`` configured, overload sheds a QUEUED
    request (typed ``shed`` result status) instead of raising — the
    load-shedding alternative for traffic where newest-wins (or
    oldest-wins) beats reject-newest."""


def _policy_fcfs(queue) -> int:
    """First come, first served: admit in arrival order."""
    return 0


def _policy_shortest_first(queue) -> int:
    """Shortest prompt first (stable): cheapest prefill next — a
    throughput-greedy admission order for mixed prompt lengths."""
    lens = [len(r.prompt) for r in queue]
    return int(np.argmin(lens))


# Admission scheduling policies: name -> chooser(queue) -> index of the
# next request to admit.  The serve-smoke lane carries a registry-sync
# guard (every name here must be covered by the engine-vs-oracle parity
# matrix) and tests/test_serve.py parametrizes its matrix over this
# registry, so registering a policy without parity coverage fails CI.
POLICIES = {
    "fcfs": _policy_fcfs,
    "shortest_first": _policy_shortest_first,
}


def _shed_oldest(queue) -> int:
    """Shed the longest-waiting queued request (newest traffic wins —
    the steady-overload choice: old queued work is the most likely to
    blow its deadline anyway)."""
    return 0


def _shed_newest(queue) -> int:
    """Shed the most recent arrival (oldest-first fairness: requests
    already queued keep their place)."""
    return len(queue) - 1


# Overload shed policies: name -> chooser(queue) -> index of the queued
# request to shed when a submit overflows capacity.  Closed registry
# like POLICIES — the serve deadline/shed test matrix parametrizes over
# it, and chaos-matrix coverage is registry-sync guarded.
SHED_POLICIES = {
    "drop_oldest": _shed_oldest,
    "drop_newest": _shed_newest,
}


@dataclass(frozen=True)
class ServeConfig:
    """Engine configuration.  ``slots`` is the fixed slot-table
    capacity (the compiled decode batch); ``max_new`` the default
    per-request token budget (prompt + budget must fit ``cfg.max_seq``,
    checked at submit); ``eos`` ends a request early (None = budget
    only).  ``temperature``/``top_k`` follow the ``generate()``
    contract per request.  ``overlap`` is the decode-collective
    schedule (None = ``config.default_overlap()``; truthy = windowed
    split-phase; False = blocking baseline) and ``algorithm`` an
    explicit per-call pin (None = latency-tier auto selection).
    ``queue_limit`` bounds the waiting queue beyond what free slots can
    immediately absorb: a submit is rejected once
    ``queued >= queue_limit + free_slots`` (None = unbounded; 0 =
    accept only what a free slot can take right now).  ``shed_policy``
    (None = reject with :class:`QueueFullError`) turns that rejection
    into load shedding: a QUEUED request is evicted with the typed
    ``shed`` result status and the new submit is accepted —
    :data:`SHED_POLICIES` picks the victim.

    **Paging (ISSUE 17).**  The KV cache is a pool of fixed-size
    TP-sharded pages of ``block_size`` tokens addressed through a
    per-slot block table (``block_size`` must divide ``cfg.max_seq``;
    checked at engine construction).  ``block_size=0`` is one page of
    ``cfg.max_seq`` tokens a slot: nothing is shared, chunked or
    overcommitted (``num_blocks`` and ``prefix_cache`` are not read).
    ``num_blocks`` sizes the pool (None = ``slots * max_seq /
    block_size``, every slot's whole extent — shrink it to overcommit
    on real length distributions, which is the point).
    ``prefix_cache`` (on by default) shares identical prompt prefixes
    copy-on-write across requests, prefilled once; ``prefill_chunk``
    (``block_size > 0``) caps the prompt tokens prefilled per engine
    step — longer prompts interleave chunk-by-chunk with ongoing decode steps
    so one long prompt never stalls resident slots' emission (the TTFT
    bound).  Both exactness-gate on ``cache_dtype`` matching the
    parameter dtype (a down-cast cache would re-quantize shared prefix
    rows the per-request oracle keeps at full precision); the gate
    disables sharing/chunking, never bitwise parity.

    **Two classes of pages (ISSUE 45).**  Layers whose attention reads a
    sliding window (a ``GQA`` mixer with a window) keep their pages in a
    class of their own, of ``window_blocks`` page ids (None = what every
    slot can hold at once: ``slots`` x the pages a window touches): a
    slot holds the pages its window touches there and gives back the
    rest while it decodes (:mod:`.paging`).  Not read for a
    configuration without such a layer; with one, ``prefix_cache`` and
    ``prefill_chunk`` are refused (``kv.validate_tp``)."""
    slots: int = 4
    max_new: int = 16
    eos: Optional[int] = None
    temperature: float = 0.0
    top_k: int = 0
    policy: str = "fcfs"
    overlap: Any = None
    algorithm: Optional[str] = None
    queue_limit: Optional[int] = None
    cache_dtype: Any = None
    shed_policy: Optional[str] = None
    block_size: int = 0
    num_blocks: Optional[int] = None
    prefix_cache: bool = True
    prefill_chunk: Optional[int] = None
    window_blocks: Optional[int] = None

    def __post_init__(self):
        if self.slots < 1:
            raise ValueError(f"slots must be >= 1, got {self.slots}")
        if self.max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {self.max_new}")
        if self.policy not in POLICIES:
            raise ValueError(
                f"unknown scheduling policy {self.policy!r}; registered: "
                f"{sorted(POLICIES)}")
        if self.temperature < 0:
            raise ValueError(
                f"temperature must be >= 0, got {self.temperature}")
        if self.queue_limit is not None and self.queue_limit < 0:
            raise ValueError(
                f"queue_limit must be >= 0 or None, got "
                f"{self.queue_limit}")
        if self.shed_policy is not None \
                and self.shed_policy not in SHED_POLICIES:
            raise ValueError(
                f"unknown shed policy {self.shed_policy!r}; registered: "
                f"{sorted(SHED_POLICIES)} (or None to reject with "
                "QueueFullError)")
        if self.block_size < 0:
            raise ValueError(
                f"block_size must be >= 0 (0 = dense slot-table cache), "
                f"got {self.block_size}")
        if self.num_blocks is not None and self.num_blocks < 1:
            raise ValueError(
                f"num_blocks must be >= 1 or None, got {self.num_blocks}")
        if self.window_blocks is not None and self.window_blocks < 1:
            raise ValueError(
                f"window_blocks must be >= 1 or None, got "
                f"{self.window_blocks}")
        if self.prefill_chunk is not None:
            if self.block_size == 0:
                raise ValueError(
                    "prefill_chunk requires paging (block_size > 0) — "
                    "chunked prefill installs per-chunk rows into pages")
            if self.prefill_chunk < 1:
                raise ValueError(
                    f"prefill_chunk must be >= 1 or None, got "
                    f"{self.prefill_chunk}")


@dataclass(eq=False)
class Request:
    """One serving request: ``prompt`` (1-d int array), its token
    budget, and (for sampled decoding) its own PRNG key — the exact
    argument set of a per-request ``generate()`` call, which is the
    engine's parity oracle.  ``deadline`` is the ABSOLUTE engine-clock
    instant past which the request is evicted with the typed
    ``deadline_expired`` status (None = no deadline).
    Identity-compared (``eq=False``): the queue removes by object, and
    array fields have no useful value equality."""
    rid: Any
    prompt: np.ndarray
    max_new: int
    key: Any = None
    deadline: Optional[float] = None
    emitted: List[int] = field(default_factory=list)

    def finished(self, eos: Optional[int]) -> bool:
        if len(self.emitted) >= self.max_new:
            return True
        return (eos is not None and self.emitted
                and self.emitted[-1] == eos)


@dataclass(eq=False)
class _PrefillJob:
    """A chunked prefill in progress (paged engines): the request holds
    its reserved slot (inactive — decode skips it) while its prompt
    lands chunk by chunk, ONE chunk per engine step, interleaved with
    the resident slots' decode — the TTFT bound: a long prompt never
    stalls emission for sequences already decoding.  ``done`` counts
    prompt rows whose K/V is installed (shared prefix included)."""
    req: Request
    slot: int
    seq: np.ndarray
    done: int = 0


class Engine:
    """Continuous-batching inference engine over a fixed slot table.

    Construct with full (replicated) parameters; the TP shards, the
    sharded KV cache, and the decode collectives follow from the
    world (see module docstring).  Construction shards ``params`` a
    layer at a time (the leaves go into the sharding program as
    arguments, never as constants of it) and lets each layer of the
    caller's go before it takes the next: ``params["blocks"]`` may be
    any iterable, walked once, and where it makes its layers as they are
    asked for, the process holds the shards, the cache and about one
    layer of the caller's leaves at the fullest moment, not a second
    copy of the model.  A caller that keeps its whole tree keeps it.
    Drive it with :meth:`submit` +
    :meth:`step`, or :meth:`run` to drain everything.  Greedy and
    sampled decoding both produce exactly the tokens of a per-request
    ``models/transformer.generate`` call (tests/test_serve.py holds
    this across admission/eviction churn on (1,), (4,) and (2,4)
    worlds, Mode A and Mode B)."""

    def __init__(self, cfg: TransformerConfig, params,
                 serve_cfg: ServeConfig = None, *, spmd: bool = False,
                 nranks: Optional[int] = None, mesh=None,
                 axis_name: Optional[str] = None, clock=None):
        # First, so that the constructor's own phases are spans of this
        # engine (closed outside a step: profiling.setup_spans) and a
        # compilation made in one names it (profiling.compile_log).
        self.stats = _prof._register_serve_stats(_prof.ServeStats())
        with self.stats.span(SPAN_CONSTRUCT):
            self._construct(cfg, params, serve_cfg, spmd, nranks, mesh,
                            axis_name, clock)

    def _construct(self, cfg, params, serve_cfg, spmd, nranks, mesh,
                   axis_name, clock):
        self.cfg = cfg
        self.serve_cfg = serve_cfg or ServeConfig()
        # The deadline clock: monotonic seconds.  Injectable so the
        # deadline-eviction tests (and the chaos matrix) drive a FAKE
        # clock deterministically — expirations then depend on the step
        # schedule, not on wall-time noise.  Multi-rank Mode B serving
        # (one Engine per rank thread) MUST inject the same
        # deterministic clock on every rank: each engine runs its own
        # expiry sweep, and per-rank wall clocks can disagree on which
        # step a deadline lands in — a divergent eviction would split
        # the slot tables feeding the decode collectives.  The default
        # wall clock is for single-engine deployments.
        self._clock = clock if clock is not None else time.monotonic
        self._spmd = bool(spmd)
        self._comm = COMM_WORLD
        if self._spmd:
            if mesh is not None:
                if axis_name is None:
                    raise ValueError(
                        "Engine(spmd=True, mesh=...) needs axis_name= — "
                        "the mesh axis the TP collectives run over "
                        "(other axes replicate)")
                self._size = int(mesh.shape[axis_name])
            else:
                self._size = int(nranks or len(jax.devices()))
        else:
            self._size = self._comm.size
        # One cache manager for every configuration: block_size=0 (a
        # slot owns max_seq rows; nothing shared, chunked or
        # overcommitted) is the pool at one page of max_seq tokens a
        # slot, with prefix sharing off.
        if self.serve_cfg.block_size == 0:
            bs, nb, share = cfg.max_seq, None, False
        else:
            bs, nb, share = (self.serve_cfg.block_size,
                             self.serve_cfg.num_blocks,
                             self.serve_cfg.prefix_cache)
        _kv.validate_tp(
            cfg, self._size, prefix_cache=share,
            prefill_chunk=self.serve_cfg.prefill_chunk)
        # A layer that keeps a per-slot state beside the cache
        # (kv.STATE_LEAVES): made with the cache, donated and handed
        # back with it, written whole by the install.
        self._stateful = _kv.has_state(cfg)
        # Exactness gate for prefix sharing and chunked prefill
        # (_exact_kv, below): both splice CACHE-dtype rows into prefill
        # attention, which is only bit-identical to the one-shot oracle
        # when the cache carries the compute dtype.  A down-cast cache
        # keeps paging (storage) but prefills every prompt in full.
        self._dtype = (self.serve_cfg.cache_dtype
                       or params["embed"].dtype)
        self._exact_kv = (jnp.dtype(self._dtype)
                          == jnp.dtype(params["embed"].dtype))
        param_dtype = params["embed"].dtype
        if self._spmd:
            from ..ops.spmd import run_spmd
            kw = {}
            if mesh is not None:
                kw["mesh"] = mesh
                kw["axis_name"] = axis_name
            else:
                kw["nranks"] = self._size
            # Shard ONCE: the stacked (size, ...) per-rank TP shards
            # ride as engine state exactly like the KV cache, so the
            # compiled step slices one rank's shards instead of
            # re-deriving them from the replicated full parameters
            # every executed step.
            with self.stats.span(SPAN_SHARD):
                self._shards = self._shard_by_layer(
                    params, lambda fn: run_spmd(fn, **kw))
            # The step takes its pool over (argument 1) and writes the
            # new rows into it, and takes the slot state over (argument
            # 2) and hands the next one back in its buffers: nothing is
            # allocated or freed for it inside the call (0.15-0.27 ms a
            # step on the chip, PERF.md section 6, PR 38).
            self._step_call = run_spmd(
                self._traced_step, donate_argnums=(1, 2), **kw)
            # One wrapper serves every prompt length: the jit under
            # run_spmd caches per input shape on its own.
            self._prefill_call = run_spmd(self._traced_prefill, **kw)
            self._chunk_call = run_spmd(self._traced_prefill_chunk, **kw)
        else:
            # Eager: the rank is concrete here (rank thread or the
            # size-1 world) — shard once.
            with self.stats.span(SPAN_SHARD):
                self._shards = self._shard_by_layer(params, lambda fn: fn)
            self._step_call = None
            self._prefill_call = None
            self._chunk_call = None
        del params
        # The input shapes each compiled program has been called with:
        # what program_texts() lowers again.
        self._prefilled: set = set()

        slots = self.serve_cfg.slots
        if cfg.max_seq % bs != 0:
            raise ValueError(
                f"block_size={bs} must divide max_seq={cfg.max_seq} "
                "(a slot's table row covers the dense attention "
                "extent — see serve.kv.init_kv_pool_tp)")
        self._blocks_per_seq = cfg.max_seq // bs
        if nb is None:
            nb = slots * self._blocks_per_seq
        # The class of pages each layer's entry lies in.  A window
        # class (layers that read a sliding window) has a pool extent,
        # a population of the manager and a table of its own.
        self._classes = _kv.page_classes(cfg)
        window = _kv.window_of(cfg)
        nb_w = 0
        if window:
            nb_w = self.serve_cfg.window_blocks or slots * min(
                _paging.pages_touched(window, bs), self._blocks_per_seq)
        # The pool's leaves as shapes: each is made below, where it is
        # to lie.
        cache = jax.eval_shape(lambda: _kv.init_kv_pool_tp(
            cfg, nb, bs, self._size, self._dtype, slots=slots,
            window_blocks=nb_w))
        self._mgr = _paging.BlockManager(
            nb, bs, prefix_cache=share and self._exact_kv, window=window,
            window_blocks=nb_w)
        # Host-side block tables, mirrored into the step as DATA: the
        # full class's, and the window class's where there is one.
        self._table = np.full((slots, self._blocks_per_seq), -1,
                              np.int32)
        self._table_w = np.full_like(self._table, -1) if window else None
        self._prefill_jobs: deque = deque()
        self._admit_seq = 0                  # preemption-victim order
        self._slot_seq = [0] * slots
        self._chunk = (self.serve_cfg.prefill_chunk
                       if self._exact_kv else None)
        # Which read the decode step compiles, asked of the
        # functions that decide it: what decode_pages_read and
        # decode_grid_steps count.
        self._grid_steps = self._kernel_grid_steps(cache, param_dtype)
        self._kernel_read = self._grid_steps > 0
        # Stacked per-rank state under SPMD: leading (size,) axis —
        # exactly the rank-major layout run_spmd's outputs carry, and
        # laid out as they are (read off the shards it just produced),
        # so the state round-trips step to step unchanged and the
        # install can reuse its buffers from the first call.  Every
        # leaf is a buffer of its own (the install donates the whole
        # pool, and one buffer cannot be donated twice in a call), made
        # as zeros where it is to lie and nowhere else first: a template
        # of the pool beside it, or a leaf's copies on their way there,
        # were the peak of the process (a class of one layer is ONE
        # leaf of 1.1 GB in `serve_swa_mix_16k`).
        state = self._state_sharding = \
            jax.tree.leaves(self._shards)[0].sharding if self._spmd \
            else None
        lead = (self._size,) if self._spmd else ()
        with self.stats.span(SPAN_POOL):
            self._cache = jax.tree.map(
                lambda a: jnp.zeros(lead + a.shape, a.dtype, device=state),
                cache)
        # Built here, first called in step(): the engine may be
        # constructed with jit disabled.
        with self.stats.span(SPAN_BUILD_INSTALL):
            self._install_call = self._build_install()
        self._tokens = np.zeros((slots,), np.int32)
        self._pos = np.zeros((slots,), np.int32)
        # The slot state on the device, beside the cache and riding as
        # it does (stacked per rank under SPMD): what a decode step
        # reads and hands back advanced.  The host's arrays above (with
        # the table and the requests' keys) stay the truth; _held is the
        # host's memory of what the device holds, and _step_inputs
        # uploads the arrays in which the two differ: all of them the
        # first time, since _held starts empty.
        self._state: Dict[str, Any] = {}
        self._held: Dict[str, np.ndarray] = {}
        # The decode program's counters as it packs them behind the
        # tokens: ((name, shape, size), ...), static, noted by _advance
        # where the step is traced.
        self._counted: tuple = ()
        self._select_syncs = 0            # device round trips of _select
        self._slot_req: List[Optional[Request]] = [None] * slots
        # True while a slot's chunked prefill is in flight: the slot is
        # reserved (occupancy counts it) but NOT in the decode active
        # set until its first token lands.
        self._prefilling: List[bool] = [False] * slots
        self._queue: deque = deque()
        self._results: Dict[Any, np.ndarray] = {}
        self._statuses: Dict[Any, str] = {}
        self._known_rids = set()
        self._next_rid = 0
        self.slot_log: List[tuple] = []   # (rid, slot) admission history
        # Optional self-tuning controller (mpi4torch_tpu.ctl): consulted
        # between steps, never during one — see attach_controller.
        self._controller = None

    def _shard_by_layer(self, params, compiled):
        """:func:`~mpi4torch_tpu.serve.kv.shard_params_tp` a layer at a
        time: the top of the tree (embedding, head, final norm), then
        each layer as ``params["blocks"]`` yields it, each through
        ``compiled(fn)(leaves)`` — ``run_spmd`` for an SPMD engine (the
        leaves are ARGUMENTS of the sharding program and come back
        stacked per rank), the function itself for an eager one — and
        let go before the next is taken.  Spans (the caller's ``.shard``
        lies over all of it): ``.shard.take`` around each ``next()`` of
        the iterable (the CALLER's time, where it makes a layer as it is
        asked; the last finds it done), ``.shard.layer`` around each
        layer's sharding and ``.shard.top`` around the top's (the
        program's), ``rid`` the layer's index."""
        cfg, span = self.cfg, self.stats.span
        by_spec = {}
        with span(SPAN_SHARD_TOP):
            shards = compiled(lambda t: t)(
                {k: v for k, v in params.items() if k != "blocks"})
        shards["blocks"] = []
        specs = cfg.layer_specs
        blocks, done = iter(params["blocks"]), object()
        while True:
            n = len(shards["blocks"])
            with span(SPAN_SHARD_TAKE, n):
                blk = next(blocks, done)
            if blk is done:
                break
            if n == len(specs):
                raise ValueError(
                    f"params['blocks'] yields more than n_layers={n} "
                    "layers")
            spec = specs[n]
            with span(SPAN_SHARD_LAYER, n):
                if spec not in by_spec:
                    by_spec[spec] = compiled(
                        lambda b, spec=spec: _kv.shard_block_tp(
                            cfg, spec, b, self._comm))
                shards["blocks"].append(by_spec[spec](blk))
            del blk        # before the iterable makes the next one
        if len(shards["blocks"]) != len(specs):
            raise ValueError(
                f"params['blocks'] has {len(shards['blocks'])} layers for "
                f"n_layers={len(specs)}")
        return shards

    def _kernel_grid_steps(self, cache, dtype) -> int:
        """The grid steps one call of the decode step's paged read walks
        over this (unstacked) pool (``read_grid``, the kernels' own
        ``grid=``; the most of the kinds of layer), 0 unless every
        layer's read is the paged kernel's: each kind of layer asked
        once, of the dispatch's own predicate with the query that layer
        will bring."""
        cfg, slots = self.cfg, self.serve_cfg.slots
        like = lambda *shape: jax.ShapeDtypeStruct(shape, dtype)
        steps = {}                    # each kind of layer once
        for spec, entry in zip(cfg.layer_specs, cache):
            if all(k in _kv.STATE_LEAVES for k in entry):
                continue              # no pages: an FFN or a state alone
            if spec.mixer in steps:
                continue
            index = getattr(spec.mixer, "index", None)
            if "ik" in entry:
                # A scoring layer visits pages where it scores (the
                # read of the selected rows visits rows, not pages).
                staged = (entry["ik"],)
                by_kernel = _paged_attn.uses_index_kernel(
                    like(slots, index.n_heads, index.head_dim),
                    entry["ik"])
            elif index is not None:
                continue
            elif "c" in entry:
                staged = (entry["c"],)
                by_kernel = _paged_attn.uses_kernel(
                    like(slots, spec.mixer.n_heads, entry["c"].shape[-1]),
                    entry["c"], spec.mixer.kv_rank)
            else:
                staged = (entry["k"], entry["v"])
                heads, hd = (spec.mixer.n_heads, spec.mixer.head_dim) \
                    if isinstance(spec.mixer, GQA) else (
                        cfg.n_heads // self._size,
                        cfg.d_model // cfg.n_heads)
                by_kernel = _paged_attn.uses_kernel(
                    like(slots, heads, hd), entry["k"])
            steps[spec.mixer] = by_kernel and math.prod(
                _paged_attn.read_grid(slots, self._blocks_per_seq, *staged))
        return max(steps.values(), default=0) if all(steps.values()) else 0

    # ------------------------------------------------------------- traced

    @staticmethod
    def _rank_slice(stacked):
        """This rank's leaves off a stacked (size, ...) state tree."""
        rank = jnp.asarray(COMM_WORLD.rank)
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, rank, 0,
                                                   keepdims=False),
            stacked)

    def _decode(self, shards, cache, state):
        """One decode step over the slot ``state`` with this rank's
        shards and pool, on every step path (compiled, eager): decode,
        then :meth:`_advance`.  Returns ``(chosen, next state, new
        pool)``."""
        stats = {}
        table = state["table"] if self._table_w is None else {
            "full": state["table"], "window": state["table_window"]}
        # Eager, the step takes each pool leaf over itself; the
        # compiled one donates through run_spmd.
        logits, cache = _kv.decode_step_paged(
            self.cfg, shards, cache, table, state["tokens"],
            state["pos"], self._comm, overlap=self.serve_cfg.overlap,
            algorithm=self.serve_cfg.algorithm, active=state["live"],
            donate=not self._spmd, stats=stats)
        return (*self._advance(state, logits, stats), cache)

    def _advance(self, state, logits, counters):
        """What every decode step ends in: choose each slot's token
        (:func:`select_rows`, behind its barrier: nothing here reaches
        back into the unembedding product), pack the step's counters
        behind the tokens into the one ``int32`` array the host
        fetches, and advance the slot state (:func:`_advanced`).
        Returns ``(chosen (slots + counted,), next state)``."""
        toks, keys = select_rows(logits, state.get("keys"),
                                 self.serve_cfg.temperature,
                                 self.serve_cfg.top_k)
        self._counted = tuple((name, c.shape, c.size)
                              for name, c in sorted(counters.items()))
        chosen = jnp.concatenate(
            [toks] + [counters[name].astype(jnp.int32).reshape(-1)
                      for name, _, _ in self._counted])
        return chosen, _advanced(jnp, state, toks, keys)

    def _traced_step(self, shards, cache, state):
        """Mode A decode step: slice this rank's shards, pool and slot
        state off the stacked leading axis and :meth:`_decode` —
        run_spmd re-stacks the per-rank outputs into the state layout,
        so the state goes into the next step as it came out of this
        one.  The block table is part of the slot state, DATA: one
        compiled program for every table state (no retrace as pages
        churn).  The logits are replicated over the ranks
        (kv.shard_params_tp), so every rank chooses the same tokens and
        the choice adds no collective."""
        return self._decode(*map(self._rank_slice,
                                 (shards, cache, state)))

    def _traced_prefill(self, shards, prompt):
        comm = COMM_WORLD
        cache = _kv.init_kv_cache_tp(self.cfg, 1, comm.size, self._dtype)
        stats = {}
        return (*_kv.prefill_tp(self.cfg, self._rank_slice(shards), cache,
                                prompt, comm, stats=stats), stats)

    def _traced_prefill_chunk(self, shards, past, chunk):
        """Mode A chunk/suffix prefill: ``past`` is the stacked
        exact-length prefix K/V gathered host-side from the pool at
        concrete page ids (compiles per (prefix, chunk) length pair,
        like prefill itself compiles per prompt length)."""
        stats = {}
        return (*_kv.prefill_chunk_tp(
            self.cfg, self._rank_slice(shards), self._rank_slice(past),
            chunk, COMM_WORLD, stats=stats), stats)

    # -------------------------------------------------------------- public

    def submit(self, prompt, *, rid=None, max_new: Optional[int] = None,
               key=None, deadline_s: Optional[float] = None):
        """Queue one request; returns its id.  Validates the
        ``generate()`` preconditions (budget fits ``max_seq``, sampled
        decoding needs a key) and applies queue backpressure
        (:class:`QueueFullError` past ``queue_limit``, or a shed per
        ``ServeConfig.shed_policy``).  ``deadline_s`` (seconds from
        now on the engine clock) bounds the request's total latency:
        past it the request is evicted with the typed
        ``deadline_expired`` result status — whatever tokens it emitted
        stay a bitwise prefix of the ``generate()`` oracle."""
        prompt = np.asarray(prompt)
        if prompt.ndim != 1 or prompt.size == 0:
            raise ValueError(
                f"prompt must be a non-empty 1-d token array; got shape "
                f"{prompt.shape}")
        budget = int(max_new if max_new is not None
                     else self.serve_cfg.max_new)
        if budget < 1:
            raise ValueError(f"max_new must be >= 1, got {budget}")
        if prompt.size + budget > self.cfg.max_seq:
            raise ValueError(
                f"prompt {prompt.size} + n_new {budget} exceeds max_seq "
                f"{self.cfg.max_seq}")
        # Worst-case page footprint (positions 0 .. p+budget-2; the
        # final token is selected, never written): a request that could
        # not run even ALONE on the pool would preempt-loop forever, so
        # it is rejected here like the max_seq check.
        bs = self._mgr.block_size
        need = -(-(int(prompt.size) + budget - 1) // bs)
        if need > self._mgr.num_blocks:
            raise ValueError(
                f"prompt {prompt.size} + n_new {budget} needs "
                f"{need} pages of {bs} tokens; the pool has only "
                f"{self._mgr.num_blocks} — raise num_blocks or "
                "shrink the request")
        w = self._mgr.window
        if w is not None and min(need, w.pages_a_slot) > w.num_blocks:
            raise ValueError(
                f"prompt {prompt.size} + n_new {budget} holds "
                f"{min(need, w.pages_a_slot)} pages of the window class "
                f"at once; its pool has only {w.num_blocks} — raise "
                "window_blocks")
        if self.serve_cfg.temperature > 0 and key is None:
            raise ValueError("temperature > 0 requires a PRNG `key`")
        if key is not None and jnp.issubdtype(key.dtype,
                                              jax.dtypes.prng_key):
            # The decode step carries the streams as raw key bits.
            key = jax.random.key_data(key)
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(
                f"deadline_s must be > 0 seconds, got {deadline_s}")
        limit = self.serve_cfg.queue_limit
        if limit is not None and \
                len(self._queue) >= limit + len(self._free_slots()):
            # The bound is on requests the engine cannot yet absorb:
            # free slots count as immediate capacity (the next step
            # admits into them), everything beyond slots + limit is
            # rejected — the queue stays bounded even before the first
            # step runs.  A configured shed policy evicts a QUEUED
            # victim (typed `shed` status) instead of rejecting the
            # newcomer; with nothing queued to shed, rejection stands.
            if self.serve_cfg.shed_policy is not None and self._queue:
                victim = self._queue[
                    SHED_POLICIES[self.serve_cfg.shed_policy](
                        self._queue)]
                self._queue.remove(victim)
                self._finish(victim, status=STATUS_SHED)  # counts "shed"
            else:
                self.stats.count("rejected")
                raise QueueFullError(
                    f"serve queue full ({len(self._queue)} waiting, "
                    f"{len(self._free_slots())} free of "
                    f"{self.serve_cfg.slots} slots; queue_limit={limit})")
        if rid is None:
            rid = self._next_rid
            self._next_rid += 1
        elif rid in self._known_rids:
            # A duplicate would silently merge two requests' events,
            # spans and results under one key.
            raise ValueError(
                f"request id {rid!r} is already in use by a queued, "
                "in-flight, or finished request of this engine")
        self._known_rids.add(rid)
        deadline = (None if deadline_s is None
                    else self._clock() + float(deadline_s))
        self._queue.append(Request(rid=rid, prompt=prompt,
                                   max_new=budget, key=key,
                                   deadline=deadline))
        self.stats.mark(rid, "submitted")
        return rid

    def admit_expired(self, prompt, *, rid=None, emitted=()):
        """Record a request that arrives ALREADY past its deadline —
        the elastic re-admission path, where resize downtime can
        consume a drained ticket's remaining deadline budget — with the
        typed ``deadline_expired`` result status.  The tokens it
        carries stay whatever oracle prefix it had earned; no prefill,
        slot, or decode step is spent.  Validates ``rid`` uniqueness
        exactly like :meth:`submit`."""
        prompt = np.asarray(prompt)
        if rid is None:
            rid = self._next_rid
            self._next_rid += 1
        elif rid in self._known_rids:
            raise ValueError(
                f"request id {rid!r} is already in use by a queued, "
                "in-flight, or finished request of this engine")
        self._known_rids.add(rid)
        req = Request(rid=rid, prompt=prompt, max_new=0,
                      emitted=list(emitted))
        self.stats.mark(rid, "submitted")
        self._finish(req, status=STATUS_EXPIRED)
        return rid

    def pending(self) -> int:
        """Requests not yet finished (queued + occupying slots)."""
        return len(self._queue) + sum(
            r is not None for r in self._slot_req)

    def occupancy(self) -> int:
        return sum(r is not None for r in self._slot_req)

    def _free_slots(self) -> List[int]:
        return [j for j, r in enumerate(self._slot_req) if r is None]

    # ---------------------------------------------------------- lifecycle

    def _select(self, req: Request, choice) -> int:
        """The one hand-over of a token to a request; every emitted
        token passes through it.  Two inputs:

        * a ``(vocab,)`` row of logits (the prefill's first token): one
          decoding choice by ``generate()``'s exact key discipline —
          split, then select with the subkey (greedy ignores the key but
          the stream advances identically) — which is one round trip to
          the device, counted in ``_select_syncs``;
        * the token the decode step already chose for the request's
          slot (a scalar out of :func:`select_rows`): handed back as it
          is, with no device call and the key untouched (``step()`` has
          set the key that came back with it).

        An instance may wrap it (``eng._select = ...``): the
        benchmark's broken-path control adds one to what it returns,
        and so makes every served token wrong, decode tokens too."""
        if np.ndim(choice) == 0:
            return int(choice)
        self._select_syncs += 1
        if req.key is None:
            req.key = jax.random.PRNGKey(0)   # unused on greedy path
        req.key, sub = jax.random.split(req.key)
        tok = select_token(jnp.asarray(choice)[None, :], sub,
                           self.serve_cfg.temperature,
                           self.serve_cfg.top_k, jnp.int32)
        return int(np.asarray(tok)[0])

    def _admit(self, events: dict) -> None:
        """Fill free slots from the queue; admission events (including
        a first token that already finishes the request — ``max_new=1``
        or an immediate EOS) land in ``events`` so the step-event
        surface never drops a token or a completion."""
        chooser = POLICIES[self.serve_cfg.policy]
        span = self.stats.span
        while self._queue and self._free_slots():
            with span(SPAN_PLAN) as plan:
                req = self._queue[chooser(self._queue)]
                plan.rid = req.rid
                job = self._plan(req)
            if job is None:
                # Page pool exhausted even after cache eviction: defer
                # admission (the request stays queued; decode keeps
                # draining pages).  Deadline expiry composes — a
                # deferred request past its deadline leaves through the
                # next sweep.
                break
            self._start(job, events)

    def _prefill_full(self, prompt):
        """The whole-prompt prefill — the IDENTICAL dispatch the
        ``generate()`` oracle uses — and its last-token logits on the
        host (a device sync: the prefill's device time ends here).
        Returns ``(logits_row, rows)``."""
        pj = jnp.asarray(prompt, jnp.int32)[None, :]
        stats = {}
        if self._spmd:
            self._prefilled.add(pj.shape[1])
            logits, rows, stats = self._prefill_call(self._shards, pj)
            logits = logits[0]
        else:
            cache1 = _kv.init_kv_cache_tp(
                self.cfg, 1, self._size, self._dtype)
            logits, rows = _kv.prefill_tp(
                self.cfg, self._shards, cache1, pj, self._comm,
                stats=stats)
        logits_row = np.asarray(logits[0])
        self._note_counters("prefill", self._prefill_counters(stats))
        return logits_row, rows

    def _note_counters(self, program: str, stats: dict) -> None:
        """A compiled program's own counters (``kv._hand_out``), as the
        host has them behind the sync its caller has just made, onto
        the record of the step that is open: ``moe_rows``, one
        ``(program, (expert layers, held) rows)`` per call of a program
        with an expert layer (one a piece where a long prompt's expert
        layers ran in pieces), in the order of the calls, with the
        counter ``moe_overflow_calls`` (the calls of an expert layer
        that had held rows behind their prefix); and, from a
        program whose expert layers have zero-compute experts, the two
        counters ``moe_zero_pairs`` and ``moe_live_pairs``, and from a
        decode step with indexed latent layers ``dsa_rows_live``,
        ``dsa_rows_read`` and ``dsa_rows_scored`` (the latent rows under
        the live slots' frontiers, the rows the selections named, the
        index keys scored, each summed over its layers), and from one
        with Mamba-2 layers ``ssm_states_live`` and
        ``ssm_states_touched`` (the live slots' kept states it had to
        advance, and those it read and wrote), which the
        step record carries by name like every counter.  No transfer is
        made here: a decode step's counters came down with its tokens
        (:meth:`_advance`), a prefill's by :meth:`_prefill_counters`."""
        if "moe_rows" in stats:
            # One entry a call of the grouped products: a long prompt's
            # expert layers ran in pieces, (pieces, expert layers, held).
            rows = stats["moe_rows"]
            for piece in rows.reshape((-1,) + rows.shape[-2:]):
                self.stats.attach("moe_rows", (program, piece))
        for name in ("moe_overflow_calls", "moe_zero_pairs",
                     "moe_live_pairs", "dsa_rows_live",
                     "dsa_rows_read", "dsa_rows_scored", "ssm_states_live",
                     "ssm_states_touched"):
            if name in stats:
                self.stats.count(name, int(stats[name]))

    def _prefill_counters(self, stats: dict) -> dict:
        """A prefill program's counters off the device, in one copy
        behind the sync on its logits (one an admission); a program
        that counts nothing costs nothing here."""
        if not stats:
            return stats
        return {k: self._fetch(v) for k, v in jax.device_get(stats).items()}

    # -------------------------------------------------------------- paged

    def _copy_block(self, dst: int, src: int) -> None:
        """Device-side page copy, every layer (COW: a partially-shared
        tail page is duplicated before the new request's suffix
        lands)."""
        if self._spmd:
            self._cache = jax.tree.map(
                lambda s: s.at[:, dst].set(s[:, src]), self._cache)
        else:
            self._cache = jax.tree.map(
                lambda s: s.at[dst].set(s[src]), self._cache)
        self.stats.count("cow_copies")

    def _build_install(self):
        """:func:`~mpi4torch_tpu.serve.kv.install_rows_paged` compiled
        with the pool donated.  Rank-local, no collective: a plain
        ``jax.jit``; under SPMD each rank writes its own slice of the
        stacked state in place, and the output keeps the state's
        layout."""
        install = _kv.install_rows_paged if self._table_w is None \
            else functools.partial(_kv.install_rows_paged,
                                   classes=self._classes)
        if not self._spmd:
            return jax.jit(install, donate_argnums=0)
        state = jax.tree.leaves(self._cache)[0].sharding

        def per_rank(pool, rows, *index):
            (pool, rows) = jax.tree.map(lambda a: a[0], (pool, rows))
            return jax.tree.map(
                lambda a: a[None], install(pool, rows, *index))

        # The page index, and the slot where a layer keeps a state.
        index = (PartitionSpec(),) * (1 + self._stateful)
        return jax.jit(
            jax.shard_map(per_rank, mesh=state.mesh,
                          in_specs=(state.spec, state.spec) + index,
                          out_specs=state.spec, check_vma=False),
            donate_argnums=0)

    def _install_rows(self, j: int, rows, lo: int, hi: int) -> None:
        """Write prefill K/V rows covering positions ``lo..hi-1`` of
        slot ``j`` into its pages.  ``rows`` is the per-layer
        ``[{"k","v"}]`` prefill output with the row axis starting at
        ``lo`` (a full-prompt prefill passes ``lo=0`` and may carry
        trailing rows beyond ``hi``; they are ignored).  ONE dispatch
        of the compiled install whatever the range: page ids, offset
        and length are data, the pool is donated and written in place
        — exact bits, and the write targets are private pages by the
        COW rule.  Whoever held ``self._cache``'s old leaves holds
        deleted arrays afterwards."""
        bs = self._mgr.block_size
        first = lo // bs
        touched = -(-hi // bs) - first
        n_pages = _kv.install_page_count(
            _kv.first_paged_leaf(rows).shape[-3], bs)

        def index_of(table, mgr):
            index = np.empty(2 + n_pages, np.int32)
            index[0], index[1] = lo % bs, hi - lo
            # Beyond the touched pages, and where the slot holds none
            # (a window layer's pages behind its window): ids outside
            # the pool, dropped.
            index[2:] = mgr.num_blocks + np.arange(n_pages)
            held = table[j, first:first + touched]
            index[2:2 + touched] = np.where(held >= 0, held,
                                            index[2:2 + touched])
            return index

        index = index_of(self._table, self._mgr)
        if self._table_w is not None:
            index = {"full": index,
                     "window": index_of(self._table_w, self._mgr.window)}
        slot = (np.int32(j),) if self._stateful else ()
        self._cache = self._install_call(self._cache, rows, index, *slot)
        self.stats.count("install_writes")

    def _count_pages(self, active: List[int]) -> None:
        """The step's page counts: the pages its live slots hold up to
        their frontier, the pages its attention visits by the read the
        engine compiled (the same pages through the kernel; every
        slot's whole table row through the gather), and the grid steps
        one call of that kernel walks for them (every slot's, live or
        free: the grid is the program's); each summed over the tables
        where the pool has two classes.  With a window class also
        ``window_pages_held`` and ``window_slots_live``: the pages of
        that class the live slots hold by its table, and those slots."""
        bs = self._mgr.block_size
        held = sum(int(self._pos[j]) // bs + 1 for j in active)
        tables, w = 1, self._mgr.window
        if w is not None:
            # A second table, the window class's: its reads visit the
            # pages from the window's first on, which is what the slots
            # hold of it while pages behind the window are released.
            tables = 2
            held += sum(int(self._pos[j]) // bs + 1
                        - w.first_page(self._pos[j]) for j in active)
            self.stats.count("window_pages_held",
                             int(np.sum(self._table_w[active] >= 0)))
            self.stats.count("window_slots_live", len(active))
        self.stats.count("decode_pages_live", held)
        self.stats.count("decode_pages_read",
                         held if self._kernel_read
                         else tables * len(active) * self._blocks_per_seq)
        self.stats.count("decode_grid_steps", tables * self._grid_steps)

    def _gather_past(self, j: int, n: int):
        """Exact-length past K/V (positions ``0..n-1``) for slot ``j``,
        host-gathered from the pool at the slot's concrete page ids —
        the suffix/chunk prefill input: the cache's own tree, whatever
        kind each layer's entry is.  Stacked ``(size, 1, n, ...)``
        leaves under SPMD, ``(1, n, ...)`` eager."""
        bs = self._mgr.block_size
        nblk = -(-n // bs)
        ids = jnp.asarray([int(self._table[j, bi])
                           for bi in range(nblk)], jnp.int32)
        lead = 1 if self._spmd else 0      # the stacked rank axis

        def take(leaf):
            g = jnp.take(leaf, ids, axis=lead)
            g = g.reshape(leaf.shape[:lead] + (1, nblk * bs)
                          + leaf.shape[lead + 2:])
            return g[(slice(None),) * (lead + 1) + (slice(0, n),)]

        return jax.tree.map(take, self._cache)

    def _plan(self, req: Request) -> Optional[_PrefillJob]:
        """Plan an admission: prefix-match the prompt against the
        block index, adopt shared pages (COW-copying a partial tail),
        allocate private pages for the rest and reserve the slot.  The
        returned job's ``done`` is the matched prefix; :meth:`_start`
        prefills the rest.  Returns None (request left
        queued) when the pool cannot supply the pages."""
        bs = self._mgr.block_size
        prompt = np.asarray(req.prompt)
        p_len = int(prompt.size)
        # Cap the match at p_len - 1: admission needs last-token logits,
        # so at least one suffix token is always computed.
        shared, l0 = self._mgr.match(prompt, p_len - 1)
        partial = l0 % bs != 0
        total = -(-p_len // bs)
        # Pages not fully covered by the share; when the tail match is
        # partial its page sits in `shared` but must be COW-copied, and
        # the copy target is the first of these fresh pages.
        n_new = total - (l0 // bs)
        fresh = self._mgr.alloc(n_new)
        if fresh is None:
            return None
        w = self._mgr.window
        if w is not None:
            # The window class: pages from the first one the first
            # decode step (at position p_len) reads; the prompt's
            # earlier rows are behind the window before they are needed
            # and are never installed.
            first_w = w.first_page(p_len)
            fresh_w = w.alloc(total - first_w)
            if fresh_w is None:
                self._mgr.release(fresh)
                return None
        self._mgr.ref(shared)
        j = self._free_slots()[0]
        if w is not None:
            self._table_w[j, first_w:total] = fresh_w
        for bi in range(l0 // bs):
            self._table[j, bi] = shared[bi]
        for i, bi in enumerate(range(l0 // bs, total)):
            self._table[j, bi] = fresh[i]
        if partial:
            self._copy_block(fresh[0], shared[-1])
            self._mgr.release([shared[-1]])   # keep only the copy
        self._queue.remove(req)
        self.stats.count("prefix_hits" if l0 else "prefix_misses")
        self._slot_req[j] = req
        self._slot_seq[j] = self._admit_seq
        self._admit_seq += 1
        self.slot_log.append((req.rid, j))
        self._prefilling[j] = True
        self._pos[j] = l0          # rows installed so far
        return _PrefillJob(req=req, slot=j, seq=prompt, done=l0)

    def _start(self, job: _PrefillJob, events: dict) -> None:
        """Prefill what :meth:`_plan` did not match — in one shot
        if it fits ``ServeConfig.prefill_chunk`` (or chunking is off),
        else as a queued :class:`_PrefillJob` advanced one chunk per
        step."""
        j, l0, p_len = job.slot, job.done, len(job.seq)
        rid = job.req.rid
        if l0 == 0 and (self._chunk is None or p_len <= self._chunk):
            # Whole-prompt miss that fits one shot: the ordinary full
            # prefill — the IDENTICAL dispatch the generate() oracle
            # uses.
            with self.stats.span(SPAN_PREFILL, rid):
                logits_row, rows = self._prefill_full(job.seq)
            with self.stats.span(SPAN_INSTALL, rid):
                self._install_rows(j, rows, 0, p_len)
            self.stats.count("prefill_tokens", p_len)
            job.done = p_len
            self._complete_admission(job, logits_row, events)
        elif self._chunk is None or p_len - l0 <= self._chunk:
            # Suffix fits one shot: single chunk call at admission
            # (first token this step).
            self._advance_job_chunk(job, events, cap=p_len - l0)
        else:
            # Long suffix: interleave — ONE chunk per step rides along
            # with the resident slots' decode (_prefill_tick).
            self._prefill_jobs.append(job)

    def _advance_job_chunk(self, job: _PrefillJob, events: dict,
                           cap: Optional[int] = None) -> bool:
        """Run ONE prefill chunk of ``job``; returns True when the
        prompt is fully installed (first token selected, slot
        activated)."""
        j = job.slot
        p_len = len(job.seq)
        c_len = min(cap if cap is not None else self._chunk,
                    p_len - job.done)
        rid = job.req.rid
        with self.stats.span(SPAN_PREFILL, rid):
            past = self._gather_past(j, job.done)
            chunk = jnp.asarray(job.seq[job.done:job.done + c_len],
                                jnp.int32)[None, :]
            stats = {}
            if self._spmd:
                logits, rows, stats = self._chunk_call(self._shards, past,
                                                       chunk)
                logits_row = np.asarray(logits[0][0])
            else:
                logits, rows = _kv.prefill_chunk_tp(
                    self.cfg, self._shards, past, chunk, self._comm,
                    stats=stats)
                logits_row = np.asarray(logits[0])
            self._note_counters("prefill", self._prefill_counters(stats))
        with self.stats.span(SPAN_INSTALL, rid):
            self._install_rows(j, rows, job.done, job.done + c_len)
        self.stats.count("prefill_tokens", c_len)
        job.done += c_len
        self._pos[j] = job.done
        if job.done == p_len:
            self._complete_admission(job, logits_row, events)
            return True
        return False

    def _complete_admission(self, job: _PrefillJob, logits_row,
                            events: dict) -> None:
        """Prompt fully resident: select the first token (the oracle's
        key discipline), register the prompt chain for future sharers,
        activate the slot — or finish immediately (``max_new=1`` /
        instant EOS), releasing the pages through the registering
        release path."""
        req, j = job.req, job.slot
        bs = self._mgr.block_size
        p_len = len(job.seq)
        with self.stats.span(SPAN_FIRST_TOKEN, req.rid):
            self.stats.mark(req.rid, "admitted")
            self.stats.count("admitted")
            tok = self._select(req, logits_row)
            req.emitted.append(tok)
            self.stats.mark(req.rid, "first_token")
            events["admitted"].append(req.rid)
            events["emitted"].setdefault(req.rid, []).append(tok)
            ids = [int(self._table[j, bi]) for bi in range(-(-p_len // bs))]
            # Content-addressed, so indexing the slot's own (immutable for
            # its lifetime) prompt pages is safe; the next identical prompt
            # prefills nothing but its final token.
            self._mgr.register(job.seq, ids, p_len)
            self._prefilling[j] = False
            self._tokens[j] = tok
            self._pos[j] = p_len
            if req.finished(self.serve_cfg.eos):
                events["finished"].append(req.rid)
                self._release_slots([j])
                self._finish(req)

    def _prefill_tick(self, events: dict) -> None:
        """Advance the HEAD chunked-prefill job by exactly one chunk —
        the global per-step prefill bound that keeps TTFT and resident
        decode latency simultaneously bounded."""
        if not self._prefill_jobs:
            return
        if self._advance_job_chunk(self._prefill_jobs[0], events):
            self._prefill_jobs.popleft()

    def _preempt_one(self) -> bool:
        """Preempt the most recently admitted resident request to free
        pages: its written rows register in the prefix index before
        release, then the request re-queues AT THE HEAD with its
        emitted tokens folded into the prompt (the elastic
        extended-prompt discipline) — re-admission prefix-matches its
        own registered pages, so the restart costs ~one COW copy plus a
        one-token suffix, and the stitched stream stays bitwise the
        generate() oracle."""
        cands = [j for j in range(self.serve_cfg.slots)
                 if self._slot_req[j] is not None]
        if not cands:
            return False
        j = max(cands, key=lambda s: self._slot_seq[s])
        req = self._slot_req[j]
        prompt = np.asarray(req.prompt)
        ext = np.concatenate([prompt.astype(np.int64),
                              np.asarray(req.emitted, np.int64)]) \
            .astype(prompt.dtype, copy=False)
        nreq = Request(rid=req.rid, prompt=ext,
                       max_new=req.max_new - len(req.emitted),
                       key=req.key, deadline=req.deadline)
        self._release_slots([j])   # registers the chain, frees pages
        self._queue.appendleft(nreq)
        self.stats.count("preempted")
        return True

    def _alloc_tick(self) -> None:
        """Lazy per-step page allocation: before decode, every active
        slot whose write position crosses into an unmapped page gets
        one.  On exhaustion the engine preempts (newest-admitted first)
        until the allocation lands — the preempted victim's pages go
        cached-then-evictable, so each round frees real capacity and
        the loop terminates (a request too big to EVER fit is rejected
        at submit).  With a window class a slot needs the page in both
        tables, and either class running dry preempts."""
        bs = self._mgr.block_size
        paged = [(self._table, self._mgr)]
        if self._table_w is not None:
            paged.append((self._table_w, self._mgr.window))
        for j in range(self.serve_cfg.slots):
            while True:
                req = self._slot_req[j]
                if req is None or self._prefilling[j]:
                    break
                bi = int(self._pos[j]) // bs
                # Class by class: a page from each that lacks one.
                for table, mgr in paged:
                    if table[j, bi] < 0:
                        got = mgr.alloc(1)
                        if got is None:
                            break
                        table[j, bi] = got[0]
                else:
                    break
                if not self._preempt_one():
                    break

    def _release_behind(self, j: int) -> None:
        """Give back the window-class page slot ``j``'s window has left:
        called behind the step that last read it, with the slot's
        position already advanced, so at most one page lies behind the
        next step's first.  The entry goes to ``-1`` (the read never
        names a page behind its span) and the page to the class's free
        list, for any slot's next allocation."""
        w = self._mgr.window
        before = w.first_page(self._pos[j]) - 1
        if before >= 0 and self._table_w[j, before] >= 0:
            w.release([int(self._table_w[j, before])])
            self._table_w[j, before] = -1
            self.stats.count("window_pages_freed")

    def kv_bytes_resident(self) -> int:
        """Deterministic KV-residency census (one rank's shard): bytes
        of cache RESERVED for request state right now — the in-use
        pages (a shared prefix counted once; at ``block_size=0`` every
        occupied slot's one page of ``max_seq`` rows), each class's at
        the bytes a page of its layers holds.  It is a census,
        not a timer, so it regresses deterministically on CPU smoke."""
        # One token's rows over every cache leaf (a leaf is (..., rows
        # of a slot or a page, *row shape), behind the stacked axis),
        # and what an occupied slot keeps whatever its length (a
        # per-slot state: (..., slots, *its shape)).
        lead = 3 if self._spmd else 2
        size = lambda a, lead: int(np.prod(a.shape[lead:])) \
            * a.dtype.itemsize
        kept, row = 0, {"full": 0, "window": 0}
        for cls, entry in zip(self._classes, self._cache):
            for k, a in entry.items():
                if k in _kv.STATE_LEAVES:
                    kept += size(a, lead - 1)
                else:
                    row[cls] += size(a, lead)
        pages = self._mgr.blocks_in_use * row["full"]
        if self._mgr.window is not None:
            pages += self._mgr.window.blocks_in_use * row["window"]
        return pages * self._mgr.block_size + self.occupancy() * kept

    def _finish(self, req: Request, status: str = STATUS_OK) -> None:
        self._results[req.rid] = np.concatenate(
            [np.asarray(req.prompt, np.int64),
             np.asarray(req.emitted, np.int64)])
        self._statuses[req.rid] = status
        self.stats.mark(req.rid, "finished")
        self.stats.count("finished" if status == STATUS_OK else status)

    def _release_slots(self, idxs: List[int]) -> None:
        """Return slots to the free pool and unmap their pages in ONE
        pass.  Shared by eviction, preemption and the elastic drain so
        the release convention has a single home.  Nothing is written
        to the freed pages: unmapped (``-1`` table entries) they read as
        zeros or are not read at all, and the causal frontier keeps
        stale mapped rows inert."""
        bs = self._mgr.block_size
        for j in idxs:
            req = self._slot_req[j]
            if req is not None:
                # Register the written rows (prompt + emitted up to
                # the write frontier) before letting the pages go:
                # eviction, drain and preemption all leave the
                # prefix index able to hand the SAME pages back to
                # a re-admission — blocks-intact by content hash.
                n = int(self._pos[j])
                seq = np.concatenate(
                    [np.asarray(req.prompt, np.int64),
                     np.asarray(req.emitted, np.int64)])[:n]
                if n:
                    ids = [int(self._table[j, bi])
                           for bi in range(-(-n // bs))]
                    self._mgr.register(seq, ids, n)
                held = [int(b) for b in self._table[j] if b >= 0]
                self._mgr.release(held)
                self._table[j, :] = -1
                if self._table_w is not None:
                    self._mgr.window.release(
                        [int(b) for b in self._table_w[j] if b >= 0])
                    self._table_w[j, :] = -1
            if self._prefilling[j]:
                self._prefilling[j] = False
                self._prefill_jobs = deque(
                    job for job in self._prefill_jobs
                    if job.slot != j)
        for j in idxs:
            self._slot_req[j] = None
            self._tokens[j] = 0
            self._pos[j] = 0

    def _evict(self, j: int, status: str = STATUS_OK) -> None:
        req = self._slot_req[j]
        self._release_slots([j])
        self.stats.count("evicted")
        self._finish(req, status=status)

    def _expire_sweep(self, events: dict) -> None:
        """Deadline sweep, run at the top of every step: queued
        requests past their deadline finish as bare prompts, slotted
        ones are evicted keeping the tokens emitted so far (a bitwise
        PREFIX of the generate() oracle) — both with the typed
        ``deadline_expired`` status, reported through the step-event
        surface like any other completion."""
        now = self._clock()
        for req in [r for r in self._queue
                    if r.deadline is not None and now >= r.deadline]:
            self._queue.remove(req)
            self._finish(req, status=STATUS_EXPIRED)
            events["expired"].append(req.rid)
        for j, req in enumerate(self._slot_req):
            if req is not None and req.deadline is not None \
                    and now >= req.deadline:
                self._evict(j, status=STATUS_EXPIRED)
                events["expired"].append(req.rid)

    def step(self) -> dict:
        """Admissions, then ONE decode step over the slot table, then
        evictions.  Returns ``{"admitted": [...], "emitted": {rid:
        [tokens]}, "finished": [rid...]}`` — admission first-tokens and
        admission-time completions (``max_new=1``, immediate EOS) are
        reported through the same surface as decode events (a freshly
        admitted request can emit TWO tokens in one step: its prefill
        first-token and its first decode token), so a front-end
        driving replies off ``step()`` never misses one.
        Finished requests' full sequences accumulate for
        :meth:`results`/:meth:`run`; deadline-expired evictions are
        reported under ``events["expired"]`` (typed
        ``deadline_expired`` result status) after the sweep that runs
        BEFORE admission — an expired queued request never burns a
        prefill."""
        span = self.stats.span
        with span(SPAN_STEP):
            events = {"admitted": [], "emitted": {}, "finished": [],
                      "expired": []}
            with span(SPAN_EXPIRE):
                # Between-steps controller consult (mpi4torch_tpu.ctl):
                # a step boundary is the only safe switch point — no
                # collective is in flight, so a ratified codec/schedule
                # switch takes effect on the NEXT step's traffic
                # atomically.  Disabled (the default) or detached, this
                # is one attribute read.
                if self._controller is not None:
                    self._controller.poll()
                self._expire_sweep(events)
            with span(SPAN_ADMIT):
                self._admit(events)
                self._prefill_tick(events)
                self._alloc_tick()
            active = [j for j, r in enumerate(self._slot_req)
                      if r is not None and not self._prefilling[j]]
            if not active:
                self._pool_levels()
                return events
            with span(SPAN_DISPATCH):
                # Ends when the step call has returned, not when the
                # device has run it.
                chosen = self._dispatch_decode()
            with span(SPAN_FETCH):
                with span(SPAN_FETCH_TOKENS):
                    # The one sync, and the one transfer of a greedy
                    # step: waits for the step and for every write
                    # queued before it, then copies the (slots,) tokens
                    # with the step's counters behind them (and, in a
                    # copy of their own, the keys of a sampling engine).
                    chosen = self._fetch(chosen)
                    keys = self._fetch(self._state["keys"]) \
                        if "keys" in self._state else None
                with span(SPAN_FETCH_COUNTERS):
                    # The host's unpacking: no transfer.
                    toks, counters = self._unpack(chosen)
                    self._note_counters("decode", counters)
            with span(SPAN_SELECT):
                # The host's bookkeeping; no device call.  First what
                # the device holds now, then the host's own arrays.
                self._held = _advanced(np, self._held, toks, keys)
                self.stats.tick(len(active), self.serve_cfg.slots)
                self._count_pages(active)
                syncs = self._select_syncs
                for j in active:
                    req = self._slot_req[j]
                    if keys is not None:
                        req.key = keys[j]
                    tok = self._select(req, toks[j])
                    req.emitted.append(tok)
                    events["emitted"].setdefault(req.rid, []).append(tok)
                    self.stats.count("decode_tokens")
                    self._pos[j] += 1
                    self._tokens[j] = tok
                    if self._table_w is not None:
                        self._release_behind(j)
                    if req.finished(self.serve_cfg.eos):
                        events["finished"].append(req.rid)
                        self._evict(j)
                self.stats.count("decode_select_syncs",
                                 self._select_syncs - syncs)
                self._pool_levels()
            return events

    def _fetch(self, out) -> np.ndarray:
        """The host's copy of what a step chose; under SPMD rank 0's
        row of the stacked result (every rank chose the same)."""
        out = np.asarray(out)
        return out[0] if self._spmd else out

    def _unpack(self, chosen: np.ndarray) -> tuple:
        """What :meth:`_advance` packed, apart again: ``(tokens
        (slots,), {counter: value in its own shape})``."""
        slots = self.serve_cfg.slots
        toks, rest, counters = chosen[:slots], chosen[slots:], {}
        for name, shape, n in self._counted:
            counters[name], rest = rest[:n].reshape(shape), rest[n:]
        return toks, counters

    def _host_state(self) -> Dict[str, np.ndarray]:
        """The slot state as the host's arrays, the truth, have it:
        ``tokens``, ``pos``, the ``live`` mask, the block ``table``
        (with a window class, ``table_window`` beside it),
        and a sampling engine's ``keys``, the live slots'
        requests' keys as ``(slots, ...)`` raw key bits (zeros in the
        other rows; a greedy engine moves no key)."""
        slots = self.serve_cfg.slots
        live = np.asarray([self._slot_req[j] is not None
                           and not self._prefilling[j]
                           for j in range(slots)])
        host = {"tokens": self._tokens, "pos": self._pos, "live": live,
                "table": self._table}
        if self._table_w is not None:
            host["table_window"] = self._table_w
        if self.serve_cfg.temperature > 0:
            rows = {j: np.asarray(self._slot_req[j].key)
                    for j in np.flatnonzero(live)}
            blank = np.zeros_like(next(iter(rows.values()),
                                       np.zeros(2, np.uint32)))
            host["keys"] = np.stack(
                [rows.get(j, blank) for j in range(slots)])
        return host

    def _step_inputs(self) -> Dict[str, Any]:
        """The slot state a decode step takes besides the shards and
        the cache, on the device.  Each of the host's arrays is held
        against the memory of what the device holds; those that differ
        (what an admission, an eviction, a crossed page or a wrapped
        ``_select`` wrote since the last step) go up, in one
        ``jax.device_put`` however many, each counted in
        ``decode_uploads``.  Where nothing differs, as in a decode-only
        step behind a decode-only step, nothing moves."""
        host = self._host_state()
        differ = [k for k, a in host.items()
                  if k not in self._held
                  or not np.array_equal(a, self._held[k])]
        self.stats.count("decode_uploads", len(differ))
        if differ:
            # Copies: the host writes its arrays in place.
            self._held.update((k, np.array(host[k])) for k in differ)
            stacked = (self._size,) if self._spmd else ()
            self._state.update(zip(differ, jax.device_put(
                [np.broadcast_to(self._held[k],
                                 stacked + self._held[k].shape)
                 for k in differ], self._state_sharding)))
        return self._state

    def _dispatch_decode(self):
        """Queue ONE decode step over the slot table (the new cache and
        the advanced slot state replace the old) and return what it
        chose, still on the device: ``(slots,)`` tokens with the step's
        counters packed behind them (``moe_rows`` where a layer has
        experts), under SPMD stacked per rank.  The ``(slots, vocab)``
        logits stay where they were computed.  The step takes the
        pool over and writes into it, and a compiled step takes
        the slot state over too: whoever held ``self._cache``'s or
        ``self._state``'s old leaves holds deleted arrays afterwards,
        as after an install."""
        span = self.stats.span
        with span(SPAN_DISPATCH_INPUTS):
            state = self._step_inputs()
        with span(SPAN_DISPATCH_CALL):
            # Ends when the call has returned: the arguments flattened
            # and the program queued, not run.
            step = self._step_call if self._spmd else self._decode
            chosen, self._state, self._cache = step(
                self._shards, self._cache, state)
            return chosen

    def _pool_levels(self) -> None:
        """Mirror the block pool's population into the gauge-semantics
        ServeStats counters (and, through the registered serve
        collector, into the ``mpi4torch_serve_*`` obs metrics) at the
        end of every step."""
        self.stats.level("blocks_in_use", self._mgr.blocks_in_use)
        self.stats.level("blocks_free", self._mgr.free_blocks)
        self.stats.level("blocks_cached", self._mgr.cached_blocks)
        w = self._mgr.window
        if w is not None:
            self.stats.level("window_blocks_in_use", w.blocks_in_use)
            self.stats.level("window_blocks_free", w.free_blocks)

    def run(self, max_steps: Optional[int] = None) -> Dict[Any, np.ndarray]:
        """Drive :meth:`step` until every submitted request finished
        (or ``max_steps``); returns ``{rid: full token sequence}`` —
        prompt + emitted, the ``generate()`` output shape."""
        steps = 0
        while self.pending():
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return dict(self._results)

    def results(self) -> Dict[Any, np.ndarray]:
        return dict(self._results)

    def statuses(self) -> Dict[Any, str]:
        """Typed result status per finished rid: ``"ok"`` (ran to
        EOS/budget), ``"deadline_expired"`` (evicted past its
        deadline; its result is the oracle-prefix it got to), or
        ``"shed"`` (queue-evicted by the overload shed policy)."""
        return dict(self._statuses)

    def status(self, rid) -> Optional[str]:
        return self._statuses.get(rid)

    def pop_results(self) -> Dict[Any, np.ndarray]:
        """Retrieve-and-drop every finished result, releasing its
        request id and memory — the steady-state serving API: a
        long-lived engine that never pops grows its result table (and
        id ledger) linearly with requests served.  A popped rid may be
        reused by a later :meth:`submit`."""
        out, self._results = self._results, {}
        self._known_rids.difference_update(out)
        for rid in out:
            self._statuses.pop(rid, None)
        return out

    # ------------------------------------------------------------ elastic

    def _inflight_records(self) -> List[dict]:
        """Host-side snapshot of every unfinished request (queued and
        slotted), in slot order then queue order — the drain payload of
        the elastic runtime (mpi4torch_tpu.elastic.replan)."""
        recs, pages = [], {}
        for j, req in enumerate(self._slot_req):
            if req is not None:
                recs.append(req)
                # Block-table state rides the drain record: which pages
                # held this request's written rows, and how many.
                # Re-admission into the same pool recovers them through
                # the content-addressed prefix index (the registering
                # _release_slots), so the ticket's copy is the EXPLICIT
                # form of what the hash chain guarantees — drained
                # requests re-admit with their prefix-shared pages
                # intact.
                n = int(self._pos[j])
                bs = self._mgr.block_size
                pages[id(req)] = {
                    "block_ids": [int(self._table[j, bi])
                                  for bi in range(-(-n // bs))],
                    "n_tokens": n}
        recs.extend(self._queue)
        return [{"rid": r.rid,
                 "prompt": np.array(r.prompt, copy=True),
                 "emitted": list(r.emitted),
                 "max_new": r.max_new,
                 "key": r.key,
                 "deadline": r.deadline,
                 "pages": pages.get(id(r))} for r in recs]

    def attach_controller(self, controller) -> None:
        """Attach a :class:`mpi4torch_tpu.ctl.SelfTuningController`:
        every subsequent :meth:`step` consults ``controller.poll()``
        FIRST (the between-steps switch point — a ratified switch lands
        before the step's collectives are issued, never mid-step).
        With ``config.ctl_enabled()`` False (the default) the consult
        is one knob read and the engine's behavior is unchanged;
        ``attach_controller(None)`` detaches."""
        self._controller = controller

    def snapshot_inflight(self) -> List[dict]:
        """Non-destructive :meth:`drain`: the same records, with the
        engine untouched.  An elastic driver snapshots after each step
        so that a rank death mid-step still leaves a survivor-held
        ledger to re-admit from (host request state is identical on
        every rank — every rank's step chooses the same tokens)."""
        return self._inflight_records()

    def drain(self) -> List[dict]:
        """Drain every unfinished request out of the engine: returns
        their records (prompt, tokens emitted so far, remaining budget,
        the advanced sampling key) and releases their slots (pages
        unmapped) and queue entries.  Finished results stay
        retrievable via :meth:`results`.  The elastic shrink/grow path:
        drain here, re-admit on the new world's engine through the
        ordinary admission POLICIES (``elastic.replan.readmit``)."""
        recs = self._inflight_records()
        self._release_slots([j for j, req in enumerate(self._slot_req)
                             if req is not None])
        self._queue.clear()
        # The drained rids leave this engine's ledger: they will be
        # re-admitted on ANOTHER engine (or back here) explicitly.
        self._known_rids.difference_update(r["rid"] for r in recs)
        return recs

    # ------------------------------------------------------------- census

    def program_texts(self) -> Dict[str, str]:
        """The compiled (Mode A) programs' text, read-only: ``"decode"``,
        the decode step over the current slot-table state, and
        ``"prefill.<n>"`` for every prompt length ``n`` this engine has
        prefilled in one piece.  Each is lowered and compiled again from
        the shapes it ran with (the persistent compile cache answers
        where it is on), so the instructions carry the names their
        events have in a profiler trace and the ``op_name`` of the
        scope they ran under (``layer_scope``)."""
        if not self._spmd:
            raise CommError(
                "program_texts reads the compiled SPMD programs; "
                "construct the engine with spmd=True")
        text = lambda call, *args: call.lower_as_called(
            *args).compile().as_text()
        # What is lowered and compiled here is on the compile log under
        # this span: a reader of set-up leaves it out.
        with self.stats.span(SPAN_PROGRAM_TEXTS):
            out = {"decode": text(self._step_call, self._shards,
                                  self._cache, self._step_inputs())}
            for n in sorted(self._prefilled):
                out[f"prefill.{n}"] = text(
                    self._prefill_call, self._shards,
                    jax.ShapeDtypeStruct((1, n), jnp.int32))
        return out

    def lower_step(self):
        """The lowered (Mode A) decode-step program over the CURRENT
        slot-table state — the deterministic census surface:
        ``overlap.scheduled_exposure(engine.lower_step())`` and the
        latency-tier span assertions read it (``make serve-smoke``,
        tests/test_serve.py)."""
        if not self._spmd:
            raise CommError(
                "lower_step censuses the compiled SPMD decode program; "
                "construct the engine with spmd=True")
        # The block table is an ARGUMENT, a leaf of the slot state: two
        # different table states lower to the identical program text
        # (the no-retrace census in `make serve-smoke` holds exactly
        # this).
        return jax.jit(self._step_call).lower(
            self._shards, self._cache, self._step_inputs())
