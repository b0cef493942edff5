"""Profiling convenience: capture a device trace with the op spans on.

The reference's only observability surface is its autograd node names
showing up in torch's profiler (SURVEY.md §5 tracing; reference:
csrc/extension.cpp:256-258).  Here every facade op already runs under a
``jax.named_scope`` (comm.py) and every SPMD collective adjoint under an
explicit ``...Backward`` scope (ops/spmd.py), so any JAX profiler trace
carries ``mpi4torch.Allreduce``-style spans; this module only packages
the capture:

    from mpi4torch_tpu.utils import profiler_trace

    with profiler_trace("/tmp/trace"):
        step(params, batch)           # compiled or eager work

    # -> /tmp/trace/plugins/profile/<run>/*.xplane.pb, viewable with
    #    TensorBoard's profile plugin or xprof / Perfetto.

On TPU the trace includes per-core timelines, HLO op breakdowns, and the
collective/ICI activity the named scopes label; on CPU it still records
host-side XLA execution (the harness smoke path, tests/test_observability).
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from collections import deque

from jax import monitoring as _monitoring
from jax.core import unsafe_am_i_under_a_jit_DO_NOT_USE as _under_a_trace
from jax.profiler import TraceAnnotation

__all__ = ["profiler_trace", "bucket_scope", "serve_step_scope",
           "layer_scope", "LAYER_SCOPES", "ServeStats", "serve_stats",
           "reset_serve_stats",
           "serve_step_log", "STEP_SPAN", "STEP_LOG_CAP",
           "compile_log", "compile_totals", "COMPILE_LOG_CAP",
           "setup_spans", "SETUP_SPAN_CAP"]

# The span that is one ``Engine.step()`` call; every other serving span
# (``mpi4torch.serve.step.<phase>``, doc/serving.md) lies inside it.
STEP_SPAN = "mpi4torch.serve.step"
STEP_LOG_CAP = 8192
# The step log: one record per closed STEP_SPAN, newest last, of every
# engine in the process.  Module-level, so it outlives the engines the
# way a flight record must; bounded, so it never grows with traffic.
_STEP_LOG: deque = deque(maxlen=STEP_LOG_CAP)
_ENGINE_IDS = itertools.count()
# What a step record says the step did: counter -> the record's key.
# ``tick()`` adds the active slots once per decode step, so the step's
# share of ``occupancy_ticks`` is the slots it decoded.
_STEP_COUNTS = (("admitted", "admitted"),
                ("prefill_tokens", "prefill_tokens"),
                ("install_writes", "install_writes"),
                ("decode_pages_live", "decode_pages_live"),
                ("decode_pages_read", "decode_pages_read"),
                ("decode_grid_steps", "decode_grid_steps"),
                ("decode_select_syncs", "decode_select_syncs"),
                ("moe_overflow_calls", "moe_overflow_calls"),
                ("moe_zero_pairs", "moe_zero_pairs"),
                ("moe_live_pairs", "moe_live_pairs"),
                ("dsa_rows_live", "dsa_rows_live"),
                ("dsa_rows_read", "dsa_rows_read"),
                ("dsa_rows_scored", "dsa_rows_scored"),
                ("ssm_states_live", "ssm_states_live"),
                ("ssm_states_touched", "ssm_states_touched"),
                ("window_pages_held", "window_pages_held"),
                ("window_slots_live", "window_slots_live"),
                ("window_pages_freed", "window_pages_freed"),
                ("decode_uploads", "decode_uploads"),
                ("step_compiles", "step_compiles"),
                ("occupancy_ticks", "active"))
# JAX's own events when a program has been traced, lowered, and compiled
# by the backend (a persistent-cache hit included: the last is the one
# ``benchmarks`` counts as ``compiles_in_window``): event -> the ``kind``
# of its record on the compile log.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_COMPILE_KINDS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
                  _COMPILE_EVENT: "compile"}
# What the persistent cache says of a compilation, on the compiling
# thread and inside the backend-compile interval: one of the two plain
# events, and behind a hit the seconds the read took.
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}
_CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
# The compile log: one record per such event of the process, newest
# last, whoever compiled (an engine's step or constructor, a training
# program, a bare ``jax.jit``); but a function traced INSIDE another's
# trace (every ``jnp`` function a model calls is one: thousands a
# program) is part of that program's trace, whose record covers it, and
# is counted, not recorded.  Bounded like the step log; the totals per
# (kind, cache) beside it are never capped.  A few hundred appends a
# process, none in the steady state: a program that has compiled sends
# no event.  The cap holds the most a cell's process has made with room
# to spare: 8,488 in ``internlm2-1.8b.serve_chat``, whose constructor
# runs with jit off and sends 2,793 small programs through trace,
# lowering and the cache one by one (PERF.md, PR 50); the other cells'
# make a few hundred.
COMPILE_LOG_CAP = 16384
_INNER_TRACES = ("trace.inner", None)     # their key in the totals
_COMPILE_LOG: deque = deque(maxlen=COMPILE_LOG_CAP)
_COMPILE_TOTALS: dict = {}                # (kind, cache) -> [ns, count]
# Spans closed while no step was open (an engine's constructor,
# ``program_texts``): ``(name, t0_ns, t1_ns, rid, engine)``, newest last.
SETUP_SPAN_CAP = 4096
_SETUP_SPANS: deque = deque(maxlen=SETUP_SPAN_CAP)
_LOG_LOCK = threading.Lock()
# Per thread: ``.stats``, the ServeStats whose step is open on this
# thread; ``.stack``, the spans open on it, innermost last, with or
# without a step (one engine's spans are written by the one thread that
# steps it, and a compilation runs on the thread that called the
# program); ``.cache``, what the persistent cache has said of the
# compilation now running on it.
_STEPPING = threading.local()


def bucket_scope(op: str, index: int, total: int, codec=None, phase=None):
    """Named scope for one bucket of a fused tree collective
    (mpi4torch_tpu.fuse):
    ``mpi4torch.<op>.bucket<i>of<n>[.<codec>][.<phase>]``.

    The fused path replaces hundreds of per-leaf op spans with a few
    per-bucket ones; these scopes keep the profiler story intact —
    every transfer in a trace is attributable to a specific bucket, and
    compressed buckets carry the codec suffix exactly like the facade's
    single-tensor ops (``mpi4torch.Allreduce.q8``).  Nested inside the
    facade's own per-op scope, so a fused q8 bucket shows as
    ``mpi4torch.Allreduce_tree.bucket0of3.q8/mpi4torch.Allreduce.q8``.

    ``phase`` labels the split-phase halves of the overlap scheduler
    (mpi4torch_tpu.overlap): ``"start"`` spans cover the issue of a
    bucket's collective, ``"wait"`` spans its completion point — so a
    trace separates *hidden* communication (device collective activity
    that falls under compute spans issued between a bucket's ``.start``
    and ``.wait``) from *exposed* communication (activity that the
    timeline shows under the ``.wait`` span itself, where the program
    had nothing else to run).  The blocking path's unsuffixed bucket
    spans are 100% exposed by construction.

    With a comm tracer installed (mpi4torch_tpu.obs) the scope name is
    additionally pushed onto the tracer's thread-local label stack, so
    Mode B chokepoint events inside the scope carry the bucket label
    (``jax.named_scope`` itself is invisible to the eager rendezvous);
    without a tracer the push is skipped entirely."""
    name = f"mpi4torch.{op}.bucket{index}of{total}"
    if codec is not None:
        name += f".{codec.name}"
    if phase is not None:
        if phase not in ("start", "wait"):
            raise ValueError(
                f"bucket_scope phase must be 'start' or 'wait', got "
                f"{phase!r}")
        name += f".{phase}"
    return _labeled_scope(name)


def serve_step_scope(what: str = "decode_step"):
    """Named scope ``mpi4torch.serve.<what>`` around one serving-engine
    phase (:mod:`mpi4torch_tpu.serve`) — the decode-step analogue of
    :func:`bucket_scope`: the span survives into the StableHLO location
    table of a lowered engine step, so every decode collective a
    scheduled-exposure census classifies is attributable to the serving
    path (its full location reads
    ``mpi4torch.serve.decode_step/.../mpi4torch.ServeDecode.bucket<i>of
    <n>.<phase>/...``), and profiler traces separate prefill spans from
    decode spans per engine step."""
    return _labeled_scope(f"mpi4torch.serve.{what}")


# The mechanisms a per-layer spec can name (models/transformer.py), each
# under its own scope: forward, recomputed and backward instructions of a
# compiled step all carry the name in their ``op_name``.
LAYER_SCOPES = {"kda": "mpi4torch.kda", "mla": "mpi4torch.mla",
                "moe": "mpi4torch.moe", "ffn": "mpi4torch.ffn",
                "dsa": "mpi4torch.dsa", "ssm": "mpi4torch.ssm",
                "ssm_scan": "mpi4torch.ssm_scan",
                "ssm_update": "mpi4torch.ssm_update",
                "attn": "mpi4torch.attn",
                "attn_window": "mpi4torch.attn_window",
                "attn_full": "mpi4torch.attn_full",
                "moe_exchange": "mpi4torch.moe_exchange"}


def layer_scope(kind: str):
    """Named scope ``mpi4torch.<kind>`` around one mechanism of a layer:
    ``kda`` (the gated delta-rule mixer, projections included), ``mla``
    (the latent-attention mixer), ``moe`` (router, grouped expert
    products, shared and zero-compute experts), ``ffn`` (the dense FFN
    of a layer that carries or joins a shortcut branch, and of no other
    layer: the path the branch runs beside), ``dsa`` (a scoring
    layer's indexer: its projections, the index-key cache write, the
    scoring and the top-k; the read of the selected rows is the latent
    read and stays under ``mla``) or ``ssm`` (a Mamba-2 mixer, whole:
    projections, convolution, recurrence, gated norm), with one of two
    more INSIDE it around the convolution and the recurrence:
    ``ssm_scan`` where a prefill runs them over a prompt (the chunked
    scan), ``ssm_update`` where a decode step advances every slot's
    kept state by one token; or ``attn`` (an attention mixer stated on
    the layer, ``GQA``, whole: projections, norms on queries and keys,
    rotation, gate, output projection), with one of two more INSIDE it
    around the attention itself (on the serving path the cache write
    and the read): ``attn_window`` on a layer with a window,
    ``attn_full`` on one without.  ``moe_exchange`` lies INSIDE ``moe``
    where the layer runs over an expert-parallel communicator
    (``parallel.moe.exchanged_experts_ffn``): the all-to-alls that carry
    the rows to their experts' owners and back, the counts that go
    ahead, and the owner's sort of what arrived; forward, recomputed and
    backward.  A table that maps ``op_name`` s to scopes looks for it
    before ``moe``, whose name it begins with."""
    return _labeled_scope(LAYER_SCOPES[kind])


@contextlib.contextmanager
def _labeled_scope(name: str):
    """``jax.named_scope(name)`` plus the obs label-stack push (a no-op
    when no comm tracer is installed — the scopes stay free with
    observability off)."""
    import jax

    from ..obs.trace import push_label

    with push_label(name), jax.named_scope(name):
        yield


class ServeStats:
    """Serving observability: engine counters, per-request marks and
    the spans of ``Engine.step()``.

    Counters (monotonic ints): ``steps`` (decode steps run), ``admitted``
    / ``evicted`` / ``finished`` / ``rejected`` (request lifecycle),
    ``decode_tokens`` (tokens emitted by decode steps; prefill's first
    token counts under ``admitted``), ``occupancy_ticks`` (sum of active
    slots over steps) and ``slot_ticks`` (slots x steps) — their ratio
    is the mean slot occupancy, THE continuous-batching utilization
    number.  Spans (per request id): ``submitted`` -> ``admitted`` ->
    ``first_token`` -> ``finished`` wall-clock timestamps, from which
    :meth:`snapshot` derives time-to-first-token and end-to-end
    latencies.  Step spans (:meth:`span`): the phases of every
    ``Engine.step()`` call on ``time.perf_counter_ns()``, kept per step
    on the process-wide step log (:func:`serve_step_log`), those closed
    outside a step (the constructor's) on :func:`setup_spans`, and all
    summed per phase under ``snapshot()["phase_s"]``; one engine's spans
    are written by the one thread that steps it.
    Thread-safe (Mode B runs one engine per rank thread);
    engines register here so :func:`serve_stats` aggregates
    process-wide.  ``evicted`` counts slots freed — a request finishing
    at admission (max_new=1 / immediate EOS) never occupied one, so
    ``finished >= evicted``.  Spans are capped at the most recent
    :data:`SPAN_CAP` requests (counters are O(1) forever; an unbounded
    span dict would grow with total traffic served)."""

    _COUNTERS = ("steps", "admitted", "evicted", "finished", "rejected",
                 "decode_tokens", "occupancy_ticks", "slot_ticks",
                 # ISSUE 15: typed non-ok completions — deadline-expired
                 # evictions and shed-policy queue evictions.
                 "deadline_expired", "shed",
                 # ISSUE 17: paged KV cache.  prefix_hits/misses count
                 # admissions that did/didn't reuse indexed prefix
                 # pages; prefill_tokens counts tokens actually run
                 # through prefill (the prefix-sharing census: reused
                 # prefix tokens never re-enter it); cow_copies and
                 # preempted count copy-on-write page copies and
                 # pool-pressure slot preemptions.  blocks_in_use /
                 # blocks_free / blocks_cached are LEVELS (absolute
                 # pool occupancy re-set each step via :meth:`level`,
                 # not monotonic counts) riding the same mirrored
                 # namespace.
                 "prefix_hits", "prefix_misses", "prefill_tokens",
                 "cow_copies", "preempted",
                 "blocks_in_use", "blocks_free", "blocks_cached",
                 # ISSUE 25: dispatches of the compiled install that
                 # writes prefill rows into the pool (one an install,
                 # however many pages or cache leaves).
                 "install_writes",
                 # ISSUE 29: pages the paged decode steps' live slots
                 # held up to their frontiers (sum of pos // block_size
                 # + 1), and pages those steps' attention visited by
                 # the read the engine compiled: the same pages through
                 # the kernel, slots x max_seq / block_size through the
                 # gather.  Their ratio says which read ran.
                 "decode_pages_live", "decode_pages_read",
                 # ISSUE 40: grid steps one call of those steps' paged
                 # read walked (``ops.paged_attention.read_grid``: slots
                 # x pages of a table row / pages a grid step; 0 through
                 # the gather).  Over ``decode_pages_live``: what a live
                 # page pays of the grid's fixed cost.
                 "decode_grid_steps",
                 # ISSUE 31: round trips to the device that the decode
                 # steps' ``decode.select`` spent choosing tokens (one
                 # per live slot while the host chose them from the
                 # logits table; 0 since the step chooses them itself).
                 "decode_select_syncs",
                 # ISSUE 34: the live (token, chosen expert) pairs of the
                 # compiled programs' expert layers that have
                 # zero-compute experts, and those of them that chose
                 # one (``kv._hand_out``; summed over a step's prefills
                 # and decode step, expert layers and live rows).  Their
                 # ratio is the share of choices that cost no expert.
                 "moe_zero_pairs", "moe_live_pairs",
                 # ISSUE 42: calls of an expert layer (a prefill's piece
                 # is one) whose held rows did not fit the prefix of the
                 # sorted pairs the layer works on and took the branch
                 # for the rows behind it
                 # (``parallel.moe.held_experts_ffn``).
                 "moe_overflow_calls",
                 # ISSUE 39: sparse latent attention's decode steps
                 # (``kv._hand_out``): the latent rows under the live
                 # slots' frontiers summed over the indexed layers, the
                 # rows their selections named (what attention read of
                 # them: min(pos + 1, top_k) a slot and layer), and the
                 # index keys the scoring layers scored.
                 "dsa_rows_live", "dsa_rows_read", "dsa_rows_scored",
                 # ISSUE 41: per decode step with Mamba-2 layers, the
                 # (live slot, layer) pairs whose kept state the step had
                 # to advance, and those it read and wrote.
                 "ssm_states_live", "ssm_states_touched",
                 # ISSUE 45: a window class of pages (layers whose
                 # attention reads a sliding window; serve/paging.py).
                 # Per decode step, the pages of that class the step's
                 # live slots held and those slots (their ratio: at most
                 # the pages a window touches), and the pages released
                 # behind the step because the window had left them.
                 # window_blocks_in_use / window_blocks_free are LEVELS,
                 # the class's pool occupancy beside blocks_in_use.
                 "window_pages_held", "window_slots_live",
                 "window_pages_freed", "window_blocks_in_use",
                 "window_blocks_free",
                 # ISSUE 36: host-to-device transfers the decode steps'
                 # ``decode.dispatch.inputs`` made (table, tokens,
                 # positions, live mask, and the keys where the engine
                 # samples), and backend compilations that ended while
                 # a step was open (each also on that step's record
                 # under ``compiles``, with the span it ended in).
                 "decode_uploads", "step_compiles")
    SPAN_CAP = 1024

    def __init__(self):
        self._lock = threading.Lock()
        self.counters = {k: 0 for k in self._COUNTERS}
        self.spans = {}
        self.engine = next(_ENGINE_IDS)   # "engine" of its step records
        self.phases = {}                  # span name -> [ns, count]
        self._open = None                 # spans of the step that is open
        self._open_attached = {}          # what attach() put on it
        self._open_counts = None

    def reset(self) -> None:
        """Zero the counters and drop the spans and phase totals (in
        place, so an engine holding this object keeps counting from
        zero)."""
        with self._lock:
            for k in list(self.counters):
                self.counters[k] = 0
            self.spans.clear()
            self.phases.clear()

    def span(self, name: str, rid=None) -> "_Span":
        """Context manager around one phase of the engine's work: a
        ``jax.profiler.TraceAnnotation(name)`` (so that in any xplane
        capture the span sits on the trace's own clock beside the
        device lines; not made at all while no profiler session records) and one
        ``(name, t0_ns, t1_ns, rid)`` from ``time.perf_counter_ns()``
        — the clock :meth:`mark` reads — appended to the record of the
        step that is open or, where none is (the constructor's phases,
        ``program_texts``), with the engine's serial to the process-wide
        set-up list (:func:`setup_spans`); either way it counts into
        ``snapshot()["phase_s"]``.  :data:`STEP_SPAN` opens a step's
        record and, closing, puts it on the process-wide step log
        (:func:`serve_step_log`).  Nesting gives the parent: a child
        lies inside its parent's interval.  While it is open the span is
        the innermost one of its thread: what a record of
        :func:`compile_log` made then names.  Always on, like the
        counters; ``rid`` may be set on the returned object before the
        block ends."""
        return _Span(self, name, rid)

    def attach(self, key: str, value) -> None:
        """Put ``value`` on the record of the step that is open, under
        ``key``: a list, in the order attached.  Outside a step nothing
        is attached: the set-up list holds spans alone, and a
        compilation made there is on :func:`compile_log` with its span.
        For what a step's compiled programs count themselves:
        ``moe_rows``, one ``(program, rows)`` per call of a program with
        an expert layer (``serve.Engine._note_counters``); and
        ``compiles``, one ``(span, rid, seconds)`` per backend
        compilation that ended while the step was open, with the
        innermost span open then: the step's view of that record of the
        compile log.  A record has the key only where something was
        attached."""
        if self._open is not None:
            self._open_attached.setdefault(key, []).append(value)

    def _open_step(self) -> None:
        self._open = []
        self._open_attached = {}
        _STEPPING.stats = self
        with self._lock:
            self._open_counts = [self.counters[c] for c, _ in _STEP_COUNTS]

    def _compiled(self, record: dict, seconds: float) -> None:
        """A backend compilation ended on the thread of the open step:
        the step's view of its record on the compile log."""
        self.attach("compiles",
                    (record["span"], record["rid"], float(seconds)))
        self.count("step_compiles")

    def _span_closed(self, name: str, t0: int, t1: int, rid) -> None:
        spans = self._open
        if spans is None:       # no step is open: the set-up list
            with self._lock:
                self._sum_phases(((name, t0, t1, rid),))
            _SETUP_SPANS.append((name, t0, t1, rid, self.engine))
            return
        spans.append((name, t0, t1, rid))
        if name != STEP_SPAN:
            return
        self._open = None
        _STEPPING.stats = None
        record = {"engine": self.engine, "t0_ns": t0, "t1_ns": t1,
                  "spans": spans, **self._open_attached}
        with self._lock:
            for (c, key), base in zip(_STEP_COUNTS, self._open_counts):
                record[key] = self.counters[c] - base
            self._sum_phases(spans)
        _STEP_LOG.append(record)

    def _sum_phases(self, spans) -> None:
        for n, a, b, _ in spans:
            tot = self.phases.setdefault(n, [0, 0])
            tot[0] += b - a
            tot[1] += 1

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + int(n)

    def level(self, name: str, value) -> None:
        """Set a gauge-semantics counter to an ABSOLUTE value (the
        paged engine's pool occupancy levels: blocks_in_use /
        blocks_free / blocks_cached, re-set every step).  Levels ride
        the same counters dict so the aggregate, reset, and obs
        mirroring cover them for free; :func:`serve_stats` summing
        across engines turns per-engine levels into fleet totals."""
        with self._lock:
            self.counters[name] = int(value)

    def tick(self, active: int, slots: int) -> None:
        """One decode step over a ``slots``-slot table with ``active``
        live slots."""
        with self._lock:
            self.counters["steps"] += 1
            self.counters["occupancy_ticks"] += int(active)
            self.counters["slot_ticks"] += int(slots)

    def mark(self, rid, event: str) -> None:
        """Record a request-lifecycle timestamp (``submitted`` /
        ``admitted`` / ``first_token`` / ``finished``); the first
        occurrence wins, so re-marking is harmless.  Oldest spans are
        evicted past :data:`SPAN_CAP` (dict order is insertion order)."""
        with self._lock:
            span = self.spans.setdefault(rid, {})
            span.setdefault(event, time.perf_counter())
            while len(self.spans) > self.SPAN_CAP:
                self.spans.pop(next(iter(self.spans)))

    def snapshot(self) -> dict:
        """Counters + derived occupancy and latency aggregates.  The
        latency dicts carry mean/max plus p50/p99 via the ONE shared
        percentile rule (:func:`mpi4torch_tpu.obs.percentile`,
        nearest-rank floor, so "p99" means one thing package-wide)."""
        from ..obs.metrics import percentile

        with self._lock:
            counters = dict(self.counters)
            spans = {rid: dict(s) for rid, s in self.spans.items()}
            phases = {n: tuple(t) for n, t in self.phases.items()}
        ttft = [s["first_token"] - s["submitted"] for s in spans.values()
                if "first_token" in s and "submitted" in s]
        e2e = [s["finished"] - s["submitted"] for s in spans.values()
               if "finished" in s and "submitted" in s]
        out = dict(counters)
        out["occupancy"] = (
            round(counters["occupancy_ticks"] / counters["slot_ticks"], 4)
            if counters["slot_ticks"] else None)
        out["n_requests_tracked"] = len(spans)
        # Per-phase totals of the step spans (ISSUE 25): what the step
        # log holds of this engine, summed, and never capped.
        out["phase_s"] = {n: {"seconds": ns / 1e9, "count": c}
                          for n, (ns, c) in phases.items()}
        if ttft:
            out["ttft_s"] = {"mean": sum(ttft) / len(ttft),
                             "max": max(ttft),
                             "p50": percentile(ttft, 0.50),
                             "p99": percentile(ttft, 0.99)}
        if e2e:
            out["e2e_s"] = {"mean": sum(e2e) / len(e2e), "max": max(e2e),
                            "p50": percentile(e2e, 0.50),
                            "p99": percentile(e2e, 0.99)}
        return out


class _Span:
    """One :meth:`ServeStats.span`.  A class, not a generator-based
    context manager: the span runs a dozen times an engine step."""

    __slots__ = ("rid", "_stats", "_name", "_ann", "_t0", "_stack")

    def __init__(self, stats: ServeStats, name: str, rid):
        self.rid = rid
        self._stats = stats
        self._name = name
        # An annotation made while no profiler session records stays
        # inert when one starts, so none is made then: between two big
        # calls of a step, with cold caches, it is half a span's cost.
        self._ann = TraceAnnotation(name) \
            if TraceAnnotation.is_enabled() else None

    def __enter__(self):
        if self._name == STEP_SPAN:
            self._stats._open_step()
        try:
            stack = _STEPPING.stack
        except AttributeError:
            stack = _STEPPING.stack = []
        self._stack = stack
        stack.append(self)
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._stack.pop()
        self._stats._span_closed(self._name, self._t0, t1, self.rid)
        return False


def _on_compile(event: str, seconds: float, fun_name=None, **_) -> None:
    """A program was traced, lowered or compiled on this thread: one
    record on the compile log, named by the innermost span open here;
    a backend compilation also goes to the step that is open here."""
    kind = _COMPILE_KINDS.get(event)
    if kind is None:
        if event == _CACHE_RETRIEVAL_EVENT:
            _STEPPING.cache = ("hit", float(seconds))
        return
    t1 = time.perf_counter_ns()
    ns = int(round(seconds * 1e9))
    if kind == "trace" and _under_a_trace():
        _tally(_INNER_TRACES, ns)
        return
    stack = getattr(_STEPPING, "stack", None)
    inner = stack[-1] if stack else None
    record = {"kind": kind, "program": fun_name, "t0_ns": t1 - ns,
              "t1_ns": t1,
              "span": None if inner is None else inner._name,
              "rid": None if inner is None else inner.rid,
              "thread": threading.get_ident()}
    cache = None
    if kind == "compile":       # the cache's answer is for this one
        cache, read_s = getattr(_STEPPING, "cache", ("off", None))
        _STEPPING.cache = ("off", None)
        record.update(cache=cache, retrieval_s=read_s)
    _tally((kind, cache), ns)
    _COMPILE_LOG.append(record)
    if kind == "compile":
        stats = getattr(_STEPPING, "stats", None)
        if stats is not None:
            stats._compiled(record, seconds)


def _tally(key: tuple, ns: int) -> None:
    with _LOG_LOCK:
        tot = _COMPILE_TOTALS.setdefault(key, [0, 0])
        tot[0] += ns
        tot[1] += 1


def _on_cache_event(event: str, **_) -> None:
    outcome = _CACHE_EVENTS.get(event)
    if outcome is not None:
        _STEPPING.cache = (outcome, None)


# Once a process; they do nothing until a program is traced, lowered or
# compiled, and a program that has compiled sends no event again.
_monitoring.register_event_duration_secs_listener(_on_compile)
_monitoring.register_event_listener(_on_cache_event)


def compile_log() -> list:
    """A copy of the process-wide compile log, oldest first: one record
    per program's trace, lowering and backend compilation JAX made in
    this process, ``{"kind": "trace" | "lower" | "compile", "program":`` JAX's
    ``fun_name`` (``jit(step)``; a trace's lacks the ``jit()``)``,
    "t0_ns", "t1_ns"`` on ``time.perf_counter_ns()``, the step log's
    clock (``t1`` when the event arrived, ``t0`` the event's seconds
    before it)``, "span", "rid":`` the innermost :meth:`ServeStats.span`
    open on the compiling thread then, or None``, "thread"}``, and on a
    ``compile`` record ``"cache": "hit" | "miss" | "off"`` (what the
    persistent compilation cache said inside the interval; ``off``:
    nothing, the program did not go by the cache) and ``"retrieval_s"``,
    the seconds a hit took to read.  The last :data:`COMPILE_LOG_CAP`
    records; :func:`compile_totals` counts all of them.  A function
    traced inside another's trace leaves no record of its own (the
    outer one's interval holds its seconds); a program traced while
    another is LOWERED does, inside that interval: unite the intervals,
    do not sum them.  A step record's ``compiles`` is the view of the
    ``compile`` records that ended while that step was open."""
    return list(_COMPILE_LOG)


def compile_totals() -> dict:
    """``{(kind, cache): {"seconds", "count"}}`` over every record the
    compile log has held since the last reset, dropped ones included
    (``cache`` is None off a ``compile`` record), and under
    ``("trace.inner", None)`` the functions traced inside another's
    trace, which the log does not hold: their seconds are part of the
    outer traces' too."""
    with _LOG_LOCK:
        return {k: {"seconds": ns / 1e9, "count": c}
                for k, (ns, c) in _COMPILE_TOTALS.items()}


def setup_spans() -> list:
    """A copy of the process-wide list of spans that closed while no
    step was open, oldest first: ``(name, t0_ns, t1_ns, rid, engine)``
    on the step log's clock, ``engine`` the ``ServeStats.engine`` serial
    as on a step record; the last :data:`SETUP_SPAN_CAP` of them (an
    engine's ``snapshot()["phase_s"]`` counts all of its own)."""
    return list(_SETUP_SPANS)


def serve_step_log() -> list:
    """A copy of the process-wide step log, oldest first: one record
    per ``Engine.step()`` call of every engine, ``{"engine", "t0_ns",
    "t1_ns", "spans": [(name, t0_ns, t1_ns, rid), ...], "admitted",
    "prefill_tokens", "install_writes", "decode_pages_live",
    "decode_pages_read", "decode_grid_steps", "decode_select_syncs",
    "moe_zero_pairs",
    "moe_live_pairs", "moe_overflow_calls", "dsa_rows_live",
    "dsa_rows_read", "dsa_rows_scored",
    "ssm_states_live", "ssm_states_touched", "window_pages_held",
    "window_slots_live", "window_pages_freed", "decode_uploads",
    "step_compiles", "active"}`` on
    the ``time.perf_counter_ns()`` clock, the last :data:`STEP_LOG_CAP`
    steps (and ``moe_rows`` / ``compiles`` where :meth:`ServeStats.attach`
    put them).  ``engine`` is the ``ServeStats.engine`` serial of the
    engine that stepped; the counts are what that step added to the
    counters of the same name (``active``: the slots it decoded)."""
    return list(_STEP_LOG)


# Weak references: an engine holds the only strong reference to its
# ServeStats, so a discarded engine drops out of the aggregate (and out
# of memory) instead of being summed forever by an append-only list.
# The registry implementation is the shared obs one
# (mpi4torch_tpu.obs.metrics.StatsSourceRegistry — re-homed there so
# there is ONE weakref-source registry in the repo, not a private copy
# per subsystem); these shims keep the historical entry points and
# semantics bit-for-bit.
_SERVE_GROUP = "serve"


def _register_serve_stats(stats: ServeStats) -> ServeStats:
    from ..obs.metrics import sources

    return sources().register(_SERVE_GROUP, stats)


def _live_serve_stats():
    from ..obs.metrics import sources

    return sources().live(_SERVE_GROUP)


def serve_stats() -> dict:
    """Process-wide aggregate of every LIVE engine's
    :class:`ServeStats` (``mpi4torch_tpu.serve.stats()`` re-exports
    this; engines register weakly, so a garbage-collected engine
    leaves the aggregate).  Counters sum across engines — under the
    eager thread-SPMD runtime each rank thread runs its own engine, so
    counts there are ``nranks`` x the logical traffic (each rank
    really did run every step)."""
    engines = _live_serve_stats()
    agg = {k: 0 for k in ServeStats._COUNTERS}
    phase_s = {}
    snaps = [e.snapshot() for e in engines]
    for snap in snaps:
        for k in agg:
            agg[k] += snap.get(k, 0)
        for name, tot in snap["phase_s"].items():
            into = phase_s.setdefault(name, {"seconds": 0.0, "count": 0})
            into["seconds"] += tot["seconds"]
            into["count"] += tot["count"]
    agg["phase_s"] = phase_s
    agg["n_engines"] = len(engines)
    agg["occupancy"] = (round(agg["occupancy_ticks"] / agg["slot_ticks"], 4)
                        if agg["slot_ticks"] else None)
    return agg


def reset_serve_stats() -> None:
    """Zero every live engine's counters/spans IN PLACE, empty the
    registry, the step log, the set-up spans and the compile log with
    its totals (test/bench isolation).  Engines
    constructed before the reset keep counting on their own (now
    zeroed) ``stats`` object but drop out of the process aggregate — a
    reset mid-flight is a bookkeeping cut, not an engine restart."""
    from ..obs.metrics import sources

    for e in sources().clear(_SERVE_GROUP):
        e.reset()
    _STEP_LOG.clear()
    _SETUP_SPANS.clear()
    _COMPILE_LOG.clear()
    with _LOG_LOCK:
        _COMPILE_TOTALS.clear()


# Serving counters in the unified metrics namespace: a snapshot-time
# collector (the engines already keep the live state; obs polls it)
# rather than a second copy of every counter.
def _register_serve_collector() -> None:
    from ..obs.metrics import register_collector

    register_collector("serve", serve_stats)


_register_serve_collector()


@contextlib.contextmanager
def profiler_trace(logdir: str):
    """Capture a JAX profiler trace of the enclosed block into ``logdir``.

    Delegates to ``jax.profiler.trace`` (exception-safe: the capture
    stops when the block exits either way) — this package's value is the
    op-span discipline documented above, not the capture mechanics.
    Traces from multiple processes of one ``init_distributed`` job may
    share a ``logdir`` — files are keyed by host."""
    import jax

    with jax.profiler.trace(logdir):
        yield
