"""Measurement-driven algorithm autotuner with a persistent cache.

The selector (:mod:`mpi4torch_tpu.tune`) deviates from ``ring`` only on
evidence.  This module produces that evidence: it benchmarks every
applicable algorithm per ``(collective, dtype, nbytes-bucket, nranks,
platform)`` key, records the winner in an in-process table, and
persists the table to a JSON cache file so later *processes* skip the
measurement entirely — steady-state steps pay zero tuning overhead.

Cache file contract:

* location — ``$MPI4TORCH_TPU_TUNE_CACHE`` if set, else
  ``~/.cache/mpi4torch_tpu/tune_cache.json``;
* versioned — the top-level ``version`` field must equal
  :data:`CACHE_VERSION`; a mismatched, corrupt, truncated, or
  hand-edited-beyond-recognition file is silently ignored (selection
  falls back to the defaults — the cache is *safe to delete at any
  time*);
* written atomically (tmp + rename) and best-effort: an unwritable
  cache directory degrades to in-process-only tuning, never an error.

Message sizes are bucketed to the next power of two, so one
measurement covers the whole bucket — the same coarse keying
production autotuners use (a 3 KiB and a 4 KiB allreduce want the same
schedule).

``python -m mpi4torch_tpu.tune.autotuner [--smoke]`` runs the sweep
from the command line and prints the JSON report (``make tune-smoke``
drives the CPU smoke variant).
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, Iterable, List, Optional, Sequence

from .. import config as _config
from .registry import available_algorithms, get_algorithm

# v2: per-size measurement switched from median-of-k to MIN-of-k
# (ISSUE 7 satellite — a single preempted/GC-hit sample could poison a
# persisted winner under the median with few iters); winners measured
# under the old rule are discarded by the version gate.
# v3: synthesized-program keys grew a tier dimension (|tiers=AxB...)
# and fold steps carry tier annotations (Step.tier) that change synth
# digests — v2 entries naming pre-tier digests are silently discarded
# by the version gate (selection falls back to the defaults until the
# census sweep re-records; _load ignores mismatched versions).
CACHE_VERSION = 3

_mem: Dict[str, dict] = {}
_from_disk: set = set()
_file_loaded = False
_generation = 0


def cache_path() -> str:
    """Path of the persistent cache file (see module docstring)."""
    env = os.environ.get("MPI4TORCH_TPU_TUNE_CACHE")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache",
                        "mpi4torch_tpu", "tune_cache.json")


def _bucket(nbytes: int) -> int:
    """Next power of two ≥ nbytes (≥ 1) — the cache's size key."""
    nbytes = max(int(nbytes), 1)
    return 1 << (nbytes - 1).bit_length()


def bucket_nbytes(nbytes: int) -> int:
    """Public form of the cache's size-bucket rule: the power-of-two
    bucket a payload of ``nbytes`` keys into.  Serving's latency report
    (:func:`mpi4torch_tpu.serve.latency_report`) uses it to show which
    cache bucket the real decode message sizes share — the aliasing the
    ``select_auto`` latency-tier guard exists for: a decode-sized key
    can hold a winner recorded by a training tail bucket of the same
    power-of-two size, so tier membership, not the cache alone, gates
    sub-crossover selection."""
    return _bucket(nbytes)


def _platform() -> str:
    import jax

    return jax.devices()[0].platform


def _codec_name(codec) -> Optional[str]:
    """Normalize a codec dimension value (Codec object, name string, or
    None) to a cache-key token."""
    if codec is None:
        return None
    return getattr(codec, "name", codec)


def _tiers_token(tiers) -> Optional[str]:
    """Normalize a tier-stack key dimension value (a tuple of factors,
    an ``AxBxC`` string, or None) to a cache-key token."""
    if tiers is None:
        return None
    if isinstance(tiers, str):
        return tiers
    return "x".join(str(int(g)) for g in tiers)


def make_key(collective: str, dtype, nbytes: int, nranks: int,
             platform: Optional[str] = None, codec=None,
             tiers=None, transition: Optional[str] = None) -> str:
    import numpy as np

    if platform is None:
        platform = _platform()
    key = "|".join([collective, str(np.dtype(dtype)),
                    str(_bucket(nbytes)), str(int(nranks)), platform])
    # The codec dimension: compressed traffic gets its OWN winner keys
    # (a q8 bucket's crossover differs from fp32's — ~4x fewer wire
    # bytes per element), and exact traffic keeps the codec-less keys it
    # always had, so compressed measurements can never hijack exact
    # selection (or vice versa).
    name = _codec_name(codec)
    if name is not None:
        key += "|codec=" + str(name)
    # The tier dimension (mpi4torch_tpu.csched tier-stack synthesis): a
    # winner ranked by the bandwidth-weighted census is specific to the
    # tier-stack factorization it was searched under — a (2,2,2) stack's
    # winner must never serve a (4,2) world.  Same growth pattern as the
    # codec dimension; flat (un-tiered) keys stay byte-identical.
    tok = _tiers_token(tiers)
    if tok is not None:
        key += "|tiers=" + str(tok)
    # The transition dimension (mpi4torch_tpu.reshard): a measured
    # redistribution winner is specific to its (layout, layout', shape)
    # transition — the same growth pattern as the codec dimension, so
    # reshard entries can never collide with collective-algorithm keys.
    if transition is not None:
        key += "|transition=" + str(transition)
    return key


def _validate_winner(collective: str, algorithm: str,
                     ent: Optional[dict] = None) -> None:
    """Winner names are validated against the registry that owns them:
    reshard entries name a planner strategy, ``synth:<digest>`` entries
    a synthesized IR program (the entry must carry the serialized
    program, which installs on successful validation — so a persisted
    winner is lowerable right after lookup), everything else a
    collective algorithm.  Raises on unknown names (record) — lookup
    callers catch and ignore stale entries."""
    if isinstance(algorithm, str) and algorithm.startswith("synth:"):
        from ..csched import synth as _synth

        _synth.validate_entry(algorithm,
                              None if ent is None else ent.get("program"))
        return
    if collective == "reshard":
        from ..reshard.plan import STRATEGIES

        if algorithm not in STRATEGIES:
            raise ValueError(
                f"unknown reshard strategy {algorithm!r}; expected one "
                f"of {STRATEGIES}")
        return
    get_algorithm(algorithm)


def _load() -> None:
    """Lazily merge the disk cache into the in-process table.  Any
    defect — missing file, bad JSON, wrong version, malformed entries —
    is treated as 'no cache': defaults apply, nothing crashes."""
    global _file_loaded
    if _file_loaded:
        return
    _file_loaded = True
    try:
        with open(cache_path(), "r", encoding="utf-8") as f:
            data = json.load(f)
    except (OSError, ValueError):
        return
    if not isinstance(data, dict) or data.get("version") != CACHE_VERSION:
        return
    entries = data.get("entries")
    if not isinstance(entries, dict):
        return
    for key, ent in entries.items():
        if (isinstance(key, str) and isinstance(ent, dict)
                and isinstance(ent.get("algorithm"), str)
                and key not in _mem):
            _mem[key] = ent
            _from_disk.add(key)


def _save() -> None:
    """Atomic, concurrency-safe, best-effort persist of the in-process
    table.

    Two rules make simultaneous tuners (multi-host jobs, a sweep next
    to a training run) safe:

    * the payload is written to a UNIQUE tempfile in the cache
      directory (``tempfile.mkstemp`` — a fixed ``.tmp`` name would let
      two processes interleave writes into the same staging file) and
      ``os.replace``d over the cache, so readers only ever see a
      complete JSON document;
    * the read-merge-replace sequence runs under an exclusive
      ``flock`` on a sidecar ``<cache>.lock`` file, and entries another
      process persisted while we tuned are merged into the written
      snapshot (disk keys we do not hold in memory) — concurrent tuning
      work is unioned rather than lost to last-writer-wins, with no
      lost-update window between the read and the replace.  On
      filesystems without ``flock`` the lock is skipped (the merge
      still narrows the race to the read→replace window; readers are
      never blocked or torn either way)."""
    import contextlib
    import tempfile

    path = cache_path()
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)

        @contextlib.contextmanager
        def _locked():
            try:
                import fcntl
                fd = os.open(path + ".lock",
                             os.O_CREAT | os.O_RDWR, 0o644)
            except (ImportError, OSError):
                yield
                return
            try:
                try:
                    fcntl.flock(fd, fcntl.LOCK_EX)
                except OSError:
                    pass  # NFS & co: fall back to merge-only safety
                yield
            finally:
                os.close(fd)

        with _locked():
            entries = dict(_mem)
            try:
                with open(path, "r", encoding="utf-8") as f:
                    on_disk = json.load(f)
                if (isinstance(on_disk, dict)
                        and on_disk.get("version") == CACHE_VERSION
                        and isinstance(on_disk.get("entries"), dict)):
                    for key, ent in on_disk["entries"].items():
                        if (isinstance(key, str) and isinstance(ent, dict)
                                and isinstance(ent.get("algorithm"), str)
                                and key not in entries):
                            entries[key] = ent
            except (OSError, ValueError):
                pass
            fd, tmp = tempfile.mkstemp(
                dir=os.path.dirname(path) or ".", prefix=".tune_cache.",
                suffix=".tmp")
            try:
                with os.fdopen(fd, "w", encoding="utf-8") as f:
                    json.dump(
                        {"version": CACHE_VERSION, "entries": entries},
                        f, indent=1, sort_keys=True)
                os.replace(tmp, path)
            except BaseException:
                try:
                    os.remove(tmp)
                except OSError:
                    pass
                raise
    except OSError:
        pass


def lookup(collective: str, dtype, nbytes: int, nranks: int,
           platform: Optional[str] = None, codec=None, tiers=None,
           transition: Optional[str] = None) -> Optional[dict]:
    """The cached entry for this key, or None.  Entries naming an
    algorithm (or reshard strategy) the owning registry no longer knows
    (stale cache across versions) are ignored."""
    from ..obs import metrics as _metrics

    _load()
    ent = _mem.get(make_key(collective, dtype, nbytes, nranks, platform,
                            codec=codec, tiers=tiers,
                            transition=transition))
    if ent is None:
        _metrics.inc("tune_cache_misses_total",
                     help="autotuner cache lookups that found no winner")
        return None
    try:
        _validate_winner(collective, ent["algorithm"], ent)
    except (ValueError, KeyError, TypeError):
        _metrics.inc("tune_cache_misses_total")
        return None
    _metrics.inc("tune_cache_hits_total",
                 help="autotuner cache lookups serving a cached winner")
    return ent


def lookup_algorithm(collective: str, dtype, nbytes: int, nranks: int,
                     platform: Optional[str] = None,
                     codec=None, tiers=None,
                     transition: Optional[str] = None) -> Optional[str]:
    ent = lookup(collective, dtype, nbytes, nranks, platform, codec=codec,
                 tiers=tiers, transition=transition)
    return None if ent is None else ent["algorithm"]


def entry_from_disk(collective: str, dtype, nbytes: int, nranks: int,
                    platform: Optional[str] = None, codec=None,
                    tiers=None) -> bool:
    """True when this key's entry was loaded from the persisted file
    (rather than measured in this process) — the
    ``tuned_from_cache`` evidence."""
    _load()
    return make_key(collective, dtype, nbytes, nranks,
                    platform, codec=codec, tiers=tiers) in _from_disk


def record(collective: str, dtype, nbytes: int, nranks: int,
           algorithm: str, platform: Optional[str] = None,
           measurements: Optional[dict] = None,
           persist: bool = True, codec=None, tiers=None,
           transition: Optional[str] = None,
           program: Optional[dict] = None,
           ctl: Optional[dict] = None) -> str:
    """Store a winner for a key (and persist).  Bumps the selection
    generation so ``run_spmd`` jit cache keys see the change and
    retrace instead of reusing a lowering picked under the old table.
    ``program`` carries a synthesized winner's serialized IR program
    (mpi4torch_tpu.csched) — required for ``synth:<digest>`` names, so
    a later process can re-install and lower the schedule straight from
    the cache entry.  ``ctl`` carries the online-switch provenance the
    self-tuning controller stamps on winners it installs between steps
    ({"provenance": "online-switched", "epoch": N, "trigger": ...} —
    rendered by ``tune --show`` so an operator can tell a measured
    winner from one a live drift episode installed)."""
    global _generation
    _load()
    key = make_key(collective, dtype, nbytes, nranks, platform,
                   codec=codec, tiers=tiers, transition=transition)
    ent = {"algorithm": algorithm, "measured_at": time.time()}
    if program is not None:
        ent["program"] = program
    if ctl is not None:
        ent["ctl"] = dict(ctl)
    _validate_winner(collective, algorithm, ent)
    name = _codec_name(codec)
    if name is not None:
        ent["codec"] = str(name)
    tok = _tiers_token(tiers)
    if tok is not None:
        ent["tiers"] = str(tok)
    if measurements:
        ent["measurements"] = measurements
    _mem[key] = ent
    _from_disk.discard(key)
    _generation += 1
    if persist:
        _save()
    return key


def generation() -> int:
    """Monotonic counter bumped on every cache mutation; part of
    ``run_spmd``'s jit cache key."""
    return _generation


def clear(remove_file: bool = False) -> None:
    """Drop the in-process table (and optionally the persisted file);
    the next lookup re-reads the file, so ``clear()`` alone round-trips
    the persisted entries while ``clear(remove_file=True)`` resets
    selection to the defaults."""
    global _file_loaded, _generation
    _mem.clear()
    _from_disk.clear()
    _file_loaded = False
    _generation += 1
    if remove_file:
        try:
            os.remove(cache_path())
        except OSError:
            pass


# ---------------------------------------------------------------------------
# Measurement
# ---------------------------------------------------------------------------

SMOKE_SIZES = (1 << 10, 1 << 14, 1 << 18)           # 1 KiB → 256 KiB
DEFAULT_SIZES = tuple(1 << s for s in range(10, 27, 2))   # 1 KiB → 64 MiB


def _candidates(nranks: int, collective: str = "allreduce") -> List[str]:
    out = []
    for name in available_algorithms():
        if get_algorithm(name).applicable(nranks, collective):
            out.append(name)
    return out


def _time_step(step, x, iters: int) -> float:
    """MIN-of-k seconds/step with a host fetch per iteration (the only
    completion barrier remote runtimes honor; ``np.asarray`` of one
    output leaf is the cheap form of it).

    Min, not median/mean: timing noise on shared or preemptible
    capacity is strictly one-sided — a preempted slice, a GC pause, or
    a noisy neighbor only ever makes a sample SLOWER — so the minimum
    is the robust estimator of the true step cost, and one bad sample
    can no longer flip a persisted cache winner (with the old
    median-of-5, TWO outliers among five samples poisoned the key for
    every later process).  Keyed into :data:`CACHE_VERSION`."""
    import jax
    import numpy as np

    def force(out):
        leaf = jax.tree.leaves(out)[0]
        np.asarray(leaf[(slice(None),) + (0,) * (leaf.ndim - 1)])

    force(step(x))          # compile + warmup
    force(step(x))
    times = []
    for _ in range(max(int(iters), 1)):
        t0 = time.perf_counter()
        force(step(x))
        times.append(time.perf_counter() - t0)
    return min(times)


def autotune_allreduce(sizes: Optional[Sequence[int]] = None,
                       nranks: Optional[int] = None,
                       dtype=None, iters: int = 5,
                       persist: bool = True,
                       apply_crossover: bool = True,
                       codecs: Sequence = (None,)) -> dict:
    """Benchmark every applicable allreduce algorithm at each payload
    size, record the winners in the cache, and (by default) set
    :func:`config.set_latency_crossover_bytes` AND
    :func:`config.set_bandwidth_crossover_bytes` from the measured
    crossovers so three-tier auto-selection (latency algorithms below,
    ring in the middle, multipath ``bidir``/``torus`` above) reflects
    the measurement.

    ``codecs`` is the sweep's codec dimension: each non-``None`` entry
    (a codec name like ``"q8"``) re-runs the per-algorithm sweep with
    that compression, restricted to the algorithms the codec declares
    (compress.codec_applicable), and records winners under the cache's
    codec-keyed dimension — so auto selection can pick the compressed
    ``bidir`` at/above the bandwidth crossover without the compressed
    measurements hijacking exact traffic's winners.  The crossover
    derivation reads only the exact (``None``) sweep.

    Returns the report dict:
    per-size per-algorithm seconds and GB/s, the winner table, the
    crossover, and ``tuned_from_cache: False`` (a report served
    without measuring — :func:`ensure_tuned_allreduce` — says True,
    with ``from_disk`` distinguishing a persisted-file round-trip from
    same-process memory)."""
    import jax
    import jax.numpy as jnp

    import mpi4torch_tpu as mpi

    if dtype is None:
        dtype = jnp.float32
    n = nranks or len(jax.devices())
    sizes = tuple(sizes) if sizes else DEFAULT_SIZES
    platform = _platform()
    itemsize = jnp.dtype(dtype).itemsize
    comm = mpi.COMM_WORLD

    report = {
        "collective": "allreduce",
        "nranks": n,
        "dtype": str(jnp.dtype(dtype)),
        "platform": platform,
        "cache_file": cache_path(),
        "tuned_from_cache": False,
        "entries": {},
    }

    def step_fn(algorithm, compression):
        def body(x):
            return comm.Allreduce(x, mpi.MPI_SUM, algorithm=algorithm,
                                  compression=compression or False)

        return mpi.run_spmd(body, nranks=n)

    def sweep_one(nbytes, x, wire, codec):
        from ..compress import codec_applicable, get_codec

        if codec is None:
            names = _candidates(n)
        else:
            cobj = get_codec(codec)
            names = [a for a in _candidates(n)
                     if codec_applicable(cobj, dtype, algorithm=a)]
        per = {}
        for name in names:
            try:
                dt = _time_step(step_fn(name, codec), x, iters)
            except Exception as e:  # noqa: BLE001 — sweep must finish
                per[name] = {"error": f"{type(e).__name__}: {e}"[:200]}
                continue
            per[name] = {"seconds_per_step": dt,
                         "gbps": round(wire / dt / 1e9, 4)}
        timed = {k: v for k, v in per.items()
                 if "seconds_per_step" in v}
        if not timed:
            return {"algorithms": per}
        winner = min(timed, key=lambda k: timed[k]["seconds_per_step"])
        record("allreduce", dtype, int(nbytes), n, winner,
               platform=platform, measurements={
                   k: v["seconds_per_step"] for k, v in timed.items()},
               persist=persist, codec=codec)
        return {
            "algorithms": per,
            "winner": winner,
            "winner_latency_optimal":
                get_algorithm(winner).latency_optimal,
            "winner_bandwidth_optimal":
                get_algorithm(winner).bandwidth_optimal,
        }

    for nbytes in sizes:
        nelem = max(1, int(nbytes) // itemsize)
        x = jnp.ones((nelem,), dtype)
        wire = 2.0 * (n - 1) / n * nelem * itemsize if n > 1 \
            else float(nelem * itemsize)
        ent = sweep_one(nbytes, x, wire, None) \
            if None in tuple(codecs) else {"algorithms": {}}
        for codec in codecs:
            if codec is None:
                continue
            ent.setdefault("codecs", {})[str(_codec_name(codec))] = \
                sweep_one(nbytes, x, wire, codec)
        report["entries"][str(int(nbytes))] = ent

    crossover = _crossover_from(report["entries"])
    report["crossover_bytes"] = crossover
    if apply_crossover and crossover is not None:
        _config.set_latency_crossover_bytes(crossover)
        report["applied_latency_crossover_bytes"] = crossover
    bandwidth = _bandwidth_crossover_from(report["entries"])
    report["bandwidth_crossover_bytes"] = bandwidth
    if apply_crossover and bandwidth is not None:
        _config.set_bandwidth_crossover_bytes(bandwidth)
        report["applied_bandwidth_crossover_bytes"] = bandwidth
    return report


def _crossover_from(entries: dict) -> Optional[int]:
    """Largest measured payload size whose winner is latency-optimal —
    the ring/latency-algorithm crossover point (None when ring wins
    everywhere, i.e. the latency regime was not reached)."""
    best = None
    for size_str, ent in entries.items():
        if ent.get("winner_latency_optimal"):
            size = int(size_str)
            best = size if best is None else max(best, size)
    return best


def _bandwidth_crossover_from(entries: dict) -> Optional[int]:
    """Smallest measured payload size from which a bandwidth-tier
    multipath algorithm (``bidir``/``torus``) wins *at every larger
    measured size too* — the ring/multipath crossover, the upper edge
    of three-tier auto selection.  None when the largest measured size
    is not won by the bandwidth tier (the multipath regime was not
    reached, or a single noisy mid-size win must not flip steady-state
    selection)."""
    sized = sorted((int(s), ent) for s, ent in entries.items()
                   if "winner" in ent)
    best = None
    for size, ent in reversed(sized):
        if not ent.get("winner_bandwidth_optimal"):
            break
        best = size
    return best


def ensure_tuned_allreduce(sizes: Optional[Sequence[int]] = None,
                           nranks: Optional[int] = None,
                           dtype=None, iters: int = 5,
                           persist: bool = True,
                           apply_crossover: bool = True) -> dict:
    """Like :func:`autotune_allreduce`, but when every requested size
    already has a cached winner, build the report from the cache
    (``tuned_from_cache: True``) and skip the measurement — the
    steady-state zero-overhead path.  ``from_disk`` in the report says
    whether ALL served entries came from the persisted file (a real
    cross-process round-trip) rather than this process's own earlier
    measurement."""
    import jax
    import jax.numpy as jnp

    if dtype is None:
        dtype = jnp.float32
    n = nranks or len(jax.devices())
    sizes = tuple(sizes) if sizes else DEFAULT_SIZES
    platform = _platform()

    cached = {}
    from_disk = True
    for nbytes in sizes:
        ent = lookup("allreduce", dtype, int(nbytes), n, platform)
        if ent is None:
            return autotune_allreduce(sizes=sizes, nranks=n, dtype=dtype,
                                      iters=iters, persist=persist,
                                      apply_crossover=apply_crossover)
        from_disk = from_disk and entry_from_disk(
            "allreduce", dtype, int(nbytes), n, platform)
        cached[str(int(nbytes))] = {
            "winner": ent["algorithm"],
            "winner_latency_optimal":
                get_algorithm(ent["algorithm"]).latency_optimal,
            "winner_bandwidth_optimal":
                get_algorithm(ent["algorithm"]).bandwidth_optimal,
            "measurements": ent.get("measurements"),
        }
    crossover = _crossover_from(cached)
    if apply_crossover and crossover is not None:
        _config.set_latency_crossover_bytes(crossover)
    bandwidth = _bandwidth_crossover_from(cached)
    if apply_crossover and bandwidth is not None:
        _config.set_bandwidth_crossover_bytes(bandwidth)
    return {
        "collective": "allreduce",
        "nranks": n,
        "dtype": str(jnp.dtype(dtype)),
        "platform": platform,
        "cache_file": cache_path(),
        "tuned_from_cache": True,
        "from_disk": from_disk,
        "entries": cached,
        "crossover_bytes": crossover,
        "bandwidth_crossover_bytes": bandwidth,
    }


def _main(argv: Iterable[str]) -> int:
    smoke = "--smoke" in argv
    sizes = SMOKE_SIZES if smoke else DEFAULT_SIZES
    if "--sweep" in argv:
        # The fast bench lane (`make bench-sweep`): ALWAYS measure —
        # the point is a fresh sizes × algorithms throughput table
        # (winners still persist, so it doubles as a tuning run).
        report = autotune_allreduce(sizes=sizes, iters=2 if smoke else 5)
    else:
        report = ensure_tuned_allreduce(sizes=sizes,
                                        iters=2 if smoke else 5)
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    import sys

    from mpi4torch_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    sys.exit(_main(sys.argv[1:]))
