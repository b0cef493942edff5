"""What every traffic kind's driver shares: the context a run is given,
the record it hands back, the comparison of numbers with their limits,
device memory, compile counting and the traced phase."""

from __future__ import annotations

import glob
import os
import shutil
from dataclasses import dataclass, field


@dataclass
class Context:
    root: str              # the checkout
    cell: dict             # the entry of BENCHMARK.json's workloads
    cfg: dict              # the configuration file
    traffic: dict          # the traffic mix file
    limits: dict           # benchmarks/limits/<cell>.json
    peaks: dict            # the peaks of this device kind ({} in rehearsal)
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    t_start: float         # perf_counter() at process start
    devices: list = field(default_factory=list)
    broken: str = ""       # tests only: break the timed path


@dataclass
class Record:
    """What a run measured.  Metric readers take their numbers from here
    and nowhere else."""
    scalars: dict = field(default_factory=dict)
    samples: dict = field(default_factory=dict)
    extras: dict = field(default_factory=dict)
    compared: list = field(default_factory=list)   # (name, value, limit)
    attempted: int = 0
    failed: int = 0
    memory_peak_bytes: int = 0
    trace: object = None
    ctx: Context = None

    @property
    def correct(self) -> bool:
        return (self.failed == 0 and bool(self.compared)
                and all(within(v, lim) for _, v, lim in self.compared))


def within(value, limit) -> bool:
    """A number passes at or under its limit; NaN never passes."""
    return value == value and value <= limit


def compare(record: Record, name: str, value, limits: dict) -> None:
    record.compared.append((name, float(value), float(limits[name])))


def worst_leaf_gap(prog, ref) -> float:
    """The widest gap between the program's per-leaf norm and the
    reference's, measured against the reference's norm of that leaf or
    of the median leaf, whichever is larger (some leaves' are all but
    zero)."""
    import numpy as np

    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    scale = np.maximum(ref, np.median(ref))
    return float(np.max(np.abs(prog - ref) / scale))


def memory_peak(devices) -> tuple:
    """(peak bytes of live buffers on the fullest chip, its limit)."""
    peak = limit = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        limit = max(limit, int(stats.get("bytes_limit", 0)))
    return peak, limit


class CompileCounter:
    """Backend compilations, from JAX's own monitoring events."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_):
        if event == self._EVENT:
            self.count += 1


class TracedPhase:
    """A short traced phase of steady state, before the measured window.
    The trace lands in ``<checkout>/.bench_tmp/trace`` and is reduced and
    removed at once."""

    def __init__(self, ctx: Context):
        self.dir = os.path.join(ctx.root, ".bench_tmp", "trace")

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        import jax
        from benchmarks import trace_reduce

        jax.profiler.stop_trace()
        (pb,) = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        trace = trace_reduce.load(pb)
        shutil.rmtree(self.dir, ignore_errors=True)
        return trace


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)
