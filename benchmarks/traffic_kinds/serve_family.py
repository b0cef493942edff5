"""Closed-loop serving from a fixed cycle of requests, for any family of
model.

``serve_closed_cycle``'s load (its ``Loop``, ``Arrivals``, ``make_cycle``,
``draw_sample`` and ``widest_gap``, imported as they are) with what is
specific to the dense decoder taken from
``benchmarks/families/<family>.py`` instead, the module the configuration
file names under ``family``: the program's configuration
(``transformer_config``), the seeded weights a layer at a time
(``make_top``, ``make_layer``, ``make_params``), the engine
(``build_engine``), the counts of its kernels' work (``kernel_calls``)
and the scopes its mechanisms run under (``scopes``, ``KERNELS``).  The
next family is a new file there and needs no new kind.

The mix's parameters and the numbers that decide ``correct`` are
``serve_closed_cycle``'s, and one more: ``served_logit_gap_mean``, the
mean over the checked tokens of the gap whose maximum is
``served_logit_gap``.  With experts the widest gap is the size of ONE
routing choice that fell otherwise (the eighth and ninth scores of a
token nearly tie now and then, and rounding of any precision decides
such a tie its own way), which a bfloat16 program shows in one token of
a thousand and a float8 one in every tenth: the maximum reads about the
same for both, the mean does not (PERF.md, section 2).  Three things are
added to the record for the per-layer metrics, all from the traced phase
or the window's step records:

* ``extras["op_scopes"]``: the scope of each instruction of the engine's
  compiled decode and prefill programs (``Engine.program_texts()``).  The
  programs' instruction names collide (each has its ``fusion.3``), so
  the traced events are renamed ``<program>:<instruction>`` first: the
  trace's ``XLA Modules`` line says which program's run an event lies
  in, and a run's program is the one whose text holds its events'
  instructions with their result types (``name_programs``);
* ``extras["kernel_calls"]``: what the family counts for the kernels'
  calls of the traced steps, from those steps' own records;
* ``extras["routing"]``: the rows each held expert took in every decode
  step (``"decode"``) and prefill (``"prefill"``) of the window,
  ``(calls, expert layers, held)`` each, from the step records'
  ``moe_rows``.

A program that keeps none of these (no ``program_texts``, no ``moe_rows``
on its records) leaves them out, and the readers then find nothing.
"""

from __future__ import annotations

import bisect
import gc
import glob
import os
import re
import shutil
import sys
import time

import numpy as np

from benchmarks import common
from benchmarks.traffic_kinds.serve_closed_cycle import (
    Loop, _break_tokens, draw_sample, served_matrix, widest_gap)
from benchmarks.traffic_kinds.train_family import load_family, op_scopes

# ``%fusion.3 = bf16[32,7680]``: an instruction and its result's type, as
# an event's name and a compiled program's text both begin.
_HEAD = re.compile(r"%?([\w.\-]+) = (\(?\w+\[[\d,]*\])")
MODULES_LINE, OPS_LINE = "XLA Modules", "XLA Ops"


class ProgramPhase(common.TracedPhase):
    """The traced phase, keeping beside the reduced trace what says
    which program an event belongs to: the first chip's program runs
    (``modules``: name, start, end) and, event by event in the trace's
    own order, each instruction with its result's type (``heads``)."""

    modules: list = ()
    heads: list = ()

    def stop(self):
        import jax
        from benchmarks import trace_reduce

        jax.profiler.stop_trace()
        (pb,) = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        trace = trace_reduce.load(pb)
        planes = {int(m.group(1)): p for p in
                  jax.profiler.ProfileData.from_file(pb).planes
                  for m in [trace_reduce.DEVICE_PLANE.match(p.name)] if m}
        if planes:
            lines = {ln.name: ln for ln in planes[min(planes)].lines}
            self.modules = sorted(
                ((e.name, e.start_ns, e.start_ns + e.duration_ns)
                 for e in getattr(lines.get(MODULES_LINE), "events", ())),
                key=lambda m: m[1])
            self.heads = [_head(e.name) for e in
                          getattr(lines.get(OPS_LINE), "events", ())]
        shutil.rmtree(self.dir, ignore_errors=True)
        return trace


def _head(text: str):
    m = _HEAD.match(text.strip())
    return (m.group(1), m.group(2)) if m else None


def program_heads(text: str) -> set:
    """Every instruction of a compiled program with its result's type."""
    return {h for h in map(_head, text.splitlines()) if h}


def name_programs(trace, modules: list, heads: list, texts: dict,
                  least: float = 0.9) -> dict:
    """Rename the first chip's events ``<program>:<instruction>``, in
    place.  An event belongs to the program run (``modules``) its start
    lies in; all runs of one module are one program, the one of
    ``texts`` that holds the largest share of their events'
    instructions with their result types, ``other`` where that share is
    under ``least`` (the install, a page copy).  Returns ``{module:
    (program, share, events)}``, or nothing where the trace has no
    program runs or the events do not line up."""
    if not trace.devices or not modules:
        return {}
    dev = trace.devices[min(trace.devices)]
    if len(heads) != len(dev.sync):
        return {}
    starts = [m[1] for m in modules]
    owner = []
    for _, start, _ in dev.sync:
        i = bisect.bisect_right(starts, start) - 1
        owner.append(modules[i][0] if i >= 0 and start < modules[i][2]
                     else None)
    known = {name: program_heads(text) for name, text in texts.items()}
    named = {}
    for module in set(owner) - {None}:
        mine = [h for h, o in zip(heads, owner) if o == module and h]
        share, program = max(
            (sum(h in have for h in mine) / max(1, len(mine)), name)
            for name, have in known.items()) if known else (0.0, "other")
        named[module] = (program if share >= least else "other", share,
                         len(mine))
    dev.sync[:] = [
        (f"{named[o][0] if o in named else 'other'}:{name}", a, b)
        for (name, a, b), o in zip(dev.sync, owner)]
    return named


def program_scopes(texts: dict, family) -> dict:
    """``extras["op_scopes"]`` over several programs: the keys are the
    renamed events' names."""
    out = {}
    for program, text in texts.items():
        for name, scoped in op_scopes(text, family.scopes(),
                                      family.KERNELS).items():
            out[f"{program}:{name}"] = scoped
    return out


def routing_of(steps: list) -> dict:
    """The step records' ``moe_rows`` by program: ``{"decode" |
    "prefill": (calls, expert layers, held)}``."""
    by = {}
    for r in steps or ():
        for program, rows in r.get("moe_rows", ()):
            by.setdefault(program, []).append(np.asarray(rows))
    return {k: np.stack(v) for k, v in by.items()}


def mean_gap(ref_logits, valid, chosen) -> float:
    """Mean, over the valid positions, of the gap by which a chosen
    token's reference logit lies below the reference's best
    (``widest_gap`` is its maximum)."""
    best = ref_logits.max(axis=-1)
    got = np.take_along_axis(ref_logits, chosen[..., None], axis=-1)[..., 0]
    return float(np.sum(np.where(valid, best - got, 0.0)) / valid.sum())


def gaps(ref_logits, valid, chosen) -> dict:
    return {"served_logit_gap": widest_gap(ref_logits, valid, chosen),
            "served_logit_gap_mean": mean_gap(ref_logits, valid, chosen)}


def reference_logits(ctx, family, sample: list, mm: str = "f32"):
    """``serve_closed_cycle.reference_logits`` with the family's
    weights: the plain reference once over each sampled request's prompt
    + served tokens, the weights regenerated from the seed a layer at a
    time."""
    import jax
    import jax.numpy as jnp

    from benchmarks.references import load as load_reference

    cfg = ctx.cfg
    ref = load_reference(cfg)
    dtype = jnp.dtype(cfg["dtype"])
    key = family.seed_key(ctx.seed)
    width = max(len(p) + len(s) for p, s in sample)
    n = max(len(s) for _, s in sample)
    tokens = np.zeros((len(sample), width), np.int32)
    rows = np.zeros((len(sample), n), np.int32)
    valid = np.zeros((len(sample), n), bool)
    for i, (p, s) in enumerate(sample):
        tokens[i, :len(p)] = p
        tokens[i, len(p):len(p) + len(s)] = s
        rows[i, :len(s)] = len(p) - 1 + np.arange(len(s))
        valid[i, :len(s)] = True
    top = jax.jit(lambda k: family.make_top(k, cfg, dtype))(key)
    layer = family.layer_maker(cfg, dtype)
    layers = (layer(key, i) for i in range(cfg["num_hidden_layers"]))
    logits = np.asarray(ref.logits_at(cfg, top, layers, jnp.asarray(tokens),
                                      jnp.asarray(rows), mm))
    return logits, valid


def build(ctx, family):
    import jax.numpy as jnp

    cfg = ctx.cfg
    params = family.make_params(cfg, ctx.seed, jnp.dtype(cfg["dtype"]))
    return family.build_engine(family.transformer_config(cfg), params,
                               ctx.traffic["engine"], len(ctx.devices))


def run(ctx):
    from benchmarks import program_spans

    cfg, tr = ctx.cfg, ctx.traffic
    family = load_family(cfg)
    rec = common.Record(ctx=ctx)
    compiles = common.CompileCounter()
    t0 = time.perf_counter()
    eng = build(ctx, family)
    rec.scalars["engine_construct_s"] = time.perf_counter() - t0

    # Warm-up and fill: the callers start one after another, so that they
    # do not finish in lock-step; every prompt length is prefetched once.
    loop = Loop(eng, tr, cfg["vocab_size"], ctx.seed)
    for client in loop.start_order:
        loop.submit(client)
        for _ in range(int(tr["stagger_steps"])):
            loop.step()
    while not loop.done:
        loop.step()
    if ctx.broken == "wrong_token":
        _break_tokens(eng)
    gc.collect()
    gc.freeze()

    if ctx.trace:
        # The programs' texts first (set-up), then a short traced phase
        # of the same steady state, before the window.
        texts = eng.program_texts() if hasattr(eng, "program_texts") else {}
        phase = ProgramPhase(ctx)
        t_tr, t_ns = time.perf_counter(), time.perf_counter_ns()
        phase.start()
        while time.perf_counter() - t_tr < float(tr["trace_seconds"]):
            loop.step()
        rec.trace = phase.stop()
        traced = [r for r in (program_spans.step_log() or {}).get(
            "records", ()) if r["t0_ns"] >= t_ns]
        named = name_programs(rec.trace, phase.modules, phase.heads, texts)
        print("programs of the traced phase:", {
            m: named[m] for m in sorted(named)}, file=sys.stderr)
        if named:
            rec.extras["op_scopes"] = program_scopes(texts, family)
        rec.extras["kernel_calls"] = family.kernel_calls(
            cfg, traced, int(tr["engine"]["block_size"]))

    stats0 = dict(eng.stats.counters)
    done0, compiles0 = len(loop.done), compiles.count
    admit_ms, decode_ms = [], []
    rec.scalars["setup_s"] = time.perf_counter() - ctx.t_start
    t_open = time.perf_counter()
    loop.arrivals.open(t_open)
    while True:
        now, took, admitted = loop.step()
        (admit_ms if admitted else decode_ms).append(took * 1e3)
        if now - t_open >= ctx.seconds:
            break
    window_s = now - t_open

    arr = loop.arrivals
    stats1 = dict(eng.stats.counters)
    delta = lambda k: stats1.get(k, 0) - stats0.get(k, 0)
    finished = loop.done[done0:]
    statuses = eng.statuses()
    bad = [r for r in finished
           if statuses.get(r) != "ok"
           or len(loop.served[r]) != loop.requests[r][1]]
    rec.attempted, rec.failed = len(finished), len(bad)
    rec.samples.update(ttft_ms=arr.ttft_ms, tok_gap_ms=arr.gap_ms,
                       admit_step_ms=admit_ms, decode_step_ms=decode_ms)
    rec.scalars.update(
        window_s=window_s, serve_tok_s=arr.tokens / window_s,
        compiles_in_window=compiles.count - compiles0,
        slot_occupancy=100.0 * delta("occupancy_ticks")
        / max(1, delta("slot_ticks")),
        prefix_hits=delta("prefix_hits"),
        prefill_tokens=delta("prefill_tokens"))
    rec.memory_peak_bytes, limit = common.memory_peak(ctx.devices)
    rec.scalars.update(live_peak_bytes=rec.memory_peak_bytes,
                       bytes_limit=limit, program_temp_bytes=0)
    rec.extras["routing"] = routing_of(program_spans.steps_of(rec))

    # The engine's state is freed (it lies in a cycle with its own
    # compiled programs, and set-up froze the collector's generations);
    # a sample of what the window finished goes against the plain
    # reference.
    loop.eng = eng = None
    gc.unfreeze()
    gc.collect()
    in_use = max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
                 for d in ctx.devices)
    t_ref = time.perf_counter()
    sample = draw_sample(loop, finished, ctx.seed, int(tr["check_requests"]))
    logits, valid = reference_logits(ctx, family, sample)
    for name, value in gaps(logits, valid,
                            served_matrix(sample, valid)).items():
        common.compare(rec, name, value, ctx.limits)
    common.compare(rec, "prefix_hits", rec.scalars["prefix_hits"], ctx.limits)
    rec.extras["notes"] = {
        "reference_s": time.perf_counter() - t_ref,
        "finished_in_window": len(finished),
        "admit_steps": len(admit_ms), "decode_steps": len(decode_ms),
        "gaps": len(arr.gap_ms), "prefill_tokens": delta("prefill_tokens"),
        "checked_tokens": int(valid.sum()),
        "in_use_before_reference": in_use,
        "rows_a_held_expert_and_decode_step": float(
            rec.extras["routing"]["decode"].mean())
        if "decode" in rec.extras["routing"] else None,
        "memory_after_reference": common.memory_peak(ctx.devices)[0]}
    return rec


def control(make_ctx, seeds: list, seconds: float) -> list:
    """One engine, a short window of the cell's load per seed; the
    weights are the first seed's (a new seed of weights is a new
    set-up), the traffic is each seed's.  The engine is let go before
    the reference runs, so it is built again for the next seed's
    traffic only where there is one."""
    ctx = make_ctx(seeds[0])
    cfg, tr = ctx.cfg, ctx.traffic
    family = load_family(cfg)
    eng = build(ctx, family)
    samples = []
    for seed in seeds:
        loop = Loop(eng, tr, cfg["vocab_size"], seed)
        for client in loop.start_order:
            loop.submit(client)
        t0 = time.perf_counter()
        while (time.perf_counter() - t0 < seconds
               or len(loop.done) < int(tr["check_requests"])):
            loop.step()
        while eng.pending():            # leave the engine empty
            ev = eng.step()
            for rid, toks in ev["emitted"].items():
                loop.served[rid].extend(toks)
            loop.done.extend(ev["finished"])
        eng.pop_results()
        samples.append(draw_sample(loop, loop.done, seed,
                                   int(tr["check_requests"])))
        loop.eng = None
    del eng, loop
    gc.unfreeze()
    gc.collect()
    out = []
    for seed, sample in zip(seeds, samples):
        ref, valid = reference_logits(ctx, family, sample)
        low, _ = reference_logits(ctx, family, sample, mm="fp8")
        out.append({
            "seed": seed, "requests": len(sample),
            "served_tokens": int(valid.sum()),
            "program": gaps(ref, valid, served_matrix(sample, valid)),
            "control": gaps(ref, valid, low.argmax(axis=-1))})
    return out
