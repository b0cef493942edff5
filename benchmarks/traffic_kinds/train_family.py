"""Training on packed full-length sequences, for any family of model.

``train_packed``'s run with what is specific to the dense decoder taken
from ``benchmarks/families/<family>.py`` instead, the module the
configuration file names under ``family``: the program's configuration
(``transformer_config``), the seeded weights (``make_params``), the
compiled step (``build_train_step``, which also hands out the step's
routing counters), the gradient for the comparison
(``build_grad_norms``), the counts (``train_flops_per_token``,
``flash_calls``, ``kernel_calls``) and the scopes its mechanisms run
under (``scopes``, ``KERNELS``).
The next family is a new file there and needs no new kind.

The mix's parameters are ``train_packed``'s, and so are the numbers that
decide ``correct``, but for one: ``grad_norm_gap`` reads the float32
norm of every leaf of the gradient the step takes of the first batch at
the seeded weights (the family's ``build_grad_norms``: the program's
``lm_loss`` differentiated as ``train_step`` differentiates it, compiled
and run once after the window, so that the timed step computes nothing
for the comparison) against the reference's own, not ``(p0 - p1) /
lr``.  Most leaves here take a change of under half a bfloat16 ulp a
step at any learning rate that leaves the others finite, so a difference
of parameters shows rounding, not the gradient (PERF.md, Findings, PR
28).  ``param_change_gap`` stays what it is, the optimizer's work as the
timed step's parameters show it.  A family's reference therefore
provides ``step(cfg, params, tokens, lr, mm) -> (loss, new_params,
grad_norms)`` beside ``sgd_step``.

Three things are added to the record for the per-layer metrics:
``extras["op_scopes"]``, the scope of each instruction of the compiled
step (read from the compiled program's text; the instruction's name is
the name of its events in the trace); ``extras["routing"]``, the routing
counters of every step of the window, kept on the device until the
window has closed; and ``extras["kernel_calls"]``, what the family
counts for the kernels' calls of the traced steps, from those steps' own
counters.
"""

from __future__ import annotations

import gc
import importlib
import re
import time

import numpy as np

from benchmarks.traffic_kinds.train_packed import generate, leaf_norms

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s.*\bop_name="([^"]*)"')
# jit(...) wrappers and closing brackets of transpose(jvp(...)) say
# nothing about what an instruction computes.
_LABEL_NOISE = re.compile(r"jit\(\w+\)/|\)+")


def load_family(cfg: dict):
    return importlib.import_module(f"benchmarks.families.{cfg['family']}")


def op_scopes(hlo_text: str, scopes: dict, kernels: dict) -> dict:
    """``{instruction name: (scope key, what it computes)}`` for the
    instructions of a compiled program whose ``op_name`` holds a scope's
    name: forward, recomputed (``rematted_computation/...``) and backward
    (``transpose(jvp(...))``) instructions all hold it; a fusion carries
    its root's ``op_name``.  What it computes is the ``op_name``'s tail
    after the scope (``while/body/dot_general``), for the breakdown.
    ``kernels`` names instructions the compiler writes itself and whose
    ``op_name`` it drops (``ragged-dot``): found by their own name."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        for key, name in scopes.items():
            head, found, tail = m.group(2).rpartition(name)
            if found:
                out[m.group(1)] = (key, _LABEL_NOISE.sub("", tail).strip("/"))
                break
        else:
            for name, key in kernels.items():
                if m.group(1).startswith(name):
                    out[m.group(1)] = (key, name)
    return out


def grad_norms(tree) -> np.ndarray:
    """A tree of per-leaf norms as one vector, in the leaves' order."""
    import jax

    return np.asarray([float(x) for x in jax.tree.leaves(tree)], np.float64)


def worst_leaf(names: list, prog, ref) -> list:
    """[name, program's norm, reference's norm] of the leaf that decides
    ``common.worst_leaf_gap``, for the run's notes."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    i = int(np.argmax(np.abs(prog - ref) / np.maximum(ref, np.median(ref))))
    return [names[i], float(prog[i]), float(ref[i])]


def run(ctx):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmarks import common, program
    from benchmarks.references import load as load_reference

    cfg, tr = ctx.cfg, ctx.traffic
    family = load_family(cfg)
    n, per_chip, seq = len(ctx.devices), tr["sequences_per_chip"], tr["seq_len"]
    lr, k_check = float(tr["lr"]), int(tr["check_steps"])
    dtype = jnp.dtype(cfg["dtype"])
    rec = common.Record(ctx=ctx)

    mesh = Mesh(np.asarray(ctx.devices), ("mpi",))
    repl = NamedSharding(mesh, P())
    tcfg = family.transformer_config(cfg, remat=bool(tr["remat"]))
    params = family.make_params(cfg, ctx.seed, dtype, repl)

    # One object: the compiled step that set-up drives through its first
    # steps is the one the window times.
    t0 = time.perf_counter()
    step = family.build_train_step(tcfg, mesh, per_chip, lr,
                                   bool(tr["data_parallel"]), ctx.broken)
    tokens_like = jax.ShapeDtypeStruct((n * per_chip, seq), jnp.int32,
                                       sharding=repl)
    compiled = step.lower(params, tokens_like).compile()
    rec.scalars["train_compile_s"] = time.perf_counter() - t0
    if ctx.trace:
        rec.extras["op_scopes"] = op_scopes(compiled.as_text(),
                                            family.scopes(), family.KERNELS)

    seen = []

    def batches():
        for b in generate(tr, cfg["vocab_size"], ctx.seed, n):
            if len(seen) < k_check:
                seen.append(b)
            yield b

    feed = program.prefetch(batches(), repl, size=int(tr["prefetch"]))

    # The first steps, through the window's own call and feed; the
    # reference follows them after the window.
    p0 = family.make_params(cfg, ctx.seed, dtype, repl)
    names = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_flatten_with_path(p0)[0]]
    prog_loss, rank_spread, first_rows = [], 0.0, []
    for k in range(k_check):
        loss, params, stats = compiled(params, next(feed))
        loss = np.asarray(loss, np.float64)
        prog_loss.append(float(loss[0]))
        rank_spread = max(rank_spread, float(loss.max() - loss.min()))
        if "moe_rows" in stats:      # per layer: largest, all held experts
            first_rows.append([[int(r.max()), int(r.sum())]
                               for r in np.asarray(stats["moe_rows"])])
    prog_delta = leaf_norms(params, p0, 1.0)
    del p0
    gc.collect()
    gc.freeze()

    trace_steps = int(tr["trace_steps"]) if ctx.trace else 0
    phase = common.TracedPhase(ctx)
    if trace_steps:
        phase.start()
    step_ms, routing, traced, steps = [], [], [], 0
    rec.scalars["setup_s"] = time.perf_counter() - ctx.t_start
    t_prev = t_open = time.perf_counter()
    while True:
        with common.span("bench.feed"):
            tokens = next(feed)
        with common.span("bench.train_step"):
            loss, params, stats = compiled(params, tokens)
            loss.block_until_ready()
        now = time.perf_counter()
        if trace_steps:
            # The traced phase comes first and is no part of the window.
            traced.append(stats)
            trace_steps -= 1
            if trace_steps == 0:
                rec.trace = phase.stop()
                rec.scalars["setup_s"] = time.perf_counter() - ctx.t_start
                t_open = time.perf_counter()
            t_prev = time.perf_counter()
            continue
        step_ms.append((now - t_prev) * 1e3)
        routing.append(stats)
        t_prev = now
        steps += 1
        if now - t_open >= ctx.seconds:
            break
    window_s = now - t_open

    tokens_per_step = n * per_chip * seq
    rec.attempted, rec.failed = steps, 0
    rec.samples["train_step_ms"] = step_ms
    # The routing counters, read only now: (steps, ...) each.
    stacked = lambda steps_: {k: np.stack([np.asarray(s[k]) for s in steps_])
                              for k in (steps_[0] if steps_ else {})}
    rec.extras["routing"], traced = stacked(routing), stacked(traced)
    if "moe_rows" in traced:
        rec.extras["kernel_calls"] = family.kernel_calls(
            cfg, traced["moe_rows"])
    rec.scalars.update(
        window_s=window_s, steps=steps, tokens_per_step=tokens_per_step,
        train_tok_s_chip=steps * tokens_per_step / window_s / n,
        flop_per_token=family.train_flops_per_token(
            cfg, seq, dict(rec.extras["routing"],
                           tokens_per_step=tokens_per_step // n)))
    rec.extras["flash_calls"] = family.flash_calls(cfg, per_chip, seq)
    rec.memory_peak_bytes, limit = common.memory_peak(ctx.devices)
    mem = compiled.memory_analysis()
    rec.scalars.update(
        live_peak_bytes=rec.memory_peak_bytes, bytes_limit=limit,
        program_temp_bytes=getattr(mem, "temp_size_in_bytes", 0) or 0)

    # The program's state is freed; the reference follows the first steps
    # from the same seed, batch rows spread over the chips.
    del params, compiled, loss, tokens, feed, stats, routing, traced
    t_ref = time.perf_counter()
    ref = load_reference(cfg)
    rows = NamedSharding(mesh, P("mpi"))
    p0 = family.make_params(cfg, ctx.seed, dtype, repl)
    prog_grad = np.asarray(family.build_grad_norms(
        tcfg, mesh, per_chip, bool(tr["data_parallel"]), ctx.broken)(
            p0, jax.device_put(seen[0], repl)), np.float64)
    grad_s = time.perf_counter() - t_ref
    p, ref_loss = p0, []
    for k in range(k_check):
        l, new, norms = ref.step(cfg, p, jax.device_put(seen[k], rows), lr)
        ref_loss.append(float(l))
        if k == 0:
            ref_grad = grad_norms(norms)
        p = new
    ref_delta = leaf_norms(p, p0, 1.0)

    lim = ctx.limits
    common.compare(rec, "loss_gap", max(
        abs(a - b) / abs(b) for a, b in zip(prog_loss, ref_loss)), lim)
    common.compare(rec, "grad_norm_gap",
                   common.worst_leaf_gap(prog_grad, ref_grad), lim)
    common.compare(rec, "param_change_gap",
                   common.worst_leaf_gap(prog_delta, ref_delta), lim)
    common.compare(rec, "rank_loss_spread", rank_spread, lim)
    rec.extras["notes"] = {
        "losses": {"program": prog_loss, "reference": ref_loss},
        "gradient_s": grad_s,
        "reference_s": time.perf_counter() - t_ref - grad_s,
        "memory_after_reference": common.memory_peak(ctx.devices)[0],
        "worst_leaf": {
            "grad_norm_gap": worst_leaf(names, prog_grad, ref_grad),
            "param_change_gap": worst_leaf(names, prog_delta, ref_delta)},
        "routing": {k: [int(v.min()), float(v.mean()), int(v.max())]
                    for k, v in rec.extras["routing"].items()},
        "checked_steps_rows_max_sum_by_layer": first_rows}
    return rec


def _control_seed(ctx) -> dict:
    """The reference in float32 against the same reference in float8,
    read exactly as a run reads the program against the reference."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmarks import common
    from benchmarks.references import load as load_reference

    cfg, tr = ctx.cfg, ctx.traffic
    family, ref = load_family(cfg), load_reference(cfg)
    mesh = Mesh(np.asarray(ctx.devices), ("mpi",))
    repl, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("mpi"))
    dtype, lr = jnp.dtype(cfg["dtype"]), float(tr["lr"])
    feed = generate(tr, cfg["vocab_size"], ctx.seed, len(ctx.devices))
    batches = [jax.device_put(next(feed), rows)
               for _ in range(int(tr["check_steps"]))]
    out = {}
    for mm in ("f32", "fp8"):
        p0 = family.make_params(cfg, ctx.seed, dtype, repl)
        p, losses = p0, []
        for k, tokens in enumerate(batches):
            loss, new, norms = ref.step(cfg, p, tokens, lr, mm)
            losses.append(float(loss))
            if k == 0:
                grad = grad_norms(norms)
            p = new
        out[mm] = (losses, grad, leaf_norms(p, p0, 1.0))
        del p, p0, new
    (l32, g32, d32), (l8, g8, d8) = out["f32"], out["fp8"]
    return {"loss_gap": max(abs(a - b) / abs(b) for a, b in zip(l8, l32)),
            "grad_norm_gap": common.worst_leaf_gap(g8, g32),
            "param_change_gap": common.worst_leaf_gap(d8, d32)}


def control(make_ctx, seeds: list, seconds: float) -> list:
    """Training's readings need no measured window."""
    return [{"seed": s, "control": _control_seed(make_ctx(s))} for s in seeds]
