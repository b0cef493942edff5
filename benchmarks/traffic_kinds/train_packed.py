"""Training on packed full-length sequences.

Parameters of a mix (``benchmarks/traffic/<mix>.json``):
``sequences_per_chip`` x ``seq_len`` tokens a chip a step, uniform over
the vocabulary from the seed, a new batch every step; ``data_parallel``
(every chip a replica, the program's DP recipe) or not; ``lr``;
``remat``; ``check_steps`` first steps that the reference follows.
"""

from __future__ import annotations

import gc
import time

import numpy as np


def generate(traffic: dict, vocab: int, seed: int, chips: int):
    """Endless batches ``(chips * sequences_per_chip, seq_len)`` int32.
    The seed changes the tokens, never the shapes."""
    rng = np.random.default_rng([int(seed), 0x7A11])
    shape = (chips * traffic["sequences_per_chip"], traffic["seq_len"])
    while True:
        yield rng.integers(0, vocab, size=shape, dtype=np.int32)


def leaf_norms(a, b, scale: float):
    """Per-leaf norm of ``(a - b) * scale`` in float32, as one vector."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(a, b):
        return jnp.stack([
            jnp.linalg.norm((x.astype(jnp.float32)
                             - y.astype(jnp.float32)).ravel()) * scale
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])

    return np.asarray(f(a, b), np.float64)


def run(ctx):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmarks import common, flops, program, weights
    from benchmarks.references import load as load_reference

    cfg, tr = ctx.cfg, ctx.traffic
    n, per_chip, seq = len(ctx.devices), tr["sequences_per_chip"], tr["seq_len"]
    lr, k_check = float(tr["lr"]), int(tr["check_steps"])
    dtype = jnp.dtype(cfg["dtype"])
    rec = common.Record(ctx=ctx)

    mesh = Mesh(np.asarray(ctx.devices), ("mpi",))
    repl = NamedSharding(mesh, P())
    tcfg = program.transformer_config(cfg, remat=bool(tr["remat"]))
    params = weights.make_params(cfg, ctx.seed, dtype, repl)

    # One object: the compiled step that set-up drives through its first
    # steps is the one the window times.
    t0 = time.perf_counter()
    step = program.build_train_step(tcfg, mesh, per_chip, lr,
                                    bool(tr["data_parallel"]), ctx.broken)
    tokens_like = jax.ShapeDtypeStruct((n * per_chip, seq), jnp.int32,
                                       sharding=repl)
    compiled = step.lower(params, tokens_like).compile()
    rec.scalars["train_compile_s"] = time.perf_counter() - t0

    seen = []

    def batches():
        for b in generate(tr, cfg["vocab_size"], ctx.seed, n):
            if len(seen) < k_check:
                seen.append(b)
            yield b

    feed = program.prefetch(batches(), repl, size=int(tr["prefetch"]))

    # The first steps, through the window's own call and feed; the
    # reference follows them after the window.
    p0 = weights.make_params(cfg, ctx.seed, dtype, repl)
    prog_loss, rank_spread = [], 0.0
    for k in range(k_check):
        loss, params = compiled(params, next(feed))
        loss = np.asarray(loss, np.float64)
        prog_loss.append(float(loss[0]))
        rank_spread = max(rank_spread, float(loss.max() - loss.min()))
        if k == 0:
            prog_grad = leaf_norms(p0, params, 1.0 / lr)
    prog_delta = leaf_norms(params, p0, 1.0)
    del p0
    gc.collect()
    gc.freeze()

    trace_steps = int(tr["trace_steps"]) if ctx.trace else 0
    phase = common.TracedPhase(ctx)
    if trace_steps:
        phase.start()
    step_ms, steps = [], 0
    rec.scalars["setup_s"] = time.perf_counter() - ctx.t_start
    t_prev = t_open = time.perf_counter()
    while True:
        with common.span("bench.feed"):
            tokens = next(feed)
        with common.span("bench.train_step"):
            loss, params = compiled(params, tokens)
            loss.block_until_ready()
        now = time.perf_counter()
        if trace_steps:
            # The traced phase comes first and is no part of the window.
            trace_steps -= 1
            if trace_steps == 0:
                rec.trace = phase.stop()
                rec.scalars["setup_s"] = time.perf_counter() - ctx.t_start
                t_open = time.perf_counter()
            t_prev = time.perf_counter()
            continue
        step_ms.append((now - t_prev) * 1e3)
        t_prev = now
        steps += 1
        if now - t_open >= ctx.seconds:
            break
    window_s = now - t_open

    tokens_per_step = n * per_chip * seq
    rec.attempted, rec.failed = steps, 0
    rec.samples["train_step_ms"] = step_ms
    rec.scalars.update(
        window_s=window_s, steps=steps, tokens_per_step=tokens_per_step,
        train_tok_s_chip=steps * tokens_per_step / window_s / n,
        flop_per_token=flops.train_flops_per_token(cfg, seq))
    rec.extras["flash_calls"] = {
        "uniform": flops.flash_call_shape(cfg, per_chip, seq)}
    rec.memory_peak_bytes, limit = common.memory_peak(ctx.devices)
    mem = compiled.memory_analysis()
    rec.scalars.update(
        live_peak_bytes=rec.memory_peak_bytes, bytes_limit=limit,
        program_temp_bytes=getattr(mem, "temp_size_in_bytes", 0) or 0)

    # The program's state is freed; the reference follows the first steps
    # from the same seed, batch rows spread over the chips.
    del params, compiled, loss, tokens, feed
    t_ref = time.perf_counter()
    ref = load_reference(cfg)
    rows = NamedSharding(mesh, P("mpi"))
    p0 = weights.make_params(cfg, ctx.seed, dtype, repl)
    p, ref_loss = p0, []
    for k in range(k_check):
        l, new = ref.sgd_step(cfg, p, jax.device_put(seen[k], rows), lr)
        ref_loss.append(float(l))
        if k == 0:
            ref_grad = leaf_norms(p0, new, 1.0 / lr)
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
        "reference_s": time.perf_counter() - t_ref,
        "memory_after_reference": common.memory_peak(ctx.devices)[0]}
    return rec


def _control_seed(ctx) -> dict:
    """The reference in float32 against the same reference in float8,
    read exactly as a run reads the program against the reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmarks import common, weights
    from benchmarks.references import load as load_reference

    cfg, tr = ctx.cfg, ctx.traffic
    ref = load_reference(cfg)
    mesh = Mesh(np.asarray(ctx.devices), ("mpi",))
    repl, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("mpi"))
    dtype, lr = jnp.dtype(cfg["dtype"]), float(tr["lr"])
    feed = generate(tr, cfg["vocab_size"], ctx.seed, len(ctx.devices))
    batches = [jax.device_put(next(feed), rows)
               for _ in range(int(tr["check_steps"]))]
    out = {}
    for mm in ("f32", "fp8"):
        p0 = weights.make_params(cfg, ctx.seed, dtype, repl)
        p, losses = p0, []
        for k, tokens in enumerate(batches):
            loss, new = ref.sgd_step(cfg, p, tokens, lr, mm)
            losses.append(float(loss))
            if k == 0:
                grad = leaf_norms(p0, new, 1.0 / lr)
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
