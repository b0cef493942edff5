"""Closed-loop serving from a fixed cycle of requests.

Parameters of a mix: ``clients`` callers, each submitting its next
request when its last one finished; ``cycle``, a fixed list of one
``[prompt tokens, answer budget]`` per caller; ``engine``, the
``ServeConfig``; ``stagger_steps`` between the first submissions, so
that the callers do not finish in lock-step.  The caller with role ``r``
sends entries ``r, r + 1, ...`` of the cycle.  The seed deals the roles
to the callers and draws every prompt's tokens: it changes names and
tokens, never the amount of work nor when it falls due.
"""

from __future__ import annotations

import gc
import time

import numpy as np


def roles(traffic: dict, seed: int) -> np.ndarray:
    """``roles[caller]``: where in the cycle the caller starts."""
    rng = np.random.default_rng([int(seed), 0x5E12])
    return rng.permutation(int(traffic["clients"]))


def make_cycle(traffic: dict, vocab: int, seed: int, k: int) -> list:
    """The ``k``-th request of every caller: ``(prompt, budget)`` per
    caller.  Over the callers it is the whole cycle, for every ``k`` and
    every seed.  No two prompts of a run share their first token, so
    none shares a prefix."""
    cycle = traffic["cycle"]
    n = len(cycle)
    out = []
    for caller, role in enumerate(roles(traffic, seed)):
        length, budget = cycle[(int(role) + k) % n]
        rng = np.random.default_rng([int(seed), 0x5E12, k, caller])
        prompt = rng.integers(0, vocab, size=int(length), dtype=np.int32)
        prompt[0] = (k * n + caller) % vocab
        out.append((prompt, int(budget)))
    return out


class Arrivals:
    """Token arrivals of each request.  An arrival is the return of a
    ``step()`` that handed the request one token or more: a step that
    hands over two (the prefill's and the first decode's) is one
    arrival.  Time to first token runs from the call of ``submit()``;
    a gap is the time between two consecutive arrivals of one request."""

    def __init__(self):
        self.submitted = {}
        self.last = {}
        self.ttft_ms, self.gap_ms, self.tokens = [], [], 0
        self.opened = None

    def open(self, now: float) -> None:
        """Start counting: only what lies wholly inside the window."""
        self.opened = now
        self.ttft_ms, self.gap_ms, self.tokens = [], [], 0

    def submit(self, rid, now: float) -> None:
        self.submitted[rid] = now

    def step_returned(self, emitted: dict, now: float) -> None:
        for rid, toks in emitted.items():
            if not toks:
                continue
            self.tokens += len(toks)
            prev = self.last.get(rid)
            if prev is None:
                t_sub = self.submitted[rid]
                if self.opened is not None and t_sub >= self.opened:
                    self.ttft_ms.append((now - t_sub) * 1e3)
            elif self.opened is not None and prev >= self.opened:
                self.gap_ms.append((now - prev) * 1e3)
            self.last[rid] = now

    def finished(self, rid) -> None:
        self.last.pop(rid, None)
        self.submitted.pop(rid, None)


class Loop:
    """The callers and the engine's host loop."""

    def __init__(self, eng, traffic, vocab, seed):
        self.eng, self.traffic, self.vocab, self.seed = eng, traffic, vocab, seed
        # Every prompt the window can need exists before it opens.
        self.cycles = [make_cycle(traffic, vocab, seed, k)
                       for k in range(len(traffic["cycle"]))]
        self.next_cycle = [0] * int(traffic["clients"])
        # Callers start in the order of their roles.
        self.start_order = [int(c) for c in np.argsort(roles(traffic, seed))]
        self.owner, self.requests, self.served = {}, {}, {}
        self.done = []                 # rids finished, in order
        self.arrivals = Arrivals()
        self.unadmitted = 0
        self.admitted_lens = []        # prompt lengths, in admission order

    def submit(self, client: int) -> None:
        from benchmarks.common import span

        c = self.next_cycle[client]
        self.next_cycle[client] += 1
        while c >= len(self.cycles):
            self.cycles.append(make_cycle(self.traffic, self.vocab,
                                          self.seed, len(self.cycles)))
        prompt, budget = self.cycles[c][client]
        with span("bench.submit"):
            now = time.perf_counter()
            rid = self.eng.submit(prompt, max_new=budget)
        self.arrivals.submit(rid, now)
        self.owner[rid] = client
        self.requests[rid] = (prompt, budget)
        self.served[rid] = []
        self.unadmitted += 1

    def step(self):
        """One ``Engine.step()``; returns (seconds, admitted any)."""
        from benchmarks.common import span

        name = ("bench.engine_step.admit" if self.unadmitted
                else "bench.engine_step.decode")
        t0 = time.perf_counter()
        with span(name):
            ev = self.eng.step()
        now = time.perf_counter()
        self.arrivals.step_returned(ev["emitted"], now)
        for rid, toks in ev["emitted"].items():
            self.served[rid].extend(toks)
        for rid in ev["admitted"]:
            self.unadmitted -= 1
            self.admitted_lens.append(len(self.requests[rid][0]))
        for rid in ev["finished"]:
            self.arrivals.finished(rid)
            self.done.append(rid)
            self.submit(self.owner[rid])
        return now, now - t0, bool(ev["admitted"])


def reference_logits(ctx, sample: list, mm: str = "f32"):
    """The plain reference, once over each sampled request's prompt +
    served tokens (padded to one shape; the weights regenerated from the
    seed a layer at a time): its logits at every position that a served
    token was predicted from, ``(requests, tokens, vocab)``, and which
    of those positions are real.  ``mm="fp8"`` is the control."""
    import jax
    import jax.numpy as jnp

    from benchmarks import weights
    from benchmarks.references import load as load_reference

    cfg = ctx.cfg
    ref = load_reference(cfg)
    dtype = jnp.dtype(cfg["dtype"])
    key = weights.seed_key(ctx.seed)
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
    top = jax.jit(lambda k: weights.make_top(k, cfg, dtype))(key)
    layer = jax.jit(lambda k, i: weights.make_layer(k, cfg, i, dtype))
    layers = (layer(key, i) for i in range(cfg["num_hidden_layers"]))
    logits = np.asarray(ref.logits_at(cfg, top, layers, jnp.asarray(tokens),
                                      jnp.asarray(rows), mm))
    return logits, valid


def widest_gap(ref_logits, valid, chosen) -> float:
    """Widest gap by which a chosen token's reference logit lies below
    the reference's best, over the valid positions."""
    best = ref_logits.max(axis=-1)
    got = np.take_along_axis(ref_logits, chosen[..., None], axis=-1)[..., 0]
    return float(np.max(np.where(valid, best - got, 0.0)))


def served_matrix(sample, valid) -> np.ndarray:
    chosen = np.zeros(valid.shape, np.int64)
    for i, (_, s) in enumerate(sample):
        chosen[i, :len(s)] = s
    return chosen


def draw_sample(loop: Loop, rids: list, seed: int, k: int) -> list:
    """The longest finished request and ``k - 1`` others drawn from the
    seed, as (prompt, served tokens)."""
    size = lambda r: len(loop.requests[r][0]) + len(loop.served[r])
    longest = max(rids, key=size)
    rest = [r for r in rids if r != longest]
    rng = np.random.default_rng([int(seed), 0xC4EC])
    pick = [longest] + [rest[i] for i in rng.permutation(len(rest))[:k - 1]]
    return [(loop.requests[r][0], np.asarray(loop.served[r], np.int32))
            for r in pick]


def run(ctx):
    import jax.numpy as jnp

    from benchmarks import common, flops, program, weights

    cfg, tr = ctx.cfg, ctx.traffic
    rec = common.Record(ctx=ctx)
    compiles = common.CompileCounter()
    tcfg = program.transformer_config(cfg)
    params = weights.make_params(cfg, ctx.seed, jnp.dtype(cfg["dtype"]))
    t0 = time.perf_counter()
    eng = program.build_engine(tcfg, params, tr["engine"], len(ctx.devices))
    rec.scalars["engine_construct_s"] = time.perf_counter() - t0
    del params

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
        # A short traced phase of the same steady state, before the window.
        phase = common.TracedPhase(ctx)
        n_adm, t_tr = len(loop.admitted_lens), time.perf_counter()
        phase.start()
        while time.perf_counter() - t_tr < float(tr["trace_seconds"]):
            loop.step()
        rec.trace = phase.stop()
        rec.extras["flash_calls"] = {"in_order": [
            flops.flash_call_shape(cfg, 1, n)
            for n in loop.admitted_lens[n_adm:]]}

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

    # A sample of what the window finished against the plain reference.
    t_ref = time.perf_counter()
    sample = draw_sample(loop, finished, ctx.seed, int(tr["check_requests"]))
    logits, valid = reference_logits(ctx, sample)
    common.compare(rec, "served_logit_gap",
                   widest_gap(logits, valid, served_matrix(sample, valid)),
                   ctx.limits)
    common.compare(rec, "prefix_hits", rec.scalars["prefix_hits"], ctx.limits)
    rec.extras["notes"] = {
        "reference_s": time.perf_counter() - t_ref,
        "finished_in_window": len(finished),
        "admit_steps": len(admit_ms), "decode_steps": len(decode_ms),
        "gaps": len(arr.gap_ms), "prefill_tokens": delta("prefill_tokens"),
        "memory_after_reference": common.memory_peak(ctx.devices)[0]}
    return rec


def _break_tokens(eng) -> None:
    """Tests only: every selected token comes out one too high."""
    select = eng._select
    vocab = eng.cfg.vocab
    eng._select = lambda req, row: (select(req, row) + 1) % vocab


def control(make_ctx, seeds: list, seconds: float) -> list:
    """One engine, a short window of the cell's load per seed; the
    weights are the first seed's (the engine bakes them into its
    programs, so a new seed of weights is a new set-up), the traffic is
    each seed's."""
    import jax.numpy as jnp

    from benchmarks import program, weights

    ctx = make_ctx(seeds[0])
    cfg, tr = ctx.cfg, ctx.traffic
    params = weights.make_params(cfg, ctx.seed, jnp.dtype(cfg["dtype"]))
    eng = program.build_engine(program.transformer_config(cfg), params,
                               tr["engine"], len(ctx.devices))
    del params
    out = []
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
        sample = draw_sample(loop, loop.done, seed,
                                  int(tr["check_requests"]))
        ref, valid = reference_logits(ctx, sample)
        low, _ = reference_logits(ctx, sample, mm="fp8")
        out.append({
            "seed": seed, "requests": len(sample),
            "served_tokens": int(valid.sum()),
            "program": {"served_logit_gap": widest_gap(
                ref, valid, served_matrix(sample, valid))},
            "control": {"served_logit_gap": widest_gap(
                ref, valid, low.argmax(axis=-1))}})
    return out


