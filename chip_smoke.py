"""On-chip smoke: the flagship train step and the serving engine, through
``run_spmd``, at full width on every chip the process sees.

Three phases, one process, no child:

1. **trainer** — ``mpi.run_spmd`` of ``models.transformer.train_step`` with
   ``comm_dp=COMM_WORLD`` (the canonical data-parallel recipe), rank-local
   tokens cut with ``COMM_WORLD.rank``, a few SGD steps on one seeded batch.
2. **kernel** — the train step's program holds the three Mosaic flash
   kernels once per layer, and the kernel matches ``impl="jnp"`` on the
   model's attention shape.
3. **server** — ``serve.Engine(spmd=True)`` answering six greedy requests
   through four slots, once with the dense cache and once paged.

``main()`` checks the device first and always runs the flagship widths;
it exits non-zero off the TPU, when a phase raises, or when a check
fails.  The phase functions take the configuration as an argument so that
tests/test_chip_smoke.py can drive the same control flow on the CPU at
tiny widths.  Times printed here are observations of one run, not
benchmark results.
"""

from __future__ import annotations

import importlib.metadata
import json
import math
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

import mpi4torch_tpu as mpi
from mpi4torch_tpu import _native, serve
from mpi4torch_tpu.models import transformer as T
from mpi4torch_tpu.ops import flash
from mpi4torch_tpu.utils.compile_cache import use_compile_cache

# The widths the repo calls its flagship:
# 541,134,848 parameters, bf16.  Depth and widths are never cut here.
FLAGSHIP = T.TransformerConfig(vocab=32768, d_model=2048, n_heads=16,
                               n_layers=8, d_ff=8192, max_seq=2048)
FLAGSHIP_DTYPE = jnp.bfloat16
# Per-chip sequences per step: 8 x 2048 tokens.  train_step's logits are
# dense at vocab 32768 and still fit a 16 GB v5e at this batch (CHANGES.md
# PR 22), so nothing is cut.
TRAIN_BATCH_PER_CHIP = 8
TRAIN_STEPS = 3
# train_step's default lr of 1e-2 moves an f32 loss by ~0.03 a step at
# this vocabulary (CPU probe at d_model 512), which a bf16 loss near 10.9
# (one ulp = 0.0625) cannot show; 0.3 moves it by a few ulps a step.
TRAIN_LR = 0.3
SERVE_SLOTS = 4
SERVE_REQUESTS = 6
SERVE_NEW_TOKENS = 32
SERVE_PROMPT_LENS = (128, 256)
SERVE_BLOCK_SIZE = 16
# tests/test_flash.py::test_compiled_bench_shape_bf16's tolerance.
KERNEL_RTOL = KERNEL_ATOL = 5e-2


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileLog:
    """Counts backend compilations by jitted-function name and persistent
    cache hits/misses, from JAX's own monitoring events."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.compiles: dict = {}
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, _secs, fun_name=None, **_):
        if event == self._COMPILE:
            self.compiles[fun_name] = self.compiles.get(fun_name, 0) + 1

    def _on_event(self, event, **_):
        if event == self._HIT:
            self.cache_hits += 1
        elif event == self._MISS:
            self.cache_misses += 1

    def spmd_programs(self) -> int:
        """Compilations of run_spmd programs (its jitted ``sm``)."""
        return self.compiles.get("jit(sm)", 0)


def peak_bytes() -> list:
    """Per-device peak bytes of live buffers since process start, where
    the backend reports it (the CPU does not).  A program's temporaries
    are not in it; the trainer reports those as ``program_bytes``."""
    out = []
    for d in jax.devices():
        stats = d.memory_stats()
        out.append(None if stats is None else stats["peak_bytes_in_use"])
    return out


def _train_body(cfg, per_chip_batch: int, lr: float):
    def step(params, tokens):
        comm = mpi.COMM_WORLD
        local = jax.lax.dynamic_slice_in_dim(
            tokens, jnp.asarray(comm.rank) * per_chip_batch,
            per_chip_batch, 0)
        return T.train_step(cfg, params, local, comm_dp=comm, lr=lr)
    return step


def run_trainer(cfg, dtype, n: int, per_chip_batch: int, steps: int,
                lr: float) -> dict:
    """Phase 1.  Returns ``(report, lowered_text, compiled_text)``: the
    losses with the set-up and per-step seconds, and the program texts
    phase 2 reads."""
    params = T.init_transformer(jax.random.PRNGKey(0), cfg, dtype=dtype)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (n * per_chip_batch, cfg.max_seq), 0,
        cfg.vocab, jnp.int32)

    t0 = time.perf_counter()
    lowered = jax.jit(mpi.run_spmd(
        _train_body(cfg, per_chip_batch, lr), nranks=n)).lower(
            params, tokens)
    compiled = lowered.compile()
    setup_s = time.perf_counter() - t0
    # An AOT executable takes its inputs where it was compiled to find
    # them (replicated over run_spmd's mesh), and says where that is.
    (params_at, tokens_at), _ = compiled.input_shardings
    tokens = jax.device_put(tokens, tokens_at)

    losses, step_s = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss, stacked = compiled(jax.device_put(params, params_at), tokens)
        loss = np.asarray(jax.block_until_ready(loss), np.float32)
        step_s.append(time.perf_counter() - t0)
        losses.append(loss)
        # Every rank's new parameters are identical (the DP recipe keeps
        # replicas in lock-step): rank 0's copy feeds the next step.
        params = jax.tree.map(lambda a: a[0], stacked)
        del stacked

    for i, loss in enumerate(losses):
        check(loss.shape == (n,), f"step {i}: loss shape {loss.shape}, "
              f"want one per rank ({n},)")
        check(np.all(np.isfinite(loss)), f"step {i}: loss {loss} not finite")
        check(np.all(loss == loss[0]),
              f"step {i}: loss differs across ranks: {loss}")
    first, last = float(losses[0][0]), float(losses[-1][0])
    # Unit-variance logits at init put the loss near ln(vocab) + 1/2.
    check(abs(first - math.log(cfg.vocab)) < 1.0,
          f"step-0 loss {first:.4f} not near ln({cfg.vocab}) = "
          f"{math.log(cfg.vocab):.4f}")
    check(last < first, f"loss did not fall: {first:.4f} -> {last:.4f}")
    mem = compiled.memory_analysis()
    report = {
        "n_params": int(n_params),
        "tokens_per_chip": per_chip_batch * cfg.max_seq,
        "losses": [float(x[0]) for x in losses],
        "setup_s": setup_s,
        "step_s": step_s,
        # The executable's own account, per device: memory_stats() counts
        # live buffers, not the temporaries a program runs in.
        "program_bytes": {"arguments": mem.argument_size_in_bytes,
                          "outputs": mem.output_size_in_bytes,
                          "temporaries": mem.temp_size_in_bytes},
    }
    return report, lowered.as_text(), compiled.as_text()


def check_kernel(cfg, dtype, per_chip_batch: int, lowered_text: str,
                 compiled_text: str) -> dict:
    """Phase 2.  On a TPU the train step must hold each flash kernel once
    per layer (``impl="auto"`` is jnp everywhere else, so zero); the
    kernel itself (interpreted off the TPU) must match the jnp oracle on
    the model's attention shape."""
    want = cfg.n_layers if jax.devices()[0].platform == "tpu" else 0
    named = {name: lowered_text.count(f'kernel_name = "{name}"')
             for name in flash.KERNEL_NAMES}
    mosaic_calls = compiled_text.count('custom_call_target="tpu_custom_call"')
    check(all(c == want for c in named.values()),
          f"lowered train step holds {named}, want {want} of each")
    # >=: XLA may rematerialize a custom call, it may not drop one.
    check(mosaic_calls >= 3 * want if want else mosaic_calls == 0,
          f"compiled train step holds {mosaic_calls} Mosaic custom calls, "
          f"want {3 * want}")

    shape = (per_chip_batch, cfg.max_seq, cfg.n_heads,
             cfg.d_model // cfg.n_heads)
    q, k, v = (jax.random.normal(key, shape, dtype)
               for key in jax.random.split(jax.random.PRNGKey(2), 3))

    def attend(impl):
        return jax.jit(lambda q, k, v: flash.flash_block_attention(
            q, k, v, causal=True, impl=impl)[0])(q, k, v)

    got = np.asarray(attend("pallas"), np.float32)
    ref = np.asarray(attend("jnp"), np.float32)
    check(got.shape == shape and np.all(np.isfinite(got)),
          f"kernel output shape {got.shape} / non-finite values")
    err = float(np.max(np.abs(got - ref)))
    check(np.allclose(got, ref, rtol=KERNEL_RTOL, atol=KERNEL_ATOL),
          f"kernel vs jnp at {shape}: max abs diff {err}")
    return {"kernels_in_lowered": named,
            "mosaic_calls_in_compiled": mosaic_calls,
            "shape": list(shape), "max_abs_diff_vs_jnp": err}


def _sharding_summary(tree) -> list:
    """Distinct (shape, sharding) of a state tree's leaves, with counts:
    where the engine's state lives between steps."""
    seen: dict = {}
    for leaf in jax.tree.leaves(tree):
        spec = getattr(leaf.sharding, "spec", leaf.sharding)
        key = (str(tuple(leaf.shape)), str(spec),
               bool(leaf.sharding.is_fully_replicated))
        seen[key] = seen.get(key, 0) + 1
    return [{"shape": s, "sharding": sp, "fully_replicated": r, "leaves": c}
            for (s, sp, r), c in seen.items()]


def run_server(cfg, params, n: int, log: CompileLog, *, paged: bool,
               slots: int, requests: int, new_tokens: int,
               prompt_lens, block_size: int) -> dict:
    """Phase 3, one engine.  ``requests`` greedy requests through
    ``slots`` slots; the decode step must compile exactly once."""
    sc = serve.ServeConfig(slots=slots, max_new=new_tokens,
                           block_size=block_size if paged else 0)
    programs0 = log.spmd_programs()
    t0 = time.perf_counter()
    eng = serve.Engine(cfg, params, sc, spmd=True, nranks=n)
    construct_s = time.perf_counter() - t0
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab,
                            size=prompt_lens[i % len(prompt_lens)])
               for i in range(requests)]
    rids = [eng.submit(p) for p in prompts]

    first_step_s, decode_s, retraces = None, [], 0
    while eng.pending():
        before = log.spmd_programs()
        s0 = time.perf_counter()
        events = eng.step()
        dt = time.perf_counter() - s0
        if first_step_s is None:
            first_step_s = dt
        elif not events["admitted"]:
            decode_s.append(dt)
            retraces += log.spmd_programs() - before
    programs = log.spmd_programs() - programs0

    results, statuses = eng.results(), eng.statuses()
    for rid, prompt in zip(rids, prompts):
        check(statuses.get(rid) == serve.STATUS_OK,
              f"request {rid}: status {statuses.get(rid)!r}")
        out = results[rid]
        check(len(out) == len(prompt) + new_tokens,
              f"request {rid}: {len(out)} tokens, want "
              f"{len(prompt)} + {new_tokens}")
        check(np.array_equal(out[:len(prompt)], prompt),
              f"request {rid}: the prompt did not come back intact")
        check(np.all((out >= 0) & (out < cfg.vocab)),
              f"request {rid}: token outside [0, {cfg.vocab})")
    # run_spmd programs of one engine: the TP sharding (the top of the
    # tree, and one for the uniform layers), one prefill per distinct
    # prompt length, and ONE decode step.
    want = 2 + len(set(len(p) for p in prompts)) + 1
    check(programs == want and retraces == 0,
          f"engine compiled {programs} run_spmd programs ({retraces} in "
          f"decode-only steps), want {want}: the decode step must compile "
          "once")
    decode_s.sort()
    return {
        "cache": "paged" if paged else "dense",
        "requests": requests, "slots": slots,
        # Set-up = construction + the first step, which compiles the
        # prefills and the decode step.
        "setup_s": construct_s + first_step_s, "construct_s": construct_s,
        "first_step_s": first_step_s,
        "decode_steps": len(decode_s),
        "decode_step_s_median": decode_s[len(decode_s) // 2],
        "spmd_programs_compiled": programs,
        "state": {"cache": _sharding_summary(eng._cache),
                  "shards": _sharding_summary(eng._shards)},
    }


def run_servers(cfg, dtype, n: int, log: CompileLog, **kw) -> list:
    params = T.init_transformer(jax.random.PRNGKey(0), cfg, dtype=dtype)
    return [run_server(cfg, params, n, log, paged=paged, **kw)
            for paged in (False, True)]


def mesh_order() -> dict:
    """The ring run_spmd's default mesh forms (plain ``jax.devices()``
    order) beside the ICI order ``mesh_utils`` would give."""
    from jax.experimental import mesh_utils

    devs = jax.devices()
    ici = mesh_utils.create_device_mesh((len(devs),), devices=devs)
    return {"run_spmd_ring": [d.id for d in devs],
            "coords": [list(getattr(d, "coords", ())) for d in devs],
            "mesh_utils_ring": [d.id for d in ici.flat]}


def _line(phase: str, **fields) -> None:
    print(f"chip_smoke {phase}: {json.dumps(fields)}", flush=True)


def _version(dist: str) -> str:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


def main() -> int:
    cache_dir = use_compile_cache()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    _line("env", jax=jax.__version__, jaxlib=_version("jaxlib"),
          libtpu=_version("libtpu"), device=device, cache_dir=cache_dir,
          native_available=_native.available(), x64=jax.config.jax_enable_x64)
    if device["platform"] != "tpu":
        print(f"chip_smoke: platform is {device['platform']!r}, not 'tpu' — "
              "this check runs on the chip only", file=sys.stderr)
        return 2

    n = len(devs)
    log = CompileLog()
    _line("mesh", **mesh_order())

    tr, lowered_text, compiled_text = run_trainer(
        FLAGSHIP, FLAGSHIP_DTYPE, n, TRAIN_BATCH_PER_CHIP, TRAIN_STEPS,
        TRAIN_LR)
    _line("trainer", **tr, peak_bytes=peak_bytes())

    _line("kernel", **check_kernel(FLAGSHIP, FLAGSHIP_DTYPE,
                                   TRAIN_BATCH_PER_CHIP, lowered_text,
                                   compiled_text))
    del lowered_text, compiled_text

    for res in run_servers(FLAGSHIP, FLAGSHIP_DTYPE, n, log,
                           slots=SERVE_SLOTS, requests=SERVE_REQUESTS,
                           new_tokens=SERVE_NEW_TOKENS,
                           prompt_lens=SERVE_PROMPT_LENS,
                           block_size=SERVE_BLOCK_SIZE):
        _line("server", **res, peak_bytes=peak_bytes())

    _line("cache", dir=cache_dir, hits=log.cache_hits,
          misses=log.cache_misses, compiles=sum(log.compiles.values()))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
