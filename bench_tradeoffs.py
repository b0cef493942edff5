"""Measure the documented lowering trade-offs on the current backend.

Three code comments in ``ops/spmd.py`` argue trade-offs from HLO text
(round-3 verdict: argued, never timed); this harness times them so the
comments can carry measured numbers:

1. **Bcast_ tree/psum crossover** (`config.bcast_tree_max_bytes`):
   sweep tensor sizes across the 256 KiB threshold, timing the
   binomial-tree lowering vs the masked-psum lowering head-to-head.
2. **Gather all-gather-then-mask cost**: Gather-to-root vs plain
   Allgather of the same shards (the overhead of masking to the root)
   and vs the theoretically cheaper psum_scatter-style adjoint path.
3. **Deterministic-reductions overhead**: the same Allreduce fwd+bwd
   step with the ordered-fold lowering vs the native psum.

Runs on the TPU; unless ``JAX_PLATFORMS=cpu`` was asked for in so many
words (a smoke check of the harness, whose numbers mean nothing) any
other platform exits non-zero.  Emits one JSON document on stdout,
per-point progress on stderr, and exits non-zero when a sweep failed.
"""

from __future__ import annotations

import json
import sys


# Share bench.py's timing rule (every timed iteration ends with a
# device->host fetch of one element derived from every output leaf; see
# bench.py _force) rather than copy it: both harnesses must always
# measure under the same rules.
from bench import (  # noqa: E402
    _exit_on_errors, _platform_or_exit, _timeit)


def _note(msg):
    print(f"bench_tradeoffs: {msg}", file=sys.stderr, flush=True)


def _on_tpu():
    import jax

    return jax.devices()[0].platform == "tpu"


def bench_bcast_crossover(n):
    """Tree vs masked-psum Bcast_ lowering across sizes (bytes/step)."""
    import jax.numpy as jnp

    import mpi4torch_tpu as mpi
    from mpi4torch_tpu.ops import spmd

    results = []
    # 16 KiB .. 16 MiB on hardware, bracketing the 256 KiB documented
    # threshold; two points on the CPU smoke path (compiles dominate).
    sweep = range(14, 25) if _on_tpu() else (16, 20)
    for log2_bytes in sweep:
        nelem = (1 << log2_bytes) // 4
        x = jnp.ones((nelem,), jnp.float32)
        point = {"bytes": nelem * 4}
        for mode, max_bytes in (("tree", 1 << 62), ("psum", 0)):
            saved = mpi.config.bcast_tree_max_bytes()
            mpi.config.set_bcast_tree_max_bytes(max_bytes)
            try:
                step = mpi.run_spmd(
                    lambda x: mpi.COMM_WORLD.Bcast_(x, 0), nranks=n)
                point[f"{mode}_s"] = _timeit(step, x, iters=10)
            finally:
                mpi.config.set_bcast_tree_max_bytes(saved)
            _note(f"bcast {point['bytes']}B {mode}: {point[f'{mode}_s']:.2e}s")
        point["tree_faster"] = point["tree_s"] < point["psum_s"]
        results.append(point)
    return results


def bench_gather_cost(n):
    """Gather-to-root (all_gather+mask lowering) vs plain Allgather."""
    import jax.numpy as jnp

    import mpi4torch_tpu as mpi

    results = []
    for log2_bytes in ((16, 20, 24) if _on_tpu() else (16,)):
        nelem = (1 << log2_bytes) // 4
        x = jnp.ones((nelem,), jnp.float32)
        gather = mpi.run_spmd(
            lambda x: mpi.COMM_WORLD.Gather(x, 0, 0), nranks=n)
        allgather = mpi.run_spmd(
            lambda x: mpi.COMM_WORLD.Allgather(x, 0), nranks=n)
        g, ag = (_timeit(gather, x, iters=10),
                 _timeit(allgather, x, iters=10))
        results.append({"shard_bytes": nelem * 4, "gather_s": g,
                        "allgather_s": ag,
                        "mask_overhead": g / ag - 1.0})
        _note(f"gather {nelem * 4}B: {g:.2e}s vs allgather {ag:.2e}s")
    return results


def bench_deterministic_overhead(n):
    """Ordered-fold Allreduce vs native psum, fwd+bwd (the bit-exactness
    tax; config.py deterministic_reductions)."""
    import jax
    import jax.numpy as jnp

    import mpi4torch_tpu as mpi
    from mpi4torch_tpu import config

    nelem = ((1 << 24) if _on_tpu() else (1 << 18)) // 4
    x = jnp.ones((nelem,), jnp.float32)

    def loss(x):
        y = mpi.COMM_WORLD.Allreduce(x, mpi.MPI_SUM)
        return jnp.vdot(y, y)

    step = mpi.run_spmd(lambda x: jax.value_and_grad(loss)(x), nranks=n)
    out = {}
    for det in (False, True):
        saved = config.deterministic_reductions()
        config.set_deterministic_reductions(det)
        try:
            out["ordered_s" if det else "native_s"] = _timeit(step, x,
                                                              iters=10)
        finally:
            config.set_deterministic_reductions(saved)
    out["tensor_bytes"] = nelem * 4
    out["overhead"] = out["ordered_s"] / out["native_s"] - 1.0
    _note(f"deterministic overhead: {out['overhead']:.1%}")
    return out


def bench_ordered_fold_paths(n):
    """Gather-fold vs chunked-ring-fold deterministic Allreduce (VERDICT r4
    item 3): both are bit-identical; this measures the memory/latency trade
    to calibrate ``config.ordered_fold_gather_max_bytes``.  Native psum is
    the
    speed-of-light reference at each size."""
    import jax.numpy as jnp

    import mpi4torch_tpu as mpi
    from mpi4torch_tpu import config
    from mpi4torch_tpu.ops import spmd

    results = []
    for log2_bytes in ((18, 21, 24, 27) if _on_tpu() else (16, 18)):
        nelem = (1 << log2_bytes) // 4
        x = jnp.ones((nelem,), jnp.float32)
        point = {"bytes": nelem * 4}
        step = mpi.run_spmd(
            lambda x: mpi.COMM_WORLD.Allreduce(x, mpi.MPI_SUM), nranks=n)
        point["psum_s"] = _timeit(step, x, iters=10)
        saved_det = config.deterministic_reductions()
        saved_thresh = config.ordered_fold_gather_max_bytes()
        config.set_deterministic_reductions(True)
        try:
            for mode, thresh in (("gather_fold", 1 << 62), ("ring_fold", 0)):
                config.set_ordered_fold_gather_max_bytes(thresh)
                step = mpi.run_spmd(
                    lambda x: mpi.COMM_WORLD.Allreduce(x, mpi.MPI_SUM),
                    nranks=n)
                point[f"{mode}_s"] = _timeit(step, x, iters=10)
        finally:
            config.set_deterministic_reductions(saved_det)
            config.set_ordered_fold_gather_max_bytes(saved_thresh)
        point["ring_vs_gather"] = point["ring_fold_s"] / point["gather_fold_s"]
        _note(f"ordered fold {point['bytes']}B: gather "
              f"{point['gather_fold_s']:.2e}s ring {point['ring_fold_s']:.2e}s "
              f"psum {point['psum_s']:.2e}s")
        results.append(point)
    return results


def bench_flash_tiling(n):
    """Sweep the Pallas flash kernels' Q/KV tile sizes at the bench shape
    — the first knob to turn if the head-to-head `flash_reference_ratio`
    lands under 1.0 on chip.  Every point is oracle-checked against the
    jnp reference before it is timed (a mis-lowering must never be
    reported as a fast configuration); failures degrade to error stanzas.
    On CPU the sweep is a harness smoke over the jnp path only."""
    import jax
    import jax.numpy as jnp

    from mpi4torch_tpu.ops import flash

    if _on_tpu():
        b, s, h, d, dtype, iters = 4, 4096, 8, 128, jnp.bfloat16, 10
        sweep = [(128, 128), (256, 128), (512, 128),
                 (128, 256), (256, 256), (512, 512)]
        impl, tol = "pallas", 2e-2
    else:
        b, s, h, d, dtype, iters = 1, 256, 2, 64, jnp.float32, 2
        sweep = [(128, 128), (256, 256)]
        impl, tol = "jnp", 1e-5

    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (b, s, h, d), dtype) for kk in keys)

    def loss_of(which):
        return lambda q, k, v: jnp.sum(flash.flash_attention(
            q, k, v, causal=True, impl=which).astype(jnp.float32) ** 2)

    ref = flash.flash_attention(q, k, v, causal=True, impl="jnp")
    gref = jax.jit(jax.grad(loss_of("jnp"), argnums=(0, 1, 2)))(q, k, v)
    results = []
    saved = (flash._Q_TILE, flash._KV_TILE)
    try:
        for qt, kt in sweep:
            flash._Q_TILE, flash._KV_TILE = qt, kt
            point = {"q_tile": qt, "kv_tile": kt}
            try:
                out = flash.flash_attention(q, k, v, causal=True, impl=impl)
                err = float(jnp.max(jnp.abs(
                    out.astype(jnp.float32) - ref.astype(jnp.float32))))
                # The timed program is fwd+bwd, so the gate must check the
                # GRADIENTS too — a mis-lowered backward (the path the
                # wide-tile _stat_tile branch feeds) must never be
                # reported as a fast configuration.
                g = jax.jit(jax.grad(loss_of(impl),
                                     argnums=(0, 1, 2)))(q, k, v)
                gerr = max(float(jnp.max(jnp.abs(
                    a.astype(jnp.float32) - b.astype(jnp.float32))))
                    for a, b in zip(g, gref))
                # Grad entries scale with the loss's 2*out factor; give
                # the same relative budget an order of magnitude slack.
                if err > tol or gerr > 50 * tol:
                    raise AssertionError(
                        f"tile ({qt},{kt}) wrong: fwd diff {err}, "
                        f"grad diff {gerr}")
                step = jax.jit(jax.value_and_grad(
                    loss_of(impl), argnums=(0, 1, 2)))
                point["fwd_bwd_s"] = _timeit(step, q, k, v, iters=iters)
                point["max_abs_diff_vs_jnp"] = err
                point["max_grad_diff_vs_jnp"] = gerr
            except Exception as e:  # noqa: BLE001 — per-point guard
                point["error"] = f"{type(e).__name__}: {str(e)[:160]}"
            results.append(point)
            _note(f"flash tiling {qt}x{kt}: {point}")
    finally:
        flash._Q_TILE, flash._KV_TILE = saved
    return results


def bench_vocab_chunk(n):
    """Sweep the chunked-vocab CE chunk width at the bench train config
    (``bench.py`` pins 4096 by analysis, never measured): time the full
    loss fwd+bwd per chunk width, plus the dense head (vocab_chunk=0 —
    the (batch, seq, vocab) logits it exists to avoid; may legitimately
    OOM on chip, its own guard records that).  On CPU this is a harness
    smoke at toy shapes."""
    import jax
    import jax.numpy as jnp

    from mpi4torch_tpu.models import transformer as T

    if _on_tpu():
        cfg = T.TransformerConfig(vocab=32768, d_model=2048, n_heads=16,
                                  n_layers=2, d_ff=8192, max_seq=2048)
        batch, dtype, iters = 8, jnp.bfloat16, 5
        sweep = (1024, 2048, 4096, 8192, 0)
    else:
        cfg = T.TransformerConfig(vocab=256, d_model=64, n_heads=4,
                                  n_layers=1, d_ff=128, max_seq=64)
        batch, dtype, iters = 2, jnp.float32, 2
        sweep = (64, 0)

    params = T.init_transformer(jax.random.PRNGKey(0), cfg, dtype=dtype)
    tokens = jax.random.randint(jax.random.PRNGKey(1),
                                (batch, cfg.max_seq), 0, cfg.vocab,
                                jnp.int32)
    results = []
    ref_loss = None
    for vc in sweep:
        point = {"vocab_chunk": vc}
        try:
            step = jax.jit(jax.value_and_grad(
                lambda p, _vc=vc: T.lm_loss(cfg, p, tokens,
                                            vocab_chunk=_vc)))
            # Correctness gate before the timing counts (the flash
            # sweep's rule: a mis-lowering must never be reported as a
            # fast configuration): every chunking computes the SAME
            # mathematical loss — compare each point's value against the
            # first successful one, at reduction-reassociation tolerance.
            loss = float(step(params)[0])
            point["loss"] = loss
            if ref_loss is None:
                ref_loss = loss
            rel = abs(loss - ref_loss) / max(abs(ref_loss), 1e-30)
            point["loss_rel_dev"] = rel
            tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
            if rel > tol:
                point["error"] = (f"loss deviates {rel:.2e} from the "
                                  "sweep's reference — not timing a "
                                  "mis-lowered configuration")
                results.append(point)
                _note(f"vocab_chunk {vc}: {point}")
                continue
            point["loss_fwd_bwd_s"] = _timeit(step, params, iters=iters)
        except Exception as e:  # noqa: BLE001 — per-point guard (OOM etc.)
            point["error"] = f"{type(e).__name__}: {str(e)[:200]}"
        results.append(point)
        _note(f"vocab_chunk {vc}: {point}")
    return results


def bench_native_reduce_crossover(n):
    """``_NATIVE_REDUCE_MIN_SIZE``: the fused native C ordered fold vs the
    pure-jnp fold for CPU-RESIDENT operands (constants.py:102-104 — the
    threshold only gates data already on the host, so this sweep is valid
    on any platform; operands are pinned to the CPU backend).  Both paths
    are documented bit-equal; each point cross-checks that before its
    timings count.  Host numpy is synchronous, so plain perf_counter
    brackets are a sound barrier here."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpi4torch_tpu import MPI_SUM, _native
    from mpi4torch_tpu import constants as C

    if not _native.available():
        return {"skipped": "native library unavailable"}

    from contextlib import contextmanager

    @contextmanager
    def forced_path(thresh):
        saved = C._NATIVE_REDUCE_MIN_SIZE
        C._NATIVE_REDUCE_MIN_SIZE = thresh
        try:
            yield
        finally:
            C._NATIVE_REDUCE_MIN_SIZE = saved

    modes = (("native", 0), ("jnp_fold", 1 << 62))
    cpu = jax.devices("cpu")[0]
    rng = np.random.default_rng(0)
    results = []
    for log2_elems in range(8, 23, 2):          # 256 .. 4M elements
        nelem = 1 << log2_elems
        with jax.default_device(cpu):
            vals = [jnp.asarray(rng.standard_normal(nelem), jnp.float32)
                    for _ in range(8)]
            point = {"elements": nelem, "bytes": nelem * 4}
            outs = {}
            for mode, thresh in modes:
                with forced_path(thresh):
                    outs[mode] = np.asarray(C.reduce_ordered(MPI_SUM, vals))
            point["bit_equal"] = bool(
                np.array_equal(outs["native"], outs["jnp_fold"]))
            if not point["bit_equal"]:
                # Timings of a wrong kernel are not data: a point that
                # fails the bit-equality contract reports only the
                # failure (never a speedup someone might act on).
                results.append(point)
                _note(f"native_reduce {nelem} elems: BIT-EQUALITY BROKEN")
                continue
            for mode, thresh in modes:
                with forced_path(thresh):
                    iters = 30 if nelem <= (1 << 18) else 10
                    ts = []
                    for _ in range(iters):
                        t0 = time.perf_counter()
                        np.asarray(C.reduce_ordered(MPI_SUM, vals))
                        ts.append(time.perf_counter() - t0)
                    ts.sort()
                    point[f"{mode}_s"] = ts[len(ts) // 2]
            point["native_speedup"] = point["jnp_fold_s"] / point["native_s"]
        results.append(point)
        _note(f"native_reduce {nelem} elems: native {point['native_s']:.2e}s"
              f" vs jnp {point['jnp_fold_s']:.2e}s"
              f" (bit_equal={point['bit_equal']})")
    return results


def bench_reduce_scatter(n):
    """Reduce_scatter vs Allreduce-then-slice (the ZeRO gradient path;
    parallel/zero.py).  On a multi-chip mesh the native psum_scatter is
    half the allreduce's wire; on one chip both are HBM-bound but the
    slice variant still writes the full-length result first."""
    import jax
    import jax.numpy as jnp

    import mpi4torch_tpu as mpi

    results = []
    for log2_bytes in ((20, 24, 26) if _on_tpu() else (16,)):
        nelem = (1 << log2_bytes) // 4
        nelem -= nelem % n
        x = jnp.ones((nelem,), jnp.float32)
        shard = nelem // n

        def rs(x):
            return mpi.COMM_WORLD.Reduce_scatter(x, mpi.MPI_SUM, 0)

        def ar_slice(x):
            full = mpi.COMM_WORLD.Allreduce(x, mpi.MPI_SUM)
            start = jnp.asarray(mpi.COMM_WORLD.rank) * shard
            return jax.lax.dynamic_slice_in_dim(full, start, shard, 0)

        t_rs = _timeit(mpi.run_spmd(rs, nranks=n), x, iters=10)
        t_ar = _timeit(mpi.run_spmd(ar_slice, nranks=n), x, iters=10)
        results.append({"bytes": nelem * 4, "reduce_scatter_s": t_rs,
                        "allreduce_slice_s": t_ar,
                        "speedup": t_ar / t_rs})
        _note(f"reduce_scatter {nelem * 4}B: {t_rs:.2e}s vs "
              f"allreduce+slice {t_ar:.2e}s")
    return results


def main():
    from mpi4torch_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    platform, _ = _platform_or_exit("bench_tradeoffs.py")

    import jax

    n = min(len(jax.devices()), 8)
    _note(f"platform={platform} devices={n}")
    result = {"platform": platform,
              "device_kind": jax.devices()[0].device_kind,
              "n_devices": n}
    for name, fn in (("bcast_crossover", bench_bcast_crossover),
                     ("gather_cost", bench_gather_cost),
                     ("deterministic", bench_deterministic_overhead),
                     ("ordered_fold_paths", bench_ordered_fold_paths),
                     ("flash_tiling", bench_flash_tiling),
                     ("native_reduce_crossover", bench_native_reduce_crossover),
                     ("vocab_chunk", bench_vocab_chunk),
                     ("reduce_scatter", bench_reduce_scatter)):
        try:
            result[name] = fn(n)
        except Exception as e:  # noqa: BLE001 — partial results still print
            result[name] = {"error": f"{type(e).__name__}: {str(e)[:200]}"}
    print(json.dumps(result, indent=1))
    _exit_on_errors(result)


if __name__ == "__main__":
    main()
