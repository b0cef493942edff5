"""Record the small device trace that test_trace_reduce.py reads.

Run on the chip (``python benchmarks/tests/record_trace.py``): a few
steps of a tiny jitted program that holds a matmul, the three flash
kernels and, on more than one chip, an all-reduce, under the
benchmark's own host spans.  Writes ``chiprun_out/trace_small/<n>chip.xplane.pb``
and prints what planes, lines and op names the trace holds.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax
import jax.numpy as jnp


def main() -> int:
    from mpi4torch_tpu.ops import flash

    devs = jax.devices()
    n = len(devs)
    print("devices", [(d.platform, d.device_kind) for d in devs])
    print("memory_stats", devs[0].memory_stats())
    mesh = jax.sharding.Mesh(devs, ("mpi",))
    P = jax.sharding.PartitionSpec

    def loss(w, q, k, v):
        o = flash.flash_attention(q, k, v, causal=True, window=256)
        y = o.reshape(o.shape[0], o.shape[1], -1) @ w
        return jnp.sum(y.astype(jnp.float32) ** 2) * 1e-6

    def step(w, q, k, v):
        # the gradient to q runs the two backward kernels as well
        l, (g, gq) = jax.value_and_grad(loss, argnums=(0, 1))(w, q, k, v)
        if n > 1:
            g = jax.lax.pmean(g, "mpi")
        l = l + 0.0 * jnp.sum(gq.astype(jnp.float32))
        return l[None], w - 0.1 * g.astype(w.dtype)

    if n > 1:
        step = jax.shard_map(
            step, mesh=mesh, in_specs=(P(), P("mpi"), P("mpi"), P("mpi")),
            out_specs=(P("mpi"), P()), check_vma=False)
    fn = jax.jit(step)
    key = jax.random.PRNGKey(0)
    kq, kk, kv, kw = jax.random.split(key, 4)
    b = 2 * n
    q = jax.random.normal(kq, (b, 512, 4, 128), jnp.bfloat16)
    k = jax.random.normal(kk, (b, 512, 2, 128), jnp.bfloat16)
    v = jax.random.normal(kv, (b, 512, 2, 128), jnp.bfloat16)
    w = jax.random.normal(kw, (512, 1024), jnp.bfloat16)
    l, w = fn(w, q, k, v)
    jax.block_until_ready(l)

    out = os.path.join("chiprun_out", "trace_small")
    tmp = os.path.join(out, "raw")
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    t0 = time.perf_counter()
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for i in range(3):
        with jax.profiler.TraceAnnotation("bench.feed"):
            time.sleep(0.005)
        with jax.profiler.TraceAnnotation("bench.train_step"):
            l, w = fn(w, q, k, v)
            jax.block_until_ready(l)
    jax.profiler.stop_trace()
    print("traced window s", time.perf_counter() - t0)
    (pb,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))
    dst = os.path.join(out, f"{n}chip.xplane.pb")
    shutil.copy(pb, dst)
    shutil.rmtree(tmp)
    print("trace bytes", os.path.getsize(dst))
    pd = jax.profiler.ProfileData.from_file(dst)
    for plane in pd.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            names = {}
            for e in evs:
                names[e.name] = names.get(e.name, 0) + 1
            top = sorted(names.items(), key=lambda kv: -kv[1])[:25]
            print("  LINE", repr(line.name), len(evs), top)
            for e in evs[:2]:
                print("     ev", e.name, e.start_ns, e.duration_ns, dict(list(e.stats)[:6]))
    # seeds beyond 32 bits, fp8 casts
    big = 2**31 + 12345
    kk = jax.random.fold_in(jax.random.PRNGKey(big & 0x7FFFFFFF), big >> 31)
    print("bigseed key ok", jax.random.key_data(kk))
    x = jnp.linspace(-3, 3, 9, dtype=jnp.float32)
    print("fp8", x.astype(jnp.float8_e4m3fn).astype(jnp.float32))
    return 0


if __name__ == "__main__":
    sys.exit(main())
