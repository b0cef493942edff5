"""Chip probe: one KDA layer's delta rule timed alone, pass by pass.

No benchmark cell: a script that answers, on one TPU chip, what one call
of ``ops/kda.py:kda_chunked`` costs at the shape
``kimi-linear-48b-a3b.train_kda_8k`` sends (``b, s, h, d`` = 2, 8,192,
32, 128; ``q``, ``k``, ``v`` bfloat16, or float32 under ``--dtype``, the
log-decay ``g`` and the write strength ``beta`` float32, as
``models/transformer.py:_kda_mixer`` hands them), forward alone and under ``jax.value_and_grad`` of a sum of it, for
``impl="jnp"`` and ``impl="pallas"``.  The ``jnp`` row's backward is
the plain path's (the chunks run again a head group at a time and
transposed by autodiff); the ``pallas`` row's ``grad - fwd`` is the two
backward kernels' (since PR 47; before it, and in a ``--baseline`` from
before it, the plain path's behind the forward kernel).  ``--baseline
PATH`` loads another ``kda.py`` (the parent commit's; one without an
``impl`` argument is timed as ``jnp``) and times it beside this one:
the before and after by pass that ``PERF.md`` quotes.  ``--check`` says
how far each row's output and each of its five gradients (``grad_gaps``:
``q``, ``k``, ``v``, ``g``, ``beta``; ``grad_gap`` the widest) lie from
the first row's, ``|a - b| / |b|`` in norms.

Times are the host's clock around calls that end in
``block_until_ready`` (each call is tens of milliseconds: the dispatch
is lost in it), the median of ``--iters`` calls after two warm-ups.

    python examples/kda_probe.py --check

One JSON line per (module, implementation), everything also in
``chiprun_out/kda_probe.jsonl``.  Exits non-zero off the TPU
(``--rehearse`` runs a tiny shape interpreted on the CPU, to debug the
script: its times mean nothing).
"""

from __future__ import annotations

import argparse
import importlib.util
import inspect
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mpi4torch_tpu.ops import kda  # noqa: E402
from mpi4torch_tpu.ops.flash import _on_tpu  # noqa: E402

# (batch, tokens, heads, head size) of one KDA layer of the cell
CELL = (2, 8192, 32, 128)
REHEARSAL = (1, 192, 2, 128)


def load_baseline(path):
    """Another ``kda.py`` as a sibling of this tree's (its relative
    imports find this tree's ``ops.flash``)."""
    spec = importlib.util.spec_from_file_location(
        "mpi4torch_tpu.ops._kda_baseline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def make_inputs(shape, seed: int, dtype=jnp.bfloat16):
    """What ``_kda_mixer`` hands the rule: unit ``q`` and ``k``, a decay
    of a few percent a token and channel, ``beta`` in (0, 1); the heads
    side by side, ``(b, s, h * d)``, as the mixer's projections leave
    them (:func:`programs` cuts them into heads inside the program, as
    the mixer does: a ``(b, s, h, d)`` operand of a program lies in
    another tiling and would be copied into this one first)."""
    b, s, h, d = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    bf, f32 = dtype, jnp.float32
    unit = lambda x: (x * jax.lax.rsqrt(
        jnp.sum(x * x, -1, keepdims=True) + 1e-6)).astype(bf)
    normal = lambda key, *sh: jax.random.normal(key, sh, f32)
    flat = lambda x: x.reshape(b, s, h * d)
    return (flat(unit(normal(ks[0], b, s, h, d))),
            flat(unit(normal(ks[1], b, s, h, d))),
            normal(ks[2], b, s, h * d).astype(bf),
            -0.1 * jax.nn.softplus(normal(ks[3], b, s, h * d)),
            jax.nn.sigmoid(normal(ks[4], b, s, h)))


def programs(mod, impl: str):
    """``(forward, value_and_grad)`` of ``mod.kda_chunked`` on operands
    with their heads side by side, jitted."""
    how = {"impl": impl} if "impl" in inspect.signature(
        mod.kda_chunked).parameters else {}
    heads = lambda x, beta: x.reshape(*beta.shape, -1)

    def fwd(q, k, v, g, beta):
        o = mod.kda_chunked(*(heads(x, beta) for x in (q, k, v, g)), beta,
                            **how)
        return o.reshape(v.shape)

    total = lambda *a: jnp.sum(fwd(*a).astype(jnp.float32))
    return jax.jit(fwd), jax.jit(jax.value_and_grad(total, argnums=range(5)))


def timed_ms(fn, args, iters: int) -> float:
    for _ in range(2):
        jax.block_until_ready(fn(*args))
    took = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        took.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(took)


def gaps(a, b) -> list[float]:
    """``|a - b| / |b|`` (norms), leaf by leaf of two trees."""
    rel = lambda x, y: float(
        np.linalg.norm(np.asarray(x, np.float64) - np.asarray(y, np.float64))
        / max(np.linalg.norm(np.asarray(y, np.float64)), 1e-30))
    return [rel(x, y) for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b),
                                      strict=True)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--impl", default="jnp,pallas")
    ap.add_argument("--baseline")
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dtype", default="bfloat16",
                    choices=("bfloat16", "float32"),
                    help="of q, k and v (the cell's: bfloat16)")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if not args.rehearse and not _on_tpu():
        print("kda_probe: no TPU here (--rehearse for the CPU)",
              file=sys.stderr)
        return 3
    shape = REHEARSAL if args.rehearse else CELL
    inputs = make_inputs(shape, args.seed, jnp.dtype(args.dtype))
    mods = [("this", kda)]
    if args.baseline:
        mods.append(("baseline", load_baseline(args.baseline)))
    device = jax.devices()[0]
    rows, kept = [], {}
    for label, mod in mods:
        takes_impl = "impl" in inspect.signature(mod.kda_chunked).parameters
        for impl in args.impl.split(",") if takes_impl else ["jnp"]:
            fwd, grad = programs(mod, impl)
            row = {"module": label, "impl": impl, "shape": list(shape),
                   "dtype": args.dtype,
                   "fwd_ms": timed_ms(fwd, inputs, args.iters),
                   "grad_ms": timed_ms(grad, inputs, args.iters),
                   "device": {"platform": device.platform,
                              "kind": device.device_kind}}
            if args.check:
                kept[label, impl] = (fwd(*inputs), grad(*inputs)[1])
                first = next(iter(kept.values()))
                row["out_gap"], = gaps(kept[label, impl][0], first[0])
                row["grad_gaps"] = dict(zip(
                    ("q", "k", "v", "g", "beta"),
                    gaps(kept[label, impl][1], first[1])))
                row["grad_gap"] = max(row["grad_gaps"].values())
            rows.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "kda_probe.jsonl"), "w") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
