"""Chip probe: the paged decode reads timed alone, a grid step at a time.

No benchmark cell: a script that answers, on one TPU chip, what one call
of each paged read of ``ops/paged_attention.py`` costs at the shapes the
serving cells send, and how that cost splits between the grid's steps
and the pages' bytes.  Each shape runs under three tables: ``dead``
(every slot free: the grid's steps and nothing else), ``live`` (every
page of every row attended) and ``cell`` (frontiers spread as the
cell's traffic spreads them).  ``--baseline PATH`` loads another
``paged_attention.py`` (the parent commit's) and times it beside this
one and says how far apart their results lie; ``--sweep`` also forces
1, 2, 4, 8 and 16 pages a grid step where they divide the table.

Every program runs a few times under one profiler trace and is read by
the kernels' own device events (``KERNEL_NAMES``), so the layout changes
around a kernel are not in its time.

    python examples/paged_read_probe.py --baseline old/paged_attention.py

One JSON line per (shape, table, implementation), everything also in
``chiprun_out/paged_read_probe.jsonl``.  Exits non-zero off the TPU
(``--rehearse`` runs tiny shapes interpreted on the CPU, to debug the
script: its times mean nothing).
"""

from __future__ import annotations

import argparse
import functools
import glob
import importlib.util
import json
import os
import shutil
import statistics
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mpi4torch_tpu.ops import paged_attention as pa  # noqa: E402

BS = 128
# name: (read, slots, heads, kv heads, width, value width, n_blk,
#        the cell's traffic: ((slots, prompt tokens), ...), answer budget)
SHAPES = {
    # openpangu-ultra-moe-718b.serve_latent_4k: five calls a step
    "latent_4k": ("latent", 32, 128, 1, 640, 512, 64,
                  ((6, 1024), (16, 2048), (10, 4096)), 512),
    # longcat-flash-chat.serve_scmoe_1k: eight calls a step
    "scmoe_1k": ("latent", 32, 64, 1, 640, 512, 32,
                 ((8, 512), (20, 1024), (4, 2048)), 1024),
    # internlm2-1.8b.serve_chat: 24 calls a step
    "chat": ("kv", 16, 16, 8, 128, 128, 20,
             ((4, 256), (8, 1024), (4, 2048)), 192),
    # glm-5.2.serve_dsa_16k: the scoring, and the read of the gathered
    # rows (four chunks of 512 rows a slot, all named)
    "dsa_index": ("index", 16, 32, 1, 128, 0, 136,
                  ((4, 4096), (8, 8192), (4, 16384)), 768),
    "dsa_sparse": ("sparse", 16, 64, 1, 640, 512, 4, ((16, 2047),), 1),
}
REHEARSAL = {
    "tiny_latent": ("latent", 3, 4, 1, 256, 128, 8, ((1, 20), (2, 70)), 40),
    "tiny_kv": ("kv", 3, 4, 2, 128, 128, 4, ((3, 17),), 30),
}


def load_baseline(path):
    """Another ``paged_attention.py`` as a sibling of this tree's (its
    relative imports find this tree's ``ops.flash`` and ``ops.ragged``)."""
    spec = importlib.util.spec_from_file_location(
        "mpi4torch_tpu.ops._paged_attention_baseline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def frontiers(fill: str, slots: int, n_blk: int, bs: int, traffic,
              budget: int):
    """A position a slot: none, every row's last, or as the cell's
    closed loop leaves them: each class of prompts with its slots spread
    evenly over an answer's progress."""
    if fill == "dead":
        return np.full(slots, -1, np.int32)
    if fill == "live":
        return np.full(slots, n_blk * bs - 1, np.int32)
    pos = np.concatenate([prompt + np.linspace(0, budget, n + 2)[1:-1]
                          for n, prompt in traffic]).astype(np.int32)
    return np.minimum(pos, n_blk * bs - 1)


def make_call(mod, read: str, shape, bs: int, dtype, seed: int):
    """``(fn, fixed)``: ``fn(*fixed, table, pos)`` is one call of the
    read (the pools go in as arguments, not as constants)."""
    _, slots, heads, kvh, w, vw, n_blk = shape[:7]
    nb = slots * n_blk
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    mk = lambda k, s: jax.random.normal(k, s, jnp.float32).astype(dtype)
    q = mk(keys[0], (slots, heads, w))
    pool = mk(keys[1], (nb, bs, kvh, w))
    interpret = not pa._on_tpu()
    if read == "kv":
        return (lambda q, pk, pv, table, pos: mod._pallas_paged(
            q, pk, pv, table, pos, 0, interpret),
            (q, pool, mk(keys[2], (nb, bs, kvh, w))))
    if read == "index":
        wts = jax.random.uniform(keys[2], (slots, heads), jnp.float32)
        return (lambda q, wts, pk, table, pos: mod._pallas_index(
            q, wts, pk, table, pos, interpret), (q, wts, pool))
    name = pa.KERNEL_NAMES[3 if read == "sparse" else 1]
    return (lambda q, pc, table, pos: mod._pallas_latent(
        q, pc, table, pos, vw, w ** -0.5, interpret, name=name), (q, pool))


def kernel_durations(trace_dir):
    """[(kernel name, duration_s), ...] in start order, of the first
    chip's ``XLA Ops`` events of the newest trace under ``trace_dir``."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return []
    planes = [p for p in jax.profiler.ProfileData.from_file(paths[-1]).planes
              if p.name.startswith("/device:TPU:")]
    if not planes:
        return []
    found = []
    for line in min(planes, key=lambda p: p.name).lines:
        if line.name != "XLA Ops":
            continue
        for e in line.events:
            if any(name in e.name for name in pa.KERNEL_NAMES):
                found.append((e.start_ns, e.duration_ns / 1e9))
    return [d for _, d in sorted(found)]


def probe_shape(name, shape, bs, dtype, iters, baseline, sweep, seed):
    read, slots, n_blk, traffic, budget = (
        shape[0], shape[1], shape[6], shape[7], shape[8])
    if read == "sparse":
        bs *= 4         # the gathered rows go over in chunks of 512
    rng = np.random.default_rng(seed)
    table = rng.permutation(slots * n_blk).astype(np.int32).reshape(
        slots, n_blk)
    impls = [("change", pa, None)]
    if baseline is not None:
        impls.append(("baseline", baseline, None))
    if sweep:
        impls += [(f"pages_{g}", pa, g) for g in (1, 2, 4, 8, 16)
                  if n_blk % g == 0]
    configs, rows, outs = [], [], {}
    rule = pa._pages_a_step
    for label, mod, forced in impls:
        if forced is not None:
            pa._pages_a_step = lambda n_blk, page_bytes, g=forced: g
        try:
            fn, fixed = make_call(mod, read, shape, bs, dtype, seed)
            call = functools.partial(jax.jit(fn), *fixed)
            for fill in ("dead", "live", "cell"):
                pos = frontiers(fill, slots, n_blk, bs, traffic, budget)
                outs[label, fill] = np.asarray(
                    jax.block_until_ready(call(table, pos)), np.float32)
                configs.append((label, fill, call, pos))
        except Exception as e:              # a group Mosaic refuses
            rows.append(dict(shape=name, impl=label,
                             error=str(e).splitlines()[0][:300]))
            print(json.dumps(rows[-1]), flush=True)
        finally:
            pa._pages_a_step = rule
    on_chip = pa._on_tpu()
    if on_chip:
        trace_dir = tempfile.mkdtemp(prefix="paged_probe_")
        jax.profiler.start_trace(trace_dir)
    for _, _, call, pos in configs:
        jax.block_until_ready([call(table, pos) for _ in range(iters)])
    events = []
    if on_chip:
        jax.profiler.stop_trace()
        events = kernel_durations(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    aligned = len(events) == iters * len(configs)
    for n, (label, fill, _, pos) in enumerate(configs):
        live = int(np.sum(np.where(pos >= 0, pos // bs + 1, 0)))
        row = dict(shape=name, impl=label, table=fill, slots=slots,
                   n_blk=n_blk, live_pages=live,
                   same_bits_as_change=bool(np.array_equal(
                       outs[label, fill], outs["change", fill])),
                   gap_to_change=float(np.max(np.abs(
                       outs[label, fill] - outs["change", fill]))))
        if aligned:
            row["call_us"] = round(statistics.median(
                events[n * iters:(n + 1) * iters]) * 1e6, 2)
        rows.append(row)
        print(json.dumps(row), flush=True)
    if on_chip and not aligned:
        print(json.dumps(dict(
            shape=name, warning="trace events do not match the calls",
            found=len(events), expected=iters * len(configs))), flush=True)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--shapes", default="")
    ap.add_argument("--baseline")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if not args.rehearse and not pa._on_tpu():
        print("paged_read_probe: no TPU here (--rehearse for the CPU)",
              file=sys.stderr)
        return 3
    shapes = REHEARSAL if args.rehearse else SHAPES
    if args.shapes:
        shapes = {k: shapes[k] for k in args.shapes.split(",")}
    bs, dtype = (16, jnp.bfloat16) if args.rehearse else (BS, jnp.bfloat16)
    baseline = load_baseline(args.baseline) if args.baseline else None
    rows = []
    for name, shape in shapes.items():
        rows += probe_shape(name, shape, bs, dtype, args.iters, baseline,
                            args.sweep, args.seed)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "paged_read_probe.jsonl"),
              "w") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
