"""Chip probe: the three flash kernels timed alone, tile by tile.

No benchmark cell: a script that answers, on one TPU chip, which tiles
each of the three Mosaic kernels of ``ops/flash.py`` runs fastest at for
the shapes the benchmark's cells send (and at 1 x 16,384 tokens under a
4,096 window, the shape of a cell that does not exist yet), and whether
``flash.tile_plan``'s choice is slower anywhere than the 128 x 128 tiles
every call had before it.

For each shape it compiles one program per kernel and tile pair, runs
each a few times under one profiler trace and reads the kernels' own
device events (``flash.KERNEL_NAMES``), so the layout changes around a
kernel are not in its time.  ``--baseline PATH`` loads another
``flash.py`` (the parent commit's) and times its launches beside them.
With ``--check`` the plan's outputs are compared with ``impl="jnp"`` on a
few heads first.

    python examples/flash_tile_probe.py                       # every shape, plan + floor
    python examples/flash_tile_probe.py --sweep --shapes train_4k
    python examples/flash_tile_probe.py --baseline old/flash.py

One JSON line per (shape, kernel, tiles) on standard output, the table
again at the end, everything also in ``chiprun_out/flash_tile_probe.jsonl``.
Exits non-zero off the TPU (``--rehearse`` runs tiny shapes interpreted
on the CPU, to debug the script: its times mean nothing).
"""

from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mpi4torch_tpu.ops import flash  # noqa: E402

# name: (batch, queries, keys, q heads, kv heads, head_dim, window,
#        q_offset, kv_offset, backward too)
SHAPES = {
    # mistral-7b-v0.1.train_1chip / train_dp4, per chip
    "train_4k": (2, 4096, 4096, 32, 8, 128, 4096, 0, 0, True),
    # mistral-7b-v0.1.train_long (no such cell yet): window < sequence
    "train_16k_w4k": (1, 16384, 16384, 32, 8, 128, 4096, 0, 0, True),
    # internlm2-1.8b.serve_chat's prefills
    "prefill_256": (1, 256, 256, 16, 8, 128, 0, 0, 0, False),
    "prefill_1k": (1, 1024, 1024, 16, 8, 128, 0, 0, 0, False),
    "prefill_2k": (1, 2048, 2048, 16, 8, 128, 0, 0, 0, False),
    # kimi-linear-48b-a3b.train_kda_8k: MLA's 2,048 blocks at 192, on
    # the diagonal and under it
    "mla32_diag": (2, 2048, 2048, 32, 32, 192, 0, 2048, 2048, True),
    "mla32_full": (2, 2048, 2048, 32, 32, 192, 0, 4096, 2048, True),
    # the same attention as one call (the parent's kernels cannot stage
    # it; the model still cuts it into the blocks above)
    "mla32_8k": (2, 8192, 8192, 32, 32, 192, 0, 0, 0, True),
    # openpangu-ultra-moe-718b.serve_latent_4k's prefill blocks
    "mla128_diag": (1, 2048, 2048, 128, 128, 192, 0, 2048, 2048, False),
    "mla128_full": (1, 2048, 2048, 128, 128, 192, 0, 2048, 0, False),
}
REHEARSAL = {
    "tiny": (1, 512, 512, 4, 2, 64, 300, 0, 0, True),
    "tiny_off": (1, 256, 512, 2, 2, 128, 0, 256, 0, True),
}
FLOOR = (128, 128)


def load_baseline(path):
    spec = importlib.util.spec_from_file_location("flash_baseline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def make_inputs(shape, dtype, seed):
    b, sq, sk, h, hkv, d = shape[:6]
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(keys[0], (b, sq, h, d), jnp.float32).astype(dtype)
    k = jax.random.normal(keys[1], (b, sk, hkv, d), jnp.float32).astype(dtype)
    v = jax.random.normal(keys[2], (b, sk, hkv, d), jnp.float32).astype(dtype)
    do = jax.random.normal(keys[3], (b, sq, h, d), jnp.float32).astype(dtype)
    return q, k, v, do


def kernel_durations(trace_dir):
    """{kernel name: [duration_s, ...] in start order} of the first
    chip's ``XLA Ops`` events of the newest trace under ``trace_dir``."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return {}
    found = {name: [] for name in flash.KERNEL_NAMES}
    planes = [p for p in jax.profiler.ProfileData.from_file(paths[-1]).planes
              if p.name.startswith("/device:TPU:")]
    if not planes:
        return {}
    plane = min(planes, key=lambda p: p.name)
    for line in plane.lines:
        if line.name != "XLA Ops":
            continue
        for e in line.events:
            for name in flash.KERNEL_NAMES:
                if name in e.name:
                    found[name].append((e.start_ns, e.duration_ns / 1e9))
    return {name: [d for _, d in sorted(ev)] for name, ev in found.items()}


def probe_shape(name, shape, dtype, pairs, iters, baseline, interpret,
                check, seed):
    b, sq, sk, h, hkv, d, window, q_off, kv_off, with_bwd = shape
    causal = True
    q, k, v, do = make_inputs(shape, dtype, seed)
    qo, ko = jnp.int32(q_off), jnp.int32(kv_off)
    plan = flash.tile_plan(sq, sk, d, dtype, causal, window)
    out, lse = jax.jit(lambda *a: flash._pallas_block(
        *a, causal, interpret, window))(q, k, v, qo, ko)
    dd = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    rows = []

    if check:
        # A few heads against the oracle (its score matrix is in HBM).
        # One KV head and its group of q heads; one q head where the
        # oracle's score matrix would not fit beside the inputs.
        nq = h // hkv if sq * sk <= 2 ** 25 else 1
        qs, ks, vs, dos = q[:, :, :nq], k[:, :, :1], v[:, :, :1], \
            do[:, :, :nq]

        def loss(impl):
            def f(q, k, v):
                o, l_ = flash.flash_block_attention(
                    q, k, v, causal=True, q_offset=qo, kv_offset=ko,
                    window=window, impl=impl)
                return jnp.sum(o.astype(jnp.float32)
                               * dos.astype(jnp.float32)) \
                    + jnp.sum(jnp.where(l_ > flash.NEG_BIG / 2,
                                        jnp.sin(l_), 0.0)), (o, l_)
            return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                              has_aux=True))
        (_, (o_k, l_k)), g_k = loss("pallas" if interpret else "auto")(
            qs, ks, vs)
        (_, (o_j, l_j)), g_j = loss("jnp")(qs, ks, vs)
        err = lambda a, c: float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - c.astype(jnp.float32))))
        rows.append(dict(
            shape=name, check="plan against impl=jnp, max abs",
            out=err(o_k, o_j), lse=err(l_k, l_j), dq=err(g_k[0], g_j[0]),
            dk=err(g_k[1], g_j[1]), dv=err(g_k[2], g_j[2]),
            grad_scale=float(jnp.max(jnp.abs(g_j[0].astype(jnp.float32))))))
        print(json.dumps(rows[-1]), flush=True)

    # One program per configuration: (label, tiles, fn, args, kernels).
    configs = []

    def add(label, tiles, fn, args, kernels):
        try:
            jitted = jax.jit(fn)
            jax.block_until_ready(jitted(*args))        # compile + warm
            configs.append((label, tiles, jitted, args, kernels))
        except Exception as e:                 # a tile pair Mosaic refuses
            rows.append(dict(shape=name, label=label, tiles=tiles,
                             error=str(e).splitlines()[0][:300]))
            print(json.dumps(rows[-1]), flush=True)

    fwd_args, bwd_args = (q, k, v, qo, ko), (q, k, v, do, lse, dd, qo, ko)
    todo = {"fwd": [("plan", plan.fwd[:2])], "dq": [("plan", plan.dq[:2])],
            "dkv": [("plan", plan.dkv[:2])]}
    for kern in todo:
        todo[kern] += [("tiles", p) for p in pairs
                       if sq % p[0] == 0 and sk % p[1] == 0]
    for label, (qt, kt) in todo["fwd"]:
        t = flash.kernel_tiles("fwd", qt, kt, sq, sk, d, dtype)
        add(label, (qt, kt), lambda *a, t=t: flash._pallas_block(
            *a, causal, interpret, window, tiles=t), fwd_args,
            flash.KERNEL_NAMES[:1])
    if with_bwd:
        # dq and dkv share a launch function: sweep both at the same
        # pair, and read each kernel's own events.
        both = [("plan", None)] + todo["dq"][1:]
        for label, pair in both:
            if label == "plan":
                t_dq, t_dkv = plan.dq, plan.dkv
                shown = (plan.dq[:2], plan.dkv[:2])
            else:
                qt, kt = pair
                t_dq = flash.kernel_tiles("dq", qt, kt, sq, sk, d, dtype)
                t_dkv = flash.kernel_tiles("dkv", qt, kt, sq, sk, d, dtype)
                shown = (qt, kt)
            add(label, shown, lambda *a, t1=t_dq, t2=t_dkv:
                flash._pallas_bwd(*a, causal, interpret, window,
                                  tiles_dq=t1, tiles_dkv=t2),
                bwd_args, flash.KERNEL_NAMES[1:])
    if baseline is not None:
        add("baseline", FLOOR, lambda *a: baseline._pallas_block(
            *a, causal, interpret, window), fwd_args,
            flash.KERNEL_NAMES[:1])
        if with_bwd:
            add("baseline", FLOOR, lambda *a: baseline._pallas_bwd(
                *a, causal, interpret, window), bwd_args,
                flash.KERNEL_NAMES[1:])

    if not interpret:
        trace_dir = tempfile.mkdtemp(prefix="flash_probe_")
        jax.profiler.start_trace(trace_dir)
    walls = []
    for _, _, jitted, args, _ in configs:
        t0 = time.perf_counter()
        outs = [jitted(*args) for _ in range(iters)]
        jax.block_until_ready(outs)
        walls.append((time.perf_counter() - t0) / iters)
        del outs
    events = {}
    if not interpret:
        jax.profiler.stop_trace()
        events = kernel_durations(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    expected = {n: sum(iters for c in configs if n in c[4])
                for n in flash.KERNEL_NAMES}
    aligned = all(len(events.get(n, [])) == expected[n]
                  for n in flash.KERNEL_NAMES)
    cursor = {n: 0 for n in flash.KERNEL_NAMES}
    for (label, tiles, _, _, kernels), wall in zip(configs, walls):
        row = dict(shape=name, label=label, tiles=tiles,
                   wall_ms=round(wall * 1e3, 4))
        for n in kernels:
            if aligned:
                mine = events[n][cursor[n]:cursor[n] + iters]
                cursor[n] += iters
                row[n + "_ms"] = round(statistics.median(mine) * 1e3, 4)
        rows.append(row)
        print(json.dumps(row), flush=True)
    if not aligned and not interpret:
        print(json.dumps(dict(
            shape=name, warning="trace events do not match the calls",
            found={n: len(v) for n, v in events.items()},
            expected=expected)), flush=True)
    # The plan's static side: tiles, bytes, masked share at this call.
    share = {
        "fwd": flash.masked_tile_share(sq, sk, plan.fwd, causal, window,
                                       q_off, kv_off),
        "dq": flash.masked_tile_share(sq, sk, plan.dq, causal, window,
                                      q_off, kv_off),
        "dkv": flash.masked_tile_share(sq, sk, plan.dkv, causal, window,
                                       q_off, kv_off, over="q")}
    rows.append(dict(shape=name, plan={k_: tuple(v_) for k_, v_ in
                                       plan._asdict().items()},
                     visited_masked=share))
    print(json.dumps(rows[-1]), flush=True)
    return rows


def table(rows):
    """Per shape and kernel: baseline, floor tiles, plan, best of sweep."""
    lines = ["shape kernel baseline_ms floor_ms plan_ms plan_tiles "
             "best_ms best_tiles"]
    for shape in dict.fromkeys(r["shape"] for r in rows):
        mine = [r for r in rows if r["shape"] == shape and "label" in r
                and "error" not in r]
        for n in flash.KERNEL_NAMES:
            key = n + "_ms"
            have = [r for r in mine if key in r]
            if not have:
                continue
            pick = lambda lab, t=None: next(
                (r for r in have if r["label"] == lab
                 and (t is None or tuple(r["tiles"]) == t)), None)
            base, floor, plan = pick("baseline"), pick("tiles", FLOOR), \
                pick("plan")
            best = min((r for r in have if r["label"] != "baseline"),
                       key=lambda r: r[key])
            f = lambda r: "-" if r is None else f"{r[key]:.3f}"
            lines.append(" ".join([
                shape, n, f(base), f(floor), f(plan),
                str(plan["tiles"]).replace(" ", "") if plan else "-",
                f(best), str(best["tiles"]).replace(" ", "")]))
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="all")
    ap.add_argument("--sweep", action="store_true",
                    help="every pair of 128..1024, not only floor + plan")
    ap.add_argument("--baseline", default=None,
                    help="another flash.py to time beside this one")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--seed", type=int, default=33)
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--dtype", default="bfloat16",
                    choices=("bfloat16", "float32"))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        print(json.dumps({"ok": False, "error": "not on a TPU",
                          "platform": device.platform}))
        return 1
    shapes = REHEARSAL if args.rehearse else SHAPES
    names = list(shapes) if args.shapes == "all" else args.shapes.split(",")
    sizes = (128, 256, 512, 1024)
    pairs = [(a, c) for a in sizes for c in sizes] if args.sweep \
        else [FLOOR]
    baseline = load_baseline(args.baseline) if args.baseline else None
    dtype = jnp.float32 if args.rehearse else jnp.dtype(args.dtype)
    rows = []
    for name in names:
        rows += probe_shape(name, shapes[name], dtype, pairs, args.iters,
                            baseline, args.rehearse, args.check, args.seed)
    text = table(rows)
    print(text, flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "flash_tile_probe.jsonl"),
              "a") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
        f.write(json.dumps({"table": text.splitlines()}) + "\n")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
