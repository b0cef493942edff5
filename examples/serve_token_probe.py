"""Chip probe: every token a serving cell's engine emits, tree against tree.

No benchmark cell: the check a change to the decode step owes before it
is believed.  The logits table is bfloat16 and its best two candidates
tie every few dozen tokens, so what XLA fuses into the unembedding
product, or a slot state gone stale, moves tokens that
``served_logit_gap`` cannot see (it samples requests that finished in
the window, and a faster program finishes others).  The cells' loops are
closed and count steps, not seconds, so two trees at one seed are asked
for the same steps.

    python examples/serve_token_probe.py --root <checkout> --workload <cell> \\
        --seed N --steps 600 --out chiprun_out/probe.<side>.json
    python examples/serve_token_probe.py --compare A.json B.json

One process a tree (a chip belongs to one process): unpack the parent
with ``git archive`` into a git-ignored directory of the repository and
give it as ``--root``.  ``--compare`` prints one JSON line and exits
non-zero unless every request's tokens are equal.  ``--rehearse`` runs
the cell's rehearsal sizes on the CPU, to debug the script.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time


def compare(path_a: str, path_b: str) -> int:
    with open(path_a) as f:
        a = json.load(f)
    with open(path_b) as f:
        b = json.load(f)
    for key in ("workload", "seed", "steps"):
        if a[key] != b[key]:
            raise SystemExit(f"probe: the files differ in {key}: "
                             f"{a[key]!r} against {b[key]!r}")
    rids = sorted(set(a["served"]) | set(b["served"]), key=int)
    first = None
    for r in rids:
        x, y = a["served"].get(r, []), b["served"].get(r, [])
        if x != y:
            at = next((i for i, (p, q) in enumerate(zip(x, y)) if p != q),
                      min(len(x), len(y)))
            first = {"rid": r, "at": at, "tokens": [len(x), len(y)]}
            break
    equal = sum(a["served"].get(r) == b["served"].get(r) for r in rids)
    print(json.dumps({
        "workload": a["workload"], "seed": a["seed"], "steps": a["steps"],
        "requests": len(rids), "requests_equal": equal,
        "tokens": sum(len(a["served"].get(r, ())) for r in rids),
        "first_difference": first,
        "seconds": [a["seconds"], b["seconds"]],
        "decode_uploads": [a["decode_uploads"], b["decode_uploads"]],
        "decode_only_steps_without_upload": [a["quiet"], b["quiet"]]}))
    return 0 if equal == len(rids) else 1


def probe(args) -> int:
    root, out = os.path.abspath(args.root), os.path.abspath(args.out)
    sys.path.insert(0, root)
    os.chdir(root)
    run = importlib.import_module("benchmarks.run")
    _, cell, cfg, traffic, limits = run.load_cell(args.workload,
                                                  args.rehearse)
    devices = run.find_devices(cell, args.rehearse)
    if devices is None:
        return 3
    from benchmarks import common
    ctx = common.Context(
        root=root, cell=cell, cfg=cfg, traffic=traffic, limits=limits,
        peaks={}, seed=args.seed, seconds=1.0, trace=False,
        rehearse=args.rehearse, t_start=time.perf_counter(),
        devices=devices, broken="")
    kind = importlib.import_module(
        f"benchmarks.traffic_kinds.{traffic['kind']}")
    if hasattr(kind, "build"):
        eng = kind.build(ctx, kind.load_family(cfg))
    else:
        import jax.numpy as jnp

        from benchmarks import program, weights
        params = weights.make_params(cfg, ctx.seed, jnp.dtype(cfg["dtype"]))
        eng = program.build_engine(program.transformer_config(cfg), params,
                                   traffic["engine"], len(devices))
        del params
    loop = kind.Loop(eng, traffic, cfg["vocab_size"], ctx.seed)
    for client in loop.start_order:
        loop.submit(client)
        for _ in range(int(traffic["stagger_steps"])):
            loop.step()
    t0 = time.perf_counter()
    for _ in range(args.steps):
        loop.step()
    took = time.perf_counter() - t0
    from mpi4torch_tpu.utils import profiling
    log = [r for r in profiling.serve_step_log() if r["active"]]
    result = {
        "workload": args.workload, "seed": args.seed, "steps": args.steps,
        "root": root, "seconds": took,
        "device": devices[0].device_kind,
        "served": {str(r): [int(t) for t in toks]
                   for r, toks in loop.served.items()},
        # a tree from before the counter logs none
        "decode_uploads": sum(r.get("decode_uploads", 0) for r in log),
        "quiet": sum(1 for r in log if not r["admitted"]
                     and r.get("decode_uploads") == 0)}
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(result, f)
    print(json.dumps({k: v for k, v in result.items() if k != "served"}
                     | {"tokens": sum(map(len, result["served"].values()))}))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--root", default=".")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=600)
    ap.add_argument("--out")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--compare", nargs=2, metavar=("A", "B"))
    args = ap.parse_args(argv)
    if args.compare:
        return compare(*args.compare)
    if not (args.workload and args.out):
        ap.error("--workload and --out, or --compare A B")
    return probe(args)


if __name__ == "__main__":
    sys.exit(main())
