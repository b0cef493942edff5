"""Headline benchmark: Allreduce fwd+bwd bandwidth + single-chip MFU.

Three measurements, all jitted XLA programs, printed as ONE JSON line on
stdout (progress/partial lines go to stderr):

1. **Allreduce forward+backward effective bandwidth** (the BASELINE.md
   primary metric).  On N>1 devices this uses ring-allreduce
   bytes-on-wire accounting ``2*(N-1)/N * size``; on a single chip there
   is no interconnect, so the number is the HBM-limited throughput of
   the same program (honestly labeled, with the roofline fraction).
2. **Flash-attention fwd+bwd MFU** — the Pallas kernel
   (mpi4torch_tpu/ops/flash.py) on a chip-sized causal shape; achieved
   FLOP/s vs the chip's peak.  Chip-meaningful even on one device.
3. **Flagship-transformer train-step MFU** — forward + backward + SGD
   update of the decoder-only transformer
   (mpi4torch_tpu/models/transformer.py) using the standard
   ``6 * n_params * n_tokens`` dense-FLOPs accounting plus the causal
   attention term.

Failure contract: the process initialises JAX once, in-process.  Unless
``JAX_PLATFORMS=cpu`` was asked for in so many words (the CPU smoke of
the harness itself, labelled ``"cpu_requested": true``), a platform other
than ``tpu`` exits non-zero before anything is timed, and a
``device_kind`` missing from the peaks table is an error, not a default.
Every sub-bench still runs inside its own guard, so one failing stanza
records an ``{"error": ...}`` entry and the later stanzas still run; the
final JSON is printed either way and the exit code is non-zero when any
stanza recorded an error.

Timing methodology: each timed iteration ends with a 1-element
device->host fetch derived from every output leaf (see ``_force``), a
completion barrier at least as strong as ``block_until_ready``.  The
JSON carries ``timing_floor_s`` (the fetch round-trip on a ready buffer)
and the HBM-roofline fraction so both sanity checks are visible.

Baseline: the reference publishes no numbers (BASELINE.md); the working
target for the headline metric is 80% of ~45 GB/s/link v5e ICI
≈ 36 GB/s/chip, so ``vs_baseline = value / 36.0``.
"""

from __future__ import annotations

import functools
import json
import os
import subprocess
import sys
import time
import traceback

# Per-chip bf16 peak FLOP/s and HBM bandwidth (GB/s) by PJRT device_kind
# substring (Google Cloud TPU documentation, per-generation pages).  A
# device that is not in the table is an error: peaks are never assumed.
_CHIP_TABLE = [
    # (substring, peak bf16 FLOP/s, HBM GB/s)
    ("v6", 918e12, 1640.0),   # Trillium
    ("v5p", 459e12, 2765.0),
    ("v5", 197e12, 819.0),    # v5e / "TPU v5 lite"
    ("v4", 275e12, 1228.0),
    ("v3", 123e12, 900.0),
]


def _chip_specs(device_kind: str):
    kind = device_kind.lower()
    for sub, peak, hbm in _CHIP_TABLE:
        if sub in kind:
            return peak, hbm
    raise ValueError(
        f"device_kind {device_kind!r} is not in bench.py's peaks table "
        f"({[sub for sub, _, _ in _CHIP_TABLE]}); add its published "
        "peaks rather than borrowing another chip's")


def _force(out):
    """Host round-trip on ONE element of the result: the completion
    barrier every timed iteration ends with.  Fetching a single element
    of an output buffer to the host requires the producing execution to
    have finished; the fetch's own cost is reported as
    ``timing_floor_s`` in the final JSON."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    leaves = jax.tree.leaves(out)
    # The transferred scalar depends on EVERY output leaf (a runtime
    # tracking per-buffer readiness could otherwise service the fetch
    # from the ready subset — e.g. a value_and_grad loss buffer exists
    # after the forward alone) and, per leaf, on its full leading axis
    # (run_spmd outputs lead with the rank axis; a [0,...,0] element
    # could be served from device 0's shard while other devices still
    # execute).  Each leaf contributes a [:, 0, ..., 0] column sum —
    # reads at most leading-dim elements, never the buffer (jnp.ravel
    # would dispatch a full-buffer COPY, the same order of HBM traffic
    # as the steps being measured).  The whole probe is ONE cached
    # jitted executable so a timed iteration pays one dispatch + one
    # 4-byte fetch regardless of leaf count.
    global _PROBE
    if _PROBE is None:
        def probe(ls):
            tot = jnp.zeros((), jnp.float32)
            for leaf in ls:
                col = (leaf if leaf.ndim == 0
                       else leaf[(slice(None),) + (0,) * (leaf.ndim - 1)])
                tot = tot + jnp.sum(col.astype(jnp.float32))
            return tot
        _PROBE = jax.jit(probe)
    # jit's dispatch cache keys on the leaves' structure/avals itself —
    # each distinct output shape compiles once (at warmup) and the timed
    # iterations pay one cached dispatch.
    return np.asarray(_PROBE(leaves))


_PROBE = None


def _timeit(fn, *args, iters: int):
    """Median seconds/step, each iteration closed by a device->host fetch
    of one result element (see _force)."""
    _force(fn(*args))     # compile + warmup
    _force(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        _force(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def _note(msg: str) -> None:
    print(f"bench.py: {msg}", file=sys.stderr, flush=True)


def _platform_or_exit(script: str):
    """``(platform, cpu_requested)`` of the one in-process JAX backend;
    exits non-zero unless it is the TPU or ``JAX_PLATFORMS=cpu`` was
    asked for in so many words.  Shared with bench_tradeoffs.py."""
    import jax

    platform = jax.devices()[0].platform
    cpu_requested = os.environ.get("JAX_PLATFORMS", "").strip() == "cpu"
    if platform != "tpu" and not cpu_requested:
        raise SystemExit(
            f"{script}: platform is {platform!r}, not 'tpu', and "
            "JAX_PLATFORMS=cpu was not requested — refusing to time "
            "another backend under the device metric names")
    return platform, cpu_requested


def _errors(node, path="result"):
    """Paths of every ``{"error": ...}`` entry in the result tree."""
    found = []
    if isinstance(node, dict):
        if "error" in node:
            found.append(path)
        for k, v in node.items():
            found.extend(_errors(v, f"{path}.{k}"))
    elif isinstance(node, (list, tuple)):
        for i, v in enumerate(node):
            found.extend(_errors(v, f"{path}[{i}]"))
    return found


def _exit_on_errors(result) -> None:
    failed = _errors(result)
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr, flush=True)
        sys.exit(1)


def _bench_allreduce(on_tpu: bool, hbm_gbps: float):
    import jax
    import jax.numpy as jnp

    import mpi4torch_tpu as mpi

    n = len(jax.devices())
    # 256 MiB/chip on TPU (1B params would OOM nothing but adds no signal
    # beyond saturation); small on the CPU smoke path.
    nelem = (1 << 26) if on_tpu else (1 << 18)
    bytes_per_pass = nelem * 4

    comm = mpi.COMM_WORLD

    def loss(x):
        y = comm.Allreduce(x, mpi.MPI_SUM)
        return jnp.vdot(y, y)

    step = mpi.run_spmd(lambda x: jax.value_and_grad(loss)(x), nranks=n)
    x = jnp.ones((nelem,), jnp.float32)
    dt = _timeit(step, x, iters=20 if on_tpu else 3)

    if n > 1:
        wire = 2.0 * (n - 1) / n * bytes_per_pass
    else:
        wire = float(bytes_per_pass)
    gbps = 2.0 * wire / dt / 1e9       # fwd psum + adjoint psum per step
    # Single chip: the same accounting (2 x tensor bytes / step) is the
    # program's minimum HBM traffic (read x + write grad), so gbps/HBM-peak
    # is a true roofline fraction — >1.0 would mean the measurement is
    # broken, which is exactly what round 3 shipped.
    roofline = gbps / hbm_gbps if (n == 1 and hbm_gbps) else None
    return {
        "gbps": round(gbps, 3),
        "n_devices": n,
        "tensor_mib": bytes_per_pass / (1 << 20),
        "seconds_per_step": dt,
        "hbm_roofline_fraction": (round(roofline, 4)
                                  if roofline is not None else None),
        "suspect": bool(roofline is not None and roofline > 1.0),
    }


def _bench_allreduce_compressed(on_tpu: bool):
    """Compressed Allreduce (mpi4torch_tpu.compress) vs the fp32 exact
    path at the same shape: bytes-on-wire per codec (measured from the
    real encoded buffers — the CPU harness's ground truth) and wall-clock
    per step (chip-meaningful when ICI is in the path; on one device the
    quantize/dequantize compute rides HBM only, so wall-clock there
    mostly prices the codec arithmetic).  The ISSUE 1 acceptance bar:
    q8's wire reduction vs fp32 must be >= 3.5x."""
    import jax
    import jax.numpy as jnp

    import mpi4torch_tpu as mpi
    from mpi4torch_tpu.compress import get_codec

    n = len(jax.devices())
    nelem = (1 << 24) if on_tpu else (1 << 18)
    fp32_bytes = nelem * 4
    comm = mpi.COMM_WORLD
    iters = 20 if on_tpu else 3

    def step_fn(compression):
        def loss(x):
            y = comm.Allreduce(x, mpi.MPI_SUM, compression=compression)
            return jnp.vdot(y, y)

        return mpi.run_spmd(lambda x: jax.value_and_grad(loss)(x), nranks=n)

    x = jnp.ones((nelem,), jnp.float32)
    dt_fp32 = _timeit(step_fn(False), x, iters=iters)

    out = {
        "n_devices": n,
        "tensor_mib": fp32_bytes / (1 << 20),
        "fp32_seconds_per_step": dt_fp32,
        "codecs": {},
    }
    for name in ("q8", "q8_ef", "bf16"):
        def _one(name=name):
            codec = get_codec(name)
            enc_bytes = codec.wire_bytes((nelem,), jnp.float32)
            dt = _timeit(step_fn(name), x, iters=iters)
            return {
                "encoded_bytes": enc_bytes,
                "wire_reduction_vs_fp32": round(fp32_bytes / enc_bytes, 3),
                "seconds_per_step": dt,
                "step_speedup_vs_fp32": round(dt_fp32 / dt, 4),
            }

        out["codecs"][name] = _guarded(f"allreduce_compressed.{name}", _one)

    q8 = out["codecs"].get("q8", {})
    out["q8_wire_reduction_target_met"] = bool(
        q8.get("wire_reduction_vs_fp32", 0.0) >= 3.5)
    return out


# The (codec × algorithm) combos of the multipath wire table: the ISSUE 6
# composition claim is read off the q8-bidir vs fp32-bidir rows; q8-ring
# is the PR 1 reference point, q8_ef_hop-bidir prices the per-hop EF
# variant's wire, q8-torus covers the striped-channel leg (skipped with a
# recorded error on worlds with no 2-level factorization).
_MULTIPATH_WIRE_TABLE = (
    ("fp32-ring", False, "ring"),
    ("fp32-bidir", False, "bidir"),
    ("q8-ring", "q8", "ring"),
    ("q8-bidir", "q8", "bidir"),
    ("q8_ef_hop-bidir", "q8_ef_hop", "bidir"),
    ("q8-torus", "q8", "torus"),
)

def _hlo_wire_bytes_per_device(txt: str):
    """Deterministic per-device bytes-on-wire of a lowered StableHLO
    program, from the collective ops' operand types under the standard
    ring accountings: a collective_permute ships its operand once; an
    all_gather over groups of size s ships the local shard (s-1) times;
    an all_reduce 2(s-1)/s of the payload; a reduce_scatter (s-1)/s;
    an all_to_all keeps 1/s local and ships the rest.
    Returns ``(total_bytes, per-op-kind breakdown)``.

    Since the static verifier landed, the parsing and the accounting
    live in :func:`mpi4torch_tpu.analyze.wire_bytes_per_device` (one
    pass over the shared StableHLO parse); this wrapper keeps the
    historical bench entry point, with the recorded wire tables
    (q8-bidir 7280 B, the (8,)->(2,4) reshard migration 98304 B, the
    serve decode step) regression-pinned bit-identical in
    tests/test_analyze.py."""
    from mpi4torch_tpu.analyze import wire_bytes_per_device

    return wire_bytes_per_device(txt)


def _multipath_wire_census(nelem: int = 1 << 12):
    """Lower every `_MULTIPATH_WIRE_TABLE` combo on the attached
    (multi-)device mesh and read the per-device wire bytes off the
    StableHLO — the deterministic half of the multipath stanza, valid on
    any platform (op counts and operand widths don't depend on where the
    program would run).  Also checks the tentpole census criterion:
    int8 collective_permutes on BOTH rotations of the q8-bidir dual
    ring."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    import mpi4torch_tpu as mpi
    from jax import shard_map

    devs = jax.devices()
    n = len(devs)
    if n < 2:
        raise RuntimeError("multipath wire census needs >= 2 devices")
    mesh = Mesh(np.asarray(devs), ("w",))
    c = mpi.comm_from_mesh(mesh, "w")
    x = jnp.ones((nelem,), jnp.float32)

    out = {"n_devices": n, "nelem": nelem,
           "fp32_payload_bytes": nelem * 4, "table": {}}
    texts = {}
    for label, codec, algo in _MULTIPATH_WIRE_TABLE:
        def _one(label=label, codec=codec, algo=algo):
            fn = shard_map(
                lambda a: c.Allreduce(a, mpi.MPI_SUM, compression=codec,
                                      algorithm=algo),
                mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
            txt = jax.jit(fn).lower(x).as_text()
            texts[label] = txt
            wire, counts = _hlo_wire_bytes_per_device(txt)
            return {"wire_bytes_per_device": wire, "collectives": counts}

        out["table"][label] = _guarded(f"multipath_census.{label}", _one)

    def wire(label):
        ent = out["table"].get(label) or {}
        return ent.get("wire_bytes_per_device")

    q8b, fpb, q8r = wire("q8-bidir"), wire("fp32-bidir"), wire("q8-ring")
    if q8b and fpb:
        out["wire_advantage_q8_bidir_vs_fp32_bidir"] = round(fpb / q8b, 3)
        out["wire_advantage_target_met"] = bool(fpb / q8b >= 3.5)
    if q8b and q8r:
        # bidir moves the same bytes as ring over 2x the links; the
        # composition win is utilization, not fewer bytes — the table
        # records that the codec leg costs no extra wire on the dual ring.
        out["q8_bidir_vs_q8_ring_wire_ratio"] = round(q8b / q8r, 3)

    if "q8-bidir" in texts:
        from mpi4torch_tpu.compress import int8_rotation_census

        perms, fwd, bwd = int8_rotation_census(texts["q8-bidir"], n)
        out["int8_permutes_on_both_rotations"] = bool(
            fwd in perms and bwd in perms)
    return out


def _multipath_wire_census_subprocess():
    """Run :func:`_multipath_wire_census` on a forced 8-virtual-device
    CPU mesh in a subprocess — the wire table for a bench world with a
    single device (where bidir/torus lower to the identity and there is
    nothing to count)."""
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    code = ("import json, bench; "
            "print(json.dumps(bench._multipath_wire_census()))")
    proc = subprocess.run(
        [sys.executable, "-c", code],
        cwd=os.path.dirname(os.path.abspath(__file__)),
        env=env, capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(
            f"multipath census subprocess failed (rc {proc.returncode}): "
            f"{proc.stderr.strip()[-300:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _bench_allreduce_compressed_multipath(on_tpu: bool):
    """Compressed allreduce ON the bandwidth tier (ISSUE 6): the
    wire-bytes × algorithm table (q8-on-ring vs q8-on-bidir vs
    fp32-on-bidir, plus the per-hop-EF and torus legs) with wall-clock
    numbers per combo alongside.

    The headline is DETERMINISTIC: per-device wire bytes are read off
    each combo's lowered StableHLO (collective operand widths × the
    standard ring accountings), so the ≥3.5x q8-bidir-vs-fp32-bidir
    verdict and the both-rotations int8 census hold identically on the
    CPU smoke sweep and on hardware.  Wall-clock seconds are
    chip-meaningful only with ICI in the path; a 1-device world runs
    the census on a forced 8-virtual-device subprocess mesh so the
    verdict is recorded either way."""
    import jax
    import jax.numpy as jnp

    import mpi4torch_tpu as mpi

    n = len(jax.devices())
    nelem = (1 << 24) if on_tpu else (1 << 18)
    comm = mpi.COMM_WORLD
    iters = 20 if on_tpu else 3

    def step_fn(compression, algorithm):
        def loss(x):
            y = comm.Allreduce(x, mpi.MPI_SUM, compression=compression,
                               algorithm=algorithm)
            return jnp.vdot(y, y)

        return mpi.run_spmd(lambda x: jax.value_and_grad(loss)(x), nranks=n)

    x = jnp.ones((nelem,), jnp.float32)
    out = {
        "n_devices": n,
        "tensor_mib": nelem * 4 / (1 << 20),
        "combos": {},
    }
    for label, codec, algo in _MULTIPATH_WIRE_TABLE:
        def _one(codec=codec, algo=algo):
            return {"seconds_per_step": _timeit(step_fn(codec, algo), x,
                                                iters=iters)}

        out["combos"][label] = _guarded(f"allreduce_multipath.{label}", _one)
    base = out["combos"].get("fp32-ring", {})
    if "seconds_per_step" in base:
        for label, ent in out["combos"].items():
            if label != "fp32-ring" and "seconds_per_step" in ent:
                ent["step_speedup_vs_fp32_ring"] = round(
                    base["seconds_per_step"] / ent["seconds_per_step"], 4)

    census = _guarded(
        "allreduce_multipath.census",
        _multipath_wire_census if n > 1 else _multipath_wire_census_subprocess)
    if "error" not in census:
        out["census_n_devices"] = census.get("n_devices")
        out["wire_table"] = census.get("table")
        for key in ("wire_advantage_q8_bidir_vs_fp32_bidir",
                    "wire_advantage_target_met",
                    "q8_bidir_vs_q8_ring_wire_ratio",
                    "int8_permutes_on_both_rotations"):
            if key in census:
                out[key] = census[key]
        out["note"] = (
            "wire bytes are deterministic (read off the lowered StableHLO"
            " per combo); wall-clock is chip-meaningful only with ICI in "
            "the path" + ("" if n > 1 else
                          " — census ran on a forced 8-virtual-device "
                          "subprocess mesh"))
    else:
        out["census_error"] = census["error"]
    return out


def _bench_guard_overhead(on_tpu: bool):
    """Integrity-guard overhead census (mpi4torch_tpu.resilience,
    ISSUE 7): a DETERMINISTIC HLO proof that the guards are free when
    off and a priced, censused addition when on.

    * ``comm_finite_guard="off"`` (default) and checksum-off lowerings
      are BIT-IDENTICAL to the pre-guard program — checked structurally
      by re-lowering the same facade call with the guard hook
      monkeypatched out entirely (the guard-less build) and comparing
      the full StableHLO text, not just op counts;
    * guard-on ("warn") records the per-collective op deltas: one
      ``is_finite`` + reduce feeding one host callback ``custom_call``;
    * ``comm_wire_checksum`` is a Mode B (rendezvous wire) leg only —
      toggling it must leave the Mode A lowering untouched, and that
      claim is censused here too."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    import mpi4torch_tpu as mpi
    from jax import shard_map
    from mpi4torch_tpu.resilience import guards as _rguards

    n = len(jax.devices())
    mesh = Mesh(np.asarray(jax.devices()), ("w",))
    cm = mpi.comm_from_mesh(mesh, "w")
    x = jnp.ones((1 << 14,), jnp.float32)

    def lowered(compression=False):
        return jax.jit(shard_map(
            lambda a: cm.Allreduce(a, mpi.MPI_SUM,
                                   compression=compression),
            mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False)).lower(x).as_text()

    def counts(text):
        return {"is_finite": text.count("stablehlo.is_finite"),
                "custom_call": text.count("stablehlo.custom_call")}

    out = {"n_devices": n, "modes": {}}
    # Guard off (the default): must match the guard-LESS build bit for
    # bit.  The bypass monkeypatch removes the hook structurally, so the
    # comparison is against a program in which the guard code never ran.
    mpi.config.set_comm_finite_guard("off")
    mpi.config.set_comm_wire_checksum(False)
    text_off = lowered()
    text_off_q8 = lowered("q8")
    hook = _rguards.spmd_finite_value
    try:
        _rguards.spmd_finite_value = lambda v, where: v
        text_bypassed = lowered()
        text_bypassed_q8 = lowered("q8")
    finally:
        _rguards.spmd_finite_value = hook
    out["guard_off_identical_to_guardless_build"] = (
        text_off == text_bypassed and text_off_q8 == text_bypassed_q8)
    out["modes"]["off"] = counts(text_off)

    # Checksum on: a Mode B wire leg — the Mode A lowering must not move.
    mpi.config.set_comm_wire_checksum(True)
    try:
        out["checksum_on_lowering_identical"] = lowered() == text_off
    finally:
        mpi.config.set_comm_wire_checksum(False)

    # Guard on: the priced deltas.
    mpi.config.set_comm_finite_guard("warn")
    try:
        text_on = lowered()
        text_on_q8 = lowered("q8")
    finally:
        mpi.config.set_comm_finite_guard("off")
    out["modes"]["warn"] = counts(text_on)
    out["guard_on_op_delta"] = {
        k: counts(text_on)[k] - counts(text_off)[k]
        for k in ("is_finite", "custom_call")}
    out["guard_on_op_delta_q8"] = {
        k: counts(text_on_q8)[k] - counts(text_off_q8)[k]
        for k in ("is_finite", "custom_call")}
    out["zero_overhead_off_path"] = bool(
        out["guard_off_identical_to_guardless_build"]
        and out["checksum_on_lowering_identical"]
        and out["modes"]["off"]["is_finite"] == 0)
    out["note"] = ("deterministic lowering census — identical on CPU "
                   "smoke and hardware; wall-clock guard cost is the "
                   "is_finite reduce + host callback and only exists "
                   "when the guard is on")
    return out


def _bench_obs_overhead(on_tpu: bool):
    """Observability-layer overhead census (mpi4torch_tpu.obs,
    ISSUE 12): the guard-overhead discipline applied to tracing.

    * obs OFF (no tracer — the default) lowers BIT-IDENTICAL to an
      obs-less build (the Mode A step-event hook monkeypatched out
      structurally), plain and q8;
    * a Mode B-only tracer must not move the Mode A lowering either
      (it keys into nothing trace-time);
    * a ``mode_a`` tracer records the priced delta: one host-callback
      ``custom_call`` per collective entry;
    * Mode B determinism: the same traced workload run twice yields
      the SAME per-rank logical event census (counts and wire bytes,
      identical across ranks and runs) — what makes reconcile() a
      contract rather than a sampled profile."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    import mpi4torch_tpu as mpi
    from mpi4torch_tpu import obs
    from jax import shard_map

    n = len(jax.devices())
    mesh = Mesh(np.asarray(jax.devices()), ("w",))
    cm = mpi.comm_from_mesh(mesh, "w")
    x = jnp.ones((1 << 14,), jnp.float32)

    def lowered(compression=False):
        return jax.jit(shard_map(
            lambda a: cm.Allreduce(a, mpi.MPI_SUM,
                                   compression=compression),
            mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False)).lower(x).as_text()

    out = {"n_devices": n}
    text_off = lowered()
    text_off_q8 = lowered("q8")
    hook = obs.tracing.spmd_collective_event
    try:
        obs.tracing.spmd_collective_event = lambda v, where: v
        out["obs_off_identical_to_obsless_build"] = (
            lowered() == text_off and lowered("q8") == text_off_q8)
    finally:
        obs.tracing.spmd_collective_event = hook
    with obs.trace():
        out["modeb_tracer_lowering_identical"] = lowered() == text_off
    with obs.trace(mode_a=True):
        out["mode_a_custom_call_delta"] = (
            lowered().count("stablehlo.custom_call")
            - text_off.count("stablehlo.custom_call"))

    # Mode B census determinism: two traced runs of one workload.
    from mpi4torch_tpu import COMM_WORLD as comm

    def body(rank):
        v = jnp.arange(512, dtype=jnp.float32) * (rank + 1)
        return comm.Allreduce(v, mpi.MPI_SUM, algorithm="ring")

    tables = []
    for _ in range(2):
        with obs.trace() as t:
            mpi.run_ranks(body, min(n, 4) if n > 1 else 2)
        mt = obs.measured_wire_table(t.events)
        tables.append({"wire_bytes": mt["wire_bytes"],
                       "counts": mt["counts"],
                       "logical_events": mt["logical_events"],
                       "per_rank_consistent":
                           mt["per_rank_consistent"]})
    out["modeb_census"] = tables[0]
    out["modeb_census_deterministic"] = bool(
        tables[0] == tables[1] and tables[0]["per_rank_consistent"])
    out["zero_overhead_off_path"] = bool(
        out["obs_off_identical_to_obsless_build"]
        and out["modeb_tracer_lowering_identical"])
    out["note"] = ("deterministic lowering + event census — identical "
                   "on CPU smoke and hardware; tracing cost exists "
                   "only while a tracer is installed (one attribute "
                   "read per chokepoint otherwise)")
    return out


def _bench_degraded_mode(on_tpu: bool):
    """Gray-failure degraded-mode census (mpi4torch_tpu.resilience,
    ISSUE 15) — deterministic, like every resilience verdict:

    * **per-rank wire census**: the schedule-failover policy re-ranks
      candidates by bytes through the SLOW rank
      (``resilience.rank_wire_bytes``); the verdict pins that the
      failover winner strictly reduces bytes through the slow rank vs
      the ring default (tree rooted away from it: ``2B`` vs
      ``4B(N-1)/N``), and that the model is self-consistent (every
      candidate moves the same TOTAL wire — same traffic, different
      concentration);
    * **zero-overhead off path**: with the gray-failure detector
      constructed (and a Mode B-only tracer installed), the Mode A
      lowering is BIT-IDENTICAL to the detector-less build — the
      detector only reads events the chokepoints already record, so
      "detector off" and "detector on" cannot diverge in compiled
      code."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    import mpi4torch_tpu as mpi
    from mpi4torch_tpu import obs
    from jax import shard_map
    from mpi4torch_tpu.resilience import (GrayFailureDetector,
                                          failover_schedule,
                                          rank_wire_bytes)

    n_dev = len(jax.devices())
    n = n_dev if n_dev > 1 else 8   # census is pure arithmetic
    nbytes = 64 * 1024
    slow = 3 % n
    winner, table = failover_schedule(slow, n, nbytes)
    totals = {a: sum(t) for a, t in table.items()}
    out = {
        "n_ranks": n,
        "nbytes": nbytes,
        "slow_rank": slow,
        "failover_winner": winner,
        "slow_rank_bytes": {a: t[slow] for a, t in table.items()},
        "per_rank_bytes": {a: list(t) for a, t in table.items()},
        "census_total_consistent": len(set(totals.values())) == 1,
        "failover_reduces_slow_rank_bytes": bool(
            table[winner][slow] < table["ring"][slow]),
        "slow_rank_byte_reduction": round(
            table["ring"][slow] / max(table[winner][slow], 1), 3),
    }
    # Sanity vs the hand formula: ring per-rank = 4(N-1)B/N.
    out["ring_matches_formula"] = (
        table["ring"][slow] == int(round(4 * (n - 1) * nbytes / n)))
    assert rank_wire_bytes("ring", n, nbytes)[0] == table["ring"][0]

    # Off-path census: detector + Mode B tracer move NOTHING trace-time.
    mesh = Mesh(np.asarray(jax.devices()), ("w",))
    cm = mpi.comm_from_mesh(mesh, "w")
    x = jnp.ones((1 << 13,), jnp.float32)

    def lowered():
        return jax.jit(shard_map(
            lambda a: cm.Allreduce(a, mpi.MPI_SUM),
            mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False)).lower(x).as_text()

    text_off = lowered()
    with obs.trace() as tracer:
        det = GrayFailureDetector(tracer)
        text_on = lowered()
        det.check()   # reads events only; no trace-time effect
    out["detector_off_path_bit_identical"] = text_on == text_off
    out["note"] = ("deterministic per-rank wire census + off-path "
                   "lowering equality — identical on CPU smoke and "
                   "hardware; wall-clock degrade latency is one "
                   "consensus round (see elastic bench)")
    return out


def _reshard_census(nrows: int = 1024, ncols: int = 256):
    """Deterministic reshard stanza core (ISSUE 9): lower the
    (8,)->(2,4) checkpoint-migration transition — rows over the flat
    world to rows x cols over the 2x4 mesh — planned vs the
    gather-everything baseline, and read BOTH estimators off each
    StableHLO: per-device wire bytes (the ring accountings of
    ``_hlo_wire_bytes_per_device``) and peak live bytes (the
    ``reshard.peak_live_bytes`` liveness census).  The verdict
    ``peak_memory_bounded`` is the strict inequality between the two
    programs under the one shared estimator."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    import mpi4torch_tpu as mpi
    from mpi4torch_tpu import reshard as rs
    from jax import shard_map

    devs = jax.devices()
    n = len(devs)
    if n < 2:
        raise RuntimeError("reshard census needs >= 2 devices")
    a = next((a for a in range(2, n) if n % a == 0 and n // a > 1), None)
    if a is None:
        raise RuntimeError(f"{n} ranks have no 2D factorization")
    fl = rs.layout((n,), 0, None)
    tl = rs.layout((a, n // a), 0, 1)
    G = (nrows, ncols)
    mesh = Mesh(np.asarray(devs), ("w",))
    cm = mpi.comm_from_mesh(mesh, "w")
    x = jnp.zeros(fl.shard_shape(G), jnp.float32)

    def lowered(strategy):
        fn = shard_map(
            lambda v: cm.Reshard(v, fl, tl, strategy=strategy),
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
        return jax.jit(fn).lower(x).as_text()

    plan = rs.plan_reshard(fl, tl, G, np.float32)
    out = {"n_devices": n, "transition": plan.transition,
           "strategy": plan.strategy,
           "shard_bytes": int(np.prod(fl.shard_shape(G))) * 4,
           "table": {}}
    for label, strategy in (("planned", None), ("gather", "gather")):
        txt = lowered(strategy)
        wire, counts = _hlo_wire_bytes_per_device(txt)
        out["table"][label] = {
            "wire_bytes_per_device": wire,
            "peak_live_bytes": rs.peak_live_bytes(txt),
            "collectives": counts,
        }
    p, g = out["table"]["planned"], out["table"]["gather"]
    out["peak_memory_bounded"] = bool(
        p["peak_live_bytes"] < g["peak_live_bytes"])
    if p["wire_bytes_per_device"]:
        out["wire_advantage_vs_gather"] = round(
            g["wire_bytes_per_device"] / p["wire_bytes_per_device"], 3)
    return out


def _reshard_census_subprocess():
    """The reshard census on a forced 8-virtual-device CPU mesh (for
    1-device bench worlds, where every transition lowers to slices and
    there is nothing to compare)."""
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    code = ("import json, bench; "
            "print(json.dumps(bench._reshard_census()))")
    proc = subprocess.run(
        [sys.executable, "-c", code],
        cwd=os.path.dirname(os.path.abspath(__file__)),
        env=env, capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(
            f"reshard census subprocess failed (rc {proc.returncode}): "
            f"{proc.stderr.strip()[-300:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _bench_reshard(on_tpu: bool):
    """Resharding stanza (ISSUE 9): the deterministic planned-vs-gather
    census for the (8,)->(2,4) migration (wire bytes + peak live bytes
    + the ``peak_memory_bounded: true`` verdict) with wall-clock per
    strategy alongside where a multi-device world exists."""
    import jax

    n = len(jax.devices())
    if n >= 2:
        res = _reshard_census()
    else:
        res = _reshard_census_subprocess()
        res["note"] = ("1-device world: census from a forced "
                       "8-virtual-device subprocess mesh")
        return res

    import jax.numpy as jnp
    import numpy as np

    import mpi4torch_tpu as mpi
    from mpi4torch_tpu import reshard as rs

    a = next(a for a in range(2, n) if n % a == 0 and n // a > 1)
    fl = rs.layout((n,), 0, None)
    tl = rs.layout((a, n // a), 0, 1)
    G = (1024, 256)
    x0 = jnp.ones(fl.shard_shape(G), jnp.float32)
    for label, strategy in (("planned", None), ("gather", "gather")):
        def step(v, strategy=strategy):
            return mpi.COMM_WORLD.Reshard(v, fl, tl, strategy=strategy)

        fn = mpi.run_spmd(lambda: step(x0), nranks=n)
        _force(fn())          # compile + warm
        res["table"][label]["seconds_per_step"] = _timeit(fn, iters=10)
    return res


def _bench_elastic(on_tpu: bool):
    """Elastic world-resize stanza (ISSUE 13): the deterministic
    wire-bytes census of the shrink replan vs the full-restart restore,
    plus wall-clock of a live (8,)->(6,) drain on the thread world.

    The comparison is the planner's own per-device accounting
    (reshard.plan_resize: the same ``_estimates`` currency every
    reshard number uses): ``planned`` is the auto-selected live-drain
    program (chunk-permute rounds — O(moved chunks) wire), ``restart``
    is the ``gather`` strategy (every rank re-materializes the full
    state then slices — exactly what a naive full-job restart's
    restore does on the wire).  The verdict
    ``replan_cheaper_than_restart`` is deterministic; wall-clock rides
    alongside (Mode B rendezvous — scheduler noise on CPU, the census
    is the headline)."""
    import time as _time

    import jax.numpy as jnp
    import numpy as np

    import mpi4torch_tpu as mpi
    from mpi4torch_tpu import reshard as rs
    from mpi4torch_tpu.elastic import (ElasticRuntime, replan_axis0)

    W, M = 8, 6
    # A representative re-layed state set: a TP head bank + two ZeRO
    # flat leaves (the elastic matrix's shapes, scaled up).
    states = {
        "tp_bank": (48, (256,)),       # 48 heads x 256 f32
        "zero_w": (12 * 4096, ()),     # flat padded elements
        "zero_b": (4096, ()),
    }
    embed_from = tuple(range(W))
    embed_to = tuple(range(M))
    table = {}
    planned_wire = restart_wire = 0
    planned_peak = restart_peak = 0
    for name, (n, row) in states.items():
        p = rs.plan_resize(n, row, W, M, np.float32,
                           embed_from=embed_from, embed_to=embed_to,
                           exec_size=W)
        g = rs.plan_resize(n, row, W, M, np.float32,
                           embed_from=embed_from, embed_to=embed_to,
                           exec_size=W, strategy="gather")
        table[name] = {
            "planned_strategy": p.strategy,
            "planned_wire_bytes": p.wire_bytes,
            "planned_peak_bytes": p.peak_bytes,
            "restart_wire_bytes": g.wire_bytes,
            "restart_peak_bytes": g.peak_bytes,
        }
        planned_wire += p.wire_bytes
        restart_wire += g.wire_bytes
        planned_peak = max(planned_peak, p.peak_bytes)
        restart_peak = max(restart_peak, g.peak_bytes)

    # Wall-clock: one live drain of the TP bank on the thread world,
    # planned vs gather (same data, same embeds, same world).
    n, row = states["tp_bank"]
    per = n // W
    bank = np.arange(n * row[0], dtype=np.float32).reshape((n,) + row)
    wall = {}
    for label, strategy in (("planned", None), ("restart", "gather")):
        rt = ElasticRuntime(W, probe_timeout=0.5, world_timeout=30.0)
        view0 = rt.view

        def drain_body(pos, rid, old_view, new_view, strategy=strategy):
            x = jnp.asarray(bank[pos * per:(pos + 1) * per])
            return np.asarray(replan_axis0(
                mpi.COMM_WORLD, x, n, old_view, new_view,
                mode="drain", strategy=strategy))

        t0 = _time.perf_counter()
        outs = rt.drain(drain_body, leaving=[6, 7])
        wall[label] = _time.perf_counter() - t0
        per_m = n // M
        for j, rid in enumerate(rt.view.alive):
            assert np.array_equal(outs[view0.position(rid)],
                                  bank[j * per_m:(j + 1) * per_m]), \
                f"{label} drain diverged"

    return {
        "worlds": f"({W},)->({M},)",
        "table": table,
        "planned_wire_bytes_total": planned_wire,
        "restart_wire_bytes_total": restart_wire,
        "wire_advantage": round(restart_wire / max(planned_wire, 1), 3),
        "planned_peak_bytes_max": planned_peak,
        "restart_peak_bytes_max": restart_peak,
        "replan_cheaper_than_restart": bool(
            planned_wire < restart_wire
            and planned_peak < restart_peak),
        "drain_seconds": wall,
        "note": ("census = reshard plan accounting (deterministic); "
                 "wall-clock is Mode B rendezvous incl. the consensus "
                 "round"),
    }


def _bench_allreduce_fused(on_tpu: bool):
    """Fused bucketed vs per-leaf Allreduce on a real DP ResNet gradient
    tree (mpi4torch_tpu.fuse, ISSUE 2): collective-launch counts read off
    the lowered StableHLO (ground truth on any platform), bytes-on-wire,
    and wall-clock per step — each with and without the q8 codec.  The
    acceptance bar: >= 5x fewer launches fused, wall-time no worse."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    import mpi4torch_tpu as mpi
    from jax import shard_map
    from mpi4torch_tpu.compress import get_codec
    from mpi4torch_tpu.fuse import bucket_layout
    from mpi4torch_tpu.models import resnet as R

    n = len(jax.devices())
    # ResNet-18-ish widths on TPU; a narrow stack on the CPU smoke path.
    if on_tpu:
        cfg = R.ResNetConfig()
        iters = 20
    else:
        cfg = R.ResNetConfig(widths=(8, 16, 32, 64),
                             stage_sizes=(2, 2, 2, 2), num_classes=10)
        iters = 3
    params, _state = R.init_resnet(jax.random.PRNGKey(0), cfg)
    grads = jax.tree.map(jnp.asarray, params)   # stand-in gradient tree
    leaves = jax.tree.leaves(grads)
    total_bytes = sum(x.size * x.dtype.itemsize for x in leaves)

    mesh = Mesh(np.asarray(jax.devices()[:n]), ("w",))
    comm = mpi.comm_from_mesh(mesh, "w")

    COLL = ("all_reduce", "all_gather", "reduce_scatter",
            "collective_permute", "all_to_all")

    def launches(fn):
        wrapped = shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                            check_vma=False)
        txt = jax.jit(wrapped).lower(grads).as_text()
        return sum(txt.count(f"stablehlo.{c}") for c in COLL)

    def perleaf(compression):
        def f(t):
            return jax.tree.map(
                lambda g: comm.Allreduce(g, mpi.MPI_SUM,
                                         compression=compression)
                / comm.size, t)
        return f

    def fused(compression):
        def f(t):
            return comm.Allreduce_tree(t, mpi.MPI_SUM, mean=True,
                                       compression=compression)
        return f

    def timed(fn):
        step = mpi.run_spmd(fn, mesh=mesh, axis_name="w")
        return _timeit(step, grads, iters=iters)

    layout = bucket_layout(grads, mpi.config.default_bucket_bytes())
    out = {
        "n_devices": n,
        "n_leaves": len(leaves),
        "n_buckets": layout.num_buckets,
        "grad_tree_mib": round(total_bytes / (1 << 20), 3),
        "bucket_bytes": mpi.config.default_bucket_bytes(),
        "variants": {},
    }

    codec = get_codec("q8")
    q8_leaf_bytes = sum(codec.wire_bytes(x.shape, x.dtype) for x in leaves)
    q8_bucket_bytes = sum(
        codec.wire_bytes((sz,), dt)
        for sz, dt in zip(layout.bucket_sizes, layout.bucket_dtypes))
    for name, compression, wire in (
            ("perleaf_fp32", False, total_bytes),
            ("fused_fp32", False, total_bytes),
            ("perleaf_q8", "q8", q8_leaf_bytes),
            ("fused_q8", "q8", q8_bucket_bytes)):
        build = fused if name.startswith("fused") else perleaf

        def _one(build=build, compression=compression, wire=wire):
            return {
                "launches": launches(build(compression)),
                "wire_bytes": int(wire),
                "seconds_per_step": timed(build(compression)),
            }

        out["variants"][name] = _guarded(f"allreduce_fused.{name}", _one)

    pl, fu = out["variants"].get("perleaf_fp32", {}), \
        out["variants"].get("fused_fp32", {})
    if "launches" in pl and "launches" in fu:
        out["launch_reduction"] = round(
            pl["launches"] / max(fu["launches"], 1), 2)
        out["step_speedup_vs_perleaf"] = round(
            pl["seconds_per_step"] / fu["seconds_per_step"], 4)
        out["launch_reduction_target_met"] = bool(
            out["launch_reduction"] >= 5.0)
        # One device: a 1-rank psum compiles to identity, so the
        # per-leaf "collectives" are free while the fused path still
        # pays its concat/slice HBM traffic — the wall-time verdict only
        # means something where a wire exists, so (like the allreduce
        # stanza's roofline handling) it is None rather than a spurious
        # false on the single-chip harness.
        out["walltime_no_worse"] = (
            bool(fu["seconds_per_step"] <= pl["seconds_per_step"] * 1.05)
            if n > 1 else None)
        if n == 1:
            out["note"] = ("single device: no wire; launch counts are "
                           "ground truth, wall-time comparison is not")
    return out


def _overlap_zero_setup(on_tpu: bool):
    """Model, optimizer and stand-in gradient tree shared by the
    overlap_zero wall-clock measurement and its schedule census
    (including the forced-multi-device censusing subprocess a 1-device
    run spawns — both sides must build the SAME step programs)."""
    import jax

    import mpi4torch_tpu as mpi
    from mpi4torch_tpu.models import transformer as T

    if on_tpu:
        cfg = T.TransformerConfig(vocab=8192, d_model=512, n_heads=8,
                                  n_layers=8, d_ff=2048, max_seq=256)
        iters = 10
        bucket_bytes = mpi.config.default_bucket_bytes()
    else:
        cfg = T.TransformerConfig(vocab=256, d_model=64, n_heads=4,
                                  n_layers=2, d_ff=128, max_seq=32)
        iters = 5
        # Small buckets so the smoke tree still splits into a real
        # multi-bucket window (the default 4 MiB would make it one
        # bucket — nothing for the scheduler to keep in flight).
        bucket_bytes = 1 << 15
    params = T.init_transformer(jax.random.PRNGKey(0), cfg)
    # Stand-in UN-reduced local gradient tree (the fused bench's trick):
    # the wire and optimizer cost are shape-determined, not
    # value-determined.
    grads = jax.tree.map(lambda p: p * 1e-3, params)

    class _Sgd:
        def init(self, p):
            return None

        def update(self, g, s, p):
            return jax.tree.map(lambda x: -0.1 * x, g), None

    return params, grads, _Sgd(), bucket_bytes, iters


def _overlap_zero_step_fn(comm, opt, params, bucket_bytes, overlap):
    import mpi4torch_tpu as mpi
    from mpi4torch_tpu.parallel import zero as Z

    def f(g):
        with mpi.config.fusion_scope(bucket_bytes):
            st = Z.zero_init(comm, opt, params)
            new_p, _ = Z.zero_step(comm, opt, params, g, st,
                                   overlap=overlap)
        return new_p
    return f


def _overlap_zero_census(on_tpu: bool = False):
    """Schedule census of the ZeRO step's two forms (mpi4torch_tpu.
    overlap.scheduled_exposure): the fraction of bucket collectives the
    lowered program leaves with NOTHING else in flight to hide them.
    Deterministic on every platform — blocking steps census to 1.0 by
    construction, the windowed split-phase step strictly lower."""
    import jax

    import mpi4torch_tpu as mpi
    from mpi4torch_tpu.overlap import scheduled_exposure

    params, grads, opt, bucket_bytes, _ = _overlap_zero_setup(on_tpu)
    comm = mpi.COMM_WORLD
    out = {"n_devices": len(jax.devices())}
    for name, ov in (("blocking", False), ("overlap", True)):
        f = _overlap_zero_step_fn(comm, opt, params, bucket_bytes, ov)
        out[name] = scheduled_exposure(
            jax.jit(mpi.run_spmd(f)).lower(grads))
    return out


def _overlap_zero_census_subprocess():
    """Run :func:`_overlap_zero_census` on a forced 8-virtual-device CPU
    mesh in a subprocess — the multi-device smoke sweep for a bench run
    whose own world has a single device (collectives lower away there,
    so the in-process census would have nothing to count)."""
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    code = ("import json, bench; "
            "print(json.dumps(bench._overlap_zero_census(False)))")
    proc = subprocess.run(
        [sys.executable, "-c", code],
        cwd=os.path.dirname(os.path.abspath(__file__)),
        env=env, capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(
            f"census subprocess failed (rc {proc.returncode}): "
            f"{proc.stderr.strip()[-300:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _bench_overlap_zero(on_tpu: bool):
    """ZeRO step on a models/ transformer grad tree, blocking vs
    split-phase overlap (mpi4torch_tpu.overlap, ISSUE 5): persists the
    *exposed-comm fraction* for both schedules, plus the overlap
    speedup.  Two estimators of the same quantity:

    * ``exposed_comm_fraction_measured`` — wall-clock,
      ``(t_full - t_compute_only) / t_full``: the share of the step the
      wire is NOT hidden behind compute.  The real number on multi-chip
      hardware with an async collective runtime; on the CPU smoke mesh
      the in-process rendezvous is synchronous and the comparison is
      scheduler noise (measured here and kept, but informational).
    * ``exposed_comm_fraction_scheduled`` — the deterministic schedule
      census (:func:`mpi4torch_tpu.overlap.scheduled_exposure`): the
      fraction of bucket collectives whose start→wait window the
      lowered program leaves EMPTY (nothing in flight to hide them).
      Blocking steps census to 1.0 by construction; the windowed
      split-phase step strictly lower.

    The headline per-variant ``exposed_comm_fraction`` (and the
    ``overlap_fraction_lower`` verdict) is the measured one on TPU and
    the scheduled one on the CPU smoke sweep — best available estimator
    per platform.  A 1-device bench world runs the census on a forced
    8-virtual-device subprocess mesh so the multi-device verdict is
    recorded either way."""
    import jax
    import jax.numpy as jnp

    import mpi4torch_tpu as mpi
    from mpi4torch_tpu.fuse import bucket_layout
    from mpi4torch_tpu.parallel import zero as Z

    n = len(jax.devices())
    params, grads, opt, bucket_bytes, iters = _overlap_zero_setup(on_tpu)
    leaves = jax.tree.leaves(grads)
    total_bytes = sum(x.size * x.dtype.itemsize for x in leaves)

    comm = mpi.COMM_WORLD

    def full_step(overlap):
        return _overlap_zero_step_fn(comm, opt, params, bucket_bytes,
                                     overlap)

    def compute_only(g):
        # The step's compute with the wire legs cut: shard locally
        # (pure slicing), update — no reduce-scatter, no all-gather.
        st = Z.zero_init(comm, opt, params)
        g_shards = Z.zero3_shard_params(comm, g)
        p_shards = Z.zero3_shard_params(comm, params)
        updates, _ = opt.update(g_shards, st, p_shards)
        return jax.tree.map(jnp.add, p_shards, updates)

    def timed(fn):
        step = mpi.run_spmd(fn)
        return _timeit(step, grads, iters=iters)

    layout = bucket_layout(grads, bucket_bytes)
    out = {
        "n_devices": n,
        "n_leaves": len(leaves),
        "n_buckets": layout.num_buckets,
        "grad_tree_mib": round(total_bytes / (1 << 20), 3),
        "bucket_bytes": bucket_bytes,
    }
    t_compute = _guarded("overlap_zero.compute_only", timed, compute_only)
    variants = {}
    for name, ov in (("blocking", False), ("overlap", True)):
        def _one(ov=ov):
            t_full = timed(full_step(ov))
            exposed = max(0.0, t_full - t_compute) / t_full \
                if isinstance(t_compute, float) and t_full > 0 else None
            return {"seconds_per_step": t_full,
                    "exposed_comm_fraction_measured": (
                        round(exposed, 4) if exposed is not None
                        else None)}
        variants[name] = _guarded(f"overlap_zero.{name}", _one)

    # The deterministic half: census the two step programs' schedules.
    # A 1-device world's collectives lower away, so the census runs on a
    # forced 8-virtual-device subprocess mesh there (the multi-device
    # smoke sweep); otherwise in-process on the measuring world.
    census = _guarded(
        "overlap_zero.census",
        _overlap_zero_census if n > 1 else _overlap_zero_census_subprocess,
        *((on_tpu,) if n > 1 else ()))
    if "error" not in census:
        out["census_n_devices"] = census.get("n_devices")
        for name in ("blocking", "overlap"):
            cv = census.get(name) or {}
            if isinstance(variants.get(name), dict):
                variants[name]["exposed_comm_fraction_scheduled"] = \
                    cv.get("exposed_fraction")
                variants[name]["census_buckets"] = cv.get("n_buckets")
    else:
        out["census_error"] = census["error"]
    # Headline fraction: the best available estimator per platform —
    # wall-clock where the collective runtime is genuinely async (TPU),
    # the schedule census on the CPU smoke path (the synchronous
    # in-process rendezvous makes wall-clock deltas scheduler noise).
    headline_key = ("exposed_comm_fraction_measured" if on_tpu
                    else "exposed_comm_fraction_scheduled")
    for name in ("blocking", "overlap"):
        if isinstance(variants.get(name), dict):
            variants[name]["exposed_comm_fraction"] = \
                variants[name].get(headline_key)
    out["compute_only_seconds"] = t_compute
    out["variants"] = variants
    blk, ovl = variants.get("blocking", {}), variants.get("overlap", {})
    ef_b = blk.get("exposed_comm_fraction")
    ef_o = ovl.get("exposed_comm_fraction")
    if ef_b is not None and ef_o is not None:
        out["overlap_fraction_lower"] = bool(ef_o < ef_b)
        if not on_tpu:
            out["note"] = (
                "cpu smoke: exposed-comm fractions are the scheduled "
                "census (deterministic; blocking = 1.0 by construction)"
                " — the synchronous in-process collective runtime makes "
                "the wall-clock _measured fractions scheduler noise; on "
                "multi-chip hardware the measured fractions are the "
                "headline")
    elif "error" not in census:
        out["overlap_fraction_lower"] = None
    if "seconds_per_step" in blk and "seconds_per_step" in ovl:
        out["overlap_speedup"] = round(
            blk["seconds_per_step"] / ovl["seconds_per_step"], 4)
        if n == 1:
            # One device: a 1-rank psum_scatter/all_gather pair is local
            # data movement — there is no wire to hide, so the wall-clock
            # numbers are slicing/copy overhead (the scheduled census
            # above ran on the forced multi-device subprocess mesh and
            # still carries the real verdict).
            out["wall_clock_note"] = (
                "single device: no wire; measured fractions are "
                "slicing/copy overhead, not communication")
    return out


def _serve_setup():
    """Smoke serving config shared by the measuring engine and the
    census: small enough to step quickly on CPU, big enough that the
    decode collectives are real."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpi4torch_tpu.models import transformer as T

    cfg = T.TransformerConfig(vocab=256, d_model=64, n_heads=8,
                              n_layers=4, d_ff=128, max_seq=64)
    params = T.init_transformer(jax.random.PRNGKey(0), cfg,
                                dtype=jnp.float32)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab, size=int(n))
               for n in (5, 9, 3, 7, 4, 6)]
    return cfg, params, prompts, 8   # max_new per request


def _serve_census(on_tpu: bool):
    """Deterministic serve verdicts off the LOWERED decode step: the
    scheduled-exposure fractions of the overlap vs blocking schedules,
    the per-device wire bytes per step (→ per-token wire bytes at full
    occupancy), and the latency-tier selection under a measured (or
    stand-in) crossover."""
    import jax
    import jax.numpy as jnp

    import mpi4torch_tpu as mpi
    from mpi4torch_tpu import serve

    n = len(jax.devices())
    cfg, params, prompts, max_new = _serve_setup()
    slots = 4
    out = {"n_devices": n}

    prev = mpi.config.latency_crossover_bytes()
    assumed = prev is None
    if assumed:
        # No measured crossover on this host: a stand-in lets the
        # selection verdict stay deterministic; flagged below.
        mpi.config.set_latency_crossover_bytes(1 << 14)
    try:
        for name, ov in (("overlap", True), ("blocking", False)):
            eng = serve.Engine(cfg, params,
                               serve.ServeConfig(slots=slots,
                                                 overlap=ov),
                               spmd=True, nranks=n)
            eng.submit(prompts[0], max_new=3)
            eng.step()
            txt = eng.lower_step().as_text(debug_info=True)
            census = mpi.overlap.scheduled_exposure(txt)
            wire, counts = _hlo_wire_bytes_per_device(txt)
            out[name] = {
                "exposed_fraction": census["exposed_fraction"],
                "n_buckets": census["n_buckets"],
                "wire_bytes_per_step": wire,
                "wire_bytes_per_token": round(wire / slots, 1),
                "wire_op_counts": counts,
            }
        rep = serve.latency_report(cfg, serve.ServeConfig(slots=slots),
                                   n, jnp.float32)
        rep["crossover_assumed"] = assumed
        out["latency_tier"] = rep
    finally:
        mpi.config.set_latency_crossover_bytes(prev)
    return out


def _serve_census_subprocess():
    """Run :func:`_serve_census` on a forced 8-virtual-device CPU mesh
    in a subprocess — the multi-device verdict for a 1-device bench
    world (collectives lower away in-process there)."""
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    code = ("import json, bench; "
            "print(json.dumps(bench._serve_census(False)))")
    proc = subprocess.run(
        [sys.executable, "-c", code],
        cwd=os.path.dirname(os.path.abspath(__file__)),
        env=env, capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(
            f"serve census subprocess failed (rc {proc.returncode}): "
            f"{proc.stderr.strip()[-300:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _bench_serve(on_tpu: bool):
    """Serving throughput/latency: the continuous-batching engine
    (slots=4, decode comm on the overlap scheduler) vs the no-overlap,
    no-continuous-batching baseline (slots=1, blocking collectives —
    the same TP decode path serving requests one at a time), on the
    smoke transformer.

    Persists tokens/sec and p50/p99 per-token latency for both, the
    continuous-batching speedup, and — the regression currency on the
    CPU smoke path, where wall-clock is scheduler noise — the
    deterministic census verdicts: scheduled exposure of the decode
    step (overlap strictly below the blocking 1.0), per-token wire
    bytes off the lowered StableHLO, and the latency-tier selection for
    the real decode message sizes."""
    import time as _time

    import jax

    from mpi4torch_tpu import serve

    n = len(jax.devices())
    cfg, params, prompts, max_new = _serve_setup()

    def run_one(slots, overlap):
        eng = serve.Engine(
            cfg, params, serve.ServeConfig(slots=slots, overlap=overlap),
            spmd=True, nranks=n)
        for p in prompts:
            eng.submit(p, max_new=max_new)
        token_lat = []
        t0 = _time.perf_counter()
        while eng.pending():
            s0 = _time.perf_counter()
            ev = eng.step()
            dt = _time.perf_counter() - s0
            n_emitted = sum(len(v) for v in ev["emitted"].values())
            token_lat.extend([dt] * n_emitted)
        wall = _time.perf_counter() - t0
        total = sum(len(p) for p in prompts)
        new_tokens = sum(len(r) for r in eng.results().values()) - total

        def pct(q):
            # ONE percentile rule repo-wide (mpi4torch_tpu.obs): the
            # same nearest-rank-floor helper ServeStats.snapshot's
            # p50/p99 aggregates use — this stanza's historical rule,
            # now shared instead of duplicated.
            from mpi4torch_tpu.obs import percentile
            v = percentile(token_lat, q)
            return None if v is None else round(v * 1e3, 3)

        return {
            "slots": slots,
            "new_tokens": new_tokens,
            "steps": eng.stats.snapshot()["steps"],
            "occupancy": eng.stats.snapshot()["occupancy"],
            "wall_s": round(wall, 4),
            "tokens_per_s": round(new_tokens / wall, 2),
            "p50_token_latency_ms": pct(0.50),
            "p99_token_latency_ms": pct(0.99),
        }

    out = {"n_devices": n, "n_requests": len(prompts),
           "max_new": max_new}
    engine = _guarded("serve.engine", run_one, 4, True)
    baseline = _guarded("serve.baseline", run_one, 1, False)
    out["engine"] = engine
    out["baseline"] = baseline
    if "tokens_per_s" in engine and "tokens_per_s" in baseline \
            and baseline["tokens_per_s"]:
        out["continuous_batching_speedup"] = round(
            engine["tokens_per_s"] / baseline["tokens_per_s"], 3)
    census = _guarded("serve.census",
                      _serve_census if n > 1 else
                      _serve_census_subprocess,
                      *((on_tpu,) if n > 1 else ()))
    out["census"] = census
    if "error" not in census:
        co = census.get("overlap") or {}
        cb = census.get("blocking") or {}
        if co.get("exposed_fraction") is not None \
                and cb.get("exposed_fraction") is not None:
            out["overlap_exposure_lower"] = bool(
                co["exposed_fraction"] < cb["exposed_fraction"])
        lt = census.get("latency_tier") or {}
        out["latency_tier_selected"] = lt.get("latency_tier")
    if not on_tpu:
        out["note"] = (
            "cpu smoke: wall-clock tokens/sec is host-loop overhead, "
            "not wire time, and the p99 tail holds the one-time "
            "step/prefill compiles (cold engine, like a cold server) — "
            "the deterministic census verdicts (exposure, per-token "
            "wire bytes, latency-tier selection) are the regression "
            "currency here; the throughput/latency numbers become the "
            "headline on real multi-chip hardware")
    return out


def _bench_serve_paged(on_tpu: bool):
    """Paged KV cache vs the dense slot table (ISSUE 17) under a
    KV-BYTE-BUDGET-MATCHED comparison on a long-tailed length
    distribution with a shared system prompt: the dense engine reserves
    every occupied slot's full ``max_seq`` rows, the paged engine only
    the pages requests actually wrote (shared prefix pages once).

    The headline is DETERMINISTIC: ``kv_bytes_resident()`` is a census
    of reserved cache bytes, integrated per step and divided by tokens
    emitted — ``paged_occupancy_gain`` is the dense/paged ratio of
    KV-bytes-resident·steps per token (the effective-occupancy claim:
    how many more concurrent sequences the same HBM holds).  Tokens/sec
    rides along for the hardware runs; on CPU smoke it is host-loop
    noise and the census is the regression currency."""
    import time as _time

    import jax
    import numpy as np

    from mpi4torch_tpu import serve

    n = len(jax.devices())
    cfg, params, _, max_new = _serve_setup()
    # Long-tailed lengths: mostly short chats, two long documents —
    # the distribution dense slot tables waste max_seq rows on.  Four
    # of the short ones share a 16-token system prompt (prefix pages
    # shared, prefilled once).
    rng = np.random.default_rng(7)
    sys_prompt = rng.integers(1, cfg.vocab, size=16)
    prompts = [np.concatenate([sys_prompt,
                               rng.integers(1, cfg.vocab, size=k)])
               for k in (3, 5, 4, 6)]
    prompts += [rng.integers(1, cfg.vocab, size=int(k))
                for k in (4, 6, 40, 48)]
    slots, bs = 4, 8
    # Byte-budget match: dense reserves slots*max_seq rows; the paged
    # pool gets exactly that many rows' worth of pages.
    num_blocks = slots * cfg.max_seq // bs

    def run_one(paged):
        eng = serve.Engine(
            cfg, params,
            serve.ServeConfig(slots=slots,
                              block_size=(bs if paged else 0),
                              num_blocks=(num_blocks if paged
                                          else None)),
            spmd=True, nranks=n)
        for p in prompts:
            eng.submit(p, max_new=max_new)
        resident_byte_steps = 0
        t0 = _time.perf_counter()
        while eng.pending():
            eng.step()
            resident_byte_steps += eng.kv_bytes_resident()
        wall = _time.perf_counter() - t0
        snap = eng.stats.snapshot()
        new_tokens = snap["decode_tokens"] + snap["admitted"]
        out = {
            "steps": snap["steps"],
            "new_tokens": new_tokens,
            "occupancy": snap["occupancy"],
            "kv_byte_steps_resident": int(resident_byte_steps),
            "kv_bytes_per_token": round(
                resident_byte_steps / max(new_tokens, 1), 1),
            "wall_s": round(wall, 4),
            "tokens_per_s": round(new_tokens / wall, 2),
        }
        if paged:
            out.update({
                "block_size": bs, "num_blocks": num_blocks,
                "prefix_hits": snap["prefix_hits"],
                "prefix_misses": snap["prefix_misses"],
                "prefill_tokens": snap["prefill_tokens"],
                "cow_copies": snap["cow_copies"],
            })
        return out

    out = {"n_devices": n, "n_requests": len(prompts),
           "max_new": max_new,
           "prompt_lengths": [int(len(p)) for p in prompts],
           "shared_prefix_tokens": int(len(sys_prompt))}
    paged = _guarded("serve_paged.paged", run_one, True)
    dense = _guarded("serve_paged.dense", run_one, False)
    out["paged"] = paged
    out["dense"] = dense
    if "kv_bytes_per_token" in paged and "kv_bytes_per_token" in dense \
            and paged["kv_bytes_per_token"]:
        # The deterministic headline: same KV byte budget, how much
        # less cache each emitted token holds resident.
        out["paged_occupancy_gain"] = round(
            dense["kv_bytes_per_token"] / paged["kv_bytes_per_token"],
            3)
        out["paged_occupancy_gain_ok"] = \
            bool(out["paged_occupancy_gain"] > 1.0)
    if not on_tpu:
        out["note"] = (
            "cpu smoke: the kv_bytes_per_token census (and the "
            "occupancy-gain ratio) is deterministic and is the "
            "regression currency; tokens/sec is host-loop overhead "
            "here and becomes meaningful on real hardware")
    return out


def _bench_allreduce_algorithms(on_tpu: bool):
    """Per-algorithm allreduce size sweep (mpi4torch_tpu.tune):
    1 KiB → 64 MiB on hardware (three points on the CPU smoke path),
    per-algorithm GB/s under ring-allreduce wire accounting for every
    registered algorithm — the latency tier (rhd/tree), hier, and the
    multipath bandwidth tier (bidir/torus) — the measured latency AND
    bandwidth crossovers, and the persistent autotuner's picks.  The autotuner stanza round-trips its JSON cache: the first
    bench run measures and persists, a second run reports
    ``tuned_from_cache: true`` with the same picks and zero tuning
    overhead — the ISSUE 3 acceptance evidence."""
    import jax

    from mpi4torch_tpu import tune

    import jax.numpy as jnp

    from mpi4torch_tpu.tune.autotuner import DEFAULT_SIZES, SMOKE_SIZES

    n = len(jax.devices())
    # The autotuner's own sweep grids: the cache keys this stanza
    # probes/persists MUST be the ones ensure_tuned/`make tune-smoke`
    # use, or tuned_from_cache goes permanently false on a grid drift.
    sizes = DEFAULT_SIZES if on_tpu else SMOKE_SIZES
    iters = 20 if on_tpu else 3

    # Cache state BEFORE this run's sweep overwrites it: a prior bench
    # run's persisted winners covering every size are the
    # `tuned_from_cache` evidence (a steady-state process would select
    # tuned algorithms with zero measurement).
    def _had_disk():
        return all(
            tune.lookup("allreduce", jnp.float32, s, n) is not None
            and tune.entry_from_disk("allreduce", jnp.float32, s, n)
            for s in sizes)

    had_disk = _guarded("allreduce_algorithms.cache_probe", _had_disk)

    # ONE sweep implementation: the autotuner's own (per-algorithm
    # seconds + ring-wire GB/s + winner + crossover, with per-candidate
    # error stanzas inside) — the bench must never fork its own copy of
    # the measurement/crossover rules.  This pass IS the tuning run:
    # winners persist to the JSON cache and the measured crossover is
    # applied, so the next process (and the next bench run) selects
    # tuned algorithms without measuring.
    rep = tune.autotune_allreduce(sizes=sizes, nranks=n, iters=iters)

    # The flat sweep table (sizes × algorithms → GB/s) — algorithm-
    # selection quality tracked across rounds (BENCH_r*.json): every
    # registered algorithm, including the bandwidth tier bidir/torus,
    # shows its measured throughput next to the winner column.
    sweep = {}
    for size_str, ent in rep["entries"].items():
        sweep[size_str] = {
            name: meas.get("gbps", meas.get("error"))
            for name, meas in ent.get("algorithms", {}).items()}
    out = {
        "n_devices": n,
        "dtype": rep["dtype"],
        "algorithms": list(tune.available_algorithms()),
        "sizes": rep["entries"],
        "sweep_gbps": sweep,
        # The crossover table's headline: the largest size where a
        # latency-optimal schedule still beats the ring, and the
        # smallest from which the multipath bandwidth tier wins through
        # the top (None = that regime not reached on this hardware).
        "crossover_bytes": rep["crossover_bytes"],
        "bandwidth_crossover_bytes": rep["bandwidth_crossover_bytes"],
        "autotuner": {
            "tuned_from_cache": bool(had_disk is True),
            "cache_file": rep["cache_file"],
            "crossover_bytes": rep["crossover_bytes"],
            "bandwidth_crossover_bytes":
                rep["bandwidth_crossover_bytes"],
            "picks": {k: v.get("winner")
                      for k, v in rep["entries"].items()},
        },
    }
    if n == 1:
        out["note"] = ("single device: no wire; per-algorithm timings "
                       "price schedule arithmetic only — the crossover "
                       "is meaningful where ICI/DCN is in the path")
    return out


def _bench_flash(on_tpu: bool, peak: float):
    """Causal flash-attention fwd+bwd achieved FLOP/s and MFU."""
    import jax
    import jax.numpy as jnp

    from mpi4torch_tpu.ops import flash

    if on_tpu:
        b, s, h, d, dtype, iters = 4, 4096, 8, 128, jnp.bfloat16, 20
    else:
        b, s, h, d, dtype, iters = 1, 256, 2, 64, jnp.float32, 2

    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (b, s, h, d), dtype) for kk in keys)

    def loss(q, k, v, window=0):
        out = flash.flash_attention(q, k, v, causal=True, impl="auto",
                                    window=window)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    dt = _timeit(step, q, k, v, iters=iters)

    def kernel_flags():
        # Mirrors the impl="auto" dispatch exactly: on a TPU the
        # eligibility predicates ARE the dispatch (no compile probe).
        return (bool(on_tpu and flash._eligible(q, k)),
                bool(on_tpu and flash._bwd_eligible(q, k)))

    # Sliding-window variant at the same shape: the two-frontier tile
    # skip should make cost ~O(window/seq) of full causal — report the
    # measured ratio so the claim is a number, not a comment.  Guarded
    # separately: a windowed-variant failure must degrade to an error
    # stanza inside "windowed", never erase the full-causal measurement
    # above (the module's robustness contract).
    window = s // 4
    try:
        wstep = jax.jit(jax.value_and_grad(
            functools.partial(loss, window=window), argnums=(0, 1, 2)))
        dt_w = _timeit(wstep, q, k, v, iters=iters)
        windowed = {
            "window": window,
            "seconds_per_step": dt_w,
            # Full causal touches ~s/2 keys per query, the window ~w:
            # ideal ratio ~ 2w/s (0.5 at w = s/4).  >=1.0 with the
            # kernel engaged means the tile skip is not working.
            "time_ratio_vs_full": round(dt_w / dt, 4),
        }
        windowed["pallas_fwd"], windowed["pallas_bwd"] = kernel_flags()
    except Exception as e:  # noqa: BLE001 — sub-measurement guard
        windowed = {"window": window,
                    "error": f"{type(e).__name__}: {str(e)[:300]}"}

    # Causal fwd = 2 matmuls * 2 FLOP/MAC * B*H*S^2*D / 2 (masked half).
    # MFU uses *model* FLOPs only (PaLM convention): fwd + 2x bwd = 3x;
    # the flash backward's score recompute is excluded (that extra work
    # would make this HFU and overstate utilization).
    fwd = 2.0 * b * h * s * s * d
    flops = 3.0 * fwd
    achieved = flops / dt
    # The timed step is fwd+bwd: report each kernel's engagement — the
    # backward is ~2/3 of the FLOPs and gates independently (its own
    # eligibility predicate), so a single flag would mislabel a
    # jnp-backward run as fully fused.
    fwd_kernel, bwd_kernel = kernel_flags()
    return {
        "tflops": round(achieved / 1e12, 3),
        "mfu": round(achieved / peak, 4) if peak else None,
        "shape": [b, s, h, d],
        "dtype": str(jnp.dtype(dtype)),
        "seconds_per_step": dt,
        "pallas_kernel": fwd_kernel and bwd_kernel,
        "pallas_fwd": fwd_kernel,
        "pallas_bwd": bwd_kernel,
        "windowed": windowed,
    }


def _bench_flash_reference_ratio(on_tpu: bool):
    """Race our Pallas flash kernel against JAX's own TPU flash attention
    (``jax.experimental.pallas.ops.tpu.flash_attention``) fwd+bwd at the
    bench shape — the one head-to-head opponent measurable on a single
    chip, so "matching-or-beating on perf" has a number (VERDICT r4
    item 2).  ``ratio`` is ours_tflops / jax_tflops = jax_s / ours_s;
    >= 1.0 means ours wins.  On CPU the opponent kernel has no lowering,
    so the smoke path races the module's own jnp reference instead
    (harness check only; the ratio is labeled)."""
    import math

    import jax
    import jax.numpy as jnp

    from mpi4torch_tpu.ops import flash

    if on_tpu:
        b, s, h, d, dtype, iters = 4, 4096, 8, 128, jnp.bfloat16, 20
    else:
        b, s, h, d, dtype, iters = 1, 256, 2, 64, jnp.float32, 2

    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (b, s, h, d), dtype) for kk in keys)

    def ours_loss(q, k, v):
        out = flash.flash_attention(q, k, v, causal=True, impl="auto")
        return jnp.sum(out.astype(jnp.float32) ** 2)

    ours = jax.jit(jax.value_and_grad(ours_loss, argnums=(0, 1, 2)))
    dt_ours = _timeit(ours, q, k, v, iters=iters)

    sm_scale = 1.0 / math.sqrt(d)   # our kernel's fixed convention
    if on_tpu:
        from jax.experimental.pallas.ops.tpu import flash_attention as jfa

        # JAX's kernel wants (batch, heads, seq, head_dim).  Hand it
        # pre-transposed inputs so the timed region is kernel-only on both
        # sides — a transpose inside the jitted opponent would charge it
        # ~6 layout copies per fwd+bwd step and bias the ratio our way.
        def jax_loss(qh, kh, vh):
            out = jfa.flash_attention(qh, kh, vh, causal=True,
                                      sm_scale=sm_scale)
            return jnp.sum(out.astype(jnp.float32) ** 2)

        opponent = "jax.experimental.pallas.ops.tpu.flash_attention"
        jq, jk, jv = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    else:
        def jax_loss(qh, kh, vh):
            out = flash.flash_attention(qh, kh, vh, causal=True, impl="jnp")
            return jnp.sum(out.astype(jnp.float32) ** 2)

        opponent = "jnp reference (cpu smoke; no TPU opponent available)"
        jq, jk, jv = q, k, v

    theirs = jax.jit(jax.value_and_grad(jax_loss, argnums=(0, 1, 2)))
    dt_jax = _timeit(theirs, jq, jk, jv, iters=iters)

    # Same computation check: fwd outputs must agree to dtype tolerance.
    ours_out = flash.flash_attention(q, k, v, causal=True, impl="auto")
    if on_tpu:
        from jax.experimental.pallas.ops.tpu import flash_attention as jfa

        jax_out = jfa.flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=True,
            sm_scale=sm_scale).transpose(0, 2, 1, 3)
    else:
        jax_out = flash.flash_attention(q, k, v, causal=True, impl="jnp")
    max_diff = float(jnp.max(jnp.abs(ours_out.astype(jnp.float32)
                                     - jax_out.astype(jnp.float32))))

    fwd = 2.0 * b * h * s * s * d          # causal: half of 2*2*B*H*S^2*D
    flops = 3.0 * fwd
    res = {
        "shape": [b, s, h, d],
        "dtype": str(jnp.dtype(dtype)),
        "opponent": opponent,
        "ours_s": dt_ours,
        "jax_s": dt_jax,
        "ours_tflops": round(flops / dt_ours / 1e12, 3),
        "jax_tflops": round(flops / dt_jax / 1e12, 3),
        "ratio": round(dt_jax / dt_ours, 4),
        "fwd_max_abs_diff": max_diff,
    }

    if on_tpu:
        # GQA head-to-head (guarded: must never erase the MHA ratio).
        # Our kernels resolve the q-head -> shared-KV-head mapping in
        # their BlockSpec index maps (KV never duplicated in HBM); the
        # opponent has no GQA entry point, so it runs the standard
        # realization — KV repeated to full head count before the
        # kernel.  The repeat is OUTSIDE the timed jit (pre-staged like
        # the layout transposes) so the timed gap is pure kernel-side
        # HBM traffic, not the repeat op itself.
        try:
            g = 4                                   # 8 q heads, 2 KV heads
            kg, vg = k[:, :, ::g, :], v[:, :, ::g, :]
            # `ours` retraces for the narrower KV shape automatically.
            dt_g_ours = _timeit(ours, q, kg, vg, iters=iters)
            krep = jnp.repeat(kg, g, axis=2).transpose(0, 2, 1, 3)
            vrep = jnp.repeat(vg, g, axis=2).transpose(0, 2, 1, 3)
            dt_g_jax = _timeit(theirs, jq, krep, vrep, iters=iters)
            res["gqa"] = {
                "q_heads": h, "kv_heads": h // g,
                "ours_s": dt_g_ours, "jax_repeated_kv_s": dt_g_jax,
                "ratio": round(dt_g_jax / dt_g_ours, 4),
            }
        except Exception as e:  # noqa: BLE001 — sub-measurement guard
            res["gqa"] = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
    return res


def _bench_train_step(on_tpu: bool, peak: float):
    """Flagship transformer fwd+bwd+update MFU (6*N*T accounting)."""
    import jax
    import jax.numpy as jnp

    from mpi4torch_tpu.models import transformer as T

    if on_tpu:
        cfg = T.TransformerConfig(vocab=32768, d_model=2048, n_heads=16,
                                  n_layers=8, d_ff=8192, max_seq=2048)
        batch, dtype, iters = 8, jnp.bfloat16, 10
        # The dense (batch, seq, vocab) logits alone are 1 GiB bf16 (+
        # f32 softmax intermediates) per step at this config; the
        # chunked-vocab loss never materializes them
        # (models/transformer.py _chunked_ce) — 8 x 4096-wide slabs.
        vocab_chunk = 4096
    else:
        cfg = T.TransformerConfig(vocab=256, d_model=64, n_heads=4,
                                  n_layers=2, d_ff=128, max_seq=64)
        batch, dtype, iters = 2, jnp.float32, 2
        vocab_chunk = 64

    params = T.init_transformer(jax.random.PRNGKey(0), cfg, dtype=dtype)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, cfg.max_seq),
                                0, cfg.vocab, jnp.int32)

    def _variant_step(vc):
        def f(params, tokens):
            loss, grads = jax.value_and_grad(
                lambda p: T.lm_loss(cfg, p, tokens, vocab_chunk=vc))(params)
            new = jax.tree.map(lambda p, g: p - 1e-3 * g.astype(p.dtype),
                               params, grads)
            return loss, new
        return jax.jit(f)

    step = _variant_step(vocab_chunk)
    dt = _timeit(step, params, tokens, iters=iters)

    n_params = sum(x.size for x in jax.tree.leaves(params))
    n_tokens = batch * cfg.max_seq
    s, hd = cfg.max_seq, cfg.d_model // cfg.n_heads
    # 6*N*T dense accounting + causal attention matmuls (fwd 2*2*B*H*S^2*
    # Dh/2 per layer, x3 for fwd+bwd model FLOPs — recompute excluded,
    # as in _bench_flash).
    attn = 3.0 * 2.0 * batch * cfg.n_heads * s * s * hd * cfg.n_layers
    flops = 6.0 * n_params * n_tokens + attn
    achieved = flops / dt

    # Where-does-the-time-go breakdown (VERDICT r4 item 8: if MFU misses
    # the 0.4 bar, the committed artifact must identify the next
    # optimization).  Each stage is timed as its own jitted program; the
    # differences attribute the step time: forward vs backward
    # (value_and_grad minus forward), optimizer update (full step minus
    # value_and_grad), the loss head (forward-with-loss minus
    # forward-to-logits), and attention share (the flash sub-bench at
    # this model's per-layer shape x n_layers).  Guarded: a breakdown
    # failure must never erase the headline number.
    def _breakdown():
        fwd_loss = jax.jit(lambda p: T.lm_loss(cfg, p, tokens,
                                               vocab_chunk=vocab_chunk))
        fwd_bwd = jax.jit(jax.value_and_grad(
            lambda p: T.lm_loss(cfg, p, tokens, vocab_chunk=vocab_chunk)))
        hidden = jax.jit(lambda p: T.forward(cfg, p, tokens,
                                             return_hidden=True))
        t_fwd_loss = _timeit(fwd_loss, params, iters=max(iters // 2, 2))
        t_fwd_bwd = _timeit(fwd_bwd, params, iters=max(iters // 2, 2))
        t_hidden = _timeit(hidden, params, iters=max(iters // 2, 2))

        from mpi4torch_tpu.ops import flash as _flash

        kq = jax.random.normal(jax.random.PRNGKey(2),
                               (batch, s, cfg.n_heads, hd), dtype)
        # Grad w.r.t. ALL of q/k/v: requesting only dq would let XLA
        # dead-code-eliminate the dkv backward kernel and under-report
        # attention's true share.
        att = jax.jit(jax.value_and_grad(lambda q, k, v: jnp.sum(
            _flash.flash_attention(q, k, v, causal=True,
                                   impl="auto").astype(jnp.float32) ** 2),
            argnums=(0, 1, 2)))
        t_attn_layer = _timeit(att, kq, kq, kq, iters=max(iters // 2, 2))
        return {
            "forward_with_loss_s": t_fwd_loss,
            "forward_to_hidden_s": t_hidden,
            "loss_head_s": max(t_fwd_loss - t_hidden, 0.0),
            "fwd_bwd_s": t_fwd_bwd,
            "backward_s": max(t_fwd_bwd - t_fwd_loss, 0.0),
            "optimizer_s": max(dt - t_fwd_bwd, 0.0),
            "attention_fwd_bwd_all_layers_s": t_attn_layer * cfg.n_layers,
            "attention_share_of_step": round(
                t_attn_layer * cfg.n_layers / dt, 4),
        }

    breakdown = _guarded("train_step.breakdown", _breakdown)

    # Ground the hand accounting against the compiler's own count: XLA's
    # cost analysis of the compiled step vs the 6*N*T model FLOPs.  Two
    # opposite-signed deviations are expected: XLA additionally counts
    # the flash recompute + optimizer arithmetic (ratio up), while 6*N*T
    # charges the embedding table as if it were a matmul when the actual
    # lookup is a gather (ratio down — dominant at small configs where
    # the table is a large parameter share, e.g. 0.85 on the CPU smoke
    # config).  A ratio far below the embedding share would mean the
    # accounting — and therefore the MFU — is inflated.
    def _xla_flops():
        # step is already @jax.jit — lower it directly (cache-friendly,
        # no redundant re-wrap/trace).
        ca = step.lower(params, tokens).compile().cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        if "flops" not in ca:
            raise KeyError(
                f"no 'flops' in cost_analysis keys {sorted(ca)[:10]}")
        return float(ca["flops"])

    xla_flops = _guarded("train_step.xla_cost", _xla_flops)
    if isinstance(xla_flops, dict):   # error stanza: count unavailable
        xla_ratio = None
    else:
        xla_ratio = round(xla_flops / flops, 3) if flops else None

    # Ablation: what the TPU-native pieces buy at this exact config,
    # measured, not argued.  (a) The Pallas flash kernels swapped for
    # the module's jnp blockwise fallback — still O(seq) memory, so the
    # opponent is the best non-kernel implementation, not a dense-scores
    # strawman; forced by patching the TRACE-TIME eligibility predicates
    # around a fresh jit closure.  (b) The dense unchunked CE head
    # (vocab_chunk=0): materializes the (batch, seq, vocab) logits this
    # config's chunking exists to avoid — may legitimately OOM, which
    # its own guard records.  Ordered last so neither can disturb the
    # numbers above.
    def _ablation():
        from mpi4torch_tpu.ops import flash as _flash

        qs = jax.ShapeDtypeStruct((batch, s, cfg.n_heads, hd), dtype)
        out = {
            "full_pipeline_s": dt,
            # False (the CPU smoke path) means both timed variants ran
            # the same jnp code and the "speedup" is pure noise.
            # Mirrors the impl="auto" dispatch exactly.
            "pallas_in_baseline": bool(
                on_tpu and _flash._eligible(qs, qs)
                and _flash._bwd_eligible(qs, qs)),
        }
        saved = _flash._eligible, _flash._bwd_eligible
        _flash._eligible = lambda q, k: False
        _flash._bwd_eligible = lambda q, k: False
        try:
            dt_jnp = _timeit(_variant_step(vocab_chunk), params, tokens,
                             iters=max(iters // 2, 2))
        finally:
            _flash._eligible, _flash._bwd_eligible = saved
        out["attn_jnp_blockwise_s"] = dt_jnp
        out["pallas_kernel_step_speedup"] = round(dt_jnp / dt, 4)

        def _dense_ce():
            dt_dense = _timeit(_variant_step(0), params, tokens,
                               iters=max(iters // 2, 2))
            return {"seconds_per_step": dt_dense,
                    "chunked_ce_step_speedup": round(dt_dense / dt, 4)}

        out["dense_ce"] = _guarded("train_step.ablation.dense_ce",
                                   _dense_ce)
        return out

    ablation = _guarded("train_step.ablation", _ablation)

    return {
        "tflops": round(achieved / 1e12, 3),
        "mfu": round(achieved / peak, 4) if peak else None,
        "xla_flops_vs_model_flops": xla_ratio,
        "n_params": n_params,
        "tokens_per_step": n_tokens,
        "vocab_chunk": vocab_chunk,
        "dtype": str(jnp.dtype(dtype)),
        "seconds_per_step": dt,
        "breakdown": breakdown,
        "ablation": ablation,
    }


def _bench_schedule_synthesis(on_tpu: bool):
    """Schedule synthesis (mpi4torch_tpu.csched.synth): the
    deterministic synthesized-vs-ring census sweep.  For each (world
    shape, size bucket) the census-ranked winner of the bounded IR
    program family is compared against the hand-written DETERMINISTIC
    ring (the ordered fold — the schedule a synthesized winner actually
    replaces) on wire bytes per rank and sequential steps; the verdict
    is hardware-independent (the repo's census regression currency), so
    it is recorded even when no TPU is attached."""
    import jax

    from mpi4torch_tpu import csched

    ndev = len(jax.devices())
    worlds = sorted({ndev, max(2, ndev // 2), 2} - {0, 1})
    sizes = (1 << 10, 1 << 14, 1 << 18, 1 << 22)
    entries = {}
    any_beats = False
    for n in worlds:
        per = {}
        for nbytes in sizes:
            res = csched.synthesize(n, nbytes, 4)
            beats = bool(res["synthesis_beats_ring"])
            any_beats = any_beats or beats
            per[str(nbytes)] = {
                "winner": res["winner"],
                "chain": res["chain"],
                "wire_bytes_per_rank":
                    res["census"]["wire_bytes_per_rank"],
                "seq_steps": res["census"]["seq_steps"],
                "ring_wire_bytes_per_rank":
                    res["ring_census"]["wire_bytes_per_rank"],
                "ring_seq_steps": res["ring_census"]["seq_steps"],
                "wire_advantage": round(
                    res["ring_census"]["wire_bytes_per_rank"]
                    / max(1, res["census"]["wire_bytes_per_rank"]), 3),
                "synthesis_beats_ring": beats,
            }
        entries[str(n)] = per
    return {
        "mode": "deterministic census sweep (wire bytes / seq steps)",
        "worlds": worlds,
        "entries": entries,
        "synthesis_beats_ring": any_beats,
    }


def _bench_allreduce_tiers(on_tpu: bool):
    """Tier-stack synthesis stanza (ISSUE 18): the bandwidth-weighted
    census verdict of the multi-pod tier-dimension search.  Per nested
    factorization of the attached world and size bucket, the weighted
    winner under a skewed slow-outer bandwidth profile (outer tier 20x
    under the inner — the DCN-under-ICI shape) is compared against the
    flat ``bidir`` baseline: the per-tier wire table, the weighted
    cost, and ``tier_weighted_gain`` (baseline weighted cost over
    winner's — > 1.0 is a win).  Deterministic census arithmetic, so
    recorded on any hardware, like the flat synthesis stanza."""
    import jax

    from mpi4torch_tpu import csched

    ndev = len(jax.devices())
    stacks = [s for s in ((2, 2, 2), (4, 2), (2, 4))
              if _prod(s) == ndev] or ([(2, ndev // 2)]
                                       if ndev >= 4 and ndev % 2 == 0
                                       else [])
    sizes = (1 << 10, 1 << 14, 1 << 18)
    entries = {}
    any_gain = False
    for stack in stacks:
        skew = tuple([1.0] * (len(stack) - 1) + [0.05])
        per = {}
        for nbytes in sizes:
            res = csched.synthesize_tiers(ndev, nbytes, 4, tiers=stack,
                                          tier_bandwidths=skew)
            gain = (res["bidir_weighted_cost"]
                    / max(res["weighted_cost"], 1e-12))
            any_gain = any_gain or res["beats_bidir"]
            per[str(nbytes)] = {
                "winner": res["winner"],
                "chain": res["chain"],
                "composition": res["composition"],
                "tier_wire": res["tier_wire"],
                "bidir_tier_wire": res["bidir_tier_wire"],
                "weighted_cost": res["weighted_cost"],
                "bidir_weighted_cost": res["bidir_weighted_cost"],
                "tier_weighted_gain": round(gain, 3),
                "outer_tier_wire_reduction": (
                    res["bidir_tier_wire"][-1] - res["tier_wire"][-1]),
                "beats_bidir": res["beats_bidir"],
            }
        entries["x".join(map(str, stack))] = per
    return {
        "mode": ("deterministic bandwidth-weighted census sweep "
                 "(slow-outer skew 20:1)"),
        "nranks": ndev,
        "stacks": ["x".join(map(str, s)) for s in stacks],
        "entries": entries,
        "tier_weighted_gain": any_gain,
    }


def _prod(t):
    p = 1
    for v in t:
        p *= int(v)
    return p


def _bench_transport(on_tpu: bool):
    """Transport-runtime stanza (ISSUE 16): the first HONEST wall-clock
    numbers for Mode B — ``process_parallel_speedup`` is thread-backend
    wall time over process-backend wall time for a GIL-bound per-rank
    compute step + allreduce, recorded next to the cpu_count that
    bounds it (on a 1-core container the honest number is ~1.0; the
    claim the repo stands behind everywhere is the DETERMINISTIC wire
    census, which must be identical across backends and is asserted
    here, not just reported)."""
    import os as _os
    import time as _time

    import jax.numpy as jnp
    import numpy as np

    import mpi4torch_tpu as mpi
    from mpi4torch_tpu import obs
    from mpi4torch_tpu.obs.reconcile import measured_wire_table

    NR, SPIN = 3, 120_000

    def body(rank):
        # Pure-Python FNV spin: holds the GIL, so rank-threads serialize
        # and worker processes don't — the workload that makes the
        # speedup a statement about the transport, not about numpy.
        h = 0x811C9DC5
        for i in range(SPIN):
            h = ((h ^ (rank + i)) * 0x01000193) & 0xFFFFFFFF
        x = jnp.full(256, float(h % 97), jnp.float32) * (rank + 1)
        return np.asarray(mpi.COMM_WORLD.Allreduce(x, mpi.MPI_SUM))

    def timed(backend):
        with obs.trace() as t:
            t0 = _time.perf_counter()
            out = mpi.run_ranks(body, NR, backend=backend)
            dt = _time.perf_counter() - t0
        census = measured_wire_table(t.events)
        return dt, out, {"wire_bytes": census["wire_bytes"],
                         "counts": census["counts"],
                         "logical_events": census["logical_events"]}

    # Warm both paths once (jit + worker-pool spawn) so the measured
    # pass prices the steady state the pool exists to provide.
    timed("thread")
    timed("process")
    t_thread, out_t, census_t = timed("thread")
    t_process, out_p, census_p = timed("process")

    for r in range(NR):
        assert np.array_equal(out_t[r], out_p[r]), \
            f"transport parity broke at rank {r}"
    assert census_t == census_p, \
        f"wire census diverged across backends: {census_t} vs {census_p}"

    from mpi4torch_tpu.transport.pool import shared_pool
    return {
        "ranks": NR,
        "cpu_count": _os.cpu_count(),
        "thread_wall_s": round(t_thread, 4),
        "process_wall_s": round(t_process, 4),
        "process_parallel_speedup": round(t_thread / max(t_process, 1e-9),
                                          3),
        "wire_census": census_t,
        "wire_census_identical": True,     # asserted above
        "pool_workers_spawned": shared_pool().spawned_total,
        "note": ("GIL-bound spin + allreduce; speedup is bounded by "
                 "cpu_count and IPC overhead — ~1.0 on a 1-core box "
                 "is the honest reading, the bitwise census is the "
                 "portable claim"),
    }


def _bench_ctl(on_tpu: bool):
    """Self-tuning controller stanza (ISSUE 19): the deterministic
    closed loop — a per-byte brownout on the episode's one collective
    drives the EWMA goodput estimate under the low watermark, the
    controller escalates to the q8/synth_q8 winner through an
    epoch-fenced consensus (the escalated phase is asserted bitwise
    against the explicit-q8 oracle), the fault clears, and the
    de-escalation restores the pre-episode configuration bitwise.  The
    recorded verdict is census arithmetic (weighted cost, per-tier
    wire) plus the ledger's own account of WHY it switched; also pinned
    here: the controller-off discipline — constructing and polling a
    disabled controller leaves the jitted lowering text bit-identical."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    import mpi4torch_tpu as mpi
    from jax import shard_map
    from mpi4torch_tpu.ctl import SelfTuningController
    from mpi4torch_tpu.ctl.__main__ import closed_loop_episode

    mesh = Mesh(np.asarray(jax.devices()), ("w",))
    cm = mpi.comm_from_mesh(mesh, "w")
    probe = jnp.arange(256, dtype=jnp.float32)

    def lowered():
        return jax.jit(shard_map(
            lambda a: cm.Allreduce(a, mpi.MPI_SUM),
            mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False)).lower(probe).as_text()

    text_before = lowered()
    off = SelfTuningController(n_ranks=8, tiers=(2, 2, 2))
    off.poll()
    off_identical = lowered() == text_before

    ev = closed_loop_episode(n=8, tiers=(2, 2, 2), backend="thread")
    esc, rec = ev["escalation"], ev["recovery"]
    bitwise_escalated = all(
        np.array_equal(g, w)
        for g, w in zip(ev["escalated"], ev["oracle_q8"]))
    bitwise_recovered = all(
        np.array_equal(g, w)
        for g, w in zip(ev["recovered"], ev["exact_before"]))
    return {
        "mode": "deterministic closed loop (eager thread backend)",
        "escalation_trigger": esc.trigger if esc else None,
        "escalation_epoch": esc.epoch if esc else None,
        "weighted_cost_before": esc.old["weighted_cost"] if esc else None,
        "weighted_cost_after": esc.new["weighted_cost"] if esc else None,
        "tier_wire_before": esc.old["tier_wire"] if esc else None,
        "tier_wire_after": esc.new["tier_wire"] if esc else None,
        "cost_reduction": round(
            esc.old["weighted_cost"] / max(esc.new["weighted_cost"], 1e-9),
            3) if esc else None,
        "compression_during": ev["compression_during"],
        "bitwise_vs_q8_oracle": bitwise_escalated,
        "stale_view_fenced": ev["stale_fenced"],
        "recovery_trigger": rec.trigger if rec else None,
        "recovery_epoch": rec.epoch if rec else None,
        "compression_after": ev["compression_after"],
        "bitwise_vs_pre_episode": bitwise_recovered,
        "ledger_triggers": ev["ledger"].triggers(),
        "controller_off_lowering_identical": off_identical,
        "note": ("brownout -> crossover escalation -> recovery; every "
                 "switch consensus-ratified, both phase results bitwise "
                 "against their oracles"),
    }


def _guarded(name: str, fn, *args):
    """Run one sub-bench; on failure return an error stanza instead of
    propagating, so later stanzas still run.  main() exits non-zero
    when any stanza recorded one."""
    try:
        res = fn(*args)
        _note(f"{name}: {json.dumps(res)}")
        return res
    except Exception as e:  # noqa: BLE001 — recorded; main() exits non-zero
        tail = traceback.format_exc().strip().splitlines()[-6:]
        _note(f"{name} FAILED: {e!r}")
        return {"error": f"{type(e).__name__}: {str(e)[:300]}",
                "traceback_tail": tail}


def main() -> None:
    result = {
        "metric": "allreduce_fwd_bwd_bandwidth_per_chip",
        "value": 0.0,
        "unit": "GB/s",
        "vs_baseline": 0.0,
    }
    from mpi4torch_tpu.utils.compile_cache import use_compile_cache

    result["compile_cache_dir"] = use_compile_cache()
    platform, cpu_requested = _platform_or_exit("bench.py")

    import jax

    device_kind = jax.devices()[0].device_kind
    _note(f"platform={platform} device_kind={device_kind} "
          f"devices={len(jax.devices())}")
    on_tpu = platform == "tpu"
    # The harness's own CPU smoke has no device peaks, so no utilization
    # or roofline fraction is derived there.
    peak, hbm = _chip_specs(device_kind) if on_tpu else (None, None)

    try:
        # The per-iteration completion fetch (see _force) has a cost of
        # its own; measure that floor on an already-materialized buffer
        # so every seconds_per_step below can be read against it.
        def _floor():
            import jax.numpy as jnp

            # Two leaves, like every real (loss, grads) output.  This is
            # a LOWER bound on the probe overhead: multi-device outputs
            # additionally pay a cross-device reduce inside the probe
            # (their [:,0,..] column spans the rank-sharded axis), which
            # an unsharded floor buffer cannot represent.
            ready = (jnp.zeros((8,), jnp.float32),
                     jnp.zeros((8,), jnp.float32))
            return _timeit(lambda: ready, iters=10)

        result["timing_floor_s"] = _guarded("timing_floor", _floor)

        ar = _guarded("allreduce", _bench_allreduce, on_tpu, hbm)
        arc = _guarded("allreduce_compressed", _bench_allreduce_compressed,
                       on_tpu)
        arm = _guarded("allreduce_compressed_multipath",
                       _bench_allreduce_compressed_multipath, on_tpu)
        arf = _guarded("allreduce_fused", _bench_allreduce_fused, on_tpu)
        ara = _guarded("allreduce_algorithms", _bench_allreduce_algorithms,
                       on_tpu)
        ovz = _guarded("overlap_zero", _bench_overlap_zero, on_tpu)
        gov = _guarded("guard_overhead", _bench_guard_overhead, on_tpu)
        obsov = _guarded("obs_overhead", _bench_obs_overhead, on_tpu)
        deg = _guarded("degraded_mode", _bench_degraded_mode, on_tpu)
        rsh = _guarded("reshard", _bench_reshard, on_tpu)
        ela = _guarded("elastic", _bench_elastic, on_tpu)
        srv = _guarded("serve", _bench_serve, on_tpu)
        srvp = _guarded("serve_paged", _bench_serve_paged, on_tpu)
        syn = _guarded("schedule_synthesis", _bench_schedule_synthesis,
                       on_tpu)
        tirs = _guarded("allreduce_tiers", _bench_allreduce_tiers, on_tpu)
        trn = _guarded("transport", _bench_transport, on_tpu)
        ctlr = _guarded("ctl", _bench_ctl, on_tpu)
        flash_res = _guarded("flash", _bench_flash, on_tpu, peak)
        ratio_res = _guarded("flash_reference_ratio",
                             _bench_flash_reference_ratio, on_tpu)
        train_res = _guarded("train_step", _bench_train_step, on_tpu, peak)

        target_gbps = 36.0  # 0.8 * ~45 GB/s v5e ICI per-link (BASELINE.md)
        gbps = float(ar.get("gbps", 0.0)) if "error" not in ar else 0.0
        # vs_baseline compares against the ICI target ONLY when ICI is in
        # the path (n > 1).  A single chip's allreduce is HBM traffic —
        # against a 36 GB/s wire target it reads as an absurd win
        # (r04 recorded 271x) — so there vs_baseline reports the
        # HBM-roofline fraction: 1.0 = the chip's own ceiling.
        n_chips = ar.get("n_devices") or 1
        if n_chips > 1:
            vs_baseline = gbps / target_gbps
        elif ar.get("suspect"):
            vs_baseline = 0.0   # broken measurement must not read as a win
        else:
            vs_baseline = ar.get("hbm_roofline_fraction") or 0.0
        result.update({
            "value": round(gbps, 3),
            "vs_baseline": round(vs_baseline, 4),
            "n_devices": ar.get("n_devices"),
            "platform": platform,
            "device_kind": device_kind,
            "cpu_requested": cpu_requested,
            "allreduce": ar,
            "allreduce_compressed": arc,
            "allreduce_compressed_multipath": arm,
            "allreduce_fused": arf,
            "allreduce_algorithms": ara,
            "overlap_zero": ovz,
            "guard_overhead": gov,
            "obs_overhead": obsov,
            "degraded_mode": deg,
            "reshard": rsh,
            "elastic": ela,
            "serve": srv,
            "serve_paged": srvp,
            "schedule_synthesis": syn,
            "allreduce_tiers": tirs,
            "transport": trn,
            "ctl": ctlr,
            "peak_flops_assumed": peak,
            "hbm_gbps_assumed": hbm,
            "flash_attention_fwd_bwd": flash_res,
            "flash_reference_ratio": ratio_res,
            "train_step": train_res,
            "note": ("ring-allreduce bytes-on-wire accounting"
                     if n_chips > 1 else
                     "single chip: HBM-limited pipeline throughput, no "
                     "ICI; MFU sub-benches are the chip-meaningful "
                     "numbers"),
        })
    except Exception as e:  # noqa: BLE001 — reported below, exits non-zero
        result["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        result["traceback_tail"] = (
            traceback.format_exc().strip().splitlines()[-6:])
    finally:
        print(json.dumps(result), flush=True)
    _exit_on_errors(result)


if __name__ == "__main__":
    main()
