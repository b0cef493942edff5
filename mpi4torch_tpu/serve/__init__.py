"""Production inference serving: continuous batching on the overlap
scheduler, with TP-sharded KV-cache decode and latency-tier collective
selection.

The "millions of users" half of the north star, composed entirely from
the training stack's ingredients:

* **engine** (:mod:`.engine`) — a continuous-batching decode loop over
  a fixed-capacity slot table: ragged admission of new requests into
  free slots each step, eviction on EOS/budget, per-slot
  position/length state through ONE static-shape compiled step program
  (no retrace as traffic churns), free slots unmapped, masked and
  provably inert, one paged cache manager behind every
  ``ServeConfig``.  Greedy and sampled decoding are BITWISE the per-request
  ``models/transformer.generate`` tokens — the engine samples with the
  same rule under the same key discipline.
* **KV sharding** (:mod:`.kv`) — heads sharded over the communicator
  by the ``parallel/tp.py`` conventions (the cache is the HBM-resident
  state that bounds serving batch size; GQA and TP savings multiply),
  two collectives per layer, and the :func:`admit_zero3` train→serve
  handoff riding the planned ``comm.Reshard`` path (arXiv 2112.01075
  via ``parallel.zero.zero3_to_tp``).
* **decode comm on the overlap scheduler** — per-layer TP allreduces
  issued split-phase through
  :func:`~mpi4torch_tpu.overlap.overlap_split_allreduce` (windowed
  chunk buckets, >= 2 transfers in flight), censused by
  :func:`~mpi4torch_tpu.overlap.scheduled_exposure` strictly < 1.0
  (``make serve-smoke`` asserts it; blocking baseline = 1.0).
* **latency-tier selection** — decode messages are a few KiB, the
  regime "The Big Send-off" (PAPERS.md) separates from bandwidth-bound
  training traffic: auto selection keys on the real chunk sizes and
  lands on rhd/tree below the measured crossover, with the
  ``tune.select_auto`` latency-tier guard keeping aliased
  bandwidth-tier cache winners out (:func:`latency_report` is the
  deterministic evidence).

Fault plans (mpi4torch_tpu.resilience) compose at the Mode B
chokepoints with zero serving-specific hooks: a ``rank_death``
mid-decode raises an attributed ``RankFailedError`` on every survivor.
See doc/serving.md for the lifecycle walkthrough and recipes.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from .. import config as _config
from ..utils.profiling import (ServeStats, reset_serve_stats,
                               serve_stats)
from .engine import (Engine, POLICIES, SHED_POLICIES, STATUS_EXPIRED,
                     STATUS_OK, STATUS_SHED, QueueFullError, Request,
                     ServeConfig, select_rows)
from .kv import (admit_zero3, decode_step_paged, decode_step_tp,
                 init_kv_cache_tp, init_kv_pool_tp, prefill_chunk_tp,
                 prefill_tp, shard_params_tp, validate_tp)
from .paging import BlockManager

__all__ = [
    "Engine",
    "ServeConfig",
    "Request",
    "POLICIES",
    "SHED_POLICIES",
    "STATUS_OK",
    "STATUS_EXPIRED",
    "STATUS_SHED",
    "QueueFullError",
    "select_rows",
    "decode_step_tp",
    "decode_step_paged",
    "prefill_tp",
    "prefill_chunk_tp",
    "shard_params_tp",
    "init_kv_cache_tp",
    "init_kv_pool_tp",
    "BlockManager",
    "admit_zero3",
    "validate_tp",
    "latency_report",
    "decode_message_bytes",
    "stats",
    "reset_stats",
    "ServeStats",
]

# Observability surface (utils/profiling.py): process-wide aggregate of
# every engine's counters/spans, and its reset.
stats = serve_stats
reset_stats = reset_serve_stats


def decode_message_bytes(cfg, serve_cfg, dtype=jnp.float32) -> int:
    """Bytes of ONE decode collective payload: the ``(slots, d_model)``
    row-parallel partial sum every layer allreduces twice per step —
    the real per-token message size latency-tier selection keys on."""
    return int(serve_cfg.slots) * int(cfg.d_model) \
        * jnp.dtype(dtype).itemsize


def latency_report(cfg, serve_cfg, nranks: int,
                   dtype=jnp.float32) -> dict:
    """Deterministic latency-tier evidence for an engine's decode
    traffic: the payload/chunk message sizes, the autotuner cache
    bucket they key into (:func:`mpi4torch_tpu.tune.bucket_nbytes` —
    the bucket a training tail of the same power-of-two size would
    share, which is what the ``select_auto`` tier guard exists for),
    the selector's pick per chunk, and whether that pick sits in the
    latency tier.  Pure function of config + tune state — the
    serve-smoke lane asserts on it next to the lowered-program span
    census."""
    from .. import tune as _tune

    payload = decode_message_bytes(cfg, serve_cfg, dtype)
    k = _config.serve_decode_buckets()
    chunk = max(payload // k, 1)
    algo = _tune.select_auto(nbytes=chunk, dtype=jnp.dtype(dtype),
                             nranks=int(nranks))
    spec = _tune.get_algorithm(algo)
    crossover = _config.latency_crossover_bytes()
    return {
        "nranks": int(nranks),
        "message_bytes": payload,
        "decode_buckets": k,
        "chunk_bytes": chunk,
        "cache_bucket_bytes": _tune.bucket_nbytes(chunk),
        "latency_crossover_bytes": crossover,
        "algorithm": algo,
        "latency_optimal": bool(spec.latency_optimal),
        "bandwidth_optimal": bool(spec.bandwidth_optimal),
        # The serving claim: with a measured crossover above the decode
        # chunk size, selection sits in the latency tier (and never on
        # a bandwidth-tier schedule).
        "latency_tier": bool(
            crossover is not None and chunk <= crossover
            and not spec.bandwidth_optimal),
    }
