"""The ``smallthinker`` family for the ``train_family`` traffic kind
(SmallThinker-21BA3B): what that kind takes from a family, as
``families/kimi_linear.py`` lists it, for a stack of window and full
grouped-query attention layers whose top-k ReGLU experts are spread over
the chips that share a layer.

What differs from the other families: the parameters are not all
replicated.  ``make_params`` lays every expert leaf (``w1``, ``w2`` of a
layer's ``experts``) over the mesh on its expert axis, so that a chip
holds ``moe_num_primary_experts / chips_per_layer`` experts of each
layer, and the step is a ``shard_map`` of the family's own that hands
each chip its experts and a communicator adopted from the mesh
(``comm_from_mesh``): ``train_step(comm_ep=...)`` exchanges the rows and
averages what is replicated.  The step hands out the first chip's
routing counters and, beside ``moe_rows``, what the exchange counted.
"""

from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp

from benchmarks import flops
from benchmarks.weights import _dense, _scale, seed_key

# The grouped products compile to custom calls whose op_name the compiler
# writes itself (``ragged-dot-none``); only the expert layer makes them.
KERNELS = {"ragged-dot": "moe"}
_TOP, _LAYER = 0, 1
# As families/kimi_linear.py found it had to: embedding rows of unit
# variance and every block's output projection a tenth of 1/sqrt(fan-in),
# so that the stream of these first eight layers stays mostly the token's
# own embedding and the routers, which read that stream as it is, route
# by token and evenly (uniform token ids: an expert's rows a chip are
# binomial around 1,536).
_EMBED_STD, _OUT_SCALE = 1.0, 0.1
# All-to-alls of an expert layer's rows a step and direction: forward,
# recomputed, backward, out and back each.
EXCHANGES_A_LAYER = 6


def layer_kinds(cfg: dict) -> list:
    """Per layer ``(window or 0, rotated)``."""
    n = cfg["num_hidden_layers"]
    sliding, rotated = cfg["sliding_window_layout"], cfg["rope_layout"]
    if len(sliding) != n or len(rotated) != n:
        raise ValueError("sliding_window_layout and rope_layout name every "
                         f"layer 0..{n - 1} once")
    return [(cfg["sliding_window_size"] if sliding[i] else 0,
             bool(rotated[i])) for i in range(n)]


def chips_per_layer(cfg: dict) -> int:
    return int(cfg["deployment_share"]["chips_per_layer"])


# ---------------------------------------------------------------- program

def scopes() -> dict:
    """``{scope key: what the op_name holds}``; the order decides: the
    exchange lies inside ``moe`` and the attention itself inside ``attn``
    (``attn`` is then what of the mixers is not under ``attn_window`` or
    ``attn_full``: projections, rotation, output projection)."""
    from mpi4torch_tpu.utils.profiling import LAYER_SCOPES

    return {k: LAYER_SCOPES[k] for k in
            ("moe_exchange", "moe", "attn_window", "attn_full", "attn")}


def transformer_config(cfg: dict, remat: bool = False):
    from mpi4torch_tpu.models.transformer import (GQA, LayerSpec,
                                                  TransformerConfig)
    from mpi4torch_tpu.parallel.moe import Experts

    if not cfg["moe_primary_router_apply_softmax"] \
            or not cfg["norm_topk_prob"] or cfg["rope_scaling"] \
            or cfg["tie_word_embeddings"]:
        raise ValueError("smallthinker: softmax routing renormalised over "
                         "the chosen, no rope scaling and an untied head "
                         "are what is built")
    n_experts, chips = cfg["moe_num_primary_experts"], chips_per_layer(cfg)
    if n_experts % chips:
        raise ValueError(f"{n_experts} experts over {chips} chips")
    experts = Experts(
        n_experts=n_experts, top_k=cfg["moe_num_active_primary_experts"],
        d_expert=cfg["moe_ffn_hidden_size"], first_expert=0,
        n_held=n_experts // chips, score="softmax", renorm=True, act="reglu")
    h = cfg["num_attention_heads"]
    layers = tuple(
        LayerSpec(mixer=GQA(n_heads=h, n_kv_heads=cfg["num_key_value_heads"],
                            head_dim=cfg["head_dim"], window=window,
                            rope=rotated,
                            rope_theta=float(cfg["rope_theta"])),
                  ffn=experts, route_on="input")
        for window, rotated in layer_kinds(cfg))
    # nope: no position table; a sliding layer's mixer rotates its own
    # queries and keys, a full layer has no position signal of its own.
    # d_ff names nothing: every layer's FFN is its experts.
    return TransformerConfig(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"], n_heads=h,
        n_kv_heads=cfg["num_key_value_heads"],
        n_layers=cfg["num_hidden_layers"], d_ff=cfg["moe_ffn_hidden_size"],
        max_seq=cfg["max_position_embeddings"], nope=True, norm="rmsnorm",
        norm_eps=float(cfg["rms_norm_eps"]), ffn="swiglu", remat=remat,
        layers=layers)


def _local_rows(comm, tokens, per_chip: int, broken: str):
    """This chip's rows of the batch (all of it on one chip: ``comm`` is
    ``None``)."""
    rank = 0 if comm is None else jnp.asarray(comm.rank)
    local = jax.lax.dynamic_slice_in_dim(tokens, rank * per_chip, per_chip, 0)
    if broken == "half_batch":
        local = jnp.concatenate([local[:1]] * per_chip, axis=0)
    return local


class _RowsAtHome:
    """``--break rows_at_home``: a communicator whose ``Alltoall`` moves
    nothing: every chip keeps the rows it had for the other chips'
    experts and runs them through its own.  Everything else is the
    communicator's."""

    def __init__(self, comm):
        self._comm = comm

    def __getattr__(self, name):
        return getattr(self._comm, name)

    def Alltoall(self, x, gatheraxis, scatteraxis, numelem):
        return jnp.concatenate(
            jnp.split(x, self._comm.size, scatteraxis), gatheraxis)


def _communicator(tcfg, mesh, dp: bool, broken: str):
    """The mesh's axis as the step's expert-parallel (and so
    data-parallel) communicator; ``None`` on one chip."""
    import mpi4torch_tpu as mpi

    (axis,) = mesh.axis_names
    held = tcfg.layers[0].ffn
    if held.n_held * mesh.size != held.n_experts:
        raise ValueError(
            f"the configuration lays a layer's {held.n_experts} experts "
            f"over {held.n_experts // held.n_held} chips; the mesh has "
            f"{mesh.size}")
    if mesh.size == 1:
        return None
    if not dp:
        raise ValueError("chips that share a layer's experts each bring "
                         "their own rows of the batch: data_parallel")
    comm = mpi.comm_from_mesh(mesh, axis)
    return _RowsAtHome(comm) if broken == "rows_at_home" else comm


def param_specs(mesh, params):
    """A ``PartitionSpec`` a leaf: a layer's expert leaves on their expert
    axis over the mesh, every other leaf replicated."""
    from jax.sharding import PartitionSpec as P

    from mpi4torch_tpu.models.transformer import held_expert_leaf

    (axis,) = mesh.axis_names
    return jax.tree_util.tree_map_with_path(
        lambda path, _: P(axis) if mesh.size > 1 and held_expert_leaf(path)
        else P(), params)


class _StepBesideGradient:
    """The jitted step, with the gradient program of the same
    arguments compiled BESIDE it: ``lower(params, tokens)`` lowers both,
    and ``compile()`` compiles :func:`build_grad_norms`' program on a
    thread while the step compiles on this one (the compiler holds no
    Python lock), and hands back the step's executable as JAX made it.
    The harness asks for the gradient after the window; the jitted
    function it is handed then finds the program already compiled (an
    ahead-of-time compile is what the function's next call with the same
    arguments runs).  A run so pays the second large compile, two minutes
    on the chip, beside the first and before its first step, and not
    behind the window; ``train_compile_s`` covers the two."""

    def __init__(self, step, norms):
        self._step, self._norms = step, norms

    def __call__(self, params, tokens):
        return self._step(params, tokens)

    def lower(self, params, tokens):
        both = self._step.lower(params, tokens), \
            self._norms.lower(params, tokens)
        return _LoweredBesideGradient(*both)


class _LoweredBesideGradient:
    def __init__(self, step, norms):
        self._step, self._norms = step, norms

    def compile(self):
        with ThreadPoolExecutor(max_workers=1) as pool:
            beside = pool.submit(self._norms.compile)
            compiled = self._step.compile()
            beside.result()
        return compiled


def build_train_step(tcfg, mesh, per_chip: int, lr: float, dp: bool,
                     broken: str = ""):
    """``train_step`` over the mesh as one jitted program whose state
    stays where it is, the old parameters' buffers donated: ``(params,
    tokens) -> (loss (chips,), new params, counters)``; the counters are
    the first chip's, and ``ep_rows_received`` ``(expert layers, chips)``
    the rows every chip's experts took.  One object an argument list (a
    second seed in one process compiles nothing), and compiling it also
    compiles the gradient program (:class:`_StepBesideGradient`)."""
    return _train_step(tcfg, mesh, int(per_chip), float(lr), bool(dp), broken)


@functools.lru_cache(maxsize=None)
def _train_step(tcfg, mesh, per_chip, lr, dp, broken):
    from jax.sharding import PartitionSpec as P

    from mpi4torch_tpu.models import transformer as T

    (axis,) = mesh.axis_names
    comm = _communicator(tcfg, mesh, dp, broken)

    def body(params, tokens):
        loss, new, stats = T.train_step(
            tcfg, params, _local_rows(comm, tokens, per_chip, broken),
            comm_ep=comm, lr=lr, return_stats=True)
        if broken == "state_unchanged":
            new = params
        return loss[None], new, jax.tree.map(lambda a: a[None], stats)

    def step(params, tokens):
        specs = param_specs(mesh, params)
        loss, new, stats = jax.shard_map(
            body, mesh=mesh, in_specs=(specs, P()),
            out_specs=(P(axis), specs, P(axis)), check_vma=False)(
                params, tokens)
        first = {k: v[0] for k, v in stats.items()}
        if "ep_rows_sent" in stats:
            first["ep_rows_received"] = jnp.sum(stats["ep_rows_sent"], axis=0)
        return loss, new, first

    return _StepBesideGradient(
        jax.jit(step, donate_argnums=(0,)),
        build_grad_norms(tcfg, mesh, per_chip, dp, broken))


def build_grad_norms(tcfg, mesh, per_chip: int, dp: bool, broken: str = ""):
    """``(params, tokens) -> (leaves,)`` float32: the norm, leaf by leaf
    in the order of ``jax.tree.leaves``, of the gradient ``train_step``
    takes of that batch (of ``lm_loss``, through the average over the
    chips and the exchange's adjoint; an expert leaf's norm over all its
    owners).  A program of its own, run once outside the window; one
    jitted function an argument list, which :func:`build_train_step`
    has compiled beside the step."""
    return _grad_norms(tcfg, mesh, int(per_chip), bool(dp), broken)


@functools.lru_cache(maxsize=None)
def _grad_norms(tcfg, mesh, per_chip, dp, broken):
    from jax.sharding import PartitionSpec as P

    from mpi4torch_tpu.constants import MPI_SUM
    from mpi4torch_tpu.models import transformer as T

    comm = _communicator(tcfg, mesh, dp, broken)

    def body(params, tokens):
        local = _local_rows(comm, tokens, per_chip, broken)

        def loss(p):
            if comm is not None:
                p = T.ep_average_tree(tcfg, comm, p)
            return T.lm_loss(tcfg, p, local, comm_ep=comm)

        flat, _ = jax.tree_util.tree_flatten_with_path(
            jax.grad(loss)(params))
        norms = []
        for path, g in flat:
            square = jnp.sum(jnp.square(g.astype(jnp.float32)))
            if comm is not None and T.held_expert_leaf(path):
                square = comm.Allreduce(square, MPI_SUM, compression=False)
            norms.append(jnp.sqrt(square))
        return jnp.stack(norms)

    def norms(params, tokens):
        return jax.shard_map(
            body, mesh=mesh, in_specs=(param_specs(mesh, params), P()),
            out_specs=P(), check_vma=False)(params, tokens)

    return jax.jit(norms)


# ---------------------------------------------------------------- weights

def make_layer(key, cfg: dict, index: int, dtype):
    """Leaves of decoder layer ``index`` (0-based) in the program's
    layout: fused ``wqkv`` = [q | k | v], fused ``w1`` = [gate | up],
    experts stacked on axis 0, all of the layer's."""
    d, f = cfg["hidden_size"], cfg["moe_ffn_hidden_size"]
    h, h_kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    n = cfg["moe_num_primary_experts"]
    k = jax.random.split(jax.random.fold_in(
        jax.random.fold_in(key, _LAYER), index), 7)
    stack = lambda key, m, n_: jax.vmap(
        lambda kk: _dense(kk, m, n_, dtype))(jax.random.split(key, n))
    return {"ln1": {"scale": _scale(k[0], d, dtype)},
            "ln2": {"scale": _scale(k[1], d, dtype)},
            "mixer": {"wqkv": _dense(k[2], d, (h + 2 * h_kv) * hd, dtype),
                      "wo": _OUT_SCALE * _dense(k[3], h * hd, d, dtype)},
            "experts": {"router": _dense(k[4], d, n, dtype),
                        "bias": jnp.zeros((n,), dtype),
                        "w1": stack(k[5], d, 2 * f),
                        "w2": _OUT_SCALE * stack(k[6], f, d)}}


def make_top(key, cfg: dict, dtype):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    k = jax.random.split(jax.random.fold_in(key, _TOP), 3)
    return {"embed": (_EMBED_STD * jax.random.normal(
                k[0], (v, d), jnp.float32)).astype(dtype),
            "ln_f": {"scale": _scale(k[1], d, dtype)},
            "unembed": _dense(k[2], d, v, dtype)}


def make_params(cfg: dict, seed: int, dtype, sharding=None):
    """The whole tree in one jitted call; given a mesh's replicated
    sharding, with the expert leaves laid over that mesh
    (:func:`param_specs`), so that no chip ever holds a whole layer."""
    import json

    return _params_maker(json.dumps(cfg, sort_keys=True), jnp.dtype(dtype),
                         sharding)(seed_key(seed))


@functools.lru_cache(maxsize=None)
def _params_maker(cfg_json: str, dtype, sharding):
    """One jitted maker a configuration, type and sharding: a run makes
    the seed's parameters three times."""
    import json

    from jax.sharding import NamedSharding

    cfg = json.loads(cfg_json)

    def build(key):
        p = make_top(key, cfg, dtype)
        p["blocks"] = [make_layer(key, cfg, i, dtype)
                       for i in range(cfg["num_hidden_layers"])]
        return p

    if isinstance(sharding, NamedSharding) \
            and sharding.mesh.size == chips_per_layer(cfg) > 1:
        mesh = sharding.mesh
        sharding = jax.tree.map(
            lambda spec: NamedSharding(mesh, spec),
            param_specs(mesh, jax.eval_shape(build, seed_key(0))))
    return jax.jit(build, out_shardings=sharding)


# ------------------------------------------------------------------ counts

def matmul_params_active(cfg: dict) -> int:
    """Matrix parameters one token is multiplied by: the projections, the
    router, all its ``moe_num_active_primary_experts`` experts (whichever
    chip holds them) and the head."""
    d, f = cfg["hidden_size"], cfg["moe_ffn_hidden_size"]
    h, h_kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    a_layer = d * (h + 2 * h_kv) * hd + h * hd * d \
        + d * cfg["moe_num_primary_experts"] \
        + cfg["moe_num_active_primary_experts"] * 3 * d * f
    return cfg["num_hidden_layers"] * a_layer + d * cfg["vocab_size"]


def _flash_shape(cfg: dict, batch: int, seq: int, window: int) -> dict:
    return {"batch": batch, "seq": seq, "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "window": window}


def train_flops_per_token(cfg: dict, seq: int, routing=None) -> float:
    """Model FLOP per trained token: 6 per matrix parameter a token
    meets, three times the forward FLOP of the attended pairs, by layer
    kind; recompute excluded.  The routing changes nothing: every token
    passes all its chosen experts, on whichever chip."""
    pairs = sum(flops.flash_fwd_cost(_flash_shape(cfg, 1, seq, window))[0]
                for window, _ in layer_kinds(cfg))
    return 6 * matmul_params_active(cfg) + 3 * pairs / seq


def kernel_calls(cfg: dict, rows) -> dict:
    """As ``families/kimi_linear.py:kernel_calls`` for the grouped
    products (``rows`` ``(steps, expert layers, held)``: what the first
    chip's experts took, from every chip; eight calls a layer where no
    round ran behind the first), and the flash kernels' calls of a stack
    whose layers differ: a step runs each layer's forward kernel twice
    (the mixer's region is recomputed on the way back; recomputed calls
    are calls) and its two backward kernels once, each call costed at its
    own layer's window by ``flops.flash_fwd_cost`` / ``flash_bwd_cost``
    (the backward pair's cost in two halves, one an event).  The
    sequences are whole (``max_position_embeddings`` tokens, the mix's
    ``seq_len``); how many a chip runs a step is read off the rows."""
    from benchmarks import program

    d, f = cfg["hidden_size"], cfg["moe_ffn_hidden_size"]
    seq, k = cfg["max_position_embeddings"], \
        cfg["moe_num_active_primary_experts"]
    held = cfg["moe_num_primary_experts"] // chips_per_layer(cfg)
    names = program.kernel_names()
    grouped, fwd, bwd = [], [], []
    for step in rows:
        batch = max(1, round(float(step.sum()) / (len(step) * k * seq)))
        for layer in step:
            r = int(layer.sum())
            for m, n in ((d, 2 * f), (f, d)):
                grouped += [(2 * r * m * n,
                             2 * (r * m + r * n + held * m * n))] * 4
        for window, _ in layer_kinds(cfg):
            shape = _flash_shape(cfg, batch, seq, window)
            fwd += [flops.flash_fwd_cost(shape)] * 2
            bwd += [tuple(c / 2 for c in flops.flash_bwd_cost(shape))] * 2
    return {"moe_grouped_dot": {"events": "ragged-dot-none", "calls": grouped,
                                "beside": "ragged-dot-metadata"},
            "flash_fwd": {"events": names["flash_fwd"], "calls": fwd},
            "flash_bwd": {
                "events": names["flash_bwd_dq"].rsplit("_", 1)[0],
                "calls": bwd}}


def ep_payload_bytes(cfg: dict, sent, chip: int = 0) -> float:
    """Bytes of rows that leave chip ``chip`` in one step's all-to-alls,
    padding not counted: the rows it sent to the OTHER chips, ``sent``
    ``(expert layers, chips)``, at the stream's width in the parameters'
    type, :data:`EXCHANGES_A_LAYER` times (as many rows come back as go
    out, and a cotangent has its row's size)."""
    away = float(sent.sum() - sent[:, chip].sum())
    return EXCHANGES_A_LAYER * away * cfg["hidden_size"] \
        * jnp.dtype(cfg["dtype"]).itemsize


def flash_calls(cfg: dict, batch: int, seq: int):
    """None: ``flops.flash_*_cost`` through ``readers/flash_roofline``
    reads one shape for every event, and this stack has two
    (:func:`kernel_calls` hands the calls over one by one)."""
    return None
