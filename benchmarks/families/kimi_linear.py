"""The ``kimi_linear`` family for the ``train_family`` traffic kind: what
that kind takes from a family instead of from ``program.py``,
``weights.py`` and ``flops.py``, which know the dense decoder only.

* ``transformer_config``: the published keys as the program's
  ``TransformerConfig`` with a per-layer spec;
* ``make_params`` / ``make_layer`` / ``make_top``: seeded weights in the
  program's layout, the whole tree in one jitted call, and leaf by leaf
  for the reference;
* ``build_train_step``: ``train_step`` under ``run_spmd``, handing out
  the step's routing counters; ``build_grad_norms``: the gradient the
  step takes, as float32 norms leaf by leaf, a program of its own for
  the comparison that decides ``correct``;
* ``train_flops_per_token``, ``flash_calls``, ``kernel_calls``: the
  benchmark's own counts;
* ``scopes``: the scope each mechanism's instructions run under, by the
  program's own names, and ``KERNELS``: instructions found by their own
  name.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks import flops
from benchmarks.weights import _dense, _scale, seed_key

# The grouped products compile to custom calls whose op_name the compiler
# writes itself (``ragged-dot-none``); only the expert layer makes them.
KERNELS = {"ragged-dot": "moe"}
_TOP, _LAYER = 0, 1
# The scales of the seeded weights.  This chip holds the first five layers
# of 27, where a trained model's stream is still mostly the token's own
# embedding; and its routers are balanced (the published recipe steers the
# selection bias until they are).  Weights drawn like Mistral's (embedding
# 0.02, every matrix 1/sqrt(fan-in)) give neither: the mixers' outputs,
# which share a large component across tokens (silu is positive; causal
# averaging), swamp the embedding, every token then meets nearly the same
# router input, one expert took 14,000 of 16,384 tokens and others none,
# and the rows the held experts took (so the step's time) swung by 50%
# from seed to seed (my chip runs, PR 28).  So: embedding rows of unit
# variance, every block's output projection a tenth of 1/sqrt(fan-in), a
# selection bias as small as a converged one.  Routing is then by token,
# a held expert sees 512 rows a step within a few per cent.
_EMBED_STD, _OUT_SCALE, _BIAS_STD = 1.0, 0.1, 0.005


def layer_kinds(cfg: dict) -> list:
    """Per layer ``(mixer, ffn)``: ``kda`` | ``mla``, ``dense`` |
    ``experts``; layers are numbered from 1 in the published lists."""
    lin = cfg["linear_attn_config"]
    n = cfg["num_hidden_layers"]
    if sorted(lin["kda_layers"] + lin["full_attn_layers"]) \
            != list(range(1, n + 1)):
        raise ValueError("kda_layers and full_attn_layers must name every "
                         f"layer 1..{n} once")
    return [("kda" if i + 1 in lin["kda_layers"] else "mla",
             "dense" if i < cfg["first_k_dense_replace"] else "experts")
            for i in range(n)]


# ---------------------------------------------------------------- program

def scopes() -> dict:
    """``{"kda" | "mla" | "moe": the scope's name}``, from the program as
    ``program.kernel_names()`` takes the kernels' names."""
    from mpi4torch_tpu.utils.profiling import LAYER_SCOPES

    return dict(LAYER_SCOPES)


def transformer_config(cfg: dict, remat: bool = False):
    from mpi4torch_tpu.models.transformer import (
        KDA, MLA, LayerSpec, TransformerConfig)
    from mpi4torch_tpu.parallel.moe import Experts

    if cfg["rms_norm_eps"] != 1e-5:
        raise ValueError("the program's rmsnorm fixes eps at 1e-5; the "
                         f"configuration states {cfg['rms_norm_eps']}")
    if not cfg["mla_use_nope"] or cfg["num_expert_group"] != 1 \
            or not cfg["moe_renormalize"] \
            or cfg["moe_router_activation_func"] != "sigmoid":
        raise ValueError("kimi_linear: only NoPE latent attention and "
                         "ungrouped, renormalised sigmoid routing are built")
    lin = cfg["linear_attn_config"]
    h = cfg["num_attention_heads"]
    kda = KDA(n_heads=lin["num_heads"], head_dim=lin["head_dim"],
              conv=lin["short_conv_kernel_size"])
    mla = MLA(n_heads=h, kv_rank=cfg["kv_lora_rank"],
              qk_nope=cfg["qk_nope_head_dim"], qk_rope=cfg["qk_rope_head_dim"],
              v_dim=cfg["v_head_dim"])
    experts = Experts(
        n_experts=cfg["published"]["num_experts"],
        top_k=cfg["num_experts_per_token"],
        d_expert=cfg["moe_intermediate_size"],
        n_shared=cfg["num_shared_experts"],
        scale=float(cfg["routed_scaling_factor"]),
        first_expert=cfg["deployment_share"]["first_expert"],
        n_held=cfg["num_experts"])
    layers = tuple(
        LayerSpec(mixer=kda if mixer == "kda" else mla,
                  ffn=experts if ffn == "experts" else None)
        for mixer, ffn in layer_kinds(cfg))
    # rope=True: no learned position table (the mixers here take no
    # position at all).
    return TransformerConfig(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"], n_heads=h,
        n_layers=cfg["num_hidden_layers"], d_ff=cfg["intermediate_size"],
        max_seq=cfg["model_max_length"], rope=True, norm="rmsnorm",
        ffn="swiglu", remat=remat, layers=layers)


def _local_rows(comm, tokens, per_chip: int, broken: str):
    local = jax.lax.dynamic_slice_in_dim(
        tokens, jnp.asarray(comm.rank) * per_chip, per_chip, 0)
    if broken == "half_batch":
        local = jnp.concatenate([local[:1]] * per_chip, axis=0)
    return local


def _first_chips(mesh):
    """Out of ``run_spmd``'s stacked results, the first chip's."""
    from jax.sharding import PartitionSpec as P

    return jax.shard_map(
        lambda tree: jax.tree.map(lambda a: a[0], tree), mesh=mesh,
        in_specs=P(*mesh.axis_names), out_specs=P(), check_vma=False)


def build_train_step(tcfg, mesh, per_chip: int, lr: float, dp: bool,
                     broken: str = ""):
    """As ``program.build_train_step``, with the step's routing counters
    as a third result (the first chip's)."""
    import mpi4torch_tpu as mpi
    from mpi4torch_tpu.models import transformer as T

    (axis,) = mesh.axis_names

    def body(params, tokens):
        comm = mpi.COMM_WORLD
        loss, new, stats = T.train_step(
            tcfg, params, _local_rows(comm, tokens, per_chip, broken),
            comm_dp=comm if dp else None, lr=lr, return_stats=True)
        if broken == "state_unchanged":
            new = params
        return loss, new, stats

    spmd = mpi.run_spmd(body, mesh=mesh, axis_name=axis, jit=False)
    unstack = _first_chips(mesh)

    def step(params, tokens):
        loss, stacked, stats = spmd(params, tokens)
        return loss, unstack(stacked), unstack(stats)

    return jax.jit(step, donate_argnums=(0,))


def build_grad_norms(tcfg, mesh, per_chip: int, dp: bool, broken: str = ""):
    """``(params, tokens) -> (leaves,)`` float32: the norm, leaf by leaf
    in the order of ``jax.tree.leaves``, of the gradient ``train_step``
    takes of that batch (of ``lm_loss``, through the data-parallel
    average where the step makes one).  A program of its own, run once
    outside the window: the timed step computes nothing for the
    comparison."""
    import mpi4torch_tpu as mpi
    from mpi4torch_tpu.models import transformer as T
    from mpi4torch_tpu.parallel.dp import all_average_tree

    (axis,) = mesh.axis_names

    def body(params, tokens):
        comm = mpi.COMM_WORLD
        local = _local_rows(comm, tokens, per_chip, broken)

        def loss(p):
            if dp:
                p = all_average_tree(comm, p)
            return T.lm_loss(tcfg, p, local)

        return jnp.stack([
            jnp.linalg.norm(g.astype(jnp.float32).ravel())
            for g in jax.tree.leaves(jax.grad(loss)(params))])

    spmd = mpi.run_spmd(body, mesh=mesh, axis_name=axis, jit=False)
    unstack = _first_chips(mesh)
    return jax.jit(lambda params, tokens: unstack(spmd(params, tokens)))


# ---------------------------------------------------------------- weights

def _kda_leaves(key, cfg, dtype):
    d = cfg["hidden_size"]
    lin = cfg["linear_attn_config"]
    h, hd, taps = lin["num_heads"], lin["head_dim"], \
        lin["short_conv_kernel_size"]
    r = hd                                   # both low-rank widths (assumed)
    k = jax.random.split(key, 12)
    # Decay as the published family starts it: exp(a_log) in [1, 16] and
    # softplus(dt_bias) log-uniform in [1e-3, 1e-1]; both leaves stay
    # float32 whatever ``dtype`` (the decay is float32 arithmetic, and a
    # step's change to them is under one bfloat16 ulp).
    dt = jnp.exp(jax.random.uniform(k[8], (h * hd,), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    return {"wqkv": _dense(k[0], d, 3 * h * hd, dtype),
            "conv": _dense(k[1], taps, 3 * h * hd, dtype),
            "wf1": _dense(k[2], d, r, dtype),
            "wf2": _dense(k[3], r, h * hd, dtype),
            "dt_bias": jnp.log(jnp.expm1(dt)),
            "a_log": jnp.log(jax.random.uniform(
                k[9], (h,), jnp.float32, 1.0, 16.0)),
            "wg1": _dense(k[4], d, r, dtype),
            "wg2": _dense(k[5], r, h * hd, dtype),
            "wb": _dense(k[6], d, h, dtype),
            "norm": {"scale": _scale(k[10], hd, dtype)},
            "wo": _OUT_SCALE * _dense(k[7], h * hd, d, dtype)}


def _mla_leaves(key, cfg, dtype):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    rank = cfg["kv_lora_rank"]
    k = jax.random.split(key, 5)
    return {"wq": _dense(k[0], d, h * (dn + dr), dtype),
            "wa": _dense(k[1], d, rank + dr, dtype),
            "kv_norm": {"scale": _scale(k[2], rank, dtype)},
            "wb": _dense(k[3], rank, h * (dn + dv), dtype),
            "wo": _OUT_SCALE * _dense(k[4], h * dv, d, dtype)}


def _expert_leaves(key, cfg, dtype):
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held, width = cfg["num_experts"], cfg["published"]["num_experts"]
    shared = cfg["num_shared_experts"] * f
    k = jax.random.split(key, 6)
    stack = lambda key, m, n: jax.vmap(
        lambda kk: _dense(kk, m, n, dtype))(jax.random.split(key, held))
    return {"router": _dense(k[0], d, width, dtype),
            "bias": (_BIAS_STD * jax.random.normal(
                k[1], (width,), jnp.float32)).astype(dtype),
            "w1": stack(k[2], d, 2 * f),
            "w2": _OUT_SCALE * stack(k[3], f, d),
            "shared_w1": _dense(k[4], d, 2 * shared, dtype),
            "shared_w2": _OUT_SCALE * _dense(k[5], shared, d, dtype)}


def make_layer(key, cfg: dict, index: int, dtype):
    """Leaves of decoder layer ``index`` (0-based) in the program's
    layout: fused ``wqkv`` = [q | k | v], fused swiglu ``w1`` = [gate |
    up], experts stacked on axis 0."""
    d = cfg["hidden_size"]
    mixer, ffn = layer_kinds(cfg)[index]
    k = jax.random.split(jax.random.fold_in(
        jax.random.fold_in(key, _LAYER), index), 5)
    blk = {"ln1": {"scale": _scale(k[0], d, dtype)},
           "ln2": {"scale": _scale(k[1], d, dtype)},
           "mixer": (_kda_leaves if mixer == "kda" else _mla_leaves)(
               k[2], cfg, dtype)}
    if ffn == "experts":
        blk["experts"] = _expert_leaves(k[3], cfg, dtype)
    else:
        f = cfg["intermediate_size"]
        blk["w1"] = _dense(k[3], d, 2 * f, dtype)
        blk["w2"] = _OUT_SCALE * _dense(k[4], f, d, dtype)
    return blk


def make_top(key, cfg: dict, dtype):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    k = jax.random.split(jax.random.fold_in(key, _TOP), 3)
    return {"embed": (_EMBED_STD * jax.random.normal(
                k[0], (v, d), jnp.float32)).astype(dtype),
            "ln_f": {"scale": _scale(k[1], d, dtype)},
            "unembed": _dense(k[2], d, v, dtype)}


def make_params(cfg: dict, seed: int, dtype, sharding=None):
    """The whole tree in one jitted call."""
    def build(key):
        p = make_top(key, cfg, dtype)
        p["blocks"] = [make_layer(key, cfg, i, dtype)
                       for i in range(cfg["num_hidden_layers"])]
        return p

    return jax.jit(build, out_shardings=sharding)(seed_key(seed))


# ------------------------------------------------------------------ counts

def _sizes(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    lin = cfg["linear_attn_config"]
    kd = lin["num_heads"] * lin["head_dim"]
    r = lin["head_dim"]
    h = cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    rank, f = cfg["kv_lora_rank"], cfg["moe_intermediate_size"]
    return {
        # matrices a token is multiplied by (the convolution, 4 taps a
        # channel, among them)
        "kda": d * 3 * kd + lin["short_conv_kernel_size"] * 3 * kd
        + 2 * (d * r + r * kd) + d * lin["num_heads"] + kd * d,
        "mla": d * h * (dn + dr) + d * (rank + dr) + rank * h * (dn + dv)
        + h * dv * d,
        "dense": 3 * d * cfg["intermediate_size"],
        "expert": 3 * d * f,
        "router": d * cfg["published"]["num_experts"],
        "shared": 3 * d * cfg["num_shared_experts"] * f,
    }


def matmul_params_active(cfg: dict, held_per_token=None) -> float:
    """Matrix parameters one token is multiplied by on this chip.  Of a
    token's ``num_experts_per_token`` experts only those held here
    count: ``held_per_token`` a layer where the run measured it (its
    routing counters), else what even routing gives, ``k * held /
    all``."""
    z = _sizes(cfg)
    if held_per_token is None:
        held_per_token = cfg["num_experts_per_token"] * cfg["num_experts"] \
            / cfg["published"]["num_experts"]
    total = cfg["hidden_size"] * cfg["vocab_size"]
    for mixer, ffn in layer_kinds(cfg):
        total += z[mixer]
        total += z["dense"] if ffn == "dense" else \
            z["router"] + z["shared"] + held_per_token * z["expert"]
    return total


def kda_flops_fwd_per_token(cfg: dict, chunk: int = 64) -> float:
    """Forward FLOP a token of one KDA layer, chunked form, all heads:
    the causal halves of the two decay Grams (k k^T and q k^T, ``C/2`` pairs a
    token, ``d_k`` each), the triangular solve against ``d_k + d_v``
    columns (``C/2`` rows a token), the pairs times the written values,
    and the three products with the state (``w S``, ``q S`` and the
    state's update, ``d_k d_v`` each)."""
    lin = cfg["linear_attn_config"]
    h, dk = lin["num_heads"], lin["head_dim"]
    dv = dk
    half = chunk / 2
    per_head = 2 * (2 * half * dk + half * (dk + dv) + half * dv
                    + 3 * dk * dv)
    return h * per_head


def mla_pairs_flops_fwd(cfg: dict, seq: int) -> float:
    """Forward FLOP of one sequence in one MLA layer: scores at the
    query-key size, the weighted sum at the value size."""
    h = cfg["num_attention_heads"]
    dqk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return h * 2 * flops.attended_pairs(seq) * (dqk + cfg["v_head_dim"])


def train_flops_per_token(cfg: dict, seq: int, routing=None) -> float:
    """Model FLOP per trained token: 6 per active matrix parameter, three
    times the forward FLOP of the MLA pairs and of KDA's chunk products;
    recompute excluded.  ``routing`` is a run's counters: the rows the
    held experts really took are what is counted (seeded routers do not
    route evenly), not the even share."""
    kinds = [m for m, _ in layer_kinds(cfg)]
    held = None
    if routing is not None and len(routing.get("moe_rows", ())):
        rows = routing["moe_rows"]            # (steps, layers, held)
        tokens = routing["tokens_per_step"]
        held = float(rows.sum()) / (rows.shape[0] * rows.shape[1] * tokens)
    return (6 * matmul_params_active(cfg, held)
            + 3 * kinds.count("mla") * mla_pairs_flops_fwd(cfg, seq) / seq
            + 3 * kinds.count("kda") * kda_flops_fwd_per_token(cfg))


def kernel_calls(cfg: dict, rows) -> dict:
    """``{kernel: {"events": what its events' names hold, "calls":
    [(FLOP, bytes) of each call], "beside": what the names of the events
    hold that prepare the calls}}`` for steps whose held experts took
    ``rows`` ``(steps, expert layers, held)``: the grouped products
    (``ragged-dot-none``, with three ``ragged-dot-metadata`` a layer that
    turn the group sizes into offsets).

    An expert layer makes eight a step, four with the fused gate and up
    matrices (``hidden x 2 width``) and four with the down matrices
    (``width x hidden``): forward, the rematerialised forward, and the
    two backward products (towards the rows, towards the weights).  Each
    needs 2 FLOP a row and matrix element of the expert the row was
    routed to, and moves at least the held rows of its row operands and
    every held expert's matrix once.  Rows of the buffer behind the held
    ones need nothing: a kernel that spends time on them reads a lower
    share."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held = cfg["num_experts"]
    calls = []
    for step in rows:
        for layer in step:
            r = int(layer.sum())
            for k, n in ((d, 2 * f), (f, d)):
                calls += [(2 * r * k * n,
                           2 * (r * k + r * n + held * k * n))] * 4
    return {"moe_grouped_dot": {"events": "ragged-dot-none", "calls": calls,
                                "beside": "ragged-dot-metadata"}}


def flash_calls(cfg: dict, batch: int, seq: int):
    """None: the MLA layer reaches the flash kernels as a triangle of
    2,048 x 2,048 block calls (diagonal ones see half their pairs, the
    others all), and ``flops.flash_*_cost`` reads one uniform shape per
    event, which would count wrong.  ``mla_time_share.train`` carries the
    layer instead."""
    return None
