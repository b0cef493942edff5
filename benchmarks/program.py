"""The only file of the benchmark that calls into the program.

Everything the benchmark takes from ``mpi4torch_tpu`` passes through
here: the model configuration, the compiled training step, the serving
engine, the compile cache, the kernel names.  Nothing here computes a
metric.
"""

from __future__ import annotations


def use_compile_cache() -> str:
    """The program's own rule (``JAX_COMPILATION_CACHE_DIR`` if set, else
    ``<checkout>/.jax_cache``), and every program kept, however quickly
    it compiled: the engine dispatches hundreds of small ones."""
    import jax
    from mpi4torch_tpu.utils.compile_cache import use_compile_cache as use

    path = use()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def kernel_names() -> dict:
    from mpi4torch_tpu.ops import flash

    fwd, dq, dkv = flash.KERNEL_NAMES
    return {"flash_fwd": fwd, "flash_bwd_dq": dq, "flash_bwd_dkv": dkv}


def transformer_config(cfg: dict, remat: bool = False):
    """The published sizes (keys of the source's ``config.json``) as the
    program's ``TransformerConfig``."""
    from mpi4torch_tpu.models.transformer import TransformerConfig

    if cfg["rms_norm_eps"] != 1e-5:
        raise ValueError("the program's rmsnorm fixes eps at 1e-5; the "
                         f"configuration states {cfg['rms_norm_eps']}")
    return TransformerConfig(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"], d_ff=cfg["intermediate_size"],
        max_seq=cfg["max_position_embeddings"],
        n_kv_heads=cfg["num_key_value_heads"],
        attn_window=cfg.get("sliding_window") or 0, rope=True,
        rope_theta=float(cfg["rope_theta"]), norm="rmsnorm", ffn="swiglu",
        remat=remat)


def build_train_step(tcfg, mesh, per_chip: int, lr: float, dp: bool,
                     broken: str = ""):
    """``train_step`` under ``run_spmd`` as one jitted program whose state
    stays where it is: parameters go in replicated and come out
    replicated (each chip keeps its own copy of the lock-step replicas;
    nothing moves between steps), the old parameters' buffers are
    donated.  ``broken`` is for the benchmark's own tests only."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import mpi4torch_tpu as mpi
    from mpi4torch_tpu.models import transformer as T

    (axis,) = mesh.axis_names

    def body(params, tokens):
        comm = mpi.COMM_WORLD
        local = jax.lax.dynamic_slice_in_dim(
            tokens, jnp.asarray(comm.rank) * per_chip, per_chip, 0)
        if broken == "half_batch":
            local = jnp.concatenate([local[:1]] * per_chip, axis=0)
        loss, new = T.train_step(
            tcfg, params, local,
            comm_dp=comm if dp and broken != "no_exchange" else None, lr=lr)
        if broken == "state_unchanged":
            new = params
        return loss, new

    spmd = mpi.run_spmd(body, mesh=mesh, axis_name=axis, jit=False)
    unstack = jax.shard_map(
        lambda tree: jax.tree.map(lambda a: a[0], tree), mesh=mesh,
        in_specs=P(axis), out_specs=P(), check_vma=False)

    def step(params, tokens):
        loss, stacked = spmd(params, tokens)
        return loss, unstack(stacked)

    return jax.jit(step, donate_argnums=(0,))


def build_engine(tcfg, params, engine: dict, nranks: int):
    """``Engine(spmd=True)``, constructed with jit off.

    The constructor runs ``run_spmd(lambda: shard_params_tp(cfg, params,
    ...))()``, whose closure lowers every parameter as a program constant
    (ROADMAP A12).  At 3.78 GB of parameters that one compile took the
    host past the one-chip machine's 40 GiB twice (RSS 48 GB after 215 s
    and still inside the constructor; my chip runs, PR 24), with the
    persistent cache on and off, so the cell could not be run at all.
    Under ``jax.disable_jit()`` the same function runs eagerly: the same
    slices of the same arrays, the same stacked shards with the same
    sharding (checked on the CPU: equal bits, equal tokens), nothing
    lowered as a constant.  Only the constructor runs so; the step and
    prefill programs compile on first use, outside, as always.  The cost:
    A12's time is not in ``setup_s`` (PERF.md, Open questions)."""
    import jax

    from mpi4torch_tpu import serve

    with jax.disable_jit():
        return serve.Engine(tcfg, params, serve.ServeConfig(**engine),
                            spmd=True, nranks=nranks)


def prefetch(batches, sharding, size: int = 2):
    from mpi4torch_tpu.utils.data import prefetch_to_device

    return prefetch_to_device(batches, size=size, device=sharding)
