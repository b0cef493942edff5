"""Compiles for a TPU v5e that is described, not attached: what Mosaic
and the TPU's XLA accept or refuse at real widths, found here at no
chip time.  Nothing runs; no result, no time.

Every test that loads the TPU's compiler lives in THIS file: one process
at a time may hold the library, the tier-1 run hands whole files to its
workers, and a second such file could land on a worker whose fixture
then skips it in silence.  The topology is described inside a fixture,
never while a module is imported."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4torch_tpu.models import transformer as T
from mpi4torch_tpu.ops import flash
from mpi4torch_tpu.ops import kda
from mpi4torch_tpu.ops import paged_attention as pa
from mpi4torch_tpu.ops import ssd

F32 = jnp.float32


@pytest.fixture(scope="module")
def v5e_2x2():
    """The four described (not attached) chips of a v5e host:
    compile-only."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices


@pytest.fixture(scope="module")
def one_v5e_chip(v5e_2x2):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e_2x2[0])


@pytest.fixture
def as_on_tpu(monkeypatch):
    """The host platform is the CPU; the dispatch predicates are told
    otherwise, here in the test and by no option of the program."""
    monkeypatch.setattr(flash, "_on_tpu", lambda: True)
    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    monkeypatch.setattr(ssd, "_on_tpu", lambda: True)
    monkeypatch.setattr(kda, "_on_tpu", lambda: True)


def test_mla_blocks_compile_for_the_v5e_with_all_three_kernels(
        one_v5e_chip, as_on_tpu):
    """At the published widths (32 heads, keys of 192 staged 256 wide)
    Mosaic takes the 2,048-token blocks, forward and both backward
    kernels."""
    like = jax.ShapeDtypeStruct((1, 2 * T._MLA_BLOCK, 32, 192), jnp.bfloat16,
                                sharding=one_v5e_chip)
    loss = lambda q, k, v: jnp.sum(T._blockwise_causal_attention(
        q, k, v, T._MLA_BLOCK).astype(F32))
    with jax.enable_x64(False):      # the kernels are traced without x64
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            like, like, like).compile().as_text()
    for name in flash.KERNEL_NAMES:
        assert name in text, name


def _flash_grads_text(chip, b, s, h, h_kv, d, window, dtype=jnp.bfloat16):
    """The compiled text of all three kernels' launches for one call at
    traced offsets, as ring blocks and MLA's triangle make it."""
    like = lambda n: jax.ShapeDtypeStruct((b, s, n, d), dtype,
                                          sharding=chip)
    off = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    loss = lambda q, k, v, qo, ko: jnp.sum(flash.flash_block_attention(
        q, k, v, causal=True, q_offset=qo, kv_offset=ko,
        window=window)[0].astype(F32))
    with jax.enable_x64(False):
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            like(h), like(h_kv), like(h_kv), off, off).compile().as_text()


# (batch, tokens, q heads, kv heads, head size, window) of every flash
# call the benchmark's five cells send, and of `train_long`'s.
CELL_CALLS = {
    "mistral-train-2x4096": (2, 4096, 32, 8, 128, 4096),
    "mistral-train_long-1x16384": (1, 16384, 32, 8, 128, 4096),
    "internlm2-prefill-256": (1, 256, 16, 8, 128, 0),
    "internlm2-prefill-1024": (1, 1024, 16, 8, 128, 0),
    "internlm2-prefill-2048": (1, 2048, 16, 8, 128, 0),
    "kimi-mla-block-2048x192": (2, 2048, 32, 32, 192, 0),
    "openpangu-mla-block-2048x192": (1, 2048, 128, 128, 192, 0),
}


@pytest.mark.parametrize("call", list(CELL_CALLS))
def test_flash_kernels_compile_at_the_cells_shapes_under_the_plan(
        one_v5e_chip, as_on_tpu, call):
    """Mosaic takes all three kernels at the tiles ``flash.tile_plan``
    gives each call the cells send, at its real shape: the plan's VMEM
    count, and the limit it asks for where it passes Mosaic's default,
    are enough."""
    b, s, h, h_kv, d, window = CELL_CALLS[call]
    plan = flash.tile_plan(s, s, d, jnp.bfloat16, True, window)
    assert None not in plan
    text = _flash_grads_text(one_v5e_chip, b, s, h, h_kv, d, window)
    for name in flash.KERNEL_NAMES:
        assert name in text, name


@pytest.mark.parametrize("s,d", [(2048, 192), (4096, 256), (8192, 128)])
def test_float32_calls_compile_under_the_plan(one_v5e_chip, as_on_tpu, s, d):
    """float32 operands contract under ``HIGHEST``: Mosaic splits each
    into bfloat16 pieces and stages more than the operands' own bytes.
    The plan's count covers it (a first count did not: 20.47 MB against
    a limit of 19 at 2,048 x 192, found on the chip), at its narrower
    float32 tiles."""
    plan = flash.tile_plan(s, s, d, jnp.float32, True)
    assert plan.fwd[:2] == plan.dq[:2] == plan.dkv[:2] == (256, 256)
    text = _flash_grads_text(one_v5e_chip, 2, s, 16, 16, d, 0, jnp.float32)
    for name in flash.KERNEL_NAMES:
        assert name in text, name


def test_one_call_over_8192_keys_of_192_compiles_since_the_plan(
        one_v5e_chip, as_on_tpu):
    """Before ``tile_plan`` Mosaic refused MLA's forward as one call
    over 8,192 keys staged 256 wide (16.38 MB of scoped VMEM against its
    default 16) although ``_eligible`` admitted it, and the backward
    declined 8,192 queries.  The plan counts what the kernels stage and
    asks Mosaic for that much: the call compiles, all three kernels.
    (``transformer._MLA_BLOCK`` still cuts it into 2,048 blocks.)"""
    plan = flash.tile_plan(8192, 8192, 192, jnp.bfloat16, True)
    assert None not in plan
    assert plan.fwd.vmem_bytes > flash._DEFAULT_SCOPED_VMEM
    text = _flash_grads_text(one_v5e_chip, 1, 8192, 32, 32, 192, 0)
    for name in flash.KERNEL_NAMES:
        assert name in text, name


def _made_with_shape(text: str, dims: str) -> list:
    """Names of the compiled instructions whose result has that shape."""
    return [m.group(1) for m in re.finditer(
        r"= \w+\[" + re.escape(dims) + r"\]\S* ([\w\-]+)\(", text)]


def _kernel_grids(fn, *args) -> dict:
    """``{kernel name: (its grid, its operands)}`` of the Pallas calls
    ``fn`` makes of these arguments (traced, not compiled)."""
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found[eqn.params["name"]] = (
                    tuple(eqn.params["grid_mapping"].grid), len(eqn.invars))
            for sub in eqn.params.values():
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("dtype,window", [
    (jnp.bfloat16, 0), (jnp.bfloat16, 300), (jnp.float32, 0)],
    ids=["bf16", "bf16-window", "f32"])
def test_paged_attention_compiles_at_the_serving_cells_shapes(
        one_v5e_chip, as_on_tpu, dtype, window):
    """InternLM2-1.8B's decode step in `serve_chat`: 16 slots, 16 query
    heads over 8 KV heads of 128, a pool of 320 pages of 128 positions.
    Mosaic takes the kernel, and the kernel's view of a pool leaf
    (``block_size * kv_heads`` rows) is the leaf's own bytes: a bitcast,
    no copy of the pool."""
    like = lambda shape, dt=dtype: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_v5e_chip)
    q, pool = like((16, 16, 128)), like((320, 128, 8, 128))
    assert pa.uses_kernel(q, pool)
    read = lambda *a: pa.paged_decode_attention(*a, window=window)
    args = (q, pool, pool, like((16, 20), jnp.int32), like((16,), jnp.int32))
    with jax.enable_x64(False):
        text = jax.jit(read).lower(*args).compile().as_text()
        grids = _kernel_grids(read, *args)
    assert pa.KERNEL_NAMES[0] in text and "tpu_custom_call" in text
    # Four page pairs a grid step (in float32, 1 MB a pair, they fill
    # the staging budget to the byte), each an operand of its own beside
    # the page ids, the positions and the query.
    assert grids == {pa.KERNEL_NAMES[0]: ((16, 20 // 4), 3 + 2 * 4)}
    assert set(_made_with_shape(text, "320,128,8,128")) == {"parameter"}
    assert set(_made_with_shape(text, "320,1024,128")) == {"bitcast"}


def test_paged_decode_step_compiles_in_place(one_v5e_chip, as_on_tpu):
    """The engine's own traced step under ``run_spmd`` with the pool
    donated, compiled for one v5e chip at head size 128: every pool leaf
    is aliased to its output, the stacked ``(1, ...)`` axis and the rank
    slice cost no copy, the write is a scatter into the leaf, the read
    is the kernel, no other instruction produces anything of a pool
    leaf's shape, and the logits are rounded before they are compared."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mpi4torch_tpu import serve
    from mpi4torch_tpu.ops.spmd import run_spmd

    cfg = T.TransformerConfig(vocab=8192, d_model=512, n_heads=4,
                              n_kv_heads=2, n_layers=2, d_ff=1024,
                              max_seq=512, rope=True, norm="rmsnorm",
                              ffn="swiglu")
    # 16 MB a leaf: a pool small enough for the chip's fast memory is
    # prefetched into it, which reads as a copy of the leaf.
    slots, bs = 64, 128
    with jax.enable_x64(False):
        eng = serve.Engine(
            cfg, T.init_transformer(jax.random.PRNGKey(0), cfg,
                                    dtype=jnp.bfloat16),
            serve.ServeConfig(slots=slots, block_size=bs), spmd=True,
            nranks=1)
        assert eng._kernel_read
        mesh = Mesh(np.array([next(iter(one_v5e_chip.device_set))]),
                    ("mpi",))
        state = NamedSharding(mesh, P("mpi"))
        like = lambda tree, sh: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            tree)
        step = run_spmd(eng._traced_step, mesh=mesh, axis_name="mpi",
                        donate_argnums=(1,))
        # The slot state rides stacked per rank, like the pool.
        args = (like(eng._shards, state), like(eng._cache, state),
                like({k: a[None] for k, a in eng._host_state().items()},
                     state))
        compiled = jax.jit(step, donate_argnums=(1,)).lower(*args).compile()
        grids = _kernel_grids(step, *args)
    # A row of 4 pages of 64 KB pairs: all four in one grid step, and
    # the engine counts the steps of that grid.
    assert grids == {pa.KERNEL_NAMES[0]: ((slots, 1), 3 + 2 * 4)}
    assert eng._grid_steps == slots
    text = compiled.as_text()
    leaves = 2 * cfg.n_layers
    leaf_bytes = slots * cfg.max_seq * 2 * 128 * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == leaves * leaf_bytes
    assert mem.temp_size_in_bytes < leaf_bytes
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
    assert alias and alias.group(1).count("may-alias") \
        + alias.group(1).count("must-alias") == leaves
    assert pa.KERNEL_NAMES[0] in text
    dims = f"{slots * cfg.max_seq // bs},{bs},2,128"
    made = _made_with_shape(text, dims) + _made_with_shape(text, "1," + dims)
    assert made.count("scatter") == leaves
    assert set(made) <= {"parameter", "bitcast", "scatter", "fusion"}, made
    # The logits table is a fusion's own result, rounded to bfloat16
    # before the step's argmax reads it (select_rows' barrier): fused
    # into the product the TPU compares unrounded accumulators, and the
    # served tokens leave the host-selected ones at every near-tie.
    assert "fusion" in _made_with_shape(text, f"{slots},{cfg.vocab}")


def test_latent_read_compiles_at_openpangus_shapes(one_v5e_chip, as_on_tpu):
    """openPangu-Ultra-MoE's decode read in `serve_latent_4k`: 32 slots,
    128 query heads on rows of 640 channels whose first 512 are the
    value, a pool of 2,048 pages of 128 rows.  Mosaic takes the kernel
    (a page is staged once and serves as key and as value), and its
    view of the pool leaf is the leaf's own bytes."""
    like = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_v5e_chip)
    q, pool = like((32, 128, 640)), like((2048, 128, 1, 640))
    assert pa.uses_kernel(q, pool, 512)
    read = lambda *a: pa.paged_latent_attention(*a, v_width=512,
                                                scale=192 ** -0.5)
    args = (q, pool, like((32, 64), jnp.int32), like((32,), jnp.int32))
    with jax.enable_x64(False):
        text = jax.jit(read).lower(*args).compile().as_text()
        grids = _kernel_grids(read, *args)
    assert pa.KERNEL_NAMES[1] in text and "tpu_custom_call" in text
    # Eight pages a grid step, under the kernel's old name.
    assert grids == {pa.KERNEL_NAMES[1]: ((32, 64 // 8), 3 + 8)}
    assert set(_made_with_shape(text, "2048,128,1,640")) == {"parameter"}
    assert set(_made_with_shape(text, "2048,128,640")) == {"bitcast"}
    assert _made_with_shape(text, "32,128,512") != []


def _real_size(name: str, family, chip):
    """A configuration at its own widths as shapes alone: ``(cfg, tcfg,
    mesh, stacked params, parameter count, stacked, like)``."""
    import json
    import os

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           name + ".json")) as f:
        cfg = json.load(f)
    tcfg = family.transformer_config(cfg)
    mesh = Mesh(np.array([next(iter(chip.device_set))]), ("mpi",))
    state, rep = NamedSharding(mesh, P("mpi")), NamedSharding(mesh, P())
    key, dt = jax.random.PRNGKey(0), jnp.bfloat16
    params = jax.eval_shape(lambda: dict(
        family.make_top(key, cfg, dt),
        blocks=[family.make_layer(key, cfg, i, dt)
                for i in range(cfg["num_hidden_layers"])]))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    stacked = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((1,) + a.shape, a.dtype,
                                       sharding=state), tree)
    like = lambda shape, d: jax.ShapeDtypeStruct(shape, d, sharding=rep)
    return cfg, tcfg, mesh, stacked(params), count, stacked, like


def _compiled_decode_step(tcfg, mesh, params, stacked, like, slots, bs, nb,
                          window_blocks=0):
    """The serving walk's paged decode step, as the engine traces it
    (pool donated, tokens chosen inside), compiled from shapes alone;
    with ``window_blocks`` the pool has a window class of that many
    pages and the step takes a table a class."""
    import mpi4torch_tpu as mpi
    from mpi4torch_tpu.ops.spmd import run_spmd
    from mpi4torch_tpu.serve import kv
    from mpi4torch_tpu.serve.engine import select_rows

    pool = jax.eval_shape(
        lambda: kv.init_kv_pool_tp(tcfg, nb, bs, 1, jnp.bfloat16,
                                   slots=slots, window_blocks=window_blocks))
    mine = lambda tree: jax.tree.map(lambda a: a[0], tree)
    table = like((slots, tcfg.max_seq // bs), jnp.int32)
    if window_blocks:
        table = {"full": table, "window": table}

    def step(shards, pool, table, tokens, pos, active):
        stats = {}
        logits, pool = kv.decode_step_paged(
            tcfg, mine(shards), mine(pool), table, tokens, pos,
            mpi.COMM_WORLD, active=active, stats=stats)
        return (*select_rows(logits, None, 0.0, 0), pool, stats)

    with jax.enable_x64(False):
        return run_spmd(
            step, mesh=mesh, axis_name="mpi",
            donate_argnums=(1,)).lower_as_called(
                params, stacked(pool), table,
                like((slots,), jnp.int32), like((slots,), jnp.int32),
                like((slots,), bool)).compile()


def _compiled_prefill(tcfg, mesh, params, like, n):
    """The one-piece prefill of ``n`` tokens, as the engine traces it."""
    import mpi4torch_tpu as mpi
    from mpi4torch_tpu.ops.spmd import run_spmd
    from mpi4torch_tpu.serve import kv

    mine = lambda tree: jax.tree.map(lambda a: a[0], tree)

    def prefill(shards, prompt):
        stats = {}
        cache = kv.init_kv_cache_tp(tcfg, 1, 1, jnp.bfloat16)
        return (*kv.prefill_tp(tcfg, mine(shards), cache, prompt,
                               mpi.COMM_WORLD, stats=stats), stats)

    with jax.enable_x64(False):
        return run_spmd(prefill, mesh=mesh, axis_name="mpi").lower_as_called(
            params, like((1, n), jnp.int32)).compile()


def _names(text: str, kernel: str) -> set:
    return set(re.findall(r"%(" + re.escape(kernel) + r"[\w.]*) = ", text))


def test_openpangus_decode_step_compiles_in_place_at_its_real_size(
        one_v5e_chip, as_on_tpu):
    """The serving walk's paged decode step at the configuration's own
    widths (4.92 B parameters, five layers, the 1.68 GB latent pool),
    from shapes alone: every pool leaf is aliased to its output, each
    latent layer reads through the kernel, each expert layer makes its
    two grouped products, and the step's temporaries stay small beside
    the 11.5 GB it is handed."""
    from benchmarks.families import openpangu_moe as fam

    cfg, tcfg, mesh, params, count, stacked, like = _real_size(
        "openpangu-ultra-moe-718b", fam, one_v5e_chip)
    assert count == 4_919_140_864
    slots, bs, nb = 32, 128, 2048
    compiled = _compiled_decode_step(tcfg, mesh, params, stacked, like,
                                     slots, bs, nb)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    layers = cfg["num_hidden_layers"]
    assert mem.alias_size_in_bytes == layers * nb * bs * 640 * 2
    assert mem.temp_size_in_bytes < 0.5e9
    assert len(_names(text, "mpi4torch_paged_latent_attn")) == layers
    assert len(_names(text, "ragged-dot-none")) \
        == 2 * (layers - cfg["first_k_dense_replace"])


def test_latent_read_compiles_at_longcats_shapes(one_v5e_chip, as_on_tpu):
    """LongCat-Flash's decode read in `serve_scmoe_1k`: 32 slots, 64
    query heads on rows of 640 channels, a pool of 1,024 pages."""
    like = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_v5e_chip)
    q, pool = like((32, 64, 640)), like((1024, 128, 1, 640))
    assert pa.uses_kernel(q, pool, 512)
    read = lambda *a: pa.paged_latent_attention(*a, v_width=512,
                                                scale=192 ** -0.5)
    args = (q, pool, like((32, 32), jnp.int32), like((32,), jnp.int32))
    with jax.enable_x64(False):
        text = jax.jit(read).lower(*args).compile().as_text()
        grids = _kernel_grids(read, *args)
    assert pa.KERNEL_NAMES[1] in text and "tpu_custom_call" in text
    assert grids == {pa.KERNEL_NAMES[1]: ((32, 32 // 8), 3 + 8)}
    assert set(_made_with_shape(text, "1024,128,1,640")) == {"parameter"}
    assert _made_with_shape(text, "32,64,512") != []


def test_longcats_programs_compile_at_their_real_size(
        one_v5e_chip, as_on_tpu, capsys):
    """LongCat-Flash-Chat as `serve_scmoe_1k` serves it (5.17 B
    parameters, eight latent sublayers for four published layers, the
    1.34 GB latent pool), from shapes alone.  The decode step: every
    pool leaf aliased to its output, eight latent reads through the
    kernel, two grouped products for each of the four shortcut
    branches, small temporaries.  The longest prefill (2,048 tokens)
    compiles and its temporaries fit beside weights and pool on a 16 GB
    chip.  Both programs' temporaries are printed."""
    from benchmarks.families import longcat_flash as fam

    cfg, tcfg, mesh, params, count, stacked, like = _real_size(
        "longcat-flash-chat", fam, one_v5e_chip)
    assert count == 5_172_749_312
    slots, bs, nb = 32, 128, 1024
    layers = cfg["num_hidden_layers"]
    pool_bytes = layers * nb * bs * 640 * 2
    compiled = _compiled_decode_step(tcfg, mesh, params, stacked, like,
                                     slots, bs, nb)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert mem.alias_size_in_bytes == pool_bytes
    assert mem.temp_size_in_bytes < 0.5e9
    assert len(_names(text, "mpi4torch_paged_latent_attn")) == layers == 8
    assert len(_names(text, "ragged-dot-none")) == 2 * cfg["num_layers"]
    for scope in ("mpi4torch.mla", "mpi4torch.moe", "mpi4torch.ffn"):
        assert scope in text, scope
    pre = _compiled_prefill(tcfg, mesh, params, like, 2048).memory_analysis()
    with capsys.disabled():
        print(f"\nlongcat-flash-chat: {count:,} parameters; decode step "
              f"temporaries {mem.temp_size_in_bytes / 1e9:.3f} GB; "
              f"2,048-token prefill temporaries "
              f"{pre.temp_size_in_bytes / 1e9:.3f} GB, outputs "
              f"{pre.output_size_in_bytes / 1e9:.3f} GB")
    held = 2 * count + pool_bytes
    assert held + pre.temp_size_in_bytes + pre.output_size_in_bytes < 15.7e9


def test_glms_two_reads_compile_at_their_cells_shapes(one_v5e_chip,
                                                      as_on_tpu):
    """GLM-5.2's decode reads in `serve_dsa_16k`: 16 slots over 136
    pages of 128 positions each.  The scoring (32 index queries of 128
    channels a slot against index-key pages) and the read of 2,048
    selected rows a slot (64 heads on rows of 640 channels, gathered by
    page and offset, a chunk of 512 a grid step): Mosaic takes both
    kernels, and each pool leaf goes in as it lies."""
    like = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_v5e_chip)
    slots, nb, n_blk = 16, 2176, 136
    q_i, keys = like((slots, 32, 128)), like((nb, 128, 1, 128))
    assert pa.uses_index_kernel(q_i, keys)
    table, pos = like((slots, n_blk), jnp.int32), like((slots,), jnp.int32)
    with jax.enable_x64(False):
        text = jax.jit(pa.paged_index_scores).lower(
            q_i, like((slots, 32), F32), keys, table, pos
        ).compile().as_text()
        grids = _kernel_grids(pa.paged_index_scores, q_i,
                              like((slots, 32), F32), keys, table, pos)
    assert pa.KERNEL_NAMES[2] in text and "tpu_custom_call" in text
    # Eight pages a grid step, as before the rule was shared.
    assert grids == {pa.KERNEL_NAMES[2]: ((slots, n_blk // 8), 4 + 8)}
    assert set(_made_with_shape(text, "2176,128,1,128")) == {"parameter"}
    q, pool = like((slots, 64, 640)), like((nb, 128, 1, 640))
    assert pa._sparse_chunk(q, pool, 2048, 512) == 512
    read = lambda *a: pa.paged_sparse_latent_attention(
        *a, v_width=512, scale=256 ** -0.5)
    args = (q, pool, table, like((slots, 2048), jnp.int32))
    with jax.enable_x64(False):
        text = jax.jit(read).lower(*args).compile().as_text()
        grids = _kernel_grids(read, *args)
    assert pa.KERNEL_NAMES[3] in text and pa.SPARSE_GATHER_SCOPE in text
    # A slot's four chunks of 512 gathered rows in ONE grid step.
    assert grids == {pa.KERNEL_NAMES[3]: ((slots, 1), 3 + 4)}
    assert set(_made_with_shape(text, "2176,128,1,640")) == {"parameter"}
    # The selected rows exist once, as gathered: 16 x 2,048 of them.
    assert "fusion" in _made_with_shape(text, "32768,640")


def test_glms_programs_compile_at_their_real_size(
        one_v5e_chip, as_on_tpu, capsys):
    """GLM-5.2 as `serve_dsa_16k` serves it (3.88 B parameters, five
    latent layers of which two score, the 1.93 GB pool of latent rows
    and index keys), from shapes alone.  The decode step: every pool
    leaf aliased to its output, two scorings and five reads of selected
    rows through their kernels, two grouped products for each of the
    four expert layers, small temporaries.  The longest prefill (16,384
    tokens in one piece) compiles and its temporaries fit beside weights
    and pool on a 16 GB chip.  Both programs' temporaries are printed."""
    from benchmarks.families import glm_dsa as fam

    cfg, tcfg, mesh, params, count, stacked, like = _real_size(
        "glm-5.2", fam, one_v5e_chip)
    assert count == 3_881_517_056
    slots, bs, nb = 16, 128, 2176
    layers = cfg["num_hidden_layers"]
    scoring = sum(kind == "full" for kind in cfg["indexer_types"])
    pool_bytes = nb * bs * (layers * 640 + scoring * 128) * 2
    assert pool_bytes == slots * cfg["max_position_embeddings"] * 6912
    compiled = _compiled_decode_step(tcfg, mesh, params, stacked, like,
                                     slots, bs, nb)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert mem.alias_size_in_bytes == pool_bytes
    assert mem.temp_size_in_bytes < 0.5e9
    assert len(_names(text, pa.KERNEL_NAMES[2])) == scoring == 2
    assert len(_names(text, pa.KERNEL_NAMES[3])) == layers == 5
    assert not _names(text, pa.KERNEL_NAMES[1] + ".") \
        and (pa.KERNEL_NAMES[1] + " ") not in text
    assert len(_names(text, "ragged-dot-none")) \
        == 2 * (layers - cfg["first_k_dense_replace"])
    for scope in ("mpi4torch.mla", "mpi4torch.dsa", "mpi4torch.moe",
                  pa.SPARSE_GATHER_SCOPE):
        assert scope in text, scope
    pre = _compiled_prefill(tcfg, mesh, params, like, 16384).memory_analysis()
    with capsys.disabled():
        print(f"\nglm-5.2: {count:,} parameters; decode step temporaries "
              f"{mem.temp_size_in_bytes / 1e9:.3f} GB; 16,384-token prefill "
              f"temporaries {pre.temp_size_in_bytes / 1e9:.3f} GB, outputs "
              f"{pre.output_size_in_bytes / 1e9:.3f} GB")
    held = 2 * count + pool_bytes
    assert held + pre.temp_size_in_bytes + pre.output_size_in_bytes < 15.7e9


def _compiled_install(tcfg, mesh, stacked, like, slots, bs, nb, n,
                      window_blocks=0):
    """The install of an ``n``-token prompt's rows (and, where a layer
    keeps one, its state into the slot's row), pool donated, as the
    engine compiles it; with ``window_blocks`` into a pool of two
    classes, under an index a class."""
    from jax.sharding import PartitionSpec as P

    from mpi4torch_tpu.serve import kv

    pool = jax.eval_shape(lambda: kv.init_kv_pool_tp(
        tcfg, nb, bs, 1, jnp.bfloat16, slots=slots,
        window_blocks=window_blocks))
    index = like((2 + kv.install_page_count(n, bs),), jnp.int32)
    classes = None
    if window_blocks:
        index, classes = {"full": index, "window": index}, \
            kv.page_classes(tcfg)
    rows = jax.eval_shape(
        lambda: kv.init_kv_cache_tp(tcfg, 1, 1, jnp.bfloat16))
    rows = [{k: a if k in kv.STATE_LEAVES else jax.ShapeDtypeStruct(
        (1, n) + a.shape[2:], a.dtype) for k, a in e.items()} for e in rows]

    def per_rank(pool, rows, index, slot):
        pool, rows = jax.tree.map(lambda a: a[0], (pool, rows))
        return jax.tree.map(lambda a: a[None], kv.install_rows_paged(
            pool, rows, index, slot, classes=classes))

    with jax.enable_x64(False):
        return jax.jit(
            jax.shard_map(per_rank, mesh=mesh,
                          in_specs=(P("mpi"), P("mpi"), P(), P()),
                          out_specs=P("mpi"), check_vma=False),
            donate_argnums=0).lower(
                stacked(pool), stacked(rows), index,
                like((), jnp.int32)).compile()


def test_nemotrons_programs_compile_at_their_real_size(
        one_v5e_chip, as_on_tpu, capsys):
    """Nemotron-3-Super as `serve_ssm_chat` serves it (4.65 B parameters:
    five Mamba-2, five latent-expert layers and one attention layer; 128
    slots' 2.68 GB of float32 state and 39 MB of convolution inputs
    beside a 0.27 GB K/V pool), from shapes alone.  The decode step:
    every leaf of pool and state aliased to its output (a second copy of
    the state would not fit), the attention layer's read through the
    paged kernel, two grouped products for each expert layer, the three
    scopes in the text.  The 256-, 512- and 1,024-token prefills and
    the install compile, the install with the state written in place,
    and the largest temporaries fit beside weights, state and pool on a
    16 GB chip.
    The programs' temporaries are printed."""
    from benchmarks.families import nemotron_h as fam

    cfg, tcfg, mesh, params, count, stacked, like = _real_size(
        "nemotron-3-super-120b-a12b", fam, one_v5e_chip)
    assert count == 4_648_163_712
    slots, bs, nb = 128, 128, 2048
    n_m = cfg["hybrid_override_pattern"].count("M")
    n_e = cfg["hybrid_override_pattern"].count("E")
    state = slots * n_m * 128 * 64 * 128 * 4
    tails = slots * n_m * 3 * 10240 * 2
    pool = nb * bs * 2 * 2 * 128 * 2
    assert (state, tails, pool) == (2_684_354_560, 39_321_600, 268_435_456)
    compiled = _compiled_decode_step(tcfg, mesh, params, stacked, like,
                                     slots, bs, nb)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert mem.alias_size_in_bytes == state + tails + pool
    assert mem.temp_size_in_bytes < 1.0e9
    assert len(_names(text, pa.KERNEL_NAMES[0])) == 1
    assert len(_names(text, "ragged-dot-none")) == 2 * n_e
    for scope in ("mpi4torch.ssm/", "mpi4torch.ssm_update", "mpi4torch.moe"):
        assert scope in text, scope
    assert "mpi4torch.ssm_scan" not in text
    held = 2 * count + state + tails + pool
    pre = {}
    for n in (256, 512, 1024):
        c = _compiled_prefill(tcfg, mesh, params, like, n)
        assert "mpi4torch.ssm_scan" in c.as_text()
        pre[n] = c.memory_analysis()
        assert held + pre[n].temp_size_in_bytes \
            + pre[n].output_size_in_bytes < 15.7e9
    inst = _compiled_install(tcfg, mesh, stacked, like, slots, bs, nb,
                             1024).memory_analysis()
    assert inst.alias_size_in_bytes == state + tails + pool
    # The state is written in place.  A K/V pool of 2 KV heads is copied
    # (the scatter wants another tiling of its pages than it arrives
    # in; one of 8 KV heads is not: PERF.md section 6, PR 41).
    assert inst.temp_size_in_bytes < pool + 0.01e9
    with capsys.disabled():
        print(f"\nnemotron-3-super: {count:,} parameters, "
              f"{held / 1e9:.2f} GB held; decode step temporaries "
              f"{mem.temp_size_in_bytes / 1e9:.3f} GB; prefill "
              "temporaries + outputs " + ", ".join(
                  f"{n}: {(m.temp_size_in_bytes + m.output_size_in_bytes) / 1e9:.3f} GB"
                  for n, m in pre.items())
              + f"; install temporaries {inst.temp_size_in_bytes / 1e9:.3f}"
              " GB")


def _trinity(chip):
    from benchmarks.families import afmoe as fam

    cfg, tcfg, mesh, params, count, stacked, like = _real_size(
        "trinity-mini", fam, chip)
    # The builder's count of the cut, the record: attention 27,263,232
    # (the norms on queries and keys are 128 each, 256 a layer: ISSUE
    # 45's own sum counted them twice and came to 256 more a layer) and
    # four norms 8,192 a layer, the dense FFN 37,748,736, an expert
    # layer's experts 128 x 6,291,456 with shared expert, router and
    # selection bias 6,553,728, embedding + head + final norm
    # 819,988,480.
    attn = 2048 * (4096 + 512 + 512 + 4096) + 4096 * 2048 + 2 * 128
    assert attn == 27_263_232
    dense = attn + 8192 + 3 * 2048 * 6144
    expert = attn + 8192 + 128 * 6_291_456 + 6_291_456 + 2048 * 128 + 128
    assert (dense, expert) == (65_020_160, 839_131_520)
    assert count == dense + 4 * expert + 2 * 200_192 * 2048 + 2048 \
        == 4_241_534_720
    return cfg, tcfg, mesh, params, count, stacked, like


# The cell's engine: 64 slots of 17,408 positions in pages of 128.
_TRINITY_POOL = dict(slots=64, bs=128, nb=8704, window_blocks=1152)


def test_trinitys_decode_step_compiles_in_place_at_its_real_size(
        one_v5e_chip, as_on_tpu, capsys):
    """Trinity-Mini as `serve_swa_mix_16k` serves it (4.24 B parameters:
    a dense and four expert layers, all 128 experts of each and the whole
    vocabulary; the full class's 2.28 GB of pages for the one full layer
    and the window class's 1.21 GB for the four sliding ones), from
    shapes alone: every leaf of both classes aliased to its output, all
    five layers' reads through the paged kernel under a table of their
    class, two grouped products an expert layer, the three scopes in the
    text, and the temporaries small beside the 12 GB the step is
    handed.  With ONE class the same slots' pages alone would be 11.4
    GB."""
    cfg, tcfg, mesh, params, count, stacked, like = _trinity(one_v5e_chip)
    slots, bs, nb, nb_w = (_TRINITY_POOL[k] for k in (
        "slots", "bs", "nb", "window_blocks"))
    page = bs * 4 * 128 * 2 * 2
    full, window = nb * page, 4 * nb_w * page
    assert (page, full, window) == (262_144, 2_281_701_376, 1_207_959_552)
    assert slots * (tcfg.max_seq // bs) * 5 * page == 11_408_506_880
    compiled = _compiled_decode_step(tcfg, mesh, params, stacked, like,
                                     slots, bs, nb, window_blocks=nb_w)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert mem.alias_size_in_bytes == full + window
    assert mem.temp_size_in_bytes < 1.0e9
    assert len(_names(text, pa.KERNEL_NAMES[0])) == 5
    assert len(_names(text, "ragged-dot-none")) == 2 * 4
    for scope in ("mpi4torch.attn/", "mpi4torch.attn_window",
                  "mpi4torch.attn_full", "mpi4torch.moe"):
        assert scope in text, scope
    held = 2 * count + full + window
    assert held + mem.temp_size_in_bytes < 15.7e9
    with capsys.disabled():
        print(f"\ntrinity-mini: {count:,} parameters, {held / 1e9:.2f} GB "
              f"held; decode step temporaries "
              f"{mem.temp_size_in_bytes / 1e9:.3f} GB")


@pytest.mark.slow
def test_trinitys_prefills_and_install_compile_at_their_real_size(
        one_v5e_chip, as_on_tpu, capsys):
    """The cell's three one-piece prefills (1,024, 4,096 and 16,384
    tokens: the sliding layers under their window, the expert layers in
    pieces of 4,096 tokens) and the install into two classes under an
    index a class compile, and the largest temporaries fit beside
    weights and pages on a 16 GB chip.  Marked slow: the three prefills
    at the real size take the TPU's compiler minutes; the decode step
    above is the tier-1 guard.  The programs' temporaries are
    printed."""
    cfg, tcfg, mesh, params, count, stacked, like = _trinity(one_v5e_chip)
    slots, bs, nb, nb_w = (_TRINITY_POOL[k] for k in (
        "slots", "bs", "nb", "window_blocks"))
    page = bs * 4 * 128 * 2 * 2
    held = 2 * count + (nb + 4 * nb_w) * page
    pre = {}
    for n in (1024, 4096, 16384):
        c = _compiled_prefill(tcfg, mesh, params, like, n)
        text = c.as_text()
        assert "mpi4torch.attn_window" in text \
            and "mpi4torch.attn_full" in text
        assert len(_names(text, flash.KERNEL_NAMES[0])) == 5
        pre[n] = c.memory_analysis()
        assert held + pre[n].temp_size_in_bytes \
            + pre[n].output_size_in_bytes < 15.7e9
    inst = _compiled_install(tcfg, mesh, stacked, like, slots, bs, nb,
                             tcfg.max_seq, window_blocks=nb_w
                             ).memory_analysis()
    assert inst.alias_size_in_bytes == (nb + 4 * nb_w) * page
    with capsys.disabled():
        print(f"\ntrinity-mini: {held / 1e9:.2f} GB held; prefill "
              "temporaries + outputs " + ", ".join(
                  f"{n}: {(m.temp_size_in_bytes + m.output_size_in_bytes) / 1e9:.3f} GB"
                  for n, m in pre.items())
              + f"; install temporaries {inst.temp_size_in_bytes / 1e9:.3f}"
              " GB")


_DP_SMALL = {"vocab_size": 2048, "hidden_size": 2048,
             "intermediate_size": 2048, "num_hidden_layers": 2,
             "num_attention_heads": 16, "num_key_value_heads": 4,
             "max_position_embeddings": 128, "sliding_window": 128,
             "rms_norm_eps": 1e-5, "rope_theta": 1e4}


def _dp_small(v5e_2x2):
    """``(tcfg, mesh, params, tokens)`` of `train_dp4`'s program at a
    small size on the four chips: matrices of 8 MiB that travel alone
    beside norm scales that share a bucket."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmarks import program, weights

    mesh = Mesh(np.asarray(v5e_2x2), ("mpi",))
    rep = NamedSharding(mesh, P())
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep)
    params = jax.tree.map(like, jax.eval_shape(
        lambda: weights.make_params(_DP_SMALL, 0, jnp.bfloat16)))
    tokens = jax.ShapeDtypeStruct((4 * 2, 128), jnp.int32, sharding=rep)
    return program.transformer_config(_DP_SMALL, remat=True), mesh, params, \
        tokens


def _all_reduced_bytes(text: str) -> int:
    """Bytes of every all-reduce's results in a compiled text."""
    width = {"bf16": 2, "f32": 4}
    total = 0
    for shapes in re.findall(r"= ([^=\n]*)\sall-reduce\(", text):
        for dtype, dims in re.findall(r"\b(bf16|f32)\[([\d,]*)\]", shapes):
            total += width[dtype] * int(np.prod(
                [int(d) for d in dims.split(",") if d] or [1]))
    return total


def test_dp_step_compiles_to_all_reduces_alone(v5e_2x2):
    """`train_dp4`'s program at a small size on the four chips, as the
    benchmark builds it.  The chip's compiler makes an
    all-reduce and a slice of a flat reduce-scatter, so the pair that a
    bucket was until PR 35 ran an all-reduce AND an all-gather a
    direction; a bucket is one all-reduce now and nothing is gathered.
    The kernels are not this test's (the dispatch stays the CPU's)."""
    from benchmarks import program

    tcfg, mesh, params, tokens = _dp_small(v5e_2x2)
    step = program.build_train_step(tcfg, mesh, 2, 0.3, True)
    with jax.enable_x64(False):
        lowered = step.lower(params, tokens)
        text = lowered.compile().as_text()
    text = re.sub(r"/\*.*?\*/", "", text)      # a tuple's /*index=5*/
    instr = lambda op: re.findall(rf"= [^=\n]*\s{op}\(", text)
    assert instr("all-reduce") and not instr("all-gather")
    assert not instr("reduce-scatter") and not instr("all-gather-start")
    low = lowered.as_text()
    assert "stablehlo.all_gather" not in low
    assert "stablehlo.reduce_scatter" not in low
    # the matrices' buckets in their own shapes, in the adjoint alone
    # since PR 51: two layers' ``wo`` and ``w2``, the embedding, the head
    assert len(re.findall(
        r"\}\) : \(tensor<2048x2048xbf16>\) -> tensor<2048x2048xbf16>",
        low)) == 2 * 2 + 2


def test_dp_step_all_reduces_the_parameters_bytes_once(v5e_2x2):
    """How often the mechanism of PR 51 engages, counted where the chip
    will run it: the step's all-reduces move the parameters' bytes once
    (the adjoint of ``replicated_tree``, and the loss), where the
    reference's recipe written out with ``all_average_tree`` moves them
    twice and keeps the averaged copy beside the parameters: the
    temporaries fall by the parameters' bytes.  On the chip this is
    `dp_collective_ms`."""
    import mpi4torch_tpu as mpi
    from benchmarks import program
    from mpi4torch_tpu.constants import MPI_SUM
    from mpi4torch_tpu.parallel.dp import all_average_tree

    tcfg, mesh, params, tokens = _dp_small(v5e_2x2)
    p_bytes = sum(p.size * p.dtype.itemsize for p in jax.tree.leaves(params))

    def recipe_body(params, tokens):
        comm = mpi.COMM_WORLD
        local = jax.lax.dynamic_slice_in_dim(
            tokens, jnp.asarray(comm.rank) * 2, 2, 0)

        def global_loss(p):
            loss = T.lm_loss(tcfg, all_average_tree(comm, p), local)
            return comm.Allreduce(loss, MPI_SUM, compression=False) / comm.size

        loss, grads = jax.value_and_grad(global_loss)(params)
        return loss, jax.tree.map(lambda p, g: p - 0.3 * g, params, grads)

    def recipe(params, tokens):
        from jax.sharding import PartitionSpec as P

        loss, stacked = mpi.run_spmd(recipe_body, mesh=mesh, axis_name="mpi",
                                     jit=False)(params, tokens)
        return loss, jax.shard_map(
            lambda tree: jax.tree.map(lambda a: a[0], tree), mesh=mesh,
            in_specs=P("mpi"), out_specs=P(), check_vma=False)(stacked)

    with jax.enable_x64(False):
        ours = program.build_train_step(tcfg, mesh, 2, 0.3, True).lower(
            params, tokens).compile()
        theirs = jax.jit(recipe, donate_argnums=(0,)).lower(
            params, tokens).compile()
    strip = lambda c: re.sub(r"/\*.*?\*/", "", c.as_text())
    loss_bytes = 16                       # the loss, whatever it rides with
    assert p_bytes <= _all_reduced_bytes(strip(ours)) <= p_bytes + loss_bytes
    assert 2 * p_bytes <= _all_reduced_bytes(strip(theirs)) \
        <= 2 * p_bytes + loss_bytes
    fell = theirs.memory_analysis().temp_size_in_bytes \
        - ours.memory_analysis().temp_size_in_bytes
    print(f"parameters {p_bytes / 1e6:.1f} MB; temporaries "
          f"{theirs.memory_analysis().temp_size_in_bytes / 1e6:.1f} -> "
          f"{ours.memory_analysis().temp_size_in_bytes / 1e6:.1f} MB")
    # 72 of 109 MB here, where some of the averaged copy shared its room
    # with later temporaries; 2.18 of 2.27 GB at the cell's size (PERF.md)
    assert 0.5 * p_bytes <= fell <= 1.1 * p_bytes


def _ragged_dots(text: str):
    """``(on the path every call takes, in a branch of a condition)``:
    the grouped products (``ragged-dot-none`` custom calls) of a compiled
    program, by the computation that holds them."""
    branches = set(re.findall(r"%([\w.-]+)", " ".join(
        re.findall(r"branch_computations=\{([^}]*)\}", text))))
    always = conditional = 0
    for block in re.split(r"\n(?=(?:ENTRY )?%[\w.-]+ \()", text):
        name = re.match(r"(?:ENTRY )?%([\w.-]+) \(", block)
        n = len(re.findall(r"ragged-dot-none[\w.]* = ", block))
        if name is not None and name.group(1) in branches:
            conditional += n
        else:
            always += n
    return always, conditional


@pytest.mark.parametrize("cell", ["train_kda_8k", "serve_ssm_chat"])
def test_the_expert_layer_compiles_on_a_prefix_at_the_cells_shapes(
        one_v5e_chip, cell):
    """``held_experts_ffn`` at the real shapes of the two cells whose
    held share is largest and smallest in rows: `train_kda_8k`'s step
    (16,384 tokens, 8 of 256 with 32 held: a prefix of 32,768 of 131,072
    pairs) under ``jax.checkpoint`` and ``jax.grad``, and
    `serve_ssm_chat`'s longest prefill (1,024 tokens, 22 of 512 with 128
    held, latent 1,024, relu2: 11,264 of 22,528).  Under the gradient
    each product of the prefix is one grouped product on the path every
    step takes (eight a training step and layer: what
    ``moe_grouped_dot_roofline`` counts), the rest of the rows are a
    second body behind a condition, and the backward's branch not taken
    hands its operands through.  The serving call holds ONE pair of
    grouped products, in a loop that takes a prefix's rows a turn (two
    events a call, and no second body's code to load at start-up)."""
    from mpi4torch_tpu.parallel import moe

    if cell == "train_kda_8k":
        spec = moe.Experts(256, 8, 1024, 0, 32, n_shared=1, scale=2.446)
        tokens, d, always, conditional = 16384, 2304, 8, 8
    else:
        spec = moe.Experts(512, 22, 2688, 0, 128, n_shared=1, scale=5.0,
                           latent=1024, act="relu2", d_shared=5376)
        tokens, d, always, conditional = 1024, 4096, 0, 2
    pairs = spec.top_k * tokens
    assert moe._prefix_rows(pairs, spec) == {
        "train_kda_8k": 32768, "serve_ssm_chat": 11264}[cell] < pairs
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=one_v5e_chip)
    p = jax.tree.map(like, jax.eval_shape(lambda: moe.init_experts(
        jax.random.PRNGKey(0), spec, d, jnp.bfloat16)))
    x = jax.ShapeDtypeStruct((tokens, d), jnp.bfloat16,
                             sharding=one_v5e_chip)

    def loss(p, x):
        y, *counts = moe.held_experts_ffn(x, p, spec)
        return jnp.sum(y.astype(F32)), counts

    fn = jax.value_and_grad(jax.checkpoint(loss), argnums=(0, 1),
                            has_aux=True) if cell == "train_kda_8k" \
        else (lambda p, x: moe.held_experts_ffn(x, p, spec))
    with jax.enable_x64(False):
        compiled = jax.jit(fn).lower(p, x).compile()
    text = re.sub(r"/\*.*?\*/", "", compiled.as_text())
    assert _ragged_dots(text) == (always, conditional)
    # the buffers are the prefix's: no product over all the pairs
    assert not re.search(rf"ragged-dot-none[\w.]* = \w+\[{pairs},", text)
    if cell == "serve_ssm_chat":
        assert len(re.findall(r" while\(", text)) == 1
    else:
        # forward and backward: one condition each; the backward's other
        # branch is its parameter, nothing is copied or zeroed
        assert len(re.findall(r" conditional\(", text)) == 2
        assert re.search(r"\n%[\w.-]+ \([^\n]*\{\n\s*ROOT %[\w.-]+ = "
                         r"\([^\n]*\) parameter\(0\)\n\}", text)
        # both bodies' temporaries are counted, the rest's of 98,304 rows
        # too (the parent's one body: 2.72 GB; this layer is not the
        # step's peak: 7.86 GB of temporaries while the delta rule's
        # plain backward set it, 6.06 GB since its kernels, PR 47)
        assert compiled.memory_analysis().temp_size_in_bytes < 3.6e9


# ------------------------------------------------- the delta rule's kernel

def _kda_calls(text: str, name: str = kda.KERNEL_NAME) -> int:
    return sum('custom_call_target="tpu_custom_call"' in line
               and name in line for line in text.splitlines())


def _the_rule_is_three_kernels(text: str):
    """One call of the forward kernel and one of each of the backward's,
    and nothing left of the plain path's backward: no loop over head
    groups or chunks, no triangular solve."""
    for name in kda.KERNEL_NAMES:
        assert _kda_calls(text, name) == 1, name
    assert not re.search(r" while\(", text)
    assert "triangular" not in text.lower()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, F32],
                         ids=["bfloat16", "float32"])
def test_the_delta_rules_kernel_compiles_at_the_cells_shape(
        one_v5e_chip, as_on_tpu, dtype):
    """One KDA layer of `train_kda_8k` (2 x 8,192 tokens, 32 heads of
    128): Mosaic takes the kernel at the block ``forward_block`` gives
    it, whose count of fast memory stays under the default scoped limit
    (no limit is asked for), and the operands are read where the
    mixer's projections leave them, heads side by side: the compiled
    program holds the kernel and no temporary, so no copy into another
    tiling.  (An operand that arrives as ``(b, s, h, d)`` lies tiled
    over ``(h, d)`` and would be copied first: 671 MB here.)"""
    b, s, h, d = 2, 8192, 32, 128
    tokens, staged = kda.forward_block(s, h, d, d, dtype)
    assert tokens == 1024 and staged < flash._DEFAULT_SCOPED_VMEM // 2
    like = lambda t, *shape: jax.ShapeDtypeStruct(shape, t,
                                                  sharding=one_v5e_chip)
    heads = lambda x: x.reshape(b, s, h, d)
    assert kda.uses_kernel(*(like(dtype, b, s, h, d),) * 3)

    def rule(q, k, v, g, beta):
        return kda.kda_chunked(heads(q), heads(k), heads(v), heads(g),
                               beta).reshape(b, s, h * d)

    with jax.enable_x64(False):
        compiled = jax.jit(rule).lower(
            *(like(dtype, b, s, h * d),) * 3, like(F32, b, s, h * d),
            like(F32, b, s, h)).compile()
    assert _kda_calls(compiled.as_text()) == 1
    assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("dtype", [jnp.bfloat16, F32],
                         ids=["bfloat16", "float32"])
def test_the_delta_rules_gradient_compiles_as_three_kernels(
        one_v5e_chip, as_on_tpu, dtype):
    """The gradient of one KDA layer's rule at `train_kda_8k`'s shape:
    Mosaic takes both backward kernels at the blocks ``backward_block``
    gives them (1,024 tokens a step for the backward's own forward, 512
    for the reverse walk, which stages eight more arrays), each count of
    fast memory under half the default scoped limit (no limit is asked
    for); the compiled program holds one call of each kernel, no
    ``while`` and no triangular solve.  Its temporaries are what the
    first backward kernel keeps for the second, a state (64 KB) and two
    chunks' inverses (32 KB) a turn of 128 tokens, 268 + 134 MB in
    float32, and the output's adjoint as this loss makes it (268 MB):
    671 MB, where the plain path's backward held a head group's thirty
    float32 arrays (2 GB)."""
    b, s, h, d = 2, 8192, 32, 128
    plan = kda.backward_block(s, h, d, d, dtype)
    assert [tokens for tokens, _ in plan.values()] == [1024, 512]
    assert tuple(plan) == kda.BACKWARD_KERNEL_NAMES
    assert all(staged < flash._DEFAULT_SCOPED_VMEM // 2
               for _, staged in plan.values())
    like = lambda t, *shape: jax.ShapeDtypeStruct(shape, t,
                                                  sharding=one_v5e_chip)
    heads = lambda x: x.reshape(b, s, h, d)

    def loss(q, k, v, g, beta):
        return jnp.sum(kda.kda_chunked(heads(q), heads(k), heads(v),
                                       heads(g), beta) ** 2)

    with jax.enable_x64(False):
        compiled = jax.jit(jax.grad(loss, argnums=range(5))).lower(
            *(like(dtype, b, s, h * d),) * 3, like(F32, b, s, h * d),
            like(F32, b, s, h)).compile()
    _the_rule_is_three_kernels(compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9


def test_a_rematerialised_kda_mixer_compiles_with_one_kernel_call(
        one_v5e_chip, as_on_tpu):
    """The gradient of one rematerialised KDA mixer at the cell's real
    size: the ``custom_vjp`` keeps the rule's inputs and nothing of its
    output, the mixer's output is saved by name, so the backward does
    not run the kernel again: one call in the compiled text (four a
    step of `train_kda_8k`), beside one of each backward kernel."""
    import json
    import os

    from benchmarks.families import kimi_linear as family

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "kimi-linear-48b-a3b.json")) as f:
        cfg = json.load(f)
    lin = cfg["linear_attn_config"]
    spec = T.KDA(lin["num_heads"], lin["head_dim"])
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=one_v5e_chip)
    p = jax.tree.map(like, jax.eval_shape(lambda: family.make_layer(
        jax.random.PRNGKey(0), cfg, 0, jnp.bfloat16)["mixer"]))
    y = jax.ShapeDtypeStruct((2, 8192, cfg["hidden_size"]), jnp.bfloat16,
                             sharding=one_v5e_chip)
    region = jax.checkpoint(lambda p_, y_: T._kda_mixer(spec, p_, y_),
                            policy=T._SAVED_IN_REMAT)
    loss = lambda p_, y_: jnp.sum(region(p_, y_).astype(F32))
    with jax.enable_x64(False):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            p, y).compile()
    assert _kda_calls(compiled.as_text()) == 1
    _the_rule_is_three_kernels(compiled.as_text())


# ------------------------------ what a rematerialised uniform block keeps

@pytest.mark.parametrize("chips", [1, 4], ids=["train_1chip", "train_dp4"])
def test_the_mistral_step_keeps_its_outputs_and_fits(v5e_2x2, as_on_tpu,
                                                     monkeypatch, chips):
    """The two Mistral cells' real step (4 layers, 2 x 4,096 tokens a
    chip, bfloat16, remat on; data parallel over the 2 x 2) as the
    benchmark builds it, with the limit a v5e reports.  The rule keeps
    all four layers' outputs; the compiled step then holds each block's
    forward products once (51 products where a step that keeps nothing
    holds 63: `y @ wqkv`, `o @ wo` and `y @ w1` of four layers; `h @ w2`
    was never run twice) and four flash forward calls, not eight; and
    what it holds, arguments and temporaries, stays inside the share of
    the chip the rule fills, though the rule counts the gradients and
    the new parameters as standing beside the old and the compiled step
    updates in place."""
    import json
    import os

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmarks import program, weights

    limit = 16_909_336_064               # bytes_limit of a v5e's memory_stats()
    monkeypatch.setattr(T, "_memory_limit_bytes", lambda: limit)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "mistral-7b-v0.1.json")) as f:
        cfg = json.load(f)
    mesh = Mesh(np.asarray(v5e_2x2[:chips]), ("mpi",))
    rep = NamedSharding(mesh, P())
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep)
    params = jax.tree.map(like, jax.eval_shape(
        lambda: weights.make_params(cfg, 0, jnp.bfloat16)))
    tokens = jax.ShapeDtypeStruct((chips * 2, 4096), jnp.int32, sharding=rep)
    tcfg = program.transformer_config(cfg, remat=True)
    p_bytes = sum(p.size * p.dtype.itemsize for p in jax.tree.leaves(params))
    assert T._remat_kept_layers(tcfg, (2, 4096), jnp.bfloat16, p_bytes,
                                limit) == 4
    step = program.build_train_step(tcfg, mesh, 2, 0.3, chips > 1)
    with jax.enable_x64(False):
        compiled = step.lower(params, tokens).compile()
    text = re.sub(r"/\*.*?\*/", "", compiled.as_text())
    assert len(re.findall(r"= \S+ convolution\(", text)) == 51
    calls = lambda name: sum(
        'custom_call_target="tpu_custom_call"' in line and name in line
        for line in text.splitlines())
    fwd, dq, dkv = flash.KERNEL_NAMES
    assert (calls(fwd), calls(dq), calls(dkv)) == (4, 4, 4)
    mem = compiled.memory_analysis()
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print(f"{chips} chip(s): arguments {mem.argument_size_in_bytes / 1e9:.3f}"
          f" GB + temporaries {mem.temp_size_in_bytes / 1e9:.3f} GB")
    assert held < T._REMAT_ROOM * limit


def test_the_experts_exchange_compiles_without_a_sort_on_four_chips(v5e_2x2):
    """SmallThinker's expert layer over the host's four chips at its
    widths (2,560 wide, 16 of 64 experts of 768 a chip, top-6; 2,048
    tokens a chip here for the compiler's time), forward and gradient:
    the all-to-alls of rows (out and back; round 0 forward and backward,
    and the one body of the later rounds each way) beside the small ones
    of the counts, and NO sort: a sort of
    100,000 keys takes the v5e's compiler 20 s an instance, and the
    adjoint of an all-to-all gathered along the rows took it minutes
    (PERF.md section 6, PR 48).  Since PR 49 the owner's grouped products
    read the buffer where it arrived: 12 ``ragged-dot`` instructions as
    before (round 0: two forward, four backward; the later rounds' turn
    of the backward: two recomputed and four; their forward turns are
    dead code under this loss, whose gradient does not read ``y``.  A
    layer's two forward, two recomputed and four backward are the eight
    that ``families/smallthinker.py:kernel_calls`` counts on), each over
    the whole ``(R * C, d)`` buffer in ``R * (held + 1) = 68`` groups,
    no gather of ``R * C`` rows into another ``R * C``, and temporaries
    under a stated ceiling: 1.84 GB here, where the regrouped form took
    0.59 (the matrices' copies by sender and the partials of their
    gradient, 0.53 GB each for ``w1``, are the cell's own, while the rows
    are an eighth of the cell's; the cell's whole step: 8.62 against
    7.53 GB, PERF.md section 6, PR 49)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import mpi4torch_tpu as mpi
    from mpi4torch_tpu.parallel import moe

    mesh = Mesh(np.asarray(v5e_2x2), ("mpi",))
    comm = mpi.comm_from_mesh(mesh, "mpi")
    d, f, n, k, tokens = 2560, 768, 64, 6, 2048
    spec = moe.Experts(n, k, f, 0, n // 4, score="softmax", act="reglu")
    specs = {"router": P(), "bias": P(), "w1": P("mpi"), "w2": P("mpi")}
    shapes = {"router": (d, n), "bias": (n,), "w1": (n, d, 2 * f),
              "w2": (n, f, d)}
    params = {name: jax.ShapeDtypeStruct(
        shape, jnp.bfloat16, sharding=NamedSharding(mesh, specs[name]))
        for name, shape in shapes.items()}
    x = jax.ShapeDtypeStruct((4 * tokens, d), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("mpi")))

    def body(p, x):
        def loss(p, x):
            y, counts = moe.exchanged_experts_ffn(x, p, spec, comm)
            return jnp.sum(y.astype(F32)), counts["rounds"]

        (_, rounds), grads = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(p, x)
        return grads, rounds[None]

    with jax.enable_x64(False):
        lowered = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(specs, P("mpi")),
            out_specs=((specs, P("mpi")), P("mpi")), check_vma=False)).lower(
                params, x)
        compiled = lowered.compile()
    text = compiled.as_text()
    assert not re.search(r"= \S+ sort\(", text)
    wide = [m for m in re.finditer(r"= (\S+) all-to-all(?:-start)?\(", text)
            if "2560" in m.group(1)]
    assert 6 <= len(wide) <= 10 and not len(wide) % 2, len(wide)
    buffer = 4 * moe._exchange_rows(tokens * k, 4)
    dots = re.findall(r"ragged-dot-none[\w.]* = .*", text)
    assert len(dots) == 12, len(dots)
    assert all(f"[{buffer}," in dot and "[68," in dot for dot in dots), dots
    rows = f"tensor<{buffer}x{d}xbf16>"
    assert not re.search(
        rf'"stablehlo.gather"\(.*: \({rows}, tensor<[^>]*>\) -> {rows}',
        lowered.as_text())
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"temporaries {temp / 1e9:.3f} GB")
    assert temp < 2.2e9
