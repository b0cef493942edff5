"""Paged decode attention (ISSUE 29): the Pallas kernel, interpreted on
the CPU host, against the gather path (``block_gather`` + the jnp block
attention) it replaces on a TPU.

The kernel's softmax is online, page by page, so it equals the gather
path to rounding; everything else is exact and is held exactly: pages
beyond a slot's frontier and pages of other slots never reach the
result (they are filled with NaN here), a free slot reads nothing and
returns zeros, an unmapped page inside the frontier reads as zeros, and
off a TPU ``impl="auto"`` IS the gather path, bit for bit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4torch_tpu.ops import paged_attention as pa
from mpi4torch_tpu.ops.flash import flash_block_attention
from mpi4torch_tpu.ops.ragged import block_gather

HD, KVH, N_BLK = 128, 2, 4
TOL = {jnp.float32: 2e-6, jnp.bfloat16: 2e-2}


def block_size(dtype):
    """The smallest page the kernel takes: the dtype's sublane tile."""
    return 8 if dtype == jnp.float32 else 16


def a_case(dtype, g, seed=0):
    """Six slots over a pool of scattered pages.  Positions 0,
    ``bs - 1``, ``bs``, ``max_seq - 1`` and one mid-page; slot 4 shares
    slot 3's first two pages (a read-only prefix); slot 5 is free (row
    all ``-1``).  Entries beyond each frontier are ``-1`` (unmapped)."""
    bs = block_size(dtype)
    rng = np.random.default_rng(seed)
    nb = 5 * N_BLK
    pos = np.array([0, bs - 1, bs, N_BLK * bs - 1, 2 * bs + 3, 0], np.int32)
    active = np.array([1, 1, 1, 1, 1, 0], bool)
    ids = [int(i) for i in rng.permutation(nb)]
    table = np.full((6, N_BLK), -1, np.int32)
    for s in range(5):
        for j in range(pos[s] // bs + 1):
            table[s, j] = ids.pop()
    table[4, :2] = table[3, :2]
    mk = lambda shape: jnp.asarray(rng.standard_normal(shape), dtype)
    q = mk((6, KVH * g, HD))
    pk, pv = mk((nb, bs, KVH, HD)), mk((nb, bs, KVH, HD))
    return q, pk, pv, table, pos, active


def gather_path(q, pk, pv, table, pos, window=0):
    """What the decode step did before the kernel, spelt out."""
    o, _ = flash_block_attention(
        q[:, None], block_gather(pk, table), block_gather(pv, table),
        causal=True, q_offset=jnp.asarray(pos), kv_offset=0, window=window,
        impl="jnp")
    return o[:, 0]


def f32(x):
    return np.asarray(x.astype(jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("g", [1, 2, 4])
@pytest.mark.parametrize("window", [0, 11])
def test_kernel_equals_the_gather_path(dtype, g, window):
    q, pk, pv, table, pos, active = a_case(dtype, g)
    got = pa.paged_decode_attention(q, pk, pv, table, pos, window=window,
                                    active=active, impl="pallas")
    want = gather_path(q, pk, pv, table, pos, window)
    assert got.dtype == q.dtype and got.shape == q.shape
    np.testing.assert_allclose(f32(got)[active], f32(want)[active],
                               atol=TOL[dtype], rtol=TOL[dtype])
    # the free slot read nothing
    assert not f32(got)[~active].any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("window", [0, 11])
def test_pages_beyond_the_frontier_and_of_other_slots_are_never_read(
        dtype, window):
    """NaN in every page that no live slot holds up to its frontier, and
    slot by slot in every page of the OTHER slots: the output is finite
    and bit for bit what the clean pool gave."""
    q, pk, pv, table, pos, active = a_case(dtype, 2, seed=1)
    bs = pk.shape[1]
    clean = f32(pa.paged_decode_attention(
        q, pk, pv, table, pos, window=window, active=active, impl="pallas"))

    def poisoned(keep):
        mask = np.ones(pk.shape[0], bool)
        mask[sorted(keep)] = False
        poison = lambda a: jnp.where(mask[:, None, None, None],
                                     jnp.asarray(jnp.nan, a.dtype), a)
        return poison(pk), poison(pv)

    held = {s: set(table[s, :pos[s] // bs + 1].tolist())
            for s in range(6) if active[s]}
    # a free slot's stale table row is not followed either
    table = table.copy()
    table[5] = table[3]
    pkn, pvn = poisoned(set().union(*held.values()))
    got = f32(pa.paged_decode_attention(
        q, pkn, pvn, table, pos, window=window, active=active,
        impl="pallas"))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, clean)
    for s, mine in held.items():
        pkn, pvn = poisoned(mine)
        got = f32(pa.paged_decode_attention(
            q, pkn, pvn, table, pos, window=window, active=active,
            impl="pallas"))
        assert np.isfinite(got[s]).all()
        np.testing.assert_array_equal(got[s], clean[s])


def test_pages_wholly_behind_the_window_are_never_read():
    q, pk, pv, table, pos, active = a_case(jnp.float32, 2, seed=2)
    bs, window = pk.shape[1], 5          # slot 3 sees its last page only
    clean = f32(pa.paged_decode_attention(
        q, pk, pv, table, pos, window=window, active=active, impl="pallas"))
    behind = table[3, :N_BLK - 1]
    pkn = pk.at[behind].set(jnp.nan)
    pvn = pv.at[behind].set(jnp.nan)
    got = f32(pa.paged_decode_attention(
        q, pkn, pvn, table, pos, window=window, active=active,
        impl="pallas"))
    # slot 4 shares two of those pages and sees one of them: its row may
    # be NaN, slot 3's may not.
    assert (pos[3] - window + 1) // bs == N_BLK - 1
    np.testing.assert_array_equal(got[3], clean[3])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_unmapped_page_inside_the_frontier_reads_as_zeros(dtype):
    """``block_gather`` hands an unmapped page over as zeros wherever it
    sits; so does the kernel, whatever page its clamped index fetched
    (page 0 here, filled with NaN)."""
    q, pk, pv, table, pos, active = a_case(dtype, 2, seed=3)
    table = table.copy()
    table[3, 1] = -1
    free = [i for i in range(pk.shape[0]) if i not in set(table.ravel())]
    pk = pk.at[0].set(pk[free[0]]).at[free[0]].set(jnp.nan)
    pv = pv.at[0].set(pv[free[0]]).at[free[0]].set(jnp.nan)
    table = np.where(table == 0, free[0], table).astype(np.int32)
    pk, pv = pk.at[0].set(jnp.nan), pv.at[0].set(jnp.nan)
    got = pa.paged_decode_attention(q, pk, pv, table, pos, active=active,
                                    impl="pallas")
    want = gather_path(q, pk, pv, table, pos)
    np.testing.assert_allclose(f32(got)[active], f32(want)[active],
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_without_an_active_mask_every_slot_reads():
    q, pk, pv, table, pos, _ = a_case(jnp.float32, 2, seed=4)
    table = table.copy()
    table[5, 0] = table[0, 0]
    got = pa.paged_decode_attention(q, pk, pv, table, pos, impl="pallas")
    want = gather_path(q, pk, pv, table, pos)
    np.testing.assert_allclose(f32(got), f32(want), atol=2e-6, rtol=2e-6)
    assert f32(got)[5].any()


def test_a_position_past_the_tables_extent_is_held_to_the_table():
    """The index maps read the table at the live pages: a position
    beyond ``max_seq`` (the engine never sends one) reads the slot's
    whole row and nothing past it, as the gather path does."""
    q, pk, pv, table, pos, active = a_case(jnp.float32, 2, seed=7)
    pos = pos.copy()
    pos[3] = N_BLK * pk.shape[1] + 5
    got = pa.paged_decode_attention(q, pk, pv, table, pos, active=active,
                                    impl="pallas")
    want = gather_path(q, pk, pv, table, pos)
    np.testing.assert_allclose(f32(got)[active], f32(want)[active],
                               atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("active", [None, "mask"])
def test_off_the_tpu_auto_is_the_gather_path_bitwise(active):
    q, pk, pv, table, pos, mask = a_case(jnp.float32, 2, seed=5)
    assert not pa.uses_kernel(q, pk)
    got = pa.paged_decode_attention(
        q, pk, pv, table, pos, window=7,
        active=mask if active else None)
    want = np.array(gather_path(q, pk, pv, table, pos, 7))
    if active:
        want[~mask] = 0.0
    np.testing.assert_array_equal(np.asarray(got), want)
    # float64 (the suite's serving dtype) has no kernel at all
    q64, pk64, pv64 = (a.astype(jnp.float64) for a in (q, pk, pv))
    np.testing.assert_array_equal(
        np.asarray(pa.paged_decode_attention(q64, pk64, pv64, table, pos)),
        np.asarray(gather_path(q64, pk64, pv64, table, pos)))


def test_one_program_for_every_table_and_position():
    q, pk, pv, table, pos, active = a_case(jnp.float32, 2, seed=6)
    f = jax.jit(lambda *a: pa.paged_decode_attention(
        *a[:5], active=a[5], impl="pallas"))
    a = f(q, pk, pv, table, pos, active)
    t2 = np.roll(table, 1, axis=0)
    b = f(q, pk, pv, t2, np.roll(pos, 1), np.roll(active, 1))
    assert f._cache_size() == 1
    np.testing.assert_allclose(
        f32(b), f32(pa.paged_decode_attention(
            q, pk, pv, t2, np.roll(pos, 1), active=np.roll(active, 1),
            impl="jnp")), atol=2e-6, rtol=2e-6)
    assert np.abs(f32(a) - f32(b)).max() > 0


class TestEligibility:
    def q(self, hd=128, dtype=jnp.bfloat16, heads=16):
        return jax.ShapeDtypeStruct((16, heads, hd), dtype)

    def pool(self, bs=128, kvh=8, hd=128, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((320, bs, kvh, hd), dtype)

    def test_the_serving_cells_shapes_are_eligible(self):
        assert pa._eligible(self.q(), self.pool())
        assert pa._eligible(self.q(dtype=jnp.float32),
                            self.pool(dtype=jnp.float32))
        assert pa._eligible(self.q(), self.pool(bs=16))
        assert pa._eligible(self.q(256), self.pool(hd=256))

    @pytest.mark.parametrize("why,q,pool", [
        ("head of 64", dict(hd=64), dict(hd=64)),
        ("head of 192", dict(hd=192), dict(hd=192)),
        ("bf16 page of 8", {}, dict(bs=8)),
        ("f32 page of 4", dict(dtype=jnp.float32),
         dict(bs=4, dtype=jnp.float32)),
        ("down-cast cache", dict(dtype=jnp.float32), {}),
        ("one-byte pool", dict(dtype=jnp.int8), dict(dtype=jnp.int8)),
        ("float64", dict(dtype=jnp.float64), dict(dtype=jnp.float64)),
        ("pages past the VMEM budget", {}, dict(bs=1024, kvh=32)),
    ])
    def test_ineligible(self, why, q, pool):
        assert not pa._eligible(self.q(**q), self.pool(**pool)), why

    def test_forced_kernel_refuses_ineligible_operands(self):
        q, pk, pv, table, pos, _ = a_case(jnp.float32, 2)
        with pytest.raises(ValueError, match="kernel-eligible"):
            pa.paged_decode_attention(q[..., :64], pk[..., :64],
                                      pv[..., :64], table, pos,
                                      impl="pallas")


@pytest.mark.parametrize("bad,match", [
    (dict(impl="mosaic"), "unknown impl"),
    (dict(window=-1), "window"),
    (dict(q=jnp.zeros((6, 3, HD))), "multiple of KV heads"),
    (dict(q=jnp.zeros((6, 2, 64))), "head_dim"),
    (dict(pos=np.zeros((5,), np.int32)), r"\(slots,\)"),
    (dict(table=np.zeros((5, N_BLK), np.int32)), "n_blk"),
])
def test_validation(bad, match):
    q, pk, pv, table, pos, _ = a_case(jnp.float32, 2)
    kw = dict(q=q, table=table, pos=pos, window=0, impl="auto")
    kw.update(bad)
    with pytest.raises(ValueError, match=match):
        pa.paged_decode_attention(kw["q"], pk, pv, kw["table"], kw["pos"],
                                  window=kw["window"], impl=kw["impl"])


# ------------------------------------------------------- the latent read

W, V = 256, 128          # a row's stored width, and its value's


def a_latent_case(dtype, heads=8, seed=0):
    """``a_case`` with a latent pool: one row a token for all heads."""
    q, pk, _, table, pos, active = a_case(dtype, 1, seed)
    rng = np.random.default_rng(seed + 1)
    mk = lambda shape: jnp.asarray(rng.standard_normal(shape), dtype)
    return (mk((6, heads, W)), mk(pk.shape[:2] + (1, W)), table, pos,
            active)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads", [4, 16])
def test_latent_kernel_equals_its_jnp_path(dtype, heads):
    q, pool, table, pos, active = a_latent_case(dtype, heads)
    kw = dict(v_width=V, scale=0.17, active=active)
    got = pa.paged_latent_attention(q, pool, table, pos, impl="pallas", **kw)
    want = pa.paged_latent_attention(q, pool, table, pos, impl="jnp", **kw)
    assert got.dtype == q.dtype and got.shape == (6, heads, V)
    np.testing.assert_allclose(f32(got)[active], f32(want)[active],
                               atol=TOL[dtype], rtol=TOL[dtype])
    assert not f32(got)[~active].any() and not f32(want)[~active].any()


def test_latent_read_is_attention_over_the_rows_as_they_lie():
    """Against the definition written out: every head scores the slot's
    rows at the stored width and sums their first ``v_width``
    channels."""
    q, pool, table, pos, active = a_latent_case(jnp.float32)
    got = pa.paged_latent_attention(q, pool, table, pos, v_width=V,
                                    scale=0.17, active=active)
    bs = pool.shape[1]
    for s in np.flatnonzero(active):
        rows = np.concatenate([np.asarray(pool[table[s, j], :, 0])
                               for j in range(pos[s] // bs + 1)])
        rows = rows[:pos[s] + 1].astype(np.float64)
        sc = 0.17 * np.asarray(q[s], np.float64) @ rows.T
        p = np.exp(sc - sc.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ rows[:, :V]
        np.testing.assert_allclose(np.asarray(got[s]), want, atol=5e-6)


def test_latent_kernel_reads_no_page_behind_a_frontier():
    """Pages no live slot's frontier reaches are NaN here: neither path
    lets them through, and a free slot reads nothing."""
    q, pool, table, pos, active = a_latent_case(jnp.float32)
    bs = pool.shape[1]
    reached = {int(table[s, j]) for s in np.flatnonzero(active)
               for j in range(pos[s] // bs + 1)}
    poisoned = np.asarray(pool).copy()
    for page in set(range(pool.shape[0])) - reached:
        poisoned[page] = np.nan
    for impl in ("pallas", "jnp"):
        got = pa.paged_latent_attention(
            q, jnp.asarray(poisoned), np.where(table >= 0, table, -1), pos,
            v_width=V, scale=0.17, active=active, impl=impl)
        want = pa.paged_latent_attention(q, pool, table, pos, v_width=V,
                                         scale=0.17, active=active,
                                         impl=impl)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_latent_kernel_wants_whole_lanes():
    q, pool, table, pos, _ = a_latent_case(jnp.float32)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    assert pa._eligible(like(q), like(pool), V)
    assert not pa._eligible(like(q), like(pool), 96)
    assert not pa._eligible(like(q[..., :200]), like(pool[..., :200]), V)
    assert not pa.uses_kernel(like(q), like(pool), V)       # no TPU here
    with pytest.raises(ValueError, match="kernel-eligible"):
        pa.paged_latent_attention(q, pool, table, pos, v_width=96,
                                  scale=1.0, impl="pallas")
    with pytest.raises(ValueError, match="v_width"):
        pa.paged_latent_attention(q, pool, table, pos, v_width=W + 1,
                                  scale=1.0)


# ------------------------------------- several pages a grid step (ISSUE 40)

# pages a grid step -> a table width that the rule gives it at these
# page sizes (the widths are small: the staging budget never binds)
WIDTHS = {1: 5, 2: 6, 4: 12, 8: 16}
# A row of 16 pages sums four times the rows of ``a_case``'s.
WIDE_TOL = 1e-5


def grouped_case(read, group, dtype=jnp.float32, seed=0, window=0):
    """Eight slots over a table of ``WIDTHS[group]`` pages, frontiers at
    0, 1, ``group - 1``, ``group``, ``group + 1`` pages, at the table's
    whole width, mid-table, and one inactive slot (``pos = -1``): groups
    wholly dead, partly live and wholly live.  ``call(table, pos, pools)``
    runs the read's kernel; ``oracle(...)`` its jnp path."""
    bs, n_blk = block_size(dtype), WIDTHS[group]
    rng = np.random.default_rng(seed)
    pages = sorted({1, max(group - 1, 1), group, min(group + 1, n_blk),
                    n_blk, (n_blk + 1) // 2})
    pos = [0] + [p * bs - 1 - int(rng.integers(0, bs - 1)) for p in pages]
    pos = np.array((pos + [n_blk * bs - 1] * 8)[:7] + [-1], np.int32)
    slots, nb = len(pos), len(pos) * n_blk + 1
    ids = [int(i) for i in rng.permutation(np.arange(1, nb))]
    table = np.full((slots, n_blk), -1, np.int32)
    for s in range(slots):
        for j in range(pos[s] // bs + 1 if pos[s] >= 0 else 0):
            table[s, j] = ids.pop()
    mk = lambda *shape: jnp.asarray(rng.standard_normal(shape), dtype)
    if read == "kv":
        q, pools = mk(slots, 2 * KVH, HD), (mk(nb, bs, KVH, HD),
                                            mk(nb, bs, KVH, HD))
        run = lambda impl: lambda table, pos, pools=pools: \
            pa.paged_decode_attention(q, *pools, table, pos, window=window,
                                      impl=impl)
    else:
        q, pools = mk(slots, 8, W), (mk(nb, bs, 1, W),)
        run = lambda impl: lambda table, pos, pools=pools: \
            pa.paged_latent_attention(q, *pools, table, pos, v_width=V,
                                      scale=0.17, impl=impl)
    assert pa.read_grid(slots, n_blk, *pools) == (slots, n_blk // group)
    return run("pallas"), run("jnp"), table, pos, pools


def same_fold(got, want, tol=WIDE_TOL):
    """A step's pages enter the softmax in one update where the one-page
    grid makes one update a page: the same sums in another order."""
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


def one_page_fold(call, table, pos, pools=None):
    """The same read through a grid of ONE page a step: a table row one
    unmapped column wider is odd, so the rule gives it one page."""
    wide = np.concatenate(
        [table, np.full((table.shape[0], 1 + table.shape[1] % 2), -1,
                        np.int32)], axis=1)
    assert wide.shape[1] % 2 == 1
    return call(wide, pos) if pools is None else call(wide, pos, pools)


@pytest.mark.parametrize("group", [1, 2, 4, 8])
@pytest.mark.parametrize("read", ["kv", "latent"])
class TestSeveralPagesAGridStep:
    def test_equals_the_one_page_fold_and_the_oracle(self, read, group):
        """(a), (b): whatever part of a group is live, the step's one
        update gives what a page at a time gives, to rounding (and,
        where the rule gives one page a step, bit for bit: the one-page
        fold is this kernel)."""
        call, oracle, table, pos, _ = grouped_case(read, group)
        got = f32(call(table, pos))
        same_fold(got, f32(one_page_fold(call, table, pos)),
                  WIDE_TOL if group > 1 else 0)
        np.testing.assert_allclose(got, f32(oracle(table, pos)),
                                   atol=WIDE_TOL, rtol=WIDE_TOL)
        assert not got[-1].any()                  # the inactive slot

    def test_bfloat16_too(self, read, group):
        call, oracle, table, pos, _ = grouped_case(read, group,
                                                   jnp.bfloat16, seed=1)
        got = f32(call(table, pos))
        same_fold(got, f32(one_page_fold(call, table, pos)), 2e-2)
        np.testing.assert_allclose(got, f32(oracle(table, pos)),
                                   atol=2e-2, rtol=2e-2)

    def test_an_unmapped_page_inside_a_live_group_reads_as_zeros(
            self, read, group):
        """(c): whatever the clamped look-up fetched (the pool's page 0,
        NaN here) is discarded."""
        call, oracle, table, pos, pools = grouped_case(read, group, seed=2)
        bs = pools[0].shape[1]
        table = table.copy()
        for s in np.flatnonzero(pos // bs >= 1):
            table[s, (pos[s] // bs) // 2] = -1   # one hole a slot
        pools = tuple(p.at[0].set(jnp.nan) for p in pools)
        got = f32(call(table, pos, pools))
        assert np.isfinite(got).all()
        same_fold(got, f32(one_page_fold(call, table, pos, pools)))
        np.testing.assert_allclose(got, f32(oracle(table, pos, pools)),
                                   atol=WIDE_TOL, rtol=WIDE_TOL)

    def test_no_operand_reads_a_dead_or_a_foreign_page(self, read, group):
        """(e): NaN in every page past a frontier (the table names real
        pages there too) and, slot by slot, in every page of the other
        slots: each operand of a group either folds a live page of its
        own slot or folds nothing."""
        call, _, table, pos, pools = grouped_case(read, group, seed=3)
        bs, n_blk = pools[0].shape[1], table.shape[1]
        clean = f32(call(table, pos))
        # Past the frontiers the table names pages of their own.
        grow = lambda p: jnp.concatenate(
            [p, jnp.full((table.size,) + p.shape[1:], jnp.nan, p.dtype)])
        dead = iter(range(pools[0].shape[0],
                          pools[0].shape[0] + table.size))
        full = table.copy()
        for s in range(table.shape[0]):
            for j in range(n_blk):
                if full[s, j] < 0:
                    full[s, j] = next(dead)
        got = f32(call(full, pos, tuple(grow(p) for p in pools)))
        np.testing.assert_array_equal(got, clean)
        held = {s: table[s, :pos[s] // bs + 1] for s in range(len(pos))
                if pos[s] >= 0}
        for s, mine in held.items():
            keep = np.zeros(pools[0].shape[0], bool)
            keep[mine] = True
            alone = tuple(jnp.where(keep[:, None, None, None], p, jnp.nan)
                          for p in pools)
            np.testing.assert_array_equal(
                f32(call(table, pos, alone))[s], clean[s])


@pytest.mark.parametrize("group", [1, 2, 4, 8])
def test_a_window_that_opens_inside_a_group(group):
    """(d): the first attended page of a slot lies in the middle of a
    group, pages before it are dead (NaN here) and the rest of the
    group is live: equal to the one-page fold and to the gather path."""
    bs, window = block_size(jnp.float32), 2 * block_size(jnp.float32) + 3
    call, oracle, table, pos, pools = grouped_case("kv", group, seed=4,
                                                   window=window)
    got = f32(call(table, pos))
    same_fold(got, f32(one_page_fold(call, table, pos)))
    np.testing.assert_allclose(got, f32(oracle(table, pos)),
                               atol=WIDE_TOL, rtol=WIDE_TOL)
    behind = {int(table[s, j]) for s in range(len(pos)) if pos[s] >= 0
              for j in range((pos[s] - window + 1) // bs)}
    ahead = {int(table[s, j]) for s in range(len(pos)) if pos[s] >= 0
             for j in range(max(pos[s] - window + 1, 0) // bs,
                            pos[s] // bs + 1)}
    if group > 1:
        firsts = {(max(p - window + 1, 0) // bs) % group
                  for p in pos if p >= 0}
        assert firsts - {0}, "no window opens inside a group"
    poison = np.zeros(pools[0].shape[0], bool)
    poison[sorted(behind - ahead)] = True
    assert poison.any()
    nan = tuple(jnp.where(poison[:, None, None, None], jnp.nan, p)
                for p in pools)
    np.testing.assert_array_equal(f32(call(table, pos, nan)), got)


@pytest.mark.parametrize("n_named", [0, 1, 513, 2048])
def test_sparse_read_over_four_chunks_equals_its_oracle(n_named):
    """(f): the read of the selected rows hands the latent kernel four
    chunks of 512 gathered rows a slot, ``n_named - 1`` as the position;
    the rule gives it all four in one grid step."""
    rng = np.random.default_rng(5)
    slots, k, bs, n_blk = 2, 2048, 128, 20
    mk = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    q, pool = mk(slots, 4, W), mk(slots * n_blk, bs, 1, W)
    table = rng.permutation(slots * n_blk).astype(np.int32).reshape(
        slots, n_blk)
    rows = np.full((slots, k), -1, np.int32)
    for s in range(slots):
        rows[s, :n_named] = rng.permutation(n_blk * bs)[:n_named]
    chunk = pa._sparse_chunk(q, pool, k, V)
    assert chunk == 512 and pa.read_grid(
        slots, k // chunk,
        jax.ShapeDtypeStruct((1, chunk, 1, W), q.dtype)) == (slots, 1)
    got = pa.paged_sparse_latent_attention(q, pool, table, rows, v_width=V,
                                           scale=0.17, impl="pallas")
    want = pa.latent_rows_attention(
        q, pa.sparse_rows_gather(pool, table, rows),
        jnp.full((slots,), n_named - 1, jnp.int32), v_width=V, scale=0.17)
    np.testing.assert_allclose(f32(got), f32(want), atol=WIDE_TOL, rtol=WIDE_TOL)
    if not n_named:
        assert not f32(got).any()


class TestTheRuleForPagesAStep:
    """``read_grid`` at the four serving cells' shapes: the pages a grid
    step takes, and that what it stages (each page double-buffered)
    stays within the budget a single page is held to."""
    like = staticmethod(lambda *shape: jax.ShapeDtypeStruct(shape,
                                                            jnp.bfloat16))

    @pytest.mark.parametrize("cell,slots,n_blk,pools,group", [
        ("serve_latent_4k", 32, 64, [(2048, 128, 1, 640)], 8),
        ("serve_scmoe_1k", 32, 32, [(1024, 128, 1, 640)], 8),
        ("serve_chat", 16, 20, [(320, 128, 8, 128)] * 2, 4),
        ("serve_dsa_16k scoring", 16, 136, [(2176, 128, 1, 128)], 8),
        ("serve_dsa_16k selected rows", 16, 4, [(64, 512, 1, 640)], 4),
    ])
    def test_the_cells_shapes(self, cell, slots, n_blk, pools, group):
        from mpi4torch_tpu.ops.flash import _KV_VMEM_BUDGET
        pools = [self.like(*p) for p in pools]
        assert pa.read_grid(slots, n_blk, *pools) \
            == (slots, n_blk // group), cell
        page = sum(int(np.prod(p.shape[1:])) * 2 for p in pools)
        assert 2 * group * page <= _KV_VMEM_BUDGET

    def test_the_budget_binds_before_the_divisors_do(self):
        # a page pair of 2 MB: two fit double-buffered, four do not
        pool = self.like(64, 512, 8, 128)
        assert pa.read_grid(4, 8, pool, pool) == (4, 4)
        # one alone is what _eligible already admits
        big = self.like(64, 1024, 8, 128)
        assert pa._eligible(self.like(4, 16, 128), big)
        assert pa.read_grid(4, 8, big, big) == (4, 8)

    @pytest.mark.parametrize("n_blk,group", [(1, 1), (7, 1), (6, 2),
                                             (12, 4), (20, 4), (24, 8)])
    def test_the_largest_divisor_of_the_tables_width(self, n_blk, group):
        assert pa.read_grid(3, n_blk, self.like(9, 16, 1, 128)) \
            == (3, n_blk // group)


@pytest.mark.parametrize("window", [0, 21])
@pytest.mark.parametrize("group", [1, 2, 4, 8])
def test_an_operand_outside_the_span_repeats_a_live_page_of_its_own(
        group, window):
    """What each operand of each grid step fetches (``_page_ids``): its
    own table entry inside the slot's span (an unmapped ``-1`` stays);
    outside it the nearest live page of the same operand, so that a
    dead step fetches nothing it will not fold; the pool's page 0 where
    the span holds no page of that operand."""
    bs, n_blk, rng = 8, WIDTHS[group], np.random.default_rng(group)
    pos = np.array([-1, 0, bs - 1, bs, n_blk * bs - 1, n_blk * bs + 9]
                   + list(rng.integers(0, n_blk * bs, 6)), np.int32)
    table = rng.permutation(np.arange(1, 1 + len(pos) * n_blk)).astype(
        np.int32).reshape(len(pos), n_blk)
    table[3, 0] = -1                               # a hole inside a span
    ids = np.asarray(pa._page_ids(jnp.asarray(table), jnp.asarray(pos), bs,
                                  window, group)).reshape(table.shape)
    for s, p in enumerate(pos):
        n_live = min(p // bs + 1, n_blk) if p >= 0 else 0
        first = max(p - window + 1, 0) // bs if window and p >= 0 else 0
        for page in range(n_blk):
            own = [q for q in range(first, n_live)
                   if q % group == page % group]
            if first <= page < n_live:
                want = table[s, page]
            elif own:
                want = table[s, min(own, key=lambda q: abs(q - page))]
            else:
                want = 0
            assert ids[s, page] == want, (s, p, page)
