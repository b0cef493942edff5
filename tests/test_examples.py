"""Example programs as integration tests (reference: examples/ — the two
scripts are parity configs #1 and #3 in BASELINE.md)."""

import importlib.util
import sys
from pathlib import Path

import numpy as np
import pytest

import mpi4torch_tpu as mpi

EXAMPLES = Path(__file__).resolve().parent.parent / "examples"


def _load(name):
    spec = importlib.util.spec_from_file_location(name, EXAMPLES / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("nranks", [2, 5])
def test_simple_linear_regression(nranks):
    mod = _load("simple_linear_regression")
    results = mpi.run_ranks(mod.main, nranks)
    params0, loss0 = results[0]
    for p, _ in results:
        np.testing.assert_array_equal(params0, p)
    np.testing.assert_allclose(params0, [0.1, 1.0, -2.0], atol=1e-5)


def test_regression_rank_count_invariance():
    # The documented property (reference doc/examples.rst:46-65): the
    # parameter-averaging Allreduce makes the optimization trajectory
    # independent of the number of ranks.
    mod = _load("simple_linear_regression")
    p2 = mpi.run_ranks(mod.main, 2)[0][0]
    p5 = mpi.run_ranks(mod.main, 5)[0][0]
    np.testing.assert_allclose(p2, p5, rtol=1e-8)


def test_resnet_cifar_dp():
    # Parity config #4: per-param-grad Allreduce DP ResNet-18.  Reduced
    # width/depth/resolution — the full-size model is the manual entry
    # point; the recipe under test is identical.
    mod = _load("resnet_cifar_dp")
    from mpi4torch_tpu.models.resnet import ResNetConfig
    small = ResNetConfig(num_classes=10, stage_sizes=(1, 1), widths=(8, 16))
    results = mpi.run_ranks(
        lambda: mod.main(steps=2, cfg=small, hw=8, batch_per_rank=2), 2)
    losses0, head0 = results[0]
    for _, h in results:
        np.testing.assert_array_equal(head0, h)
    assert losses0[-1] < losses0[0]


@pytest.mark.slow  # multi-minute stencil convergence; TPU-manual lane (tier-1 budget)
class TestHaloExchangeStencil:
    # Parity config #5: 2D stencil PDE loss over the differentiable
    # Isend/Irecv/Wait halo-exchange ring, solved with the
    # domain-decomposed L-BFGS (globally-reduced line-search scalars).

    def test_converges_and_reassembles(self):
        mod = _load("halo_exchange_stencil")
        results = mpi.run_ranks(lambda: mod.main(steps=60), 4)
        losses0 = results[0][0]
        assert losses0[-1] < 1e-6 * losses0[0]
        full = np.concatenate([u for _, u in results], axis=0)
        assert full.shape == (mod.GRID_N, mod.GRID_M)

    def test_rank_count_invariance(self):
        # The solved field must not depend on the decomposition: 1 rank
        # (no communication at all) and 4 ranks (two ring exchanges per
        # loss evaluation) land on the same solution of lap(u) = g.
        mod = _load("halo_exchange_stencil")
        u1 = mpi.run_ranks(lambda: mod.main(steps=60), 1)[0][1]
        r4 = mpi.run_ranks(lambda: mod.main(steps=60), 4)
        u4 = np.concatenate([u for _, u in r4], axis=0)
        np.testing.assert_allclose(u4, u1, atol=1e-8)


@pytest.mark.parametrize("attn", ["ring", "ulysses"])
def test_ring_attention_longcontext(attn):
    # SURVEY.md §2.5 SP/CP demo: sharded attention == dense oracle over
    # the full context, values and gradients, on 4 ranks.
    mod = _load("ring_attention_longcontext")
    nranks, spr = 4, 8
    q, k, v = mod.make_qkv(nranks * spr)
    import jax
    import jax.numpy as jnp
    ref_out = mod.dense_attention(q, k, v, causal=True)
    ref_dq = jax.grad(lambda q: jnp.sum(
        mod.dense_attention(q, k, v, causal=True) ** 2))(q)
    results = mpi.run_ranks(lambda: mod.main(spr, attn), nranks)
    out = np.concatenate([o for o, _ in results], axis=1)
    dq = np.concatenate([g for _, g in results], axis=1)
    np.testing.assert_allclose(out, np.asarray(ref_out), rtol=1e-9,
                               atol=1e-11)
    np.testing.assert_allclose(dq, np.asarray(ref_dq), rtol=1e-9, atol=1e-11)


@pytest.mark.parametrize("nranks", [2, 5])
def test_isend_recv_wait(nranks):
    mod = _load("isend_recv_wait")
    results = mpi.run_ranks(mod.main, nranks)
    for r, (res, grad) in enumerate(results):
        left = (r - 1 + nranks) % nranks
        assert res[0] == (1.0 + r) + (1.0 + left)
        assert grad[0] == 2.0


@pytest.mark.parametrize("nranks", [4, 8])
def test_variable_token_exchange(nranks):
    # Butterfly p2p + ragged repartition demo (examples docstring): the
    # span contents, padding zeros, and per-rank gradient oracle are the
    # example's own asserts; run its __main__ under both rank counts.
    import subprocess
    import sys as _sys

    import os as _os

    env = dict(_os.environ)
    env["PYTHONPATH"] = (str(EXAMPLES.parent) + _os.pathsep
                         + env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [_sys.executable, str(EXAMPLES / "variable_token_exchange.py"),
         str(nranks)],
        capture_output=True, text=True, timeout=300, env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "OK:" in proc.stdout


def test_checkpoint_resume(tmp_path):
    # Preempted-then-resumed DP training must equal the uninterrupted
    # run bit-for-bit (the example asserts this internally too).
    mod = _load("checkpoint_resume")
    import sys as _sys
    argv = _sys.argv
    _sys.argv = ["checkpoint_resume", "3", str(tmp_path / "w")]
    try:
        outs = mpi.run_ranks(mod.main, 3)
    finally:
        _sys.argv = argv
    for o in outs:
        np.testing.assert_array_equal(o, outs[0])
    # Converged toward y = 3x + 0.5.
    assert abs(outs[0][0] - 3.0) < 1.5 and abs(outs[0][1] - 0.5) < 1.5


@pytest.mark.parametrize("nranks", [2, 4])
def test_pipeline_training(nranks):
    # GPipe and 1F1B agree on step 1 (asserted inside main) and 1F1B
    # training converges on every rank.
    mod = _load("pipeline_training")
    outs = mpi.run_ranks(mod.main, nranks)
    for losses in outs:
        assert losses == outs[0]
        assert losses[-1] < 0.7 * losses[0]


@pytest.mark.parametrize("nranks", [2, 4])
def test_tensor_parallel_mlp(nranks):
    # TP trajectory matches the single-device oracle at every step
    # (asserted inside main); rank-count invariant.
    mod = _load("tensor_parallel_mlp")
    outs = mpi.run_ranks(mod.main, nranks)
    for losses in outs:
        assert losses == outs[0]


@pytest.mark.slow  # heavyweight MoE compile; TPU-manual lane (tier-1 budget)
def test_expert_parallel_moe():
    # EP loss and (rank-summed / size) grads equal the per-shard dense
    # oracle at every step (asserted inside main).
    mod = _load("expert_parallel_moe")
    outs = mpi.run_ranks(mod.main, 2)
    for losses in outs:
        assert losses == outs[0]


@pytest.mark.slow  # multi-minute generation loop; TPU-manual lane (tier-1 budget)
def test_generate_kv_cache():
    # DP training in lock-step, then KV-cache generation equal to the
    # full-forward greedy oracle (asserted inside main); the tiny LM must
    # actually have learned the repeating pattern it was trained on.
    mod = _load("generate_kv_cache")
    gen, want = mod.main(2)
    assert (gen == want).mean() >= 0.9


def test_zero_sharded_optimizer():
    # ZeRO-1 example: sharded-Adam params equal the replicated oracle on
    # every rank (asserted inside main).
    mod = _load("zero_sharded_optimizer")
    got, ref = mod.main(4)
    np.testing.assert_allclose(np.asarray(got["b"]), np.asarray(ref["b"]),
                               rtol=1e-9)


def test_vit_patch_parallel():
    # DP ViT training + patch-parallel (non-causal ring attention)
    # inference matching the single-process forward.
    mod = _load("vit_patch_parallel")
    results = mpi.run_ranks(lambda: mod.main(steps=2), 2)
    losses0, head0, shard0, single0 = results[0]
    for _, h, sh, si in results:
        np.testing.assert_array_equal(head0, h)
        np.testing.assert_allclose(sh, si, rtol=1e-5, atol=1e-6)
    assert losses0[-1] < losses0[0]


def test_compressed_data_parallel():
    # Compressed gradient sync (doc/compression.md): the q8_ef and
    # carried-EF runs must land within 2% of the fp32 baseline loss —
    # the subsystem's acceptance gate, exercised through the shipped
    # example itself.  Shortened horizon: the variants track each other
    # at any step count (tests/test_compress.py gates the full-length
    # convergence), so the integration test need not re-run it.
    mod = _load("compressed_data_parallel")
    mod.STEPS = 60
    results = mpi.run_ranks(mod.main, 2)
    fp32, ef, st = results[0]
    assert abs(ef - fp32) <= 0.02 * fp32
    assert abs(st - fp32) <= 0.02 * fp32
    for r in results[1:]:
        assert r == results[0]   # rank-identical training trajectories


class TestFlashTileProbe:
    """examples/flash_tile_probe.py is a chip script; its control flow
    runs here at tiny shapes, interpreted (its times mean nothing)."""

    def test_rehearsal_writes_the_plan_and_a_check(self, tmp_path,
                                                   monkeypatch, capsys):
        import json

        mod = _load("flash_tile_probe")
        monkeypatch.chdir(tmp_path)
        assert mod.main(["--rehearse", "--shapes", "tiny_off", "--iters",
                         "1", "--check"]) == 0
        rows = [json.loads(line) for line in open(
            tmp_path / "chiprun_out" / "flash_tile_probe.jsonl")]
        check = next(r for r in rows if "check" in r)
        assert max(check[k] for k in ("out", "lse", "dq", "dk", "dv")) < 1e-4
        plan = next(r for r in rows if "plan" in r)
        assert plan["plan"]["fwd"][:2] == [256, 256]      # float32 here
        assert plan["visited_masked"]["fwd"] == [2, 1]
        assert json.loads(capsys.readouterr().out.splitlines()[-1])["ok"]

    def test_refuses_to_time_off_the_tpu(self, capsys):
        mod = _load("flash_tile_probe")
        assert mod.main(["--shapes", "prefill_256"]) == 1
        assert "not on a TPU" in capsys.readouterr().out

    def test_table_names_baseline_floor_plan_and_best(self):
        mod = _load("flash_tile_probe")
        key = mod.flash.KERNEL_NAMES[0] + "_ms"
        rows = [dict(shape="s", label="plan", tiles=[256, 512], **{key: 3.0}),
                dict(shape="s", label="tiles", tiles=[128, 128], **{key: 9.0}),
                dict(shape="s", label="tiles", tiles=[512, 512], **{key: 2.5}),
                dict(shape="s", label="baseline", tiles=[128, 128],
                     **{key: 10.0}),
                dict(shape="s", label="tiles", tiles=[1024, 1024],
                     error="refused")]
        line = mod.table(rows).splitlines()[1].split()
        assert line == ["s", mod.flash.KERNEL_NAMES[0], "10.000", "9.000",
                        "3.000", "[256,512]", "2.500", "[512,512]"]

    def test_no_trace_no_durations(self, tmp_path):
        assert _load("flash_tile_probe").kernel_durations(str(tmp_path)) == {}


class TestKdaProbe:
    """examples/kda_probe.py is a chip script; its control flow runs
    here at a tiny shape, the kernels interpreted (its times mean
    nothing)."""

    def test_rehearsal_times_both_paths_and_a_baseline(self, tmp_path,
                                                       monkeypatch):
        import json

        mod = _load("kda_probe")
        monkeypatch.chdir(tmp_path)
        assert mod.main(["--rehearse", "--iters", "1", "--check",
                         "--baseline", mod.kda.__file__]) == 0
        rows = [json.loads(line) for line in open(
            tmp_path / "chiprun_out" / "kda_probe.jsonl")]
        assert [(r["module"], r["impl"]) for r in rows] == [
            ("this", "jnp"), ("this", "pallas"),
            ("baseline", "jnp"), ("baseline", "pallas")]
        for row in rows:
            assert row["fwd_ms"] > 0 and row["grad_ms"] > 0
            # bfloat16 operands: the two paths round differently on the
            # way into each product, forward and (the kernels' backward
            # is their own arithmetic) backward: 3.7e-3 seen in a
            # gradient; the plain path twice is one program
            assert row["out_gap"] < 2e-3 and set(row["grad_gaps"]) == set(
                ("q", "k", "v", "g", "beta"))
            assert row["grad_gap"] < (2e-2 if row["impl"] == "pallas"
                                      else 1e-9)

    def test_refuses_to_time_off_the_tpu(self, capsys):
        assert _load("kda_probe").main([]) == 3
        assert "no TPU here" in capsys.readouterr().err
