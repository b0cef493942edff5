"""The on-chip smoke's control flow, on the CPU.

``chip_smoke.py`` spends chip time, so everything about it that the CPU can
check is checked here: its phase functions pass at tiny widths with x64 off
(the chip runs without x64), its device check cannot be skipped, the shared
compile-cache function resolves one fixed directory, and the paths that
used to hide the device now raise."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

import chip_smoke
from mpi4torch_tpu.models import transformer as T
from mpi4torch_tpu.ops import flash
from mpi4torch_tpu.utils import compile_cache

ROOT = Path(__file__).resolve().parent.parent
# hd = 64 keeps the attention shape kernel-eligible (interpreted here);
# 4 heads shard over the 4-rank TP world.  One layer: compiles dominate
# this file's cost, and tier-1 rides its time budget.
TINY = T.TransformerConfig(vocab=256, d_model=256, n_heads=4, n_layers=1,
                           d_ff=256, max_seq=128)


@pytest.fixture
def no_x64():
    with jax.enable_x64(False):
        yield


@pytest.mark.parametrize(
    "n", [pytest.param(1, marks=pytest.mark.slow), 4])
def test_phases_pass_at_tiny_widths(n, no_x64):
    tr, lowered_text, compiled_text = chip_smoke.run_trainer(
        TINY, jnp.float32, n, per_chip_batch=2, steps=3, lr=1e-2)
    assert len(tr["losses"]) == 3 and tr["losses"][-1] < tr["losses"][0]

    kern = chip_smoke.check_kernel(TINY, jnp.float32, 2, lowered_text,
                                   compiled_text)
    # Off the TPU impl="auto" is jnp: no Mosaic call in the program.
    assert set(kern["kernels_in_lowered"].values()) == {0}

    log = chip_smoke.CompileLog()
    dense, paged = chip_smoke.run_servers(
        TINY, jnp.float32, n, log, slots=4, requests=6, new_tokens=4,
        prompt_lens=(5, 9), block_size=16)
    assert (dense["cache"], paged["cache"]) == ("dense", "paged")
    for res in (dense, paged):
        # TP shards (the tree's top, the layers) + two prefill lengths
        # + ONE decode step.
        assert res["spmd_programs_compiled"] == 5
        assert res["decode_steps"] > 0


def test_trainer_check_fails_when_loss_does_not_fall(no_x64):
    with pytest.raises(chip_smoke.SmokeFailure, match="did not fall"):
        chip_smoke.run_trainer(TINY, jnp.float32, 1, per_chip_batch=2,
                               steps=2, lr=0.0)


def test_kernel_check_demands_the_kernel_on_a_tpu(no_x64, monkeypatch):
    class FakeTpu:
        platform = "tpu"

    monkeypatch.setattr(jax, "devices", lambda *a: [FakeTpu()])
    with pytest.raises(chip_smoke.SmokeFailure, match="want 1 of each"):
        chip_smoke.check_kernel(TINY, jnp.float32, 2, "", "")


def test_main_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "platform is 'cpu', not 'tpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


class TestCompileCache:
    def test_obeys_the_environment(self, monkeypatch, tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_fixed_in_checkout_path_from_any_cwd(self, monkeypatch,
                                                 tmp_path):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.chdir(tmp_path)
        before = jax.config.jax_compilation_cache_dir
        try:
            assert compile_cache.use_compile_cache() == \
                str(ROOT / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == \
                str(ROOT / ".jax_cache")
        finally:
            jax.config.update("jax_compilation_cache_dir", before)


def test_flash_raises_when_the_kernel_does_not_lower(monkeypatch):
    """On a (faked) TPU an eligible shape IS the kernel: the CPU cannot
    lower a compiled Mosaic call, and that failure must surface — not
    turn into a warning and the jnp path."""
    monkeypatch.setattr(flash, "_on_tpu", lambda: True)
    q = jnp.ones((1, 128, 2, 64), jnp.float32)
    assert flash._eligible(q, q)
    with pytest.raises(Exception, match="(?i)interpret|mosaic|tpu"):
        jax.block_until_ready(
            flash.flash_attention(q, q, q, causal=True, impl="auto"))
