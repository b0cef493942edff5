"""Driver entry-point contract tests: entry() compiles single-chip,
dryrun_multichip() compiles+executes the full distributed step on the
virtual 8-device CPU mesh."""

import pytest
import os
import sys

import jax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import __graft_entry__ as graft  # noqa: E402


def test_entry_compiles_and_runs():
    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)
    assert out.shape == (4, 128, 256)  # (batch, seq, vocab) logits


@pytest.mark.slow  # heavyweight compile/run; TPU-manual lane (tier-1 budget)
def test_dryrun_multichip_8():
    # 8 devices: the 3D dp x sp x ep mesh (MoE transformer; DP + ring
    # attention + expert dispatch in one program).
    graft.dryrun_multichip(8)


@pytest.mark.slow  # heavyweight compile/run; TPU-manual lane (tier-1 budget)
def test_dryrun_multichip_4():
    # Non-multiple-of-8: the 2D dp x sp dense-FFN fallback.
    graft.dryrun_multichip(4)
