"""Named-op observability (SURVEY.md §5 tracing).

The reference's only observability surface is its autograd node names
(e.g. ``MPIAllreduceSumBackward``, csrc/extension.cpp:256-258) showing up
in torch's profiler.  Here every facade op runs under a
``jax.named_scope`` and every SPMD *collective* adjoint under an explicit
``...Backward`` scope, so lowered programs (and hence JAX profiler
traces) carry the spans.  The p2p adjoints are the exception: their
reverse-direction permute comes from XLA's built-in transpose of
``ppermute`` and carries the forward scope's transpose metadata instead
of a dedicated span.  Asserted on the lowered StableHLO text."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi4torch_tpu as mpi
from mpi4torch_tpu import COMM_WORLD as comm


def _lowered_text(fn, *args):
    # debug_info keeps the loc()/name-stack metadata the profiler uses.
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


class TestNamedScopes:
    def test_forward_and_backward_spans_in_spmd_program(self):
        def prog(x):
            def loss(v):
                y = comm.Allreduce(v, mpi.MPI_SUM)
                z = comm.Allgather(y, 0)
                return jnp.sum(z * z)
            return jax.value_and_grad(loss)(x)

        def wrapped(x):
            return mpi.run_spmd(prog, nranks=4, jit=False)(x)

        import re

        txt = _lowered_text(wrapped, jnp.ones(8))
        # \b-terminated: "mpi4torch.Allreduce\b" cannot be satisfied by the
        # Backward span's substring, so forward-scope removal is caught.
        for span in ("mpi4torch\\.Allreduce\\b", "mpi4torch\\.Allgather\\b",
                     "mpi4torch\\.AllreduceBackward\\b",
                     "mpi4torch\\.AllgatherBackward\\b"):
            assert re.search(span, txt), f"missing span {span}"

    def test_p2p_spans(self):
        def prog(x):
            h = comm.Isend(x, (comm.rank + 1) % comm.size, 0)
            buf = mpi.JoinDummies(jnp.zeros_like(x), [h.dummy])
            y = comm.Recv(buf, (comm.rank - 1) % comm.size, 0)
            ret = comm.Wait(mpi.JoinDummiesHandle(h, [y]))
            return mpi.JoinDummies(x + y, [ret])

        def wrapped(x):
            return mpi.run_spmd(prog, nranks=4, jit=False)(x)

        txt = _lowered_text(wrapped, jnp.ones(4))
        for span in ("mpi4torch.Isend", "mpi4torch.Recv", "mpi4torch.Wait"):
            assert span in txt, f"missing span {span}"

    def test_scopes_transparent_to_eager_semantics(self):
        # The scopes must not change any value/grad (eager backend runs
        # them as plain context managers).
        def body():
            x = jnp.full(3, float(comm.rank) + 1.0)
            y = comm.Allreduce(x, mpi.MPI_SUM)
            g = jax.grad(
                lambda v: jnp.sum(comm.Allreduce(v, mpi.MPI_SUM)))(x)
            return np.asarray(g), np.asarray(y)

        outs = mpi.run_ranks(body, 3)
        for g, y in outs:
            np.testing.assert_array_equal(y, np.full(3, 6.0))
            np.testing.assert_array_equal(g, np.full(3, 3.0))


class TestProfilerTrace:
    def test_trace_captures_op_spans(self, tmp_path):
        # The capture wrapper writes a profile dir; the named-scope
        # discipline it documents is asserted on HLO elsewhere in this
        # file.
        import os

        from mpi4torch_tpu.utils import profiler_trace

        logdir = str(tmp_path / "trace")

        def prog(x):
            return comm.Allreduce(x, mpi.MPI_SUM)

        step = mpi.run_spmd(prog, nranks=2)
        x = jnp.ones(8)
        step(x)                       # compile outside the trace window
        with profiler_trace(logdir):
            jax.block_until_ready(step(x))
        found = []
        for root, _dirs, files in os.walk(logdir):
            found += [f for f in files if f.endswith(".xplane.pb")]
        assert found, f"no xplane files under {logdir}"

    def test_exception_safe(self, tmp_path):
        from mpi4torch_tpu.utils import profiler_trace

        with pytest.raises(RuntimeError, match="boom"):
            with profiler_trace(str(tmp_path / "t")):
                raise RuntimeError("boom")
        # A new trace can start after the failed one (stop_trace ran).
        with profiler_trace(str(tmp_path / "t2")):
            pass
