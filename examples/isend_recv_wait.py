"""Nonblocking ring exchange with differentiable dependency tokens.

The TPU-native port of the reference's second example (reference:
examples/isend-recv-wait.py): each rank sends a value to its right
neighbor and receives from its left neighbor, with the
JoinDummies/JoinDummiesHandle token discipline encoding the orderings the
AD engine cannot see on its own (reference doc/basic_usage.rst:184-197).
The backward pass routes each gradient over the ring in the *reverse*
direction automatically.

Run:  python examples/isend_recv_wait.py [nranks]
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_ENABLE_X64", "1")

import jax
jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

import mpi4torch_tpu as mpi

comm = mpi.COMM_WORLD


def main():
    def program(a):
        handle = comm.Isend(a, (comm.rank + 1) % comm.size, 0)
        recvbuffer = mpi.JoinDummies(jnp.empty_like(a), [handle.dummy])
        b = comm.Recv(recvbuffer, (comm.rank - 1 + comm.size) % comm.size, 0)
        wait_ret = comm.Wait(mpi.JoinDummiesHandle(handle, [b]))
        res = mpi.JoinDummies(a + b, [wait_ret])
        return res.sum(), res

    a = jnp.asarray([1.0 + comm.rank])
    (_, res), grad = jax.value_and_grad(program, has_aux=True)(a)
    print(f"rank {comm.rank}: res = {np.asarray(res)}, "
          f"a.grad = {np.asarray(grad)}")
    return np.asarray(res), np.asarray(grad)


if __name__ == "__main__":
    nranks = int(sys.argv[1]) if len(sys.argv) > 1 else 4
    results = mpi.run_ranks(main, nranks)
    for r, (res, grad) in enumerate(results):
        left = (r - 1 + nranks) % nranks
        assert res[0] == (1.0 + r) + (1.0 + left)
        # a_r reaches its own output and the right neighbor's output
        assert grad[0] == 2.0
    print(f"OK: ring values and ring-routed gradients correct on "
          f"{nranks} ranks")
