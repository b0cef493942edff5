"""Mesh helpers (mesh.py): axis ordering, tier assignment, and that the
result plugs straight into comm_from_mesh/run_spmd collectives."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi4torch_tpu as mpi


class TestDeviceMesh:
    def test_axes_order_and_sizes(self):
        mesh = mpi.device_mesh({"dp": 2, "tp": 4})
        assert mesh.axis_names == ("dp", "tp")
        assert mesh.shape["dp"] == 2 and mesh.shape["tp"] == 4
        # Last axis varies fastest over the device order.
        devs = np.asarray(jax.devices()[:8]).reshape(2, 4)
        assert (mesh.devices == devs).all()

    def test_size_mismatch_raises(self):
        with pytest.raises(ValueError, match="multiply to"):
            mpi.device_mesh({"dp": 3, "tp": 2})

    def test_collectives_over_helper_mesh(self):
        mesh = mpi.device_mesh({"dp": 2, "tp": 4})
        comm_tp = mpi.comm_from_mesh(mesh, "tp")
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def body():
            return comm_tp.Allreduce(jnp.ones(()), mpi.MPI_SUM)[None]

        out = jax.jit(shard_map(body, mesh=mesh, in_specs=(),
                                out_specs=P(("dp", "tp")),
                                check_vma=False))()
        np.testing.assert_array_equal(np.asarray(out), 4.0)


class TestHybridMesh:
    def test_single_granule_degrades_to_device_mesh(self):
        # CPU harness: every device reports process 0 -> one granule,
        # dcn axes must be 1 and the result is an ordinary mesh.
        mesh = mpi.hybrid_mesh({"tp": 8}, {"dp": 1})
        assert mesh.axis_names == ("dp", "tp")
        assert mesh.shape["dp"] == 1 and mesh.shape["tp"] == 8

    def test_single_granule_rejects_wide_dcn(self):
        with pytest.raises(ValueError, match="one granule"):
            mpi.hybrid_mesh({"tp": 4}, {"dp": 2})  # 4x2 = 8 devices

    def test_axis_name_collision_raises(self):
        with pytest.raises(ValueError, match="disjoint"):
            mpi.hybrid_mesh({"dp": 8}, {"dp": 1})

    def test_size_mismatch_raises(self):
        with pytest.raises(ValueError, match="multiply to"):
            mpi.hybrid_mesh({"tp": 3}, {"dp": 1})
