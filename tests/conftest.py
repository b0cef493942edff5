"""Test harness configuration.

The reference CI runs every test under ``mpirun -np {2,5,7}`` with
oversubscribed processes on one host (reference:
.github/workflows/test.yml:62-84).  The analogue here: a CPU platform with 8
virtual XLA devices (for the SPMD mesh backend) and the thread-SPMD eager
runtime (for per-rank tests) — see SURVEY.md §4 'What the rebuild needs'.

Must run before jax is imported anywhere.

Hardware gate: the suite is a CPU suite, so the platform is pinned with
``JAX_PLATFORMS=cpu`` whatever the ambient environment says.  The pin
must not be inescapable, or the compiled-kernel tests could never run:
``MPI4TORCH_TPU_REAL_DEVICES=1`` leaves the platform untouched and the
real devices visible.  ``make tpu-test`` runs the hardware-gated subset
with the hatch open (on the chip, through the chip tool).
"""

import os

_real_devices = os.environ.get("MPI4TORCH_TPU_REAL_DEVICES", "") == "1"
if not _real_devices:
    os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
# The reference test suite is float64 throughout (torch.double) — but only
# on the CPU harness.  On TPU, x64 is unsupported (f64 is emulated; the
# kernel tests run bf16/f32 anyway), so the hardware run keeps default
# precision unless the user says otherwise.
if not _real_devices:
    os.environ.setdefault("JAX_ENABLE_X64", "1")

import jax  # noqa: E402

# Warm the backend up on the main thread so rank-threads never race
# backend initialization.
jax.devices()
