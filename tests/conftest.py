"""Test environment: force an 8-device virtual CPU mesh before JAX initializes.

Mirrors the reference's test strategy of deterministic fake clusters
(/root/reference/cluster_test.go ModHasher): multi-device behavior is tested
on CPU-backed virtual devices, and Pallas kernels run in interpret mode.
"""

import os

# Tests are CPU-only: set before anything imports jax. (The one file
# that talks to the TPU's compiler, tests/test_tpu_compile.py, does so
# from inside a fixture and compiles for a chip that is described, not
# attached.)
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Cost routing would send every tiny-fixture Count to the host path —
# the suite's device tests assert WHICH engine served, so routing is
# off by default here; TestCostRouting opts back in with the explicit
# device_min_work arg (which beats this env).
os.environ.setdefault("PILOSA_TPU_DEVICE_MIN_WORK", "0")

# Deterministic chaos: the fault-injection schedule (prob= draws) runs
# off one seeded RNG, so the fault-marked tests replay identically.
os.environ.setdefault("PILOSA_TPU_FAULT_SEED", "0")

