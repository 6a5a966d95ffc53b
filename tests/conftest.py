"""Test configuration: force an 8-device virtual CPU mesh so multi-chip sharding
paths (pjit / shard_map over a Mesh) are exercised without TPU hardware.

Mirrors the reference's strategy of testing distributed semantics in-process
(reference: deeplearning4j-scaleout/spark/dl4j-spark/src/test/java/org/deeplearning4j/spark/BaseSparkTest.java:90
uses master=local[n]); here N virtual XLA CPU devices play that role.
"""
import os
import sys
from pathlib import Path

# repo root on sys.path regardless of how pytest was invoked: tests import
# repo-level helpers (tools/smoke_serving.py) that are not in the package
_ROOT = str(Path(__file__).resolve().parents[1])
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = _flags + " --xla_force_host_platform_device_count=8"

import jax

# The tests run on the CPU wherever they are started: they need the 8 virtual
# devices above for the mesh paths and f64 for the gradient checks, and
# neither exists on a TPU. Pinned through the config API so that it holds
# with or without JAX_PLATFORMS in the environment.
jax.config.update("jax_platforms", "cpu")

# Gradient checks follow the reference's double-precision-on-CPU strategy
# (reference: gradientcheck/GradientCheckUtil.java:29-38 requires DOUBLE dtype).
jax.config.update("jax_enable_x64", True)
