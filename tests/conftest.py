"""Test harness: force an 8-device virtual CPU mesh before jax imports.

Multi-chip TPU hardware is not available in CI; sharding correctness is
validated on a virtual host-platform mesh (the driver separately dry-runs
the multi-chip path via __graft_entry__.dryrun_multichip).
"""

import os

# Force CPU even when the environment points at a real accelerator: CI must
# be hermetic and the virtual 8-device mesh only exists on the host
# platform.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "1")

# Under pytest, plugins may import/configure jax before this conftest runs,
# so the env vars alone are not reliable — set the config directly too.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
