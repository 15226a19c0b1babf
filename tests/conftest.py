"""Test-session environment setup.

Force a 4-device CPU platform *before* anything imports jax, so the
mesh-sharded engine (``hype_sharded``, DESIGN.md §4c) is exercised on a
real multi-device mesh in every CI run. Harmless for the single-device
engines: jit still places un-sharded computations on device 0. The
persistent compile cache stays off, so a test run writes no
``.jax_cache`` into the checkout.
"""
import os

_FLAG = "--xla_force_host_platform_device_count=4"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " " + _FLAG).strip()
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
