"""Benchmark package init: simulate a multi-device CPU mesh.

Must run before anything imports jax (``python -m benchmarks.run``
imports this first), so the engine-scaling sweep can exercise the
mesh-sharded engine's device-count axis on CPU. No-op when the flag is
already set or when jax was imported earlier in the process — the
engine-scaling sweep then skips device counts that do not exist.
"""
import os

_FLAG = "--xla_force_host_platform_device_count=4"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " " + _FLAG).strip()
