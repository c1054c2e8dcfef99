"""Root conftest: run JAX on a virtual 8-device CPU mesh for all tests.

Mirrors the reference's test strategy (SURVEY.md section 4): "distributed"
behavior is exercised with many in-process endpoints before real hardware —
here, an 8-device host-platform mesh standing in for a TPU slice. Both
variables are read when JAX first initialises a backend, so they are set
before any test module imports jax.
"""
import faulthandler
faulthandler.enable()

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

# Pay jax's one-time import here, before any test starts servers: the
# first `import jax` inside a test competes with that test's busy server
# threads for the interpreter lock and can take tens of seconds.
import jax  # noqa: E402,F401
