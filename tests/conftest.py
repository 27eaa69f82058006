"""Test configuration: force JAX onto a virtual 8-device CPU platform.

The tests never touch a chip: sharding tests run against
``--xla_force_host_platform_device_count=8`` (see the build rules in the
repo docs).  Must run before any ``import jax`` anywhere in the suite.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "true")

from hbbft_tpu.utils.jaxcache import enable_cache  # noqa: E402

enable_cache()
