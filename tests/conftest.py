"""Test configuration: an 8-device virtual CPU platform.

Multi-chip hardware isn't available in CI; sharded paths are validated on a
virtual CPU mesh (jax's xla_force_host_platform_device_count), matching the
driver's dryrun_multichip environment.  JAX_PLATFORMS set from outside wins.
The persistent compile cache is wtf_tpu's (wtf_tpu.compile_cache_dir): the
step program is large and tests compile it for several (lanes, chunk)
shapes, so caching across test processes cuts reruns several-fold.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
