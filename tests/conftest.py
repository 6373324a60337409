"""Test configuration: force an 8-device virtual CPU mesh before jax import.

Sharding tests run against 8 virtual CPU devices so multi-chip layouts are
validated without TPU pod hardware; the driver separately dry-runs the
multi-chip path via __graft_entry__.dryrun_multichip.
"""
import os
import tempfile

# Unit tests run on the virtual CPU mesh whatever the environment names:
# full-precision convs for the torch-parity oracle and no accelerator
# compile latency. Set before any test imports jax, and again through
# jax.config below.
os.environ["JAX_PLATFORMS"] = "cpu"
# The persistent compile cache (core/compile_cache.py) defaults to
# <checkout>/.jax_cache, which the chip tool copies to the chip with the
# tree: keep the suite's CPU entries out of it, in the temp directory.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(tempfile.gettempdir(), "chunkflow-pytest-jax-cache"),
)
# Anomaly-triggered profiler capture (core/profiling.py) is ON by
# default in production, but a background jax.profiler window starting
# mid-suite (many tests deliberately drive 100%-dominant stalls and
# retraces with a sink configured) would race the tests that own the
# one-session-at-a-time profiler. Default it off for the suite; the
# dedicated profiling tests opt back in with monkeypatch.
os.environ.setdefault("CHUNKFLOW_PROFILE_ON_ANOMALY", "0")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Locksmith concurrency sanitizer (chunkflow_tpu/testing/locksmith.py):
# proxy every Lock/RLock/Condition this codebase creates and raise on
# lock-order cycles, so the whole tier-1 suite doubles as a concurrency
# test. Installed BEFORE any chunkflow module import so module-level
# locks (scheduler watermark, profiling state, telemetry registry) are
# covered too. Default ON for the suite; CHUNKFLOW_LOCKSMITH=0 disables
# (and then install() is a strict no-op — no proxies, no files).
os.environ.setdefault("CHUNKFLOW_LOCKSMITH", "1")
from chunkflow_tpu.testing import locksmith  # noqa: E402

locksmith.install()

# Kernelcheck Pallas sanitizer (chunkflow_tpu/testing/kernelcheck.py):
# poison VMEM scratch, assert DMA windows in-bounds and verify the RMW
# grid order on every interpret-mode kernel run, so the tier-1 parity
# suites double as kernel sanitizer runs. Default ON for the suite;
# CHUNKFLOW_KERNELCHECK=0 disables (a strict no-op — no callbacks, no
# poison, byte-identical traces).
os.environ.setdefault("CHUNKFLOW_KERNELCHECK", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
