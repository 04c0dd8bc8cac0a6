"""Test environment: the suite runs on 8 virtual CPU devices.

Multi-chip sharding logic is tested without TPU hardware, per the reference's
"mini-cluster in one JVM" testing idea (SURVEY.md §4): all roles in-process.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# keep the suite free of persistent-compilation-cache I/O: the planner
# and QueryService enable it by default (compilecache/persist.py), and
# with the serve-grade thresholds every tiny test compile would be
# serialized to disk — pure overhead against the tier-1 wall-clock
# budget. Tests that exercise the cache itself pass explicit dirs with
# force=True, which overrides this. setdefault: a dev can still opt in.
os.environ.setdefault("GEOMESA_TPU_COMPILE_CACHE_DIR", "off")

import jax

jax.config.update("jax_platforms", "cpu")
