"""Cells on several chips run here on host-platform CPU devices. The flag
acts only if it is set before JAX starts its backends, so it is set as the
tests are collected (the same count as the repository's ``tests/``)."""

import os

_FLAG = "--xla_force_host_platform_device_count"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + f" {_FLAG}=8").strip()
