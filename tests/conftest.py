"""Test config: force CPU platform with 8 virtual devices.

Carry-over from the reference's test strategy (SURVEY.md §4): multi-node is
simulated locally — their trick is multi-process on 127.0.0.1; ours is
XLA host-platform fake devices for in-process SPMD tests. The platform is
pinned to the CPU by updating jax config before any backend init.
"""
import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

# Tests only ever COMPILE for a described TPU (tests/test_aot_tpu_compile.py,
# tests/test_hlo_golden.py) — no chip is attached — so libtpu's one-process
# lockfile would only make parallel (xdist) workers skip those tests.
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reseed():
    import paddle_tpu as paddle
    paddle.seed(2024)
    yield
