"""CPU rehearsals of the benchmark. Run with

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

Not part of tier-1. The measurement path has no CPU branch: these tests
steer it from here (the device check is replaced, the data files are a
tiny copy made by tiny.py)."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture
def cpu_device(monkeypatch):
    """Let a run through on whatever JAX has here, reporting it truly."""
    import jax
    from benchmark.harness import device

    def accept(chips):
        devs = jax.devices()
        assert len(devs) >= chips
        return {"platform": devs[0].platform, "kind": devs[0].device_kind,
                "count": len(devs)}

    monkeypatch.setattr(device, "require", accept)


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    from benchmark.tests import tiny
    # the rehearsals keep their compiled programs out of the checkout's cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    return tiny.make_root(str(tmp_path / "root"))
