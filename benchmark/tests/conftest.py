"""CPU tests of the yardstick: ``python -m pytest benchmark/tests -q``.

They check arithmetic, contracts and control flow. Nothing here yields a
time, a rate or a utilisation of a device."""

import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402


@pytest.fixture
def tree(tmp_path, monkeypatch):
    """A copy of ``BENCHMARK.json`` and ``benchmark/`` that a test may add
    files to, with the seed cache and the compile cache kept inside it."""
    from benchmark.harness import seedcache

    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "out",
                                                  "__pycache__", "tests"))
    monkeypatch.setattr(seedcache, "ROOT", str(root / "benchmark" / ".cache"))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    return root
