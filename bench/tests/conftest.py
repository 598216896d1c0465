"""The harness's own tests, run by hand on the CPU:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


@pytest.fixture
def tiny_arch(monkeypatch):
    """The program's own tiny preset of each arch in place of its served
    widths."""
    import repro.configs

    get = repro.configs.get_config
    monkeypatch.setattr(repro.configs, "get_config",
                        lambda name: get(name).reduced())
