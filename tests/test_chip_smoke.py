"""``chip_smoke.py``, the on-chip check of the served path, on the CPU.

Without a TPU the script must refuse before doing any work.  Its two
phases are rehearsed here at reduced widths with the kernels in interpret
mode (the channel-shard phase on four forced host devices), so an API
change that would break the chip check fails here first.  Every case runs
in a subprocess: importing the script turns on the persistent compile
cache, which the test process must not do.
"""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run(tmp_path, args, **env):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"), **env)
    return subprocess.run([sys.executable, *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)


def test_refuses_without_tpu(tmp_path):
    r = _run(tmp_path, ["chip_smoke.py"])
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout


REHEARSALS = {
    "serving": ("chip_smoke.run_serving(2, 0, arch_flags=('--reduced',), "
                "prompt_len=(5, 40), max_new=6)", {}),
    "channel_shard": ("chip_smoke.run_channel_shard(2, 0, "
                      "arch_flags=('--reduced',))",
                      {"XLA_FLAGS":
                       "--xla_force_host_platform_device_count=4"}),
}


@pytest.mark.parametrize("phase", sorted(REHEARSALS))
def test_phase_rehearsal(tmp_path, phase):
    call, env = REHEARSALS[phase]
    r = _run(tmp_path, ["-c", f"import chip_smoke; {call}; print('PHASE-OK')"],
             **env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "PHASE-OK" in r.stdout
