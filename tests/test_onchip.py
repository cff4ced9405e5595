"""chip_smoke.py, from the suite.

The suite itself is pinned to the CPU (conftest.py), so the chip is only
reachable from a child with a clean environment: one process per chip, and
this one never touches it.

* Without a TPU the script must give up at the platform check: non-zero
  exit, no ``"ok": true`` line, nothing built or compiled.  That runs
  everywhere, in a second or two.
* With a TPU attached, the whole script runs ONCE (minutes: marked slow)
  and each phase's JSON line is checked as its own case.
"""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
SMOKE = REPO / "chip_smoke.py"
PHASES = ("setup", "a_transport_inproc", "b_transport_sockets",
          "c_served_model", "d_trainer")


def _json_lines(stdout: str) -> list:
    return [json.loads(l) for l in stdout.splitlines() if l.startswith("{")]


def test_chip_smoke_refuses_the_cpu():
    t0 = time.perf_counter()
    out = subprocess.run(
        [sys.executable, str(SMOKE)], capture_output=True, text=True,
        timeout=120, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    took = time.perf_counter() - t0
    assert out.returncode != 0
    assert "no TPU" in out.stderr, out.stderr[-2000:]
    # It gave up at the platform check: no phase line (not even setup, which
    # follows the native build), no last line, and no time to have compiled.
    assert _json_lines(out.stdout) == [], out.stdout
    assert '"ok": true' not in out.stdout
    assert took < 30, f"took {took:.1f}s to refuse the CPU"


def _has_chip() -> bool:
    """A TPU's device node, seen without touching JAX (v5e: /dev/vfio/<n>;
    older generations: /dev/accel<n>)."""
    dev = Path("/dev")
    return any(dev.glob("accel*")) or any(
        p.name.isdigit() for p in (dev / "vfio").glob("*"))


@pytest.fixture(scope="module")
def smoke_run():
    if not _has_chip():
        pytest.skip("no TPU attached to this machine")
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    return subprocess.run([sys.executable, str(SMOKE)], capture_output=True,
                          text=True, timeout=1250, env=env, cwd=REPO)


@pytest.mark.slow
@pytest.mark.parametrize("phase", PHASES)
def test_onchip_phase(smoke_run, phase):
    lines = [l for l in _json_lines(smoke_run.stdout)
             if l.get("phase") == phase]
    assert len(lines) == 1 and lines[0]["ok"] is True, (
        f"{phase}: {lines}\n{smoke_run.stdout[-3000:]}\n"
        f"{smoke_run.stderr[-3000:]}")


@pytest.mark.slow
def test_onchip_last_line(smoke_run):
    assert smoke_run.returncode == 0, smoke_run.stderr[-3000:]
    last = json.loads(smoke_run.stdout.strip().splitlines()[-1])
    assert last["ok"] is True and last["device"]["platform"] == "tpu"
    assert set(last) == {"ok", "device"}
