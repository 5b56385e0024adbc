"""chip_smoke.py on the CPU: it refuses to run without a TPU, and its
phases pass at a tiny size (the same code the chip runs at full width)."""
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402


def test_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) == 2
    out, err = capsys.readouterr()
    assert "platform 'cpu'" in err
    assert '"ok"' not in out


def test_fails_alone_in_a_directory(tmp_path):
    """Without the rest of the repo the script fails and prints no result
    (on the CPU it stops at the platform check, on the chip at the import
    of the program)."""
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


@pytest.mark.parametrize("phase", ["train", "serve", "kernels", "sharded"])
def test_phase_passes_tiny(phase, capsys):
    kw = {"n_dev": 1} if phase == "sharded" else {}
    getattr(chip_smoke, f"phase_{phase}")(chip_smoke.TINY, 0, **kw)
    out = capsys.readouterr().out
    assert "PASS" in out and "FAIL" not in out


SHARDED_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import dataclasses, sys
sys.path.insert(0, {repo!r})
import chip_smoke
chip_smoke.phase_sharded(
    dataclasses.replace(chip_smoke.TINY, clients=8), 0, n_dev=4)
"""


def test_sharded_phase_on_four_host_devices():
    """The --chips 4 phase on four virtual CPU devices: client leaves land
    on all four, and the sharded round matches the dense one."""
    out = subprocess.run(
        [sys.executable, "-c", SHARDED_CHILD.format(repo=str(REPO))],
        capture_output=True, text=True, timeout=600, cwd=REPO)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-3000:])
    assert "span 4 devices" in out.stdout
    assert "FAIL" not in out.stdout
