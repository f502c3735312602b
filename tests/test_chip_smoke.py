"""``chip_smoke.py`` refuses to report a result anywhere but on a TPU.

It must exit non-zero and print no ``"ok"`` line when JAX finds only a
CPU, and when it runs from a directory that holds nothing else of the
repository.  (What it checks on the chip is run on the chip.)
"""
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _run(script: str, cwd: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)         # only what the script finds itself
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_cpu():
    out = _run(SCRIPT, ROOT)
    assert out.returncode != 0
    assert "platform=cpu" in out.stdout
    assert "no TPU: JAX found platform 'cpu'" in out.stderr
    assert '"ok"' not in out.stdout


def test_chip_smoke_alone_prints_no_result(tmp_path):
    lone = shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    out = _run(str(lone), str(tmp_path))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
