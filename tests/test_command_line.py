"""Shell out to tests/command_line.sh (reference test strategy §4: the
composed-pipeline smoke runs as REAL shell commands, not CliRunner)."""
import os
import subprocess
import sys

import pytest


def test_command_line_smoke(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(repo, "tests", "command_line.sh")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["PATH"] = os.path.dirname(sys.executable) + os.pathsep + env["PATH"]
    proc = subprocess.run(
        ["bash", script], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=900,
    )
    if proc.returncode != 0:
        pytest.fail(
            f"command_line.sh failed (rc={proc.returncode})\n"
            f"--- stdout ---\n{proc.stdout[-4000:]}\n"
            f"--- stderr ---\n{proc.stderr[-4000:]}"
        )
    assert "ALL COMMAND-LINE SMOKE TESTS PASSED" in proc.stdout
