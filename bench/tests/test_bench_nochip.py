"""Without a TPU the command fails and prints no result."""
import os
import subprocess
import sys

from bench.tests.conftest import ROOT


def test_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join("bench", "run.py"), "--workload",
         "sc2-train-random", "--seed", "4294967311", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no" in p.stderr.lower() or "tpu" in p.stderr.lower()


def test_command_fails_for_an_unknown_workload():
    p = subprocess.run(
        [sys.executable, os.path.join("bench", "run.py"), "--workload",
         "no-such-cell", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and "{" not in p.stdout
