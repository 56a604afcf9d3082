"""``chip_smoke.py`` finds a TPU or fails; its CPU rehearsal is explicit."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _run(args, cwd=REPO, pythonpath=REPO, timeout=600, code=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=pythonpath)
    cmd = [sys.executable, "-c", code] if code else \
        [sys.executable, os.path.join(cwd, "chip_smoke.py")] + args
    return subprocess.run(cmd, env=env, cwd=cwd, capture_output=True,
                          text=True, timeout=timeout)


def _result_lines(stdout):
    return [ln for ln in stdout.splitlines() if ln.startswith("{")]


def test_without_a_tpu_it_fails_and_runs_nothing():
    out = _run([])
    assert out.returncode == 2, out.stderr[-2000:]
    assert out.stdout.splitlines()[0].startswith("platform=cpu ")
    assert not _result_lines(out.stdout)
    assert "[train" not in out.stdout and "[serve" not in out.stdout
    assert "no TPU visible" in out.stderr


def test_alone_in_a_directory_it_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    # past the device check (rehearsal), so the missing package is what stops it
    out = _run(["--rehearse-cpu"], cwd=str(tmp_path), pythonpath="")
    assert out.returncode not in (0, 2), out.stdout[-2000:]
    assert "deepspeed_tpu" in out.stderr
    assert not _result_lines(out.stdout)


@pytest.mark.slow
def test_rehearsal_passes_and_a_forced_dispatch_failure_fails():
    out = _run(["--rehearse-cpu"])
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["ok"] is True and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    assert "REHEARSAL" in out.stdout

    # a non-transient failure inside a decode dispatch: the phase must FAIL
    # (not report cancelled requests) and the exit code must say so
    code = (
        "import sys, chip_smoke\n"
        "from deepspeed_tpu.utils import fault_injection as fi\n"
        "with fi.inject('serving.chunk_compute', fi.FaultSpec(\n"
        "        kind='io_error', exc_type=RuntimeError, max_faults=1)):\n"
        "    sys.exit(chip_smoke.main(['--rehearse-cpu']))\n")
    out = _run([], code=code)
    assert out.returncode == 1, out.stdout[-3000:]
    assert not _result_lines(out.stdout)
    assert "[serve-bloom-7b] FAILED" in out.stdout
    assert "[serve-neox-4l] ok" in out.stdout
