"""``utils/device.py``: where the compile cache goes, the one-process-per-chip
rule, and the published-peaks table."""

import json
import os
import re
import subprocess
import sys

import pytest

from deepspeed_tpu.utils import device

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_PROBE = (
    "import json, jax\n"
    "before = jax.config.jax_compilation_cache_dir\n"
    "from deepspeed_tpu.utils.device import enable_compile_cache\n"
    "path = enable_compile_cache()\n"
    "print(json.dumps([before, path, jax.config.jax_compilation_cache_dir]))\n")


def _probe(env_dir):
    env = {k: v for k, v in os.environ.items() if k != device.CACHE_ENV}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env[device.CACHE_ENV] = env_dir
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env, cwd="/",
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_cache_dir_from_environment_is_used_and_nothing_is_written(tmp_path):
    want = str(tmp_path / "placed_from_outside")
    before, path, after = _probe(want)
    # jax read the variable itself; the function returned it and wrote no config
    assert before == want and path == want and after == want


def test_default_cache_dir_is_fixed_inside_the_checkout():
    first, second = _probe(None), _probe(None)
    assert first[0] is None                      # nothing configured beforehand
    assert first[1] == first[2] == os.path.join(REPO, ".jax_cache")
    assert second == first                       # two processes, one path
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_one_setter_in_the_tree():
    setters = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("tests", "chiprun_out", "__pycache__")]
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(root, name)) as f:
                    if re.search(r"jax_compilation_cache_dir|set_cache_dir|"
                                 r"initialize_cache", f.read()):
                        setters.append(os.path.relpath(
                            os.path.join(root, name), REPO))
    assert setters == ["deepspeed_tpu/utils/device.py"]


def test_claims_chips_only_cpu_is_exempt():
    assert device.claims_chips({})
    assert device.claims_chips({"JAX_PLATFORMS": "tpu"})
    assert device.claims_chips({"JAX_PLATFORMS": "tpu,cpu"})
    assert not device.claims_chips({"JAX_PLATFORMS": "cpu"})
    assert not device.claims_chips({"JAX_PLATFORMS": " CPU "})


def test_local_chip_children_are_refused(monkeypatch):
    from deepspeed_tpu.autotuning.scheduler import ExperimentScheduler
    from deepspeed_tpu.inference.serving.subproc import refuse_chip_child
    refuse_chip_child({"JAX_PLATFORMS": "cpu"})
    with pytest.raises(RuntimeError, match="one process at a time"):
        refuse_chip_child({"PATH": "/bin"})
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")   # children only; ours is live
    with pytest.raises(ValueError, match="one process at a time"):
        ExperimentScheduler("m", {}, max_parallel=2)
    # distinct overlays = the caller pinned each slot to its own devices
    ExperimentScheduler("m", {}, max_parallel=2,
                        slot_envs=[{"TPU_VISIBLE_CHIPS": "0"},
                                   {"TPU_VISIBLE_CHIPS": "1"}])
    ExperimentScheduler("m", {}, max_parallel=1)


def test_launcher_refuses_several_workers_on_a_chip_host(tmp_path):
    script = tmp_path / "noop.py"
    script.write_text("print('ran')\n")
    env = dict(os.environ, JAX_PLATFORMS="",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.launcher.launch",
         "--nproc_per_node", "2", str(script)],
        env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 2, out.stderr[-2000:]
    assert "ran" not in out.stdout
    assert "one" in (out.stdout + out.stderr) and "process" in (out.stdout + out.stderr)


def test_peaks_table_known_and_unknown():
    assert device.device_peaks("TPU v5 lite")["bf16_tflops"] == 197.0
    assert device.device_peaks("TPU v5 lite")["hbm_gbytes_per_s"] == 819.0
    with pytest.raises(KeyError, match="no published peaks"):
        device.device_peaks("cpu")
