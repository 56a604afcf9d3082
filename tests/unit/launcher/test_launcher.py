"""Launcher tests.

Mirrors reference ``tests/unit/launcher/test_ds_arguments.py`` + ``test_run.py`` (hostfile
and filter parsing) and adds an integration lane: a 2-process CPU
launch on localhost running a real DP train step through the CLI.
"""

import json
import os
import socket
import subprocess
import sys

import pytest

from deepspeed_tpu.launcher.runner import (filter_resources, parse_args,
                                           parse_hostfile)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ------------------------------------------------------------------- parsing
class TestResourceParsing:
    def test_hostfile(self, tmp_path):
        hf = tmp_path / "hostfile"
        hf.write_text("worker-0 slots=4\n# comment\nworker-1 slots=8\n\n")
        pool = parse_hostfile(str(hf))
        assert pool == {"worker-0": 4, "worker-1": 8}

    def test_hostfile_bad_line(self, tmp_path):
        hf = tmp_path / "hostfile"
        hf.write_text("worker-0 gpus=4\n")
        with pytest.raises(ValueError):
            parse_hostfile(str(hf))

    def test_missing_hostfile_empty(self):
        assert parse_hostfile("/nonexistent/hostfile") == {}

    def test_include_hosts(self):
        pool = {"a": 4, "b": 4, "c": 4}
        assert filter_resources(pool, include="a,c") == {"a": 4, "c": 4}

    def test_include_slots(self):
        pool = {"a": 4, "b": 4}
        assert filter_resources(pool, include="a@0,1") == {"a": 2}

    def test_exclude_host(self):
        pool = {"a": 4, "b": 4}
        assert filter_resources(pool, exclude="b") == {"a": 4}

    def test_exclude_slot(self):
        pool = {"a": 4, "b": 4}
        assert filter_resources(pool, exclude="b@3") == {"a": 4, "b": 3}

    def test_include_exclude_mutually_exclusive(self):
        with pytest.raises(ValueError):
            filter_resources({"a": 1}, include="a", exclude="a")

    def test_cli_args(self):
        args = parse_args(["--num_procs", "4", "train.py", "--lr", "0.1"])
        assert args.num_procs == 4
        assert args.user_script == "train.py"
        assert args.user_args == ["--lr", "0.1"]


# ------------------------------------------------------------------- integration
class TestLocalLaunch:
    def _run_cli(self, cli_args, env_extra=None, timeout=240):
        # one CPU device per worker; several workers per node are only
        # accepted for CPU runs (a chip host gives its chips to one process)
        env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
        env["JAX_PLATFORMS"] = "cpu"
        env["DS_TPU_REPO"] = REPO
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env.update(env_extra or {})
        return subprocess.run(
            [sys.executable, "-m", "deepspeed_tpu.launcher.runner"] + cli_args,
            capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO)

    def test_two_process_dp_train(self, tmp_path):
        """CLI launches 2 CPU processes that jointly train one
        DP step (cross-process collectives), both ranks agreeing on the loss."""
        child = os.path.join(REPO, "tests", "unit", "launcher", "dp_train_child.py")
        proc = self._run_cli(
            ["--launcher", "local", "--num_procs", "2",
             "--master_port", str(_free_port()),
             child, "--out", str(tmp_path)])
        assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
        r0 = (tmp_path / "rank0.txt").read_text()
        r1 = (tmp_path / "rank1.txt").read_text()
        assert r0 == r1, f"ranks disagree: {r0} vs {r1}"
        losses = eval(r0)
        assert len(losses) == 2 and all(l == l for l in losses)  # finite

    def test_two_process_partitioned_offload(self, tmp_path):
        """Multi-process ZeRO-Offload: per-process partitioned
        masters over a real 2-process mesh, with identical resulting parameters on
        both ranks and a partition-file checkpoint round-trip."""
        child = os.path.join(REPO, "tests", "unit", "launcher",
                             "offload_train_child.py")
        proc = self._run_cli(
            ["--launcher", "local", "--num_procs", "2",
             "--master_port", str(_free_port()),
             child, "--out", str(tmp_path)])
        assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
        r0 = eval((tmp_path / "rank0.txt").read_text())
        r1 = eval((tmp_path / "rank1.txt").read_text())
        assert r0["checksum"] == r1["checksum"], (r0, r1)
        assert r0["losses"] == r1["losses"]
        assert r0["losses"][-1] < r0["losses"][0]
        assert r0["resumed_loss_finite"] and r1["resumed_loss_finite"]

    def test_two_process_param_offload(self, tmp_path):
        """Multi-process ZeRO-3 parameter offload: per-process
        partitioned masters in the segment-streaming tier over a real 2-process
        mesh; both ranks end with bitwise-identical pushed params, and the
        per-rank partition files round-trip."""
        child = os.path.join(REPO, "tests", "unit", "launcher",
                             "param_offload_train_child.py")
        proc = self._run_cli(
            ["--launcher", "local", "--num_procs", "2",
             "--master_port", str(_free_port()),
             child, "--out", str(tmp_path)], timeout=420)
        assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
        r0 = eval((tmp_path / "rank0.txt").read_text())
        r1 = eval((tmp_path / "rank1.txt").read_text())
        assert r0["digest"] == r1["digest"], (r0, r1)
        assert r0["losses"] == r1["losses"]
        assert r0["decreased"] and r1["decreased"]
        assert r0["resumed_loss_finite"] and r1["resumed_loss_finite"]

        # OFFLINE consolidation: merge the per-rank
        # partition files into one universal checkpoint with no engine/mesh,
        # and verify exact equality against the pushed full params
        import numpy as np
        from deepspeed_tpu.checkpoint import DeepSpeedCheckpoint
        from deepspeed_tpu.checkpoint.export import \
            consolidate_partitioned_checkpoint
        out = consolidate_partitioned_checkpoint(
            str(tmp_path / "ckpt"), "t0", str(tmp_path / "univ"))
        expected = np.load(tmp_path / "expected_full.npz")
        merged = DeepSpeedCheckpoint(out).merged_state_dict()
        assert set(expected.files) == set(merged.keys())
        for name in expected.files:
            np.testing.assert_array_equal(np.asarray(merged[name]),
                                          expected[name], err_msg=name)
        # adamw RAM moments consolidated too
        some = sorted(expected.files)[0]
        m_file = os.path.join(out, "zero", some, "exp_avg.pt")
        assert os.path.isfile(m_file), m_file
        import torch
        got_m = torch.load(m_file, weights_only=False)["param"]
        assert tuple(got_m.shape) == expected[some].shape

    def test_ssh_lane_with_fake_ssh(self, tmp_path):
        """The ssh launcher beyond localhost Gloo: a fake
        ``ssh`` on PATH records each session and executes the remote command
        LOCALLY, driving the full lane — hostfile parse → per-node command
        construction (quoting survives the remote shell re-tokenization) →
        per-node spawner — across two fake nodes."""
        log = tmp_path / "ssh.log"
        fake = tmp_path / "ssh"
        fake.write_text(
            "#!/bin/sh\n"
            '# log the TARGET HOST distinctly from the command (and printf, not\n'
            '# echo: dash echo would expand backslash escapes in env values);\n'
            '# the host is the argument before the final remote-command string\n'
            'prev=""\n'
            'for a; do host="$prev"; prev="$a"; done\n'
            f'printf "HOST=%s CMD=%s\\n" "$host" "$prev" >> {log}\n'
            'exec sh -c "$prev"\n')
        fake.chmod(0o755)
        hf = tmp_path / "hostfile"
        hf.write_text("nodeA slots=1\nnodeB slots=1\n")
        proc = self._run_cli(
            ["--launcher", "ssh", "--hostfile", str(hf),
             "--master_port", str(_free_port()),
             "--no_python", "/bin/true"],
            env_extra={"PATH": f"{tmp_path}:{os.environ['PATH']}"},
            timeout=240)
        assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
        sessions = log.read_text().strip().splitlines()
        assert len(sessions) == 2
        assert any(s.startswith("HOST=nodeA ") for s in sessions)
        assert any(s.startswith("HOST=nodeB ") for s in sessions)
        assert any("--node_rank=0" in s for s in sessions)
        assert any("--node_rank=1" in s for s in sessions)
        assert all("--num_nodes=2" in s and "--master_addr=nodeA" in s
                   for s in sessions)

    def test_failure_propagates(self, tmp_path):
        """A failing rank propagates its exit code through the spawner (reference
        launch.py poll loop)."""
        bad = tmp_path / "bad.py"
        bad.write_text("import os, sys\n"
                       "sys.exit(3 if os.environ['RANK'] == '1' else 0)\n")
        proc = self._run_cli(
            ["--launcher", "local", "--num_procs", "2",
             "--master_port", str(_free_port()), str(bad)],
            timeout=120)
        assert proc.returncode == 3, proc.stderr

    def test_elastic_bin_runs(self, tmp_path):
        """bin/ds_tpu_elastic (reference bin/ds_elastic): prints the elastic
        config and computed batch/world/micro results."""
        cfg = tmp_path / "cfg.json"
        cfg.write_text(json.dumps({
            "train_batch_size": 64,
            "elasticity": {"enabled": True, "max_train_batch_size": 128,
                           "micro_batch_sizes": [2, 4], "min_gpus": 1,
                           "max_gpus": 16, "version": 0.1}}))
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bin", "ds_tpu_elastic"),
             "-c", str(cfg), "-w", "4"],
            capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
        assert proc.returncode == 0, proc.stderr
        assert "final_batch_size" in proc.stdout
        assert "micro_batch_size .... 2" in proc.stdout

    def test_ssh_bin_parses_hostfile(self, tmp_path):
        """bin/ds_tpu_ssh: hostfile parsing + error contract (no ssh in CI)."""
        proc = subprocess.run(
            [os.path.join(REPO, "bin", "ds_tpu_ssh"), "-f", "/nonexistent",
             "echo", "hi"], capture_output=True, text=True, timeout=30)
        assert proc.returncode == 1 and "not found" in proc.stderr
        hf = tmp_path / "hostfile"
        hf.write_text("# comment\n\n")
        proc = subprocess.run(
            [os.path.join(REPO, "bin", "ds_tpu_ssh"), "-f", str(hf), "true"],
            capture_output=True, text=True, timeout=30)
        assert proc.returncode == 1 and "no hosts" in proc.stderr

    def test_env_report_runs(self):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.run([sys.executable, "-m", "deepspeed_tpu.env_report"],
                              capture_output=True, text=True, timeout=120, env=env,
                              cwd=REPO)
        assert proc.returncode == 0
        assert "ds_report" in proc.stdout
        assert "cpu_adam" in proc.stdout
