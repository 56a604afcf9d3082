"""Experiment scheduler: subprocess-isolated, parallel measured trials.

Behavioural equivalent of reference ``deepspeed/autotuning/scheduler.py``
(``ResourceManager:1``): the reference launches every experiment as a separate
multi-GPU job so a hard failure (OOM-kill, kernel abort) marks ONE experiment
failed instead of killing the tuner, and runs experiments in parallel on disjoint
resources. The in-process ``Autotuner._measure`` path keeps trials cheap on a
single chip but cannot survive hard crashes; this scheduler restores the
reference's isolation/parallelism for multi-host or crash-prone tuning spaces.

Protocol: each experiment runs ``python -m <runner_module> --config <json-file>
--overrides <json-file> --out <json-file>`` in a fresh process (own XLA backend,
own HBM). The runner builds the engine with the overrides merged in, measures a
few steps, and writes ``{"status": "ok", "latency_s": ..., "throughput": ...,
"flops": ...}`` to ``--out``. Missing/partial output, a non-zero exit, or a
timeout mark the experiment failed/timeout. ``slot_envs`` gives each parallel
slot its own environment overlay (e.g. disjoint device sets on a pod).

One process per chip: every runner claims the chips its environment shows it,
so the tuner process itself stays off jax (``Autotuner`` does, in subprocess
mode), and more than one slot at a time is only accepted when each slot's
overlay differs — i.e. the caller pinned the slots to disjoint devices — or
the runners are CPU-only (``JAX_PLATFORMS=cpu``).
"""

import json
import os
import subprocess
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

from ..utils.device import claims_chips
from ..utils.logging import log_dist, logger


class ExperimentScheduler:
    def __init__(self, runner_module: str, base_config: Dict,
                 results_dir: str = "autotuning_results",
                 timeout_s: float = 600.0, max_parallel: int = 1,
                 slot_envs: Optional[List[Dict[str, str]]] = None,
                 python: Optional[str] = None):
        self.runner_module = runner_module
        self.base_config = dict(base_config)
        self.results_dir = results_dir
        self.timeout_s = float(timeout_s)
        self.max_parallel = max(1, int(max_parallel))
        self.slot_envs = slot_envs or [{}] * self.max_parallel
        if not (len(self.slot_envs) >= self.max_parallel):
            raise AssertionError("need one env overlay per parallel slot")
        slots = self.slot_envs[:self.max_parallel]
        if self.max_parallel > 1 \
                and any(claims_chips({**os.environ, **e}) for e in slots) \
                and len({tuple(sorted(e.items())) for e in slots}) < len(slots):
            raise ValueError(
                f"max_parallel={self.max_parallel} runners with identical "
                "slot_envs would all claim the same chips, and a chip serves "
                "one process at a time: give every slot its own device set "
                "through slot_envs, or run one experiment at a time")
        self.python = python or sys.executable

    def _launch(self, exp_id: int, overrides: Dict, workdir: str, slot: int):
        cfg_f = os.path.join(workdir, f"exp{exp_id}_config.json")
        ovr_f = os.path.join(workdir, f"exp{exp_id}_overrides.json")
        out_f = os.path.join(workdir, f"exp{exp_id}_result.json")
        # per-experiment log file, NOT a pipe: an undrained pipe fills its buffer
        # and deadlocks a verbose (engine-building) runner into a false timeout
        log_f = os.path.join(self.results_dir, f"exp{exp_id}.log")
        with open(cfg_f, "w") as f:
            json.dump(self.base_config, f)
        with open(ovr_f, "w") as f:
            json.dump(overrides, f)
        env = dict(os.environ)
        env.update(self.slot_envs[slot])
        log_fh = open(log_f, "w")
        proc = subprocess.Popen(
            [self.python, "-m", self.runner_module, "--config", cfg_f,
             "--overrides", ovr_f, "--out", out_f],
            env=env, stdout=log_fh, stderr=subprocess.STDOUT)
        return {"id": exp_id, "overrides": overrides, "proc": proc,
                "out_f": out_f, "log_f": log_f, "log_fh": log_fh,
                "slot": slot, "t0": time.time()}

    def _finish(self, job, timed_out: bool) -> Dict[str, Any]:
        rec: Dict[str, Any] = {"exp": job["overrides"], "exp_id": job["id"],
                               "slot": job["slot"], "log": job["log_f"],
                               "wall_s": round(time.time() - job["t0"], 2)}
        if timed_out:
            job["proc"].kill()
            job["proc"].wait()
        job["log_fh"].close()
        if timed_out:
            rec["status"] = "timeout"
            return rec
        rc = job["proc"].returncode
        if rc == 0 and os.path.isfile(job["out_f"]):
            try:
                with open(job["out_f"]) as f:
                    result = json.load(f)
                rec.update(result)
                rec.setdefault("status", "ok")
                return rec
            except (json.JSONDecodeError, OSError) as e:
                rec["status"] = "failed"
                rec["error"] = f"unreadable result file: {e}"
                return rec
        rec["status"] = "failed"
        rec["returncode"] = rc
        try:
            with open(job["log_f"]) as f:
                rec["error"] = f.read()[-2000:]
        except OSError:
            rec["error"] = ""
        return rec

    def run(self, experiments: List[Dict]) -> List[Dict[str, Any]]:
        """Run every experiment; returns one record per experiment, input order.
        A crashed or timed-out experiment yields a failed/timeout record and the
        scheduler continues — the reference resource manager's contract."""
        os.makedirs(self.results_dir, exist_ok=True)
        records: List[Optional[Dict]] = [None] * len(experiments)
        with tempfile.TemporaryDirectory(dir=self.results_dir) as workdir:
            pending = list(enumerate(experiments))
            running: List[Dict] = []
            free_slots = list(range(self.max_parallel))
            while pending or running:
                while pending and free_slots:
                    exp_id, ovr = pending.pop(0)
                    slot = free_slots.pop(0)
                    running.append(self._launch(exp_id, ovr, workdir, slot))
                    log_dist(f"[scheduler] exp {exp_id} {ovr} -> slot {slot}",
                             ranks=[0])
                time.sleep(0.05)
                still = []
                for job in running:
                    rc = job["proc"].poll()
                    timed_out = (rc is None and
                                 time.time() - job["t0"] > self.timeout_s)
                    if rc is None and not timed_out:
                        still.append(job)
                        continue
                    rec = self._finish(job, timed_out)
                    records[job["id"]] = rec
                    free_slots.append(job["slot"])
                    if rec["status"] != "ok":
                        logger.warning(f"[scheduler] exp {job['id']} "
                                       f"{rec['status']}: "
                                       f"{rec.get('error', '')[:200]}")
                running = still
        return [r for r in records if r is not None]
