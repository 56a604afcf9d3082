"""Per-node process spawner.

TPU-native analogue of reference ``deepspeed/launcher/launch.py`` (``main:129``): given this
node's rank and the world layout, spawn one Python process per local worker with the
coordinator env contract that ``comm.init_distributed`` consumes
(``COORDINATOR_ADDRESS``/``NPROC``/``PROCESS_ID``/``LOCAL_RANK``), forward SIGINT/SIGTERM to
the children, and propagate the first failure (killing the stragglers) — the reference's
sig_names/поll loop, minus CUDA_VISIBLE_DEVICES bookkeeping, which has no TPU analogue:
libtpu gives a host's chips to ONE process, and that process drives all of them through
the mesh. So on a chip host ``--nproc_per_node`` must be 1 and anything larger is
refused; several workers per node are for ``JAX_PLATFORMS=cpu`` runs (the multi-process
tests), where nothing claims a chip.
"""

import argparse
import os
import signal
import subprocess
import sys
import time
from typing import List

from ..utils.device import claims_chips
from ..utils.logging import logger


def parse_args(args=None):
    parser = argparse.ArgumentParser(description="deepspeed_tpu per-node launcher")
    parser.add_argument("--node_rank", type=int, default=0,
                        help="rank of this node in the job")
    parser.add_argument("--num_nodes", type=int, default=1)
    parser.add_argument("--nproc_per_node", type=int, default=1,
                        help="worker processes to spawn on this node")
    parser.add_argument("--master_addr", type=str, default="127.0.0.1",
                        help="coordinator host (jax.distributed rendezvous)")
    parser.add_argument("--master_port", type=int, default=29500)
    parser.add_argument("--module", action="store_true",
                        help="interpret the script as a python module (python -m)")
    parser.add_argument("--no_python", action="store_true",
                        help="exec the script directly, not via the python interpreter")
    parser.add_argument("--max_restarts", type=int, default=0,
                        help="restart the whole worker group up to N times after a "
                             "rank failure (training scripts resume from the latest "
                             "committed checkpoint tag)")
    parser.add_argument("--restart_backoff", type=float, default=1.0,
                        help="base seconds between restarts (exponential: "
                             "base * 2**attempt)")
    parser.add_argument("training_script", type=str)
    parser.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return parser.parse_args(args)


def build_cmd(args) -> List[str]:
    if args.no_python:
        cmd = [args.training_script]
    elif args.module:
        cmd = [sys.executable, "-u", "-m", args.training_script]
    else:
        cmd = [sys.executable, "-u", args.training_script]
    return cmd + list(args.training_script_args)


def _spawn_group(args, world_size: int, cmd: List[str],
                 attempt: int) -> List[subprocess.Popen]:
    processes: List[subprocess.Popen] = []
    for local_rank in range(args.nproc_per_node):
        env = os.environ.copy()
        env["COORDINATOR_ADDRESS"] = f"{args.master_addr}:{args.master_port}"
        env["MASTER_ADDR"] = args.master_addr
        env["MASTER_PORT"] = str(args.master_port)
        env["NPROC"] = env["WORLD_SIZE"] = str(world_size)
        env["PROCESS_ID"] = env["RANK"] = str(
            args.node_rank * args.nproc_per_node + local_rank)
        env["LOCAL_RANK"] = str(local_rank)
        env["NODE_RANK"] = str(args.node_rank)
        env["DS_TPU_RESTART_ATTEMPT"] = str(attempt)
        logger.info(f"[launch] node {args.node_rank} local {local_rank} -> "
                    f"rank {env['RANK']}/{world_size}"
                    f"{f' (restart {attempt})' if attempt else ''}: "
                    f"{' '.join(cmd)}")
        processes.append(subprocess.Popen(cmd, env=env))
    return processes


def _wait_group(processes: List[subprocess.Popen]) -> int:
    """Reference launch.py poll loop: first non-zero exit kills the rest,
    escalating terminate -> kill so a worker stuck in a collective (SIGTERM
    pending) can't hang us. Returns the first failing exit code (0 = clean)."""
    exit_code = 0
    kill_deadline = None
    alive = list(processes)
    while alive:
        time.sleep(0.1)
        if kill_deadline is not None and time.monotonic() > kill_deadline:
            for q in alive:
                try:
                    q.kill()
                except OSError:
                    pass
            kill_deadline = None
        for p in list(alive):
            rc = p.poll()
            if rc is None:
                continue
            alive.remove(p)
            if rc != 0 and exit_code == 0:
                exit_code = rc
                logger.error(f"[launch] rank process {p.args!r} failed with {rc}; "
                             "terminating remaining workers")
                kill_deadline = time.monotonic() + 15.0
                for q in alive:
                    try:
                        q.terminate()
                    except OSError:
                        pass
    return exit_code


def main(args=None):
    args = parse_args(args)
    if args.nproc_per_node > 1 and claims_chips(os.environ):
        logger.error(
            f"[launch] --nproc_per_node={args.nproc_per_node}: every worker "
            "would claim this host's chips, and libtpu gives them to one "
            "process at a time. Run ONE process per host (it drives all local "
            "chips through the mesh); for a CPU multi-process run set "
            "JAX_PLATFORMS=cpu.")
        sys.exit(2)
    world_size = args.num_nodes * args.nproc_per_node
    cmd = build_cmd(args)

    processes: List[subprocess.Popen] = []
    signaled = {"got": None}

    def forward_signal(signum, frame):
        signaled["got"] = signum      # operator/scheduler stop: no restart
        for p in processes:
            if p.poll() is None:
                try:
                    p.send_signal(signum)
                except OSError:
                    pass

    signal.signal(signal.SIGINT, forward_signal)
    signal.signal(signal.SIGTERM, forward_signal)

    # bounded rank-failure restarts (reference torchelastic max_restarts): a
    # crash/wedge respawns the WHOLE group after exponential backoff; training
    # scripts resume from the latest committed checkpoint tag. Single-node
    # scope: multi-node jobs restart through the scheduler (the whole-slice
    # replacement discipline, see elastic_agent.py docstring).
    max_restarts = max(0, args.max_restarts)
    if max_restarts and args.num_nodes > 1:
        logger.warning("[launch] --max_restarts on a multi-node job restarts "
                       "only this node's workers; the coordinator contract "
                       "requires ALL nodes to restart — prefer scheduler-level "
                       "restarts for multi-node")
    exit_code = 0
    for attempt in range(max_restarts + 1):
        processes[:] = _spawn_group(args, world_size, cmd, attempt)
        exit_code = _wait_group(processes)
        if exit_code == 0:
            break
        if signaled["got"] is not None:
            logger.info(f"[launch] stopped by signal {signaled['got']}; "
                        "not restarting")
            break
        if attempt < max_restarts:
            delay = args.restart_backoff * (2 ** attempt)
            logger.error(f"[launch] worker group failed (exit {exit_code}); "
                         f"restart {attempt + 1}/{max_restarts} in {delay:.1f}s")
            time.sleep(delay)
            # a stop signal delivered DURING the backoff sleep must also
            # suppress the respawn (PEP 475 resumes the sleep after the
            # handler runs, so the loop-top check alone would miss it)
            if signaled["got"] is not None:
                logger.info(f"[launch] stopped by signal {signaled['got']} "
                            "during backoff; not restarting")
                break
    sys.exit(exit_code)


if __name__ == "__main__":
    main()
