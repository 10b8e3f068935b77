"""Multi-process ``jax.distributed`` job launcher — the cluster-install /
``mml-exec`` analog.

The reference installs itself onto a Spark cluster via an HDInsight script
action and launches work through a shell wrapper (reference:
tools/hdi/install-mmlspark.sh, tools/bin/mml-exec:1-50); its multi-node MPI
launcher was a never-wired stub
(cntk-train/src/main/scala/CommandBuilders.scala:95-117). The TPU-native
equivalent is one coordinator + N ``jax.distributed`` worker processes:

``local`` mode (default) starts all N workers on THIS host — the smoke/dev
path, and exactly how the multi-host test suite runs. ``pod`` mode execs
the command once with only the coordinator env set, for running under an
external per-host scheduler (GKE/xmanager/`gcloud compute tpus tpus-vm ssh
--worker=all`), where each TPU-VM worker invokes the same command and JAX
discovers its process id from the TPU runtime.

Worker wiring is environment-based (read back by
``mmlspark_tpu.utils.env.distributed_init``):

* ``MMLSPARK_TPU_COORDINATOR``    — host:port of process 0
* ``MMLSPARK_TPU_NUM_PROCESSES``  — world size
* ``MMLSPARK_TPU_PROCESS_ID``     — this worker's rank (local mode)

Failure semantics (SURVEY §5 failure detection): the launcher watches all
workers; the first nonzero exit terminates the rest (grace period, then
kill) and the launcher exits with that worker's code — a died worker can
never leave the remaining ranks silently hung inside a collective.
Combined with ``TrainConfig.checkpoint_dir`` the restart path is: rerun
the same launch command and training resumes from the last checkpoint.

Usage::

    python -m mmlspark_tpu.tools.launch -n 4 -- python train_job.py
    python -m mmlspark_tpu.tools.launch -n 4 --cpu-devices 2 -- \\
        python tests/multihost_worker.py        # CPU-mesh simulation
    python -m mmlspark_tpu.tools.launch --mode pod \\
        --coordinator tpu-host-0:8476 -- python train_job.py
"""

from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import threading
import time
from typing import IO, Sequence


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _pump(stream: IO[str], rank: int, out: IO[str], tail: list[str]) -> None:
    """Prefix a worker's merged stdout/stderr with its rank; keep a tail
    ring for the failure report."""
    for line in stream:
        tail.append(line)
        if len(tail) > 40:
            del tail[0]
        out.write(f"[worker {rank}] {line}")
        out.flush()


# coordinator-bind failures that justify retrying on a fresh port: the
# _free_port() probe closes its socket before worker 0 binds it (TOCTOU —
# another process can grab it in between, e.g. parallel CI launches)
_BIND_RETRY_MARKERS = ("already in use", "failed to bind", "errno 98",
                       "eaddrinuse")  # matched case-insensitively


def launch_local(cmd: Sequence[str], num_processes: int,
                 coordinator: str | None = None,
                 cpu_devices: int | None = None,
                 grace_seconds: float = 10.0,
                 extra_env: dict[str, str] | None = None,
                 port_retries: int = 3) -> int:
    """Start ``num_processes`` copies of ``cmd`` on this host and wait.

    Returns the exit code: 0 if every worker succeeded, else the first
    failing worker's code (the rest are terminated). The reference's only
    failure handling was an exit-code check on the single external CNTK
    process (cntk-train/src/main/scala/CNTKLearner.scala:147-151); here the
    check spans the whole worker set. When the coordinator port was
    auto-picked, a coordinator bind failure retries the whole launch on a
    fresh port (advisor round 4: the free-port probe is racy)."""
    if num_processes > 1 and not cpu_devices:
        from mmlspark_tpu.utils.env import children_reach_tpu
        if children_reach_tpu(extra_env):
            # one process per chip, and nothing here assigns chips to
            # ranks: N local workers would fight over the same devices
            raise ValueError(
                f"launch: {num_processes} local worker processes on a "
                "TPU host would all claim its chips (a chip belongs to "
                "one process) — pass cpu_devices/--cpu-devices N to "
                "rehearse on virtual CPU devices, or run one process "
                "that drives all local chips")
    auto_port = coordinator is None
    attempts = max(1, port_retries) if auto_port else 1
    for attempt in range(attempts):
        code, bind_failed = _launch_local_once(
            cmd, num_processes, coordinator or f"localhost:{_free_port()}",
            cpu_devices, grace_seconds, extra_env)
        if code == 0 or not (auto_port and bind_failed):
            return code
        if attempt + 1 < attempts:
            sys.stderr.write(
                f"coordinator bind failed (attempt {attempt + 1}/"
                f"{attempts}); retrying on a fresh port\n")
    return code


def _launch_local_once(cmd: Sequence[str], num_processes: int,
                       coordinator: str,
                       cpu_devices: int | None = None,
                       grace_seconds: float = 10.0,
                       extra_env: dict[str, str] | None = None
                       ) -> tuple[int, bool]:
    """One launch attempt; returns (exit_code, coordinator_bind_failed)."""
    procs: list[subprocess.Popen] = []
    tails: list[list[str]] = []
    threads = []
    for rank in range(num_processes):
        env = dict(os.environ)
        env.update(extra_env or {})
        env["MMLSPARK_TPU_COORDINATOR"] = coordinator
        env["MMLSPARK_TPU_NUM_PROCESSES"] = str(num_processes)
        env["MMLSPARK_TPU_PROCESS_ID"] = str(rank)
        if cpu_devices:
            env["JAX_PLATFORMS"] = "cpu"
            flags = env.get("XLA_FLAGS", "")
            env["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{cpu_devices}").strip()
        p = subprocess.Popen(list(cmd), env=env, stdout=subprocess.PIPE,
                             stderr=subprocess.STDOUT, text=True,
                             errors="replace")
        tail: list[str] = []
        t = threading.Thread(target=_pump, args=(p.stdout, rank, sys.stdout,
                                                 tail), daemon=True)
        t.start()
        procs.append(p)
        tails.append(tail)
        threads.append(t)

    failed_rank: int | None = None
    seen_done: set[int] = set()
    try:
        while True:
            codes = [p.poll() for p in procs]
            # attribute failure to the FIRST worker observed dead across
            # polls, not the lowest rank in this poll — when a crash takes
            # peers down with it (jax.distributed aborting on a lost
            # coordinator), the root cause is the earliest exit, and rank
            # order would misreport a consequential death as the cause
            for rank, code in enumerate(codes):
                if code is not None and rank not in seen_done:
                    seen_done.add(rank)
                    if code != 0 and failed_rank is None:
                        failed_rank = rank
            if failed_rank is not None or all(c == 0 for c in codes):
                break
            time.sleep(0.2)
    except KeyboardInterrupt:
        failed_rank = -1
    if failed_rank is not None:
        # first failure (or interrupt): give survivors a grace period to
        # notice the lost peer (jax.distributed heartbeats), then kill —
        # never leave ranks hung inside a dead collective
        deadline = time.time() + grace_seconds
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in procs:
            while p.poll() is None and time.time() < deadline:
                time.sleep(0.2)
            if p.poll() is None:
                p.kill()
    for p in procs:
        p.wait()
    for t in threads:
        t.join(timeout=2.0)
    if failed_rank is not None and failed_rank >= 0:
        code = procs[failed_rank].returncode
        tail_text = "".join(tails[failed_rank])
        sys.stderr.write(
            f"worker {failed_rank} exited with code {code}; last output:\n"
            + "".join(f"  {ln}" for ln in tails[failed_rank][-15:]))
        low = tail_text.lower()
        bind_failed = any(m in low for m in _BIND_RETRY_MARKERS)
        return code or 1, bind_failed
    if failed_rank == -1:
        return 130, False
    return 0, False


def launch_pod(cmd: Sequence[str], coordinator: str | None,
               num_processes: int | None) -> int:
    """Exec the command for THIS pod worker: set the coordinator env (rank
    and world size come from the TPU runtime via JAX auto-discovery unless
    given) and replace the current process."""
    env = dict(os.environ)
    if coordinator:
        env["MMLSPARK_TPU_COORDINATOR"] = coordinator
    if num_processes:
        env["MMLSPARK_TPU_NUM_PROCESSES"] = str(num_processes)
    os.execvpe(cmd[0], list(cmd), env)


def main(argv: Sequence[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="mmlspark-tpu-launch",
        description="Launch an N-process jax.distributed job "
                    "(see module docstring)")
    ap.add_argument("-n", "--num-processes", type=int, default=None,
                    help="world size (required in local mode)")
    ap.add_argument("--coordinator", default=None,
                    help="host:port of process 0 (local default: a free "
                         "localhost port)")
    ap.add_argument("--mode", choices=("local", "pod"), default="local")
    ap.add_argument("--cpu-devices", type=int, default=None,
                    help="local mode: give each worker this many virtual "
                         "CPU devices (JAX_PLATFORMS=cpu + "
                         "xla_force_host_platform_device_count) — the "
                         "hardware-free simulation rig")
    ap.add_argument("--grace-seconds", type=float, default=10.0,
                    help="after a worker fails, seconds before survivors "
                         "are killed")
    ap.add_argument("cmd", nargs=argparse.REMAINDER,
                    help="worker command (prefix with --)")
    args = ap.parse_args(argv)
    cmd = list(args.cmd)
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        ap.error("no worker command given (append: -- python worker.py ...)")
    if args.mode == "pod":
        return launch_pod(cmd, args.coordinator, args.num_processes)
    if not args.num_processes or args.num_processes < 1:
        ap.error("--num-processes is required in local mode")
    try:
        return launch_local(cmd, args.num_processes, args.coordinator,
                            args.cpu_devices, args.grace_seconds)
    except ValueError as e:  # a request this host cannot honour
        print(str(e), file=sys.stderr)
        return 2


if __name__ == "__main__":
    raise SystemExit(main())
