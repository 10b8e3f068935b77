"""Elastic fault-tolerant training service — supervision as policy over
signals.

The reference ran distributed DNN training as a supervised out-of-process
job: ``CNTKLearner`` shelled out to ``mpiexec`` and checked ONE exit code
(reference: cntk-train/src/main/scala/CNTKLearner.scala:140-161) — the job
either finished or died. The TPU-native analog separates the three
concerns that conflation hides:

* **sensors** — the PR 9 anomaly plane: flight-recorder heartbeats (one
  beat per train step / committed batch), the straggler detector's
  fenced step-time exchange, exit codes, and progress deadlines. The
  worker-side :class:`ServiceBeacon` publishes them into the service
  directory, one JSON per worker, atomically.
* **policy** — :class:`RecoveryPolicy`: a PURE decision function from a
  typed :class:`Signal` and the supervision ledger to a typed
  :class:`Action` (restart from checkpoint, evict a straggler, elastic
  re-scale to a smaller topology, fail). Unit-testable without a single
  process spawned.
* **actuator** — :class:`TrainSupervisor`: launches the worker
  generation, watches the sensors, executes the policy's actions, and
  records every decision (``decisions.jsonl`` on disk always; obs
  ``service/*`` events + ``train.service.*`` gauges when the tracer is
  on).

**Elastic re-scale contract.** A generation trains at a rung of the
configured topology ladder. On permanent worker loss the supervisor
drops one rung: the mesh re-forms on the survivors, and the new
generation restores the latest ``TrainCheckpointer`` step with restore
targets built on the NEW mesh — every leaf reshards on read
(``train/checkpoint.py``; in-process rescale uses
:func:`~mmlspark_tpu.train.checkpoint.reshard_state`). Ingest stays
deterministic across the topology change through
:func:`elastic_stream`: batch composition derives from a GLOBAL
seeded walk, each worker taking its rank's slice of every global batch
— so the resumed schedule replays the consumed prefix as no-ops and no
example is dropped or double-consumed across the boundary, at any world
size. The ``check_train_elastic`` tier-1 gate holds the result to the
PR 10 discipline extended to topology change: the recovered run's loss
tail and final params are BIT-identical to an uninterrupted
continuation at the surviving topology.

CLI: ``python tools/train_service.py`` (supervise a worker command, or
run the built-in self-test worker the gate and dryrun use).
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import shutil
import subprocess
import threading
import time
from typing import Any, Callable, Iterator, Sequence

import numpy as np

from mmlspark_tpu.core.logging_utils import get_logger
from mmlspark_tpu.core.retry import RetryPolicy
from mmlspark_tpu.obs import fleet as _obs_fleet
from mmlspark_tpu.obs import flight as _obs_flight
from mmlspark_tpu.obs import runtime as _obs_rt
from mmlspark_tpu.obs.metrics import Counter as _ObsCounter
from mmlspark_tpu.obs.metrics import registry as _obs_registry
from mmlspark_tpu.service.core import (
    SupervisedProcess, SupervisorJournal, atomic_write_json, join_pumps,
    read_beacon, terminate_processes,
)

_log = get_logger(__name__)

# worker contract: everything arrives through the environment (the same
# wiring style as mmlspark_tpu.tools.launch), read back by
# ServiceWorkerInfo.from_env()
ENV_DIR = "MMLSPARK_TPU_SERVICE_DIR"
ENV_RANK = "MMLSPARK_TPU_SERVICE_RANK"
ENV_WORLD = "MMLSPARK_TPU_SERVICE_WORLD"
ENV_GENERATION = "MMLSPARK_TPU_SERVICE_GENERATION"
ENV_DEVICES = "MMLSPARK_TPU_SERVICE_DEVICES"
ENV_CKPT = "MMLSPARK_TPU_SERVICE_CKPT"
# set when the supervisor carries a publish policy: the worker brackets
# its result handoff in the lifecycle publish-fence span so worker and
# publisher stitch into one fleet-timeline flow (obs/fleet.py)
ENV_PUBLISH_FENCE = "MMLSPARK_TPU_SERVICE_PUBLISH_FENCE"

# the exit code a preempted worker dies with (EX_TEMPFAIL): policy
# default treats it as PERMANENT capacity loss → immediate re-scale,
# no restart burned on a host that is gone
PREEMPT_EXIT_CODE = 75

WATCH_THREAD = "ServiceWatch"
BEACON_THREAD = "ServiceBeacon"


# the beacon transport lives in the shared supervisor core
# (mmlspark_tpu/service/core.py) — kept under the historical name for
# in-repo callers
_atomic_write_json = atomic_write_json


# ---------------------------------------------------------------------------
# deterministic elastic ingest
# ---------------------------------------------------------------------------


def elastic_batch_indices(n: int, batch_size: int, seed: int,
                          epoch: int) -> Iterator[np.ndarray]:
    """The GLOBAL batch walk for one epoch: a seeded permutation of
    ``range(n)`` cut into ``batch_size`` slices (final slice partial).
    Every topology — any world size, any dp extent — derives its batches
    from THIS walk, which is what makes elastic re-scale replayable: the
    resumed prefix names exactly the examples the dead topology consumed."""
    order = np.random.default_rng(seed + epoch).permutation(n)
    for s in range(0, n, batch_size):
        yield order[s:s + batch_size]


def elastic_stream(x: np.ndarray, y: np.ndarray, *, batch_size: int,
                   seed: int, epochs: int = 1, rank: int = 0,
                   world: int = 1) -> Callable[[], Iterator[tuple]]:
    """Topology-independent sharded ingest for ``Trainer.fit_stream``.

    Returns a zero-arg callable yielding this worker's ``(x, y)`` chunks:
    slice ``rank`` of every global batch from
    :func:`elastic_batch_indices`, across all ``epochs`` in one pass
    (drive it with ``TrainConfig(epochs=1)`` — the walk owns the epoch
    structure, so the schedule fingerprint is identical at every world
    size). Chunk size equals the local batch size, so ``fit_stream``'s
    rebatcher maps chunks 1:1 onto steps and the assembled GLOBAL batch
    is the process-order concatenation of the walk's slices — the same
    rows in the same order whether one worker holds them all or ``world``
    workers hold a slice each.

    Sharded walks require ``batch_size | len(x)``: a short tail batch
    would slice unevenly across ranks (some slices short or empty),
    desynchronizing the per-rank chunk streams — from the next epoch on
    the assembled "global" batch would silently mix rows of different
    walk positions. That is a LOUD error here, not a masked tail; pad or
    trim the dataset (a world of 1 keeps the masked-tail behavior —
    there is no cross-rank pairing to corrupt). The same divisibility is
    what makes cross-topology replay bit-compatible anyway.
    """
    if not 0 <= rank < world:
        raise ValueError(f"rank {rank} outside world {world}")
    if batch_size % world:
        raise ValueError(
            f"batch_size {batch_size} must divide over {world} workers")
    if world > 1 and len(x) % batch_size:
        raise ValueError(
            f"elastic_stream with world {world} requires batch_size "
            f"({batch_size}) to divide the dataset ({len(x)} rows): a "
            "partial tail batch slices unevenly across ranks and "
            "desynchronizes the per-rank chunk streams from the next "
            "epoch on — pad or trim the dataset")
    bs_local = batch_size // world

    def source() -> Iterator[tuple]:
        for epoch in range(epochs):
            for idx in elastic_batch_indices(len(x), batch_size, seed,
                                             epoch):
                mine = idx[rank * bs_local:(rank + 1) * bs_local]
                if len(mine):  # world==1: the masked tail may be short
                    yield x[mine], y[mine]

    return source


# ---------------------------------------------------------------------------
# worker side: env contract + liveness beacon
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ServiceWorkerInfo:
    """This worker's identity under the supervisor (from the env)."""

    service_dir: str
    rank: int
    world: int
    generation: int
    devices: int | None
    checkpoint_dir: str | None

    @staticmethod
    def from_env() -> "ServiceWorkerInfo | None":
        service_dir = os.environ.get(ENV_DIR)
        if not service_dir:
            return None
        devices = os.environ.get(ENV_DEVICES)
        return ServiceWorkerInfo(
            service_dir=service_dir,
            rank=int(os.environ.get(ENV_RANK, "0")),
            world=int(os.environ.get(ENV_WORLD, "1")),
            generation=int(os.environ.get(ENV_GENERATION, "0")),
            devices=int(devices) if devices else None,
            checkpoint_dir=os.environ.get(ENV_CKPT) or None)

    def beacon_path(self) -> str:
        return os.path.join(self.service_dir, f"beacon_{self.rank}.json")

    def result_path(self) -> str:
        return os.path.join(
            self.service_dir,
            f"result_gen{self.generation}_rank{self.rank}.json")


class ServiceBeacon:
    """Worker-side liveness publisher: samples the PR 9 sensors — the
    flight recorder's heartbeat table (one beat per train step /
    committed batch) and the registry's straggler series — and writes
    them atomically to ``beacon_<rank>.json`` on an interval. The
    supervisor's deadline monitoring and straggler-evict policy read
    ONLY this file: worker and supervisor share no memory, so the same
    sensor surface works across hosts (a shared filesystem is the
    transport, like the checkpoint itself)."""

    def __init__(self, info: ServiceWorkerInfo, interval_s: float = 0.25):
        self.info = info
        self.interval_s = float(interval_s)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name=f"{BEACON_THREAD}[{info.rank}]",
            daemon=True)

    def start(self) -> "ServiceBeacon":
        self._thread.start()
        return self

    def _sample(self, status: str) -> dict:
        sample: dict[str, Any] = {
            "rank": self.info.rank, "pid": os.getpid(),
            "generation": self.info.generation,
            "ts": time.time(), "status": status,
            "progress": 0, "busy": False,
            "stragglers": 0, "host_step_ms": {},
            "counters": [],
        }
        rec = _obs_flight._rec
        if rec is not None:
            beats = rec.heartbeats()
            sample["heartbeats"] = beats
            sample["progress"] += int(sum(hb["beats"]
                                          for hb in beats.values()))
            sample["busy"] = any(hb["busy"] for hb in beats.values())
        # straggler sensors ride the registry (obs/anomaly.py publishes
        # them on the fenced liveness exchange); iterate the interned
        # metric objects — no string key parsing. The train.* counter
        # EXCERPT is the supervisor's fleet-aggregation feed: it reads
        # per-worker deltas off the beacons and publishes
        # `train.fleet.*` series (docs/training_service.md)
        for m in _obs_registry().iter_metrics():
            labels = dict(m.labels)
            if m.name == "train.steps":
                sample["progress"] += int(m.value)
            elif m.name == "train.stragglers":
                sample["stragglers"] += int(m.value)
            elif m.name == "train.host_step_ms":
                sample["host_step_ms"][str(labels.get("host"))] = m.value
            elif m.name == "train.loss" and hasattr(m, "values"):
                # the eval series (Trainer._note_loss publishes every
                # logged loss into this windowed histogram) — what the
                # supervisor's lifecycle EvalGate judges mid-run
                sample["eval"] = [float(v) for v in m.values()]
            if isinstance(m, _ObsCounter) \
                    and m.name.startswith("train."):
                sample["counters"].append([m.name, labels, m.value])
        return sample

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                _atomic_write_json(self.info.beacon_path(),
                                   self._sample("running"))
            except Exception:  # pragma: no cover - beacon never kills
                pass           # the worker it reports on

    def close(self, status: str = "exited") -> None:
        """Stop the publisher thread (joined, never leaked) and write the
        terminal status so the supervisor can distinguish a clean exit
        from a vanished process."""
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=5.0)
        try:
            _atomic_write_json(self.info.beacon_path(),
                               self._sample(status))
        except Exception:  # pragma: no cover - best-effort terminal write
            pass


@contextlib.contextmanager
def service_context(beacon_interval_s: float = 0.25):
    """Worker-side entry: read the supervisor's env contract, start the
    liveness beacon, and guarantee its shutdown. Yields the
    :class:`ServiceWorkerInfo` (or None when not running under a
    supervisor — library code can call this unconditionally).

    The flight recorder and obs tracer are enabled through their own env
    vars (``MMLSPARK_TPU_FLIGHT``/``MMLSPARK_TPU_OBS``, which the
    supervisor sets on the worker env) — this context adds no competing
    enable path."""
    info = ServiceWorkerInfo.from_env()
    if info is None:
        yield None
        return
    os.makedirs(info.service_dir, exist_ok=True)
    beacon = ServiceBeacon(info, interval_s=beacon_interval_s).start()
    try:
        yield info
    except BaseException:
        beacon.close(status="crashed")
        raise
    else:
        beacon.close(status="exited")


# ---------------------------------------------------------------------------
# signals, actions, policy
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class WorkerExit:
    """A worker process exited with a nonzero code (crash, preemption,
    or a signal — negative codes are deaths by signal)."""
    rank: int
    code: int


@dataclasses.dataclass(frozen=True)
class WorkerHang:
    """A busy worker made no progress (beacon beats + step counters
    frozen) past the deadline."""
    rank: int
    stalled_s: float


@dataclasses.dataclass(frozen=True)
class WorkerStraggling:
    """The straggler detector named this worker's host in ``count``
    successive liveness windows."""
    rank: int
    count: int


Signal = Any  # WorkerExit | WorkerHang | WorkerStraggling


@dataclasses.dataclass(frozen=True)
class Restart:
    reason: str
    delay_s: float = 0.0


@dataclasses.dataclass(frozen=True)
class Rescale:
    reason: str
    evict_rank: int | None = None


@dataclasses.dataclass(frozen=True)
class Fail:
    reason: str


@dataclasses.dataclass(frozen=True)
class Proceed:
    reason: str = ""


Action = Any  # Restart | Rescale | Fail | Proceed


@dataclasses.dataclass
class Ledger:
    """The supervision history the policy conditions on."""
    restarts_used: int = 0
    rung: int = 0
    rungs_total: int = 1

    @property
    def can_rescale(self) -> bool:
        return self.rung + 1 < self.rungs_total


@dataclasses.dataclass(frozen=True)
class RecoveryPolicy:
    """Signal → action, pure. The table (docs/training_service.md):

    ==========================  =========================================
    signal                      action
    ==========================  =========================================
    exit in preempt codes       re-scale (permanent capacity loss)
    exit nonzero / hang         restart from latest checkpoint while the
                                budget lasts, backoff-paced; then
                                re-scale (if a rung remains and
                                ``rescale_on_exhausted``), else fail
    straggler named ≥ N times   evict the named worker → re-scale
    straggler below N           proceed (transient skew is not a fault)
    ==========================  =========================================

    ``restart_backoff`` reuses the :class:`RetryPolicy` schedule (its
    ``retry_on`` is unused here; ``max_attempts`` bounds nothing — the
    restart budget is ``max_restarts``).
    """

    max_restarts: int = 2
    restart_backoff: RetryPolicy = RetryPolicy(
        max_attempts=64, base_delay_s=0.5, max_delay_s=30.0, jitter=0.5)
    preempt_exit_codes: tuple[int, ...] = (PREEMPT_EXIT_CODE,)
    rescale_on_exhausted: bool = True
    hang_timeout_s: float | None = None
    evict_straggler_after: int | None = None

    def _backoff(self, k: int) -> float:
        for i, d in enumerate(self.restart_backoff.delays()):
            if i == k:
                return d
        return self.restart_backoff.max_delay_s

    def _lost(self, reason: str, ledger: Ledger) -> Action:
        if ledger.restarts_used < self.max_restarts:
            return Restart(reason,
                           delay_s=self._backoff(ledger.restarts_used))
        if self.rescale_on_exhausted and ledger.can_rescale:
            return Rescale(f"{reason}; restart budget "
                           f"({self.max_restarts}) exhausted")
        return Fail(f"{reason}; restart budget exhausted and no smaller "
                    "topology to re-scale to")

    def decide(self, sig: Signal, ledger: Ledger) -> Action:
        if isinstance(sig, WorkerExit):
            if sig.code == 0:
                return Proceed("clean exit")
            if sig.code in self.preempt_exit_codes:
                if ledger.can_rescale:
                    return Rescale(
                        f"worker {sig.rank} preempted (exit {sig.code})",
                        evict_rank=sig.rank)
                return Fail(f"worker {sig.rank} preempted and no smaller "
                            "topology to re-scale to")
            return self._lost(
                f"worker {sig.rank} died (exit {sig.code})", ledger)
        if isinstance(sig, WorkerHang):
            return self._lost(
                f"worker {sig.rank} hung ({sig.stalled_s:.1f}s without "
                "progress while busy)", ledger)
        if isinstance(sig, WorkerStraggling):
            if (self.evict_straggler_after is not None
                    and sig.count >= self.evict_straggler_after):
                if ledger.can_rescale:
                    return Rescale(
                        f"worker {sig.rank} named straggler in "
                        f"{sig.count} windows", evict_rank=sig.rank)
                return Proceed("straggler persists but no smaller "
                               "topology; keeping it")
            return Proceed("straggler below eviction threshold")
        raise TypeError(f"unknown signal {sig!r}")


# ---------------------------------------------------------------------------
# the supervisor
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Topology:
    """One rung of the elastic ladder: how many worker processes, and —
    on the hardware-free dryrun rig — how many virtual CPU devices each
    gets (``None`` inherits the environment, i.e. real accelerators)."""
    world: int = 1
    devices: int | None = None


@dataclasses.dataclass
class ServiceConfig:
    """Supervisor configuration. ``cmd`` is the worker argv, launched
    ``world`` times per generation with the env contract set
    (rank/world/generation/devices/service dir/checkpoint dir)."""

    cmd: Sequence[str]
    service_dir: str
    topologies: tuple[Topology, ...] = (Topology(),)
    checkpoint_dir: str | None = None
    policy: RecoveryPolicy = dataclasses.field(default_factory=RecoveryPolicy)
    poll_s: float = 0.1
    grace_seconds: float = 10.0
    worker_obs: bool = True      # MMLSPARK_TPU_OBS=1 on workers (the
    #                              straggler sensors publish through it)
    worker_flight: bool = True   # flight recorder dir per worker under
    #                              service_dir/flight/ (post-mortems land
    #                              where the supervisor can find them)
    worker_fleet: bool = True    # propagate this process's fleet dir
    #                              (obs/fleet.py, MMLSPARK_TPU_FLEET) so
    #                              workers export telemetry snapshots
    #                              into the same fleet plane
    snapshot_recovery: bool = True  # archive the checkpoint dir at each
    #                                 re-scale (the exact recovery point,
    #                                 for audit/bit-compat verification)
    coordinator: str | None = None  # world>1: host:port of rank 0
    extra_env: dict[str, str] = dataclasses.field(default_factory=dict)
    publish: Any | None = None   # lifecycle.PublishPolicy: eval-gate and
    #                              dark-publish passing checkpoints to a
    #                              ModelRepo on clean completion (and
    #                              optionally every K checkpoints) —
    #                              the train→serve deployment plane
    #                              (docs/lifecycle.md)

    def __post_init__(self) -> None:
        if not self.topologies:
            raise ValueError("at least one topology rung is required")
        for i, t in enumerate(self.topologies[1:], 1):
            prev = self.topologies[i - 1]
            if t.world > prev.world:
                raise ValueError(
                    "topology ladder must not GROW across rungs (rung "
                    f"{i} has world {t.world} > {prev.world}) — rungs "
                    "are what remains after capacity loss")
            if (t.devices is not None and prev.devices is not None
                    and t.devices > prev.devices):
                raise ValueError(
                    "topology ladder must not GROW across rungs (rung "
                    f"{i} has devices {t.devices} > {prev.devices}) — "
                    "rungs are what remains after capacity loss")


@dataclasses.dataclass
class GenerationReport:
    generation: int
    topology: Topology
    exit_codes: dict[int, int | None]
    signal: Any = None
    action: Any = None


@dataclasses.dataclass
class ServiceReport:
    ok: bool = False
    reason: str = ""
    generations: list = dataclasses.field(default_factory=list)
    restarts: int = 0
    rescales: int = 0
    evictions: int = 0
    snapshots: list = dataclasses.field(default_factory=list)

    @property
    def final_topology(self) -> Topology | None:
        return (self.generations[-1].topology
                if self.generations else None)


class _Worker(SupervisedProcess):
    """One supervised worker process + its output pump and progress
    tracking (the shared :class:`SupervisedProcess` core under the
    train service's pump naming)."""

    def __init__(self, rank: int, proc: subprocess.Popen):
        super().__init__(rank, proc, log_prefix="service worker",
                         thread_name=f"{WATCH_THREAD}[pump{rank}]")


class TrainSupervisor:
    """Launch, watch, and recover a supervised training job (see module
    docstring). ``run()`` blocks until the job completes at some rung of
    the topology ladder or the policy gives up, and returns the
    :class:`ServiceReport` with every signal → action decision taken."""

    def __init__(self, cfg: ServiceConfig):
        self.cfg = cfg
        self._refuse_unrunnable_on_tpu_host()
        os.makedirs(cfg.service_dir, exist_ok=True)
        self._decisions_path = os.path.join(cfg.service_dir,
                                            "decisions.jsonl")
        # every supervisor decision is an event: appended to the on-disk
        # decisions.jsonl ALWAYS (supervision forensics must not depend
        # on telemetry being on), mirrored as an obs service/<kind>
        # event + train.service.* counters when the tracer is enabled —
        # the shared SupervisorJournal discipline (service/core.py)
        self._journal = SupervisorJournal(
            self._decisions_path, event_prefix="service", cat="service",
            counter_prefix="train.service.",
            counter_kinds=("restart", "rescale", "evict", "worker_exit",
                           "hang"),
            log_label="train service")
        self._straggler_total = 0  # global verdict windows this generation
        self._publisher = None
        if cfg.publish is not None:
            # lazy import: supervising plain training jobs must not pull
            # the lifecycle/models planes in
            from mmlspark_tpu.lifecycle.publish import Publisher
            self._publisher = Publisher(
                cfg.publish, cfg.service_dir,
                run_id=f"train-{os.getpid()}-{int(time.time())}",
                train_journal=self._decisions_path)

    def _refuse_unrunnable_on_tpu_host(self) -> None:
        """A ladder rung this supervisor cannot honour on real chips is
        refused at construction, loudly, never degraded: all workers
        start on THIS host and each brings up the default JAX backend,
        so on a TPU host ``world > 1`` means processes fighting over the
        same chips (one process per chip, and nothing here assigns
        them), and ``devices=N`` means N *virtual CPU* devices — a job
        that asked for four devices next to four chips would train on
        the CPU without a word. ``JAX_PLATFORMS=cpu`` in the
        supervisor's environment (or ``extra_env``) is the explicit way
        to rehearse such a ladder on a TPU host. Decided from device
        files — the supervisor itself never touches a backend."""
        from mmlspark_tpu.utils.env import children_reach_tpu
        if not children_reach_tpu(self.cfg.extra_env):
            return
        for topo in self.cfg.topologies:
            if topo.devices is not None:
                raise ValueError(
                    f"Topology(world={topo.world}, devices={topo.devices})"
                    " on a TPU host: `devices` grants virtual CPU "
                    "devices, not chips — drop it to train on the "
                    "host's chips (one worker drives them all), or set "
                    "JAX_PLATFORMS=cpu to rehearse on virtual devices")
            if topo.world > 1:
                raise ValueError(
                    f"Topology(world={topo.world}) on a TPU host: every "
                    "worker process would claim the host's chips; one "
                    "process per chip — use world=1 (one worker drives "
                    "all local chips), or set JAX_PLATFORMS=cpu to "
                    "rehearse on virtual devices")

    # -- observability of the supervisor itself --

    def _record(self, kind: str, payload: dict) -> None:
        self._journal.record(kind, payload)

    def _gauges(self, generation: int, topo: Topology) -> None:
        if _obs_rt._enabled:
            reg = _obs_registry()
            reg.gauge("train.service.generation").set(generation)
            reg.gauge("train.service.world").set(topo.world)
            if topo.devices is not None:
                reg.gauge("train.service.devices").set(topo.devices)

    # -- process management --

    def _spawn(self, generation: int, topo: Topology) -> list[_Worker]:
        self._straggler_total = 0  # verdict windows are per-generation
        coordinator = self.cfg.coordinator
        if topo.world > 1 and coordinator is None:
            import socket
            with socket.socket() as s:
                s.bind(("localhost", 0))
                coordinator = f"localhost:{s.getsockname()[1]}"
        workers = []
        for rank in range(topo.world):
            env = dict(os.environ)
            env.update(self.cfg.extra_env)
            env[ENV_DIR] = self.cfg.service_dir
            env[ENV_RANK] = str(rank)
            env[ENV_WORLD] = str(topo.world)
            env[ENV_GENERATION] = str(generation)
            if self.cfg.checkpoint_dir:
                env[ENV_CKPT] = self.cfg.checkpoint_dir
            if topo.devices is not None:
                env[ENV_DEVICES] = str(topo.devices)
                env["JAX_PLATFORMS"] = "cpu"
                # REPLACE any inherited device-count flag: the ladder's
                # whole point is that rungs differ in device count, and
                # a supervisor running inside an 8-device test rig would
                # otherwise hand every rung the rig's count
                flags = [f for f in env.get("XLA_FLAGS", "").split()
                         if "xla_force_host_platform_device_count"
                         not in f]
                flags.append("--xla_force_host_platform_device_count="
                             f"{topo.devices}")
                env["XLA_FLAGS"] = " ".join(flags)
            if topo.world > 1:
                env["MMLSPARK_TPU_COORDINATOR"] = coordinator
                env["MMLSPARK_TPU_NUM_PROCESSES"] = str(topo.world)
                env["MMLSPARK_TPU_PROCESS_ID"] = str(rank)
            if self.cfg.worker_obs:
                env.setdefault("MMLSPARK_TPU_OBS", "1")
            if self._publisher is not None:
                env.setdefault(ENV_PUBLISH_FENCE, "1")
            if self.cfg.worker_flight:
                env.setdefault("MMLSPARK_TPU_FLIGHT", os.path.join(
                    self.cfg.service_dir, "flight",
                    f"gen{generation}_rank{rank}"))
            if self.cfg.worker_fleet:
                fdir = _obs_fleet.fleet_dir()
                if fdir:
                    env.setdefault("MMLSPARK_TPU_FLEET", fdir)
            proc = subprocess.Popen(
                list(self.cfg.cmd), env=env, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True, errors="replace")
            workers.append(_Worker(rank, proc))
            # supervisor-side flight heartbeat per worker: a supervisor
            # with its own recorder on shows which worker stopped moving
            # in ITS post-mortems too. Registered IDLE: only beacon
            # progress marks it busy — an armed-busy row with no beacon
            # evidence (compile, a worker that never beacons) would
            # ripen into spurious watchdog hang dumps, the dead-busy-row
            # class PR 9 fixed for drain_barrier
            rec = _obs_flight._rec
            if rec is not None:
                rec.arm(f"service/worker{rank}")
                rec.disarm(f"service/worker{rank}")
        self._record("launch", {
            "generation": generation, "world": topo.world,
            "devices": topo.devices, "pids":
                {w.rank: w.proc.pid for w in workers}})
        self._gauges(generation, topo)
        return workers

    def _terminate(self, workers: list[_Worker]) -> None:
        terminate_processes(workers, self.cfg.grace_seconds)
        self._forget(workers)

    def _forget(self, workers: list[_Worker]) -> None:
        """Shutdown hygiene: drop dead workers' supervisor-side flight
        heartbeat rows (a long-lived supervisor with generation churn
        must not bloat every dump's heartbeat table — nor ripen dead
        busy rows into spurious hang dumps) and join the output pumps
        (no stray threads after an evict)."""
        rec = _obs_flight._rec
        for w in workers:
            if rec is not None:
                rec.forget(f"service/worker{w.rank}")
        join_pumps(workers)

    # -- sensor reads --

    def _read_beacon(self, generation: int, rank: int) -> dict | None:
        # generation-checked (a stale file from the previous generation
        # is not this worker) — shared with the fleet supervisor
        return read_beacon(self.cfg.service_dir, rank, generation)

    def _poll_sensors(self, generation: int,
                      workers: list[_Worker]) -> Signal | None:
        policy = self.cfg.policy
        rec = _obs_flight._rec
        beacons: dict[int, dict | None] = {}
        for w in workers:
            b = self._read_beacon(generation, w.rank)
            beacons[w.rank] = b
            if b is None:
                # no current-generation liveness signal at all: keep the
                # supervisor-side heartbeat row idle (no evidence of
                # busy), but a worker wedged BEFORE its first beacon
                # (backend/distributed init, a dead beacon thread) must
                # still hit the deadline — absence of the signal past
                # the timeout IS the hang signal (baseline: spawn time)
                if rec is not None:
                    rec.disarm(f"service/worker{w.rank}")
                if (policy.hang_timeout_s is not None
                        and w.proc.poll() is None):
                    stalled = time.monotonic() - w.progress_ts
                    if stalled > policy.hang_timeout_s:
                        return WorkerHang(w.rank, stalled)
                continue
            progress = int(b.get("progress", 0))
            if progress != w.last_progress:
                w.last_progress = progress
                w.progress_ts = time.monotonic()
                if rec is not None:
                    rec.beat(f"service/worker{w.rank}")
            elif not b.get("busy") and rec is not None:
                rec.disarm(f"service/worker{w.rank}")  # idle, not hung
            elif (policy.hang_timeout_s is not None and b.get("busy")
                  and w.proc.poll() is None):
                stalled = time.monotonic() - w.progress_ts
                if stalled > policy.hang_timeout_s:
                    return WorkerHang(w.rank, stalled)
        # fleet aggregation: ONE read of the beacon set produces both
        # the published `train.fleet.*` series and the inputs the
        # straggler verdict below consumes — policy and telemetry see
        # the same numbers by construction, never two derivations
        agg = self._fleet_aggregates(beacons)
        self._publish_fleet(workers, beacons, agg)
        # straggler verdicts are GLOBAL: the fenced exchange increments
        # train.stragglers identically in EVERY process, so the window
        # count is the MAX across beacons — summing per-beacon increments
        # would count each verdict world× and evict world× too early
        total = agg["straggler_windows"]
        if total > self._straggler_total:
            delta = total - self._straggler_total
            hosts = agg["host_step_ms"]
            if hosts:
                slow = max(hosts, key=lambda h: hosts[h] or 0.0)
                for target in workers:
                    if str(target.rank) == str(slow):
                        # commit the tally only WITH attribution: a
                        # beacon sampled between the counter bump and
                        # the gauge publication must not silently eat
                        # verdict windows — leave them for the next poll
                        self._straggler_total = total
                        target.straggler_hits += delta
                        return WorkerStraggling(
                            target.rank, target.straggler_hits)
        return None

    def _fleet_aggregates(self, beacons: dict[int, dict | None]) -> dict:
        """Merge one poll's beacons into the fleet view: live worker
        count, summed progress, the GLOBAL straggler verdict-window
        count (max across beacons — every process counts each fenced
        verdict identically), and the per-host step-time table (from
        the beacon that has witnessed the most verdicts — the most
        current attribution). ``workers`` counts only RUNNING-status
        beacons: the final terminal-beacon read after a clean
        completion folds in the last counter deltas, and an
        exited/crashed beacon must not leave the liveness gauge
        reporting dead workers as live on an idle supervisor.
        Progress/straggler/step-time reads stay cumulative truth
        whatever the status."""
        live = [b for b in beacons.values() if b]
        host_step_ms: dict = {}
        for b in sorted(live, key=lambda b: int(b.get("stragglers", 0)),
                        reverse=True):
            if b.get("host_step_ms"):
                host_step_ms = b["host_step_ms"]
                break
        return {
            "workers": sum(1 for b in live
                           if b.get("status", "running") == "running"),
            "progress": sum(int(b.get("progress", 0)) for b in live),
            "straggler_windows": max(
                (int(b.get("stragglers", 0)) for b in live), default=0),
            "host_step_ms": host_step_ms,
        }

    def _publish_fleet(self, workers: list[_Worker],
                       beacons: dict[int, dict | None],
                       agg: dict) -> None:
        """Publish the beacon-derived fleet aggregates as first-class
        `train.fleet.*` series in the SUPERVISOR's registry (tracer-
        gated, like every supervisor series): liveness/progress/skew
        gauges, plus per-worker DELTAS of the beacon registry excerpts
        re-accumulated as `train.fleet.<counter>{rank=…}` counters — so
        downstream consumers (the timeseries sampler, a fleet exporter
        on the supervisor, /metrics scrapes) read one aggregated
        surface instead of re-deriving from raw beacon files."""
        if not _obs_rt._enabled:
            return
        reg = _obs_registry()
        reg.gauge("train.fleet.workers").set(agg["workers"])
        reg.gauge("train.fleet.progress").set(agg["progress"])
        reg.gauge("train.fleet.straggler_windows").set(
            agg["straggler_windows"])
        for host, ms in agg["host_step_ms"].items():
            if isinstance(ms, (int, float)):
                reg.gauge("train.fleet.host_step_ms",
                          host=str(host)).set(float(ms))
        for w in workers:
            b = beacons.get(w.rank)
            if not b:
                continue
            for row in b.get("counters") or ():
                try:
                    name, labels, value = row
                    value = float(value)
                    labels = {str(k): v for k, v in dict(labels).items()}
                except (TypeError, ValueError):
                    continue
                key = (name, tuple(sorted(labels.items())))
                last = w.counter_last.get(key)
                # a backward value is a restarted worker's fresh
                # registry: the new total is all new progress
                delta = value if (last is None or value < last) \
                    else value - last
                w.counter_last[key] = value
                if delta > 0:
                    # rank= is the fleet dimension: a worker counter
                    # that already carries its own rank label (worker
                    # code is arbitrary) is overridden, never a
                    # duplicate-keyword TypeError killing the watch loop
                    flabels = {**labels, "rank": w.rank}
                    reg.counter(
                        "train.fleet." + name[len("train."):],
                        **flabels).add(delta)

    def _watch(self, generation: int,
               workers: list[_Worker]) -> Signal | None:
        """Block until the generation finishes (returns None) or a fault
        signal fires (returns it; remaining workers still running).
        Re-entrant for the same worker set: a signal the policy declines
        to act on (Proceed) resumes the watch without re-reporting
        already-seen exits."""
        while True:
            for w in workers:
                code = w.proc.poll()
                if code is not None and not getattr(w, "exit_recorded",
                                                    False):
                    w.exit_recorded = True
                    self._record("worker_exit", {
                        "generation": generation, "rank": w.rank,
                        "code": code})
                    rec = _obs_flight._rec
                    if rec is not None:
                        rec.forget(f"service/worker{w.rank}")
                    if code != 0:
                        return WorkerExit(w.rank, code)
            if all(w.proc.poll() is not None for w in workers):
                return None
            sig = self._poll_sensors(generation, workers)
            if sig is not None:
                return sig
            if self._publisher is not None:
                self._publish_poll(generation)
            time.sleep(self.cfg.poll_s)

    # -- eval-gated publication (the lifecycle deployment plane) --

    def _publish_poll(self, generation: int) -> None:
        """Mid-run publication sensors, ridden on the watch loop: retry
        a torn publish, then feed the every-K-checkpoints gate off
        rank 0's beacon eval series (docs/lifecycle.md). Never raises —
        a broken publish hook must not take supervision down."""
        pub = self._publisher
        try:
            record = pub.retry_pending()
            if record is None:
                beacon = self._read_beacon(generation, 0) or {}
                record = pub.on_checkpoint_poll(
                    generation, self.cfg.checkpoint_dir,
                    beacon.get("eval") or [])
            if record:
                self._record("publish", {
                    "generation": generation, "model": record["model"],
                    "version": record["version"],
                    "lifecycle_journal": pub.journal.path})
        except Exception as e:  # pragma: no cover - defensive
            _log.warning("train service: publish poll failed: %s", e)

    def _publish_complete(self, generation: int) -> None:
        """Clean-completion publication: judge rank 0's result file
        (the worker bracketed its write in the publish-fence span; the
        gate + publish here is the other side of that fence). The
        cross-reference lands in BOTH journals: the lifecycle record
        carries the train decisions path, this record carries the
        lifecycle decisions path."""
        pub = self._publisher
        if pub is None:
            return
        try:
            pub.retry_pending()
            path = os.path.join(
                self.cfg.service_dir,
                f"result_gen{generation}_rank0.json")
            with open(path, encoding="utf-8") as f:
                result = json.load(f)
            record = pub.on_complete(generation, result)
            if record:
                self._record("publish", {
                    "generation": generation, "model": record["model"],
                    "version": record["version"],
                    "lifecycle_journal": pub.journal.path})
        except Exception as e:
            _log.warning("train service: completion publish failed: %s",
                         e)

    def _snapshot(self, generation: int) -> str | None:
        """Archive the checkpoint dir at the recovery point — the state
        the re-scaled generation will restore, preserved for audit (the
        bit-compat gate re-runs an uninterrupted continuation from it)."""
        ck = self.cfg.checkpoint_dir
        if not (self.cfg.snapshot_recovery and ck and os.path.isdir(ck)):
            return None
        dest = os.path.join(self.cfg.service_dir,
                            f"recovery_gen{generation}")
        if os.path.exists(dest):  # pragma: no cover - re-entry
            shutil.rmtree(dest)
        shutil.copytree(ck, dest)
        return dest

    # -- the supervision loop --

    def run(self) -> ServiceReport:
        report = ServiceReport()
        ledger = Ledger(rungs_total=len(self.cfg.topologies))
        generation = 0
        workers: list[_Worker] = []
        try:
            while True:
                topo = self.cfg.topologies[ledger.rung]
                workers = self._spawn(generation, topo)
                while True:
                    sig = self._watch(generation, workers)
                    if sig is None:
                        action = None
                        break
                    action = self.cfg.policy.decide(sig, ledger)
                    if not isinstance(action, Proceed):
                        break
                    # policy declined to act (e.g. straggler below the
                    # eviction threshold): the generation keeps running,
                    # resume the watch
                    self._record("proceed", {"generation": generation,
                                             "signal": repr(sig),
                                             "reason": action.reason})
                gen_report = GenerationReport(
                    generation, topo,
                    {w.rank: w.proc.poll() for w in workers}, signal=sig,
                    action=action)
                report.generations.append(gen_report)
                if sig is None:
                    # one final fleet publication off the TERMINAL
                    # beacons: the watch loop returns the moment every
                    # worker exits, which can precede its last
                    # mid-run sensor poll — without this read the
                    # train.fleet.* aggregates would understate the
                    # completed generation by up to one beacon interval
                    beacons = {w.rank:
                               self._read_beacon(generation, w.rank)
                               for w in workers}
                    self._publish_fleet(
                        workers, beacons,
                        self._fleet_aggregates(beacons))
                    self._forget(workers)
                    workers = []
                    self._publish_complete(generation)
                    report.ok = True
                    report.reason = (
                        f"completed at rung {ledger.rung} "
                        f"(world={topo.world}, devices={topo.devices})")
                    self._record("done", {"generation": generation,
                                          "rung": ledger.rung})
                    return report
                self._terminate(workers)
                workers = []
                if isinstance(action, Restart):
                    ledger.restarts_used += 1
                    report.restarts += 1
                    self._record("restart", {
                        "generation": generation, "reason": action.reason,
                        "delay_s": round(action.delay_s, 3),
                        "restarts_used": ledger.restarts_used})
                    if action.delay_s:
                        time.sleep(action.delay_s)
                    generation += 1
                    continue
                if isinstance(action, Rescale):
                    snap = self._snapshot(generation + 1)
                    if snap:
                        report.snapshots.append(snap)
                    ledger.rung += 1
                    report.rescales += 1
                    if action.evict_rank is not None:
                        report.evictions += 1
                        self._record("evict", {
                            "generation": generation,
                            "rank": action.evict_rank,
                            "reason": action.reason})
                    self._record("rescale", {
                        "generation": generation, "reason": action.reason,
                        "rung": ledger.rung,
                        "world": self.cfg.topologies[ledger.rung].world,
                        "devices":
                            self.cfg.topologies[ledger.rung].devices,
                        "snapshot": snap})
                    generation += 1
                    continue
                report.ok = False
                report.reason = action.reason
                self._record("fail", {"generation": generation,
                                      "reason": action.reason})
                return report
        finally:
            if workers:
                self._terminate(workers)
            # supervisor shutdown hygiene across ALL generations: no
            # service/ heartbeat rows may survive the run
            rec = _obs_flight._rec
            if rec is not None:
                for name in list(rec.heartbeats()):
                    if name.startswith("service/worker"):
                        rec.forget(name)


# ---------------------------------------------------------------------------
# built-in self-test worker (the gate / dryrun workload)
# ---------------------------------------------------------------------------


def selftest_data(n: int = 256, dim: int = 8,
                  seed: int = 0) -> tuple[np.ndarray, np.ndarray]:
    """The deterministic XOR dataset the self-test worker, the
    ``check_train_elastic`` gate, and the dryrun all share. ``n`` is a
    multiple of the gate's batch size, so the elastic walk has no
    partial tail batch (bit-compatible cross-topology replay)."""
    r = np.random.default_rng(seed)
    x = r.normal(size=(n, dim)).astype(np.float32)
    y = ((x[:, 0] > 0) ^ (x[:, 1] > 0)).astype(np.int64)
    return x, y


def selftest_config(checkpoint_dir: str | None) -> Any:
    """The self-test schedule: 2 passes over 256 rows at global batch 32
    → 16 steps, checkpoint every 5. Identical at every ladder rung (the
    fingerprint the resumed generation must match)."""
    from mmlspark_tpu.train.loop import TrainConfig
    return TrainConfig(batch_size=32, epochs=1, learning_rate=5e-3,
                       optimizer="momentum", log_every=1, seed=0,
                       donate_state=False, prefetch_depth=2,
                       checkpoint_dir=checkpoint_dir, checkpoint_every=5,
                       resume=True)


SELFTEST_EPOCH_PASSES = 2


def run_selftest_worker() -> int:
    """One supervised training worker: MLP on the shared XOR set through
    ``Trainer.fit_stream`` with :func:`elastic_stream` ingest, mesh
    ``dp×fsdp`` over whatever devices this generation granted. Supports
    induced preemption (``MMLSPARK_TPU_SERVICE_DIE_AT_STEP=<k>`` +
    ``MMLSPARK_TPU_SERVICE_DIE_GEN=<g>``: hard ``os._exit(75)`` after
    the walk yields ``k`` chunks in generation ``g`` — mid-training,
    no cleanup, like a preempted pod worker). Writes the loss history,
    final step, and full final params to ``result_gen<g>_rank<r>`` files
    for the bit-compat gate."""
    with service_context() as info:
        if info is None:
            raise SystemExit("not under a train service supervisor "
                             f"({ENV_DIR} unset)")
        import jax

        from mmlspark_tpu.utils.jit_cache import place_compilation_cache
        place_compilation_cache()
        # pin the platform only when the supervisor granted virtual
        # devices (Topology.devices set ⇒ JAX_PLATFORMS=cpu in our env);
        # a devices=None rung inherits the environment — real
        # accelerators on a TPU host
        plat = os.environ.get("JAX_PLATFORMS")
        if plat and info.devices is not None:
            try:
                jax.config.update("jax_platforms", plat)
            except Exception:  # pragma: no cover - backend already up
                pass
        if info.world > 1:
            from mmlspark_tpu.utils.env import distributed_init
            distributed_init()
        from mmlspark_tpu.models.zoo import MLP
        from mmlspark_tpu.parallel.mesh import MeshSpec, make_mesh
        from mmlspark_tpu.train.loop import Trainer

        n_dev = len(jax.devices())
        mesh = make_mesh(MeshSpec(
            dp=-1, fsdp=2 if n_dev % 2 == 0 else 1))
        cfg = selftest_config(info.checkpoint_dir)
        # a non-default data seed degrades the run on purpose (different
        # data → different trained params): how the lifecycle gate
        # manufactures a candidate whose answers drift from the fleet's
        x, y = selftest_data(seed=int(os.environ.get(
            "MMLSPARK_TPU_SERVICE_SELFTEST_DATA_SEED", "0")))

        die_at = int(os.environ.get("MMLSPARK_TPU_SERVICE_DIE_AT_STEP",
                                    "0"))
        die_gen = int(os.environ.get("MMLSPARK_TPU_SERVICE_DIE_GEN", "0"))
        die_rank = int(os.environ.get("MMLSPARK_TPU_SERVICE_DIE_RANK",
                                      "0"))
        die_here = (die_at and info.generation == die_gen
                    and info.rank == die_rank)
        base = elastic_stream(x, y, batch_size=cfg.batch_size,
                              seed=cfg.seed, epochs=SELFTEST_EPOCH_PASSES,
                              rank=info.rank, world=info.world)

        def source():
            for k, chunk in enumerate(base(), 1):
                if die_here and k > die_at:
                    os._exit(PREEMPT_EXIT_CODE)  # induced preemption
                yield chunk

        tr = Trainer(MLP(features=(16,), num_outputs=2), cfg, mesh=mesh)
        tr.fit_stream(source, input_spec=(x.shape[1],))

        steps = int(np.asarray(tr.state["step"]))

        def host_full(leaf):
            # a world>1 mesh fsdp-shards params ACROSS processes —
            # np.asarray on a non-addressable global array raises; gather
            # the full value first (replicated params pass straight through)
            if getattr(leaf, "is_fully_addressable", True):
                return np.asarray(leaf)
            from jax.experimental import multihost_utils
            return np.asarray(multihost_utils.process_allgather(
                leaf, tiled=True))

        flat = jax.tree_util.tree_flatten_with_path(tr.params)[0]
        params_path = os.path.join(
            info.service_dir,
            f"params_gen{info.generation}_rank{info.rank}.npz")
        np.savez(params_path, **{
            "/".join(str(getattr(k, "key", k)) for k in path):
                host_full(leaf) for path, leaf in flat})
        # the result write is the train→deployment-plane handoff: when a
        # publisher is listening (ENV_PUBLISH_FENCE) and the tracer is
        # on, bracket it in the publish-fence span — the supervisor's
        # Publisher brackets its read+gate+publish in the same span, so
        # the two processes' fleet exports stitch into one flow
        fence_cm = contextlib.nullcontext()
        if os.environ.get(ENV_PUBLISH_FENCE) and _obs_rt._enabled:
            from mmlspark_tpu.obs.spans import span as _obs_span
            from mmlspark_tpu.lifecycle.publish import PUBLISH_FENCE_SPAN
            fence_cm = _obs_span(PUBLISH_FENCE_SPAN, "lifecycle")
        with fence_cm:
            _atomic_write_json(info.result_path(), {
                "rank": info.rank, "world": info.world,
                "generation": info.generation, "devices": n_dev,
                "mesh": {a: int(s) for a, s in
                         zip(mesh.axis_names, mesh.devices.shape)},
                "steps": steps,
                "resumed": steps - len(tr.history),
                "history": [float(v) for v in tr.history],
                "params_npz": params_path,
            })
    return 0
