"""The fleet actuator: launch, watch, heal, and scale serve backends.

:class:`ServeSupervisor` is the serve-side sibling of
``train/service.py``'s :class:`TrainSupervisor`, built on the SAME
shared supervision core (``mmlspark_tpu/service/``): beacons are the
sensor transport (``atomic_write_json``/``read_beacon``,
generation-checked), :class:`SupervisedProcess` wraps each child with
its output pump, recovery runs through the train service's PURE
:class:`RecoveryPolicy` (restart-with-backoff, budgeted), and every
decision lands in ``decisions.jsonl`` via :class:`SupervisorJournal`
(mirrored as obs ``fleet/*`` events + ``serve.fleet.*`` counters when
the tracer is on).

What is serve-specific:

* the beacon carries a PORT — backends bind ephemerally and the beacon
  is how the supervisor learns the address it feeds the shared
  :class:`~mmlspark_tpu.serve.fleet.pool.BackendPool` (the router's
  routing table). A backend is routable the moment its first
  ``running`` beacon lands and unroutable the moment its process dies
  (``mark_down``) — the router's transport-failure evidence and the
  supervisor's exit-code evidence converge on the same table.
* restarts point the fresh process at the SAME compile cache
  (``MMLSPARK_TPU_COMPILE_CACHE``), so a respawned or scaled-up
  backend warms its whole bucket ladder from PR 15 AOT artifacts —
  zero fresh XLA compiles on the serving path (the fleet gate pins
  this off the beacon's cache stats).
* the autoscaling loop: each watch tick aggregates the beacons'
  SLO reads (PR 14 ``serve.slo_burn_*`` fast-window burn, occupancy)
  into a :class:`~mmlspark_tpu.obs.timeseries.MetricHistory`
  (``serve.fleet.burn_max`` / ``serve.fleet.occupancy_mean``), and
  :class:`~mmlspark_tpu.serve.fleet.scale.ScalePolicy` — pure, like
  every policy here — decides ScaleUp/ScaleDown/Hold. Scale-down is
  ZERO-DROP by construction: the victim is drained in the pool first
  (no new work routes to it, active :generate streams keep their
  affinity), and SIGTERM is sent only once its last lease/stream is
  gone; the worker then drains its own queue and exits 0.

Threading: ONE watch thread (``ServeFleetWatch``) owns all supervisor
state. The public surface (``scale_up``/``scale_down``/``close``)
enqueues typed commands under a ``named_lock`` witness — nothing
blocks under the lock (CC102), the watch thread is joined on close
(CC104).
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import threading
import time
from collections import deque
from typing import Sequence

from mmlspark_tpu.core.logging_utils import get_logger
from mmlspark_tpu.obs import fleet as _obs_fleet
from mmlspark_tpu.obs import runtime as _obs_rt
from mmlspark_tpu.obs.lockwitness import named_lock
from mmlspark_tpu.obs.metrics import registry as _obs_registry
from mmlspark_tpu.obs.timeseries import MetricHistory
from mmlspark_tpu.serve.fleet.pool import BackendPool
from mmlspark_tpu.serve.fleet.scale import (
    BURN_SERIES, OCCUPANCY_SERIES, FleetLedger, ScaleDown, ScalePolicy,
    ScaleUp, signal_from_history,
)
from mmlspark_tpu.service.core import (
    SupervisedProcess, SupervisorJournal, read_beacon,
    terminate_processes, join_pumps,
)
from mmlspark_tpu.train.service import (
    ENV_DIR, ENV_GENERATION, ENV_RANK, ENV_WORLD, Fail, Ledger, Proceed,
    RecoveryPolicy, Restart, WorkerExit, WorkerHang,
)

_log = get_logger(__name__)

WATCH_THREAD = "ServeFleetWatch"

# worker-side ServeConfig knobs the supervisor passes through the env
# (defined here, NOT in worker.py, so launching `-m ...fleet.worker`
# does not find the worker module pre-imported by the package __init__)
ENV_SLO = "MMLSPARK_TPU_SERVE_FLEET_SLO"
ENV_MAX_QUEUE = "MMLSPARK_TPU_SERVE_FLEET_MAX_QUEUE"
ENV_REPO = "MMLSPARK_TPU_SERVE_FLEET_REPO"  # model repo root: workers
#   serve every repo model's CURRENT version at boot and accept
#   versioned hot-swap commands from the lifecycle deployer's
#   deploy.json (serve/fleet/worker.py watches it each beacon tick)


def _default_worker_cmd() -> list[str]:
    return [sys.executable, "-m", "mmlspark_tpu.serve.fleet.worker"]


def _ensure_importable(env: dict) -> None:
    """Prepend the directory holding ``mmlspark_tpu`` to the child's
    ``PYTHONPATH`` so the default ``-m ...fleet.worker`` spawn resolves
    regardless of the caller's cwd (a CLI launched from a scratch dir
    imports the package off ``sys.path``, which children don't inherit)."""
    pkg_parent = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    prior = env.get("PYTHONPATH")
    if prior:
        if pkg_parent in prior.split(os.pathsep):
            return
        env["PYTHONPATH"] = pkg_parent + os.pathsep + prior
    else:
        env["PYTHONPATH"] = pkg_parent


@dataclasses.dataclass
class FleetConfig:
    """Supervisor configuration. ``cmd`` is one backend's argv (default:
    the built-in self-test worker the gate and bench use), launched once
    per backend with the shared ``MMLSPARK_TPU_SERVICE_*`` env contract
    — rank is the backend id, generation counts that backend's
    restarts."""

    service_dir: str
    cmd: Sequence[str] | None = None
    initial_backends: int = 2
    # preempt_exit_codes=(): a serve backend has no topology ladder to
    # re-scale down, so EVERY death takes the budgeted restart path
    policy: RecoveryPolicy = RecoveryPolicy(
        rescale_on_exhausted=False, preempt_exit_codes=())
    scale: ScalePolicy = dataclasses.field(default_factory=ScalePolicy)
    scale_window_s: float = 60.0  # history window the signal condenses
    poll_s: float = 0.1
    grace_s: float = 10.0
    beacon_timeout_s: float | None = 15.0  # alive-but-silent deadline
    start_grace_s: float | None = 120.0  # FIRST-beacon deadline: a cold
    #   backend pays jax import + (cache-miss) XLA compiles before it
    #   can beacon at all, so startup gets its own allowance — the
    #   beacon_timeout_s stall deadline applies once it has beaconed
    compile_cache: str | None = None       # → MMLSPARK_TPU_COMPILE_CACHE
    repo: str | None = None                # → ENV_REPO (lifecycle repo)
    slo: dict | None = None                # → worker ServeConfig.slo
    max_queue: int | None = None           # → worker ServeConfig.max_queue
    worker_obs: bool = True
    worker_fleet: bool = True  # propagate this process's fleet dir so
    #                            backends export serve.* telemetry into
    #                            the same plane (obs/fleet.py)
    extra_env: dict[str, str] = dataclasses.field(default_factory=dict)

    def __post_init__(self) -> None:
        if self.initial_backends < 1:
            raise ValueError("initial_backends must be >= 1: "
                             f"{self.initial_backends}")


class _Backend(SupervisedProcess):
    """One supervised backend process + its fleet-side bookkeeping."""

    def __init__(self, bid: int, proc: subprocess.Popen):
        super().__init__(bid, proc, log_prefix="fleet backend",
                         thread_name=f"{WATCH_THREAD}[pump{bid}]")
        self.generation = 0
        self.ledger = Ledger()   # per-backend restart budget
        self.draining = False    # scale-down in progress
        self.term_sent = False   # SIGTERM already delivered (drain)
        self.last_beacon_ts: float | None = None


@dataclasses.dataclass
class _Respawn:
    """A restart the policy granted, waiting out its backoff."""
    bid: int
    generation: int
    due: float  # monotonic
    ledger: Ledger


class ServeSupervisor:
    """Launch/watch/heal/scale the backend fleet (module docstring).

    ``start()`` spawns the initial backends and the watch thread;
    ``pool`` (shared with the :class:`FleetRouter`) is the live routing
    table this supervisor maintains. ``close()`` stops everything
    thread-clean."""

    def __init__(self, cfg: FleetConfig, pool: BackendPool | None = None):
        self.cfg = cfg
        self.pool = pool if pool is not None else BackendPool()
        os.makedirs(cfg.service_dir, exist_ok=True)
        self._journal = SupervisorJournal(
            os.path.join(cfg.service_dir, "decisions.jsonl"),
            event_prefix="fleet", cat="fleet",
            counter_prefix="serve.fleet.",
            counter_kinds=("spawn", "restart", "scale_up", "scale_down",
                           "backend_exit", "hang", "fail", "drained"),
            log_label="serve fleet")
        self.history = MetricHistory(maxlen=4096)
        self._backends: dict[int, _Backend] = {}  # watch-thread-owned
        self._respawns: list[_Respawn] = []
        self._next_bid = 0
        self._fleet_ledger = FleetLedger()
        self._last_scale: float | None = None  # monotonic
        self._cmd_lock = named_lock("serve.fleet.supervisor")
        self._commands: deque[str] = deque()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch,
                                        name=WATCH_THREAD, daemon=True)
        self._started = False
        self._closed = False

    # -- public surface (any thread): enqueue, never touch state --

    def start(self) -> "ServeSupervisor":
        if self._started:
            return self
        if self.cfg.initial_backends > 1:
            refusal = self._chip_refusal()
            if refusal:
                raise RuntimeError(
                    f"initial_backends={self.cfg.initial_backends}: "
                    + refusal)
        self._started = True
        for _ in range(self.cfg.initial_backends):
            self._spawn(self._alloc_bid(), generation=0, ledger=Ledger())
        self._thread.start()
        return self

    def scale_up(self) -> None:
        """Request one more backend (journaled as a manual scale-up)."""
        with self._cmd_lock:
            self._commands.append("scale_up")

    def scale_down(self) -> None:
        """Request a zero-drop drain of one backend."""
        with self._cmd_lock:
            self._commands.append("scale_down")

    def close(self) -> None:
        """Stop the watch thread, terminate every backend (SIGTERM →
        grace → kill), join the pumps. Idempotent."""
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=30.0)
        workers = list(self._backends.values())
        terminate_processes(workers, self.cfg.grace_s)
        join_pumps(workers)
        for b in workers:
            self.pool.remove(b.rank)
        self._backends.clear()
        self._journal.record("stop", {
            "backends": len(workers),
            "scale_ups": self._fleet_ledger.scale_ups,
            "scale_downs": self._fleet_ledger.scale_downs})

    def __enter__(self) -> "ServeSupervisor":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()

    def status(self) -> dict:
        """Point-in-time fleet view (CLI/debugging; the pool snapshot is
        the authoritative routing table). ``rollout`` condenses the
        beacon-reported served versions into the convergence view the
        lifecycle deployer blocks fleet-wide promotion on: a model is
        converged when every up backend serves the same repo version."""
        backends = self.pool.snapshot()
        by_model: dict[str, set] = {}
        for row in backends:
            if row["state"] != "up":
                continue
            for model, version in row["versions"].items():
                by_model.setdefault(model, set()).add(version)
        return {
            "backends": backends,
            "rollout": {
                model: {"converged": len(vs) == 1,
                        "versions": sorted(vs)}
                for model, vs in sorted(by_model.items())},
            "respawns_pending": len(self._respawns),
            "scale_ups": self._fleet_ledger.scale_ups,
            "scale_downs": self._fleet_ledger.scale_downs,
        }

    # -- spawn/respawn (watch thread, or start() before it runs) --

    def _chip_refusal(self) -> str | None:
        """Why this host cannot run a second backend process, or None.

        Backends are whole processes that each bring up the default JAX
        backend. On a TPU host that is the TPU, a chip belongs to one
        process at a time, and nothing here assigns chips to children —
        a second backend would hang or die fighting the first for them.
        Refuse loudly instead. (``JAX_PLATFORMS=cpu`` in the
        supervisor's environment or ``extra_env`` is the explicit way to
        rehearse a multi-backend fleet on such a host.) The supervisor
        finds this out from device files, never by touching a backend
        itself."""
        from mmlspark_tpu.utils.env import children_reach_tpu
        if not children_reach_tpu(self.cfg.extra_env):
            return None
        return ("this is a TPU host and every fleet backend process "
                "would claim its chips; one process per chip — run one "
                "backend per host (it can drive all the host's chips: "
                "--mesh dp=N), or set JAX_PLATFORMS=cpu to rehearse on "
                "virtual devices")

    def _alloc_bid(self) -> int:
        bid = self._next_bid
        self._next_bid += 1
        return bid

    def _spawn(self, bid: int, generation: int, ledger: Ledger) -> None:
        env = dict(os.environ)
        env.update(self.cfg.extra_env)
        env[ENV_DIR] = self.cfg.service_dir
        env[ENV_RANK] = str(bid)
        env[ENV_WORLD] = "1"  # backends are independent replicas, not
        #                       a mesh — no cross-process collectives
        env[ENV_GENERATION] = str(generation)
        if self.cfg.compile_cache:
            env["MMLSPARK_TPU_COMPILE_CACHE"] = self.cfg.compile_cache
        if self.cfg.repo:
            env[ENV_REPO] = self.cfg.repo
        if self.cfg.slo is not None:
            env[ENV_SLO] = json.dumps(self.cfg.slo)
        if self.cfg.max_queue is not None:
            env[ENV_MAX_QUEUE] = str(self.cfg.max_queue)
        if self.cfg.worker_obs:
            env.setdefault("MMLSPARK_TPU_OBS", "1")
        if self.cfg.worker_fleet:
            fdir = _obs_fleet.fleet_dir()
            if fdir:
                env.setdefault("MMLSPARK_TPU_FLEET", fdir)
        if self.cfg.cmd:
            cmd = list(self.cfg.cmd)
        else:
            cmd = _default_worker_cmd()
            _ensure_importable(env)
        proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True,
                                errors="replace")
        b = _Backend(bid, proc)
        b.generation = generation
        b.ledger = ledger
        self._backends[bid] = b
        self._journal.record("spawn", {
            "bid": bid, "generation": generation, "pid": proc.pid,
            "compile_cache": self.cfg.compile_cache})

    # -- the watch loop (single owner of all supervisor state) --

    def _watch(self) -> None:
        while not self._stop.wait(self.cfg.poll_s):
            try:
                self._drain_commands()
                self._reap_exits()
                self._run_respawns()
                self._read_beacons()
                self._step_drains()
                self._scale_tick()
            except Exception:  # pragma: no cover - the watch must
                _log.exception("serve fleet watch tick failed")  # survive

    def _drain_commands(self) -> None:
        while True:
            with self._cmd_lock:
                cmd = self._commands.popleft() if self._commands \
                    else None
            if cmd is None:
                return
            if cmd == "scale_up":
                self._execute_scale_up("manual scale_up request")
            elif cmd == "scale_down":
                self._execute_scale_down("manual scale_down request")

    def _reap_exits(self) -> None:
        for bid, b in list(self._backends.items()):
            code = b.proc.poll()
            if code is None or b.exit_recorded:
                continue
            b.exit_recorded = True
            was_routable = self.pool.mark_down(bid)
            self._journal.record("backend_exit", {
                "bid": bid, "generation": b.generation, "code": code,
                "draining": b.draining, "was_routable": was_routable})
            if b.draining:
                # the zero-drop drain completing: expected, clean
                self.pool.remove(bid)
                join_pumps([b])
                del self._backends[bid]
                self._journal.record("drained", {"bid": bid,
                                                 "code": code})
                continue
            action = self.cfg.policy.decide(WorkerExit(bid, code),
                                            b.ledger)
            if isinstance(action, Proceed):
                # exit 0 without a drain request is still capacity loss
                # — recover it, but keep it bounded by the same restart
                # budget so a clean-exit loop cannot spin forever
                if b.ledger.restarts_used < self.cfg.policy.max_restarts:
                    action = Restart("backend exited cleanly without a "
                                     "drain request", delay_s=0.5)
                else:
                    action = Fail("clean-exit loop; restart budget "
                                  f"({self.cfg.policy.max_restarts}) "
                                  "exhausted")
            self._apply_recovery(b, action)

    def _apply_recovery(self, b: _Backend, action) -> None:
        bid = b.rank
        join_pumps([b])
        del self._backends[bid]
        if isinstance(action, Restart):
            b.ledger.restarts_used += 1
            self._journal.record("restart", {
                "bid": bid, "reason": action.reason,
                "delay_s": round(action.delay_s, 3),
                "restarts_used": b.ledger.restarts_used,
                "generation": b.generation + 1})
            self._respawns.append(_Respawn(
                bid, b.generation + 1,
                time.monotonic() + action.delay_s, b.ledger))
            return
        # Fail (or any non-restart action a custom policy returns):
        # this backend stays down; the pool forgets it
        self.pool.remove(bid)
        self._journal.record("fail", {
            "bid": bid,
            "reason": getattr(action, "reason", repr(action))})

    def _run_respawns(self) -> None:
        now = time.monotonic()
        due = [r for r in self._respawns if r.due <= now]
        self._respawns = [r for r in self._respawns if r.due > now]
        for r in due:
            self._spawn(r.bid, r.generation, r.ledger)

    def _read_beacons(self) -> None:
        burns, occs = [], []
        now_mono = time.monotonic()
        # snapshot: a hang verdict mutates _backends via _apply_recovery
        for bid, b in list(self._backends.items()):
            if b.proc.poll() is not None:
                continue
            beacon = read_beacon(self.cfg.service_dir, bid, b.generation)
            if beacon is None or beacon.get("status") not in (
                    "running", "draining"):
                # alive but silent past the deadline → hang signal (the
                # baseline is spawn time via SupervisedProcess); a
                # backend that has NEVER beaconed is still booting and
                # gets the start grace instead of the stall deadline
                deadline = (self.cfg.start_grace_s
                            if b.last_beacon_ts is None
                            and self.cfg.start_grace_s is not None
                            else self.cfg.beacon_timeout_s)
                if (deadline is not None and not b.draining
                        and now_mono - b.progress_ts > deadline):
                    self._hang(b, now_mono - b.progress_ts)
                continue
            ts = beacon.get("ts")
            if ts != b.last_beacon_ts:
                b.last_beacon_ts = ts
                b.progress_ts = now_mono
            if beacon.get("status") == "running":
                # the beacon is the address channel: first beacon makes
                # the backend routable; a draining pool entry is never
                # resurrected by a late beacon (pool.add preserves it)
                self.pool.add(bid, str(beacon.get("host", "127.0.0.1")),
                              int(beacon.get("port", 0)),
                              generation=b.generation,
                              versions=beacon.get("versions"))
            if not b.draining:
                burns.append(float(beacon.get("burn_short", 0.0)))
                occs.append(float(beacon.get("occupancy", 0.0)))
        now = time.time()
        if burns:
            self.history.append(now, BURN_SERIES, max(burns))
        if occs:
            self.history.append(now, OCCUPANCY_SERIES,
                                sum(occs) / len(occs))
        if _obs_rt._enabled:
            reg = _obs_registry()
            reg.gauge("serve.fleet.backends").set(self.pool.up_count())
            if burns:
                reg.gauge(BURN_SERIES).set(max(burns))
            if occs:
                reg.gauge(OCCUPANCY_SERIES).set(sum(occs) / len(occs))

    def _hang(self, b: _Backend, stalled_s: float) -> None:
        bid = b.rank
        self.pool.mark_down(bid)
        self._journal.record("hang", {
            "bid": bid, "generation": b.generation,
            "stalled_s": round(stalled_s, 3)})
        action = self.cfg.policy.decide(WorkerHang(bid, stalled_s),
                                        b.ledger)
        terminate_processes([b], self.cfg.grace_s)
        b.exit_recorded = True
        self._apply_recovery(b, action)

    def _step_drains(self) -> None:
        """Advance zero-drop drains: SIGTERM a draining backend only
        once the pool shows its last lease/stream gone — the worker
        then drains its own queue and exits 0 (reaped as ``drained``)."""
        for b in self._backends.values():
            if (b.draining and not b.term_sent
                    and b.proc.poll() is None
                    and self.pool.idle(b.rank)):
                try:
                    b.proc.terminate()
                except OSError:  # pragma: no cover - exited just now
                    pass
                b.term_sent = True

    # -- autoscaling --

    def _live_count(self) -> int:
        """Backends the fleet counts as capacity: spawned and not
        draining (a pending respawn still owns its slot — a restart
        must not read as a capacity drop and trigger a scale-up)."""
        managed = sum(1 for b in self._backends.values()
                      if not b.draining)
        return managed + len(self._respawns)

    def _scale_tick(self) -> None:
        now_mono = time.monotonic()
        self._fleet_ledger.since_scale_s = (
            float("inf") if self._last_scale is None
            else now_mono - self._last_scale)
        sig = signal_from_history(
            self.history, now=time.time(), backends=self._live_count(),
            policy=self.cfg.scale, window_s=self.cfg.scale_window_s)
        action = self.cfg.scale.decide(sig, self._fleet_ledger)
        if isinstance(action, ScaleUp):
            self._execute_scale_up(action.reason)
        elif isinstance(action, ScaleDown):
            self._execute_scale_down(action.reason)

    def _execute_scale_up(self, reason: str) -> None:
        refusal = self._chip_refusal() if self._live_count() else None
        if refusal:
            _log.error("fleet scale-up refused: %s", refusal)
            self._journal.record("scale_up_refused",
                                 {"reason": reason, "detail": refusal})
            self._last_scale = time.monotonic()  # cooldown, no retry storm
            return
        bid = self._alloc_bid()
        self._journal.record("scale_up", {
            "bid": bid, "reason": reason,
            "backends": self._live_count()})
        self._spawn(bid, generation=0, ledger=Ledger())
        self._fleet_ledger.scale_ups += 1
        self._last_scale = time.monotonic()

    def _execute_scale_down(self, reason: str) -> None:
        # victim: the least-loaded up backend (the cheapest zero-drop
        # drain); ties break toward the NEWEST bid so the original
        # fleet core is the last to go
        candidates = [s for s in self.pool.snapshot()
                      if s["state"] == "up"
                      and s["bid"] in self._backends
                      and not self._backends[s["bid"]].draining]
        if not candidates:
            self._journal.record("scale_down_skipped",
                                 {"reason": reason,
                                  "detail": "no drainable backend"})
            return
        victim = min(candidates,
                     key=lambda s: (s["inflight"] + s["streams"],
                                    -s["bid"]))["bid"]
        self.pool.drain(victim)
        self._backends[victim].draining = True
        self._journal.record("scale_down", {
            "bid": victim, "reason": reason,
            "backends": self._live_count()})
        self._fleet_ledger.scale_downs += 1
        self._last_scale = time.monotonic()
