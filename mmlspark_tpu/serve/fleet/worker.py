"""The runnable backend serve worker the fleet supervisor launches.

``python -m mmlspark_tpu.serve.fleet.worker`` under the supervisor's
env contract (the SAME ``MMLSPARK_TPU_SERVICE_*`` contract as train
workers — the shared ``mmlspark_tpu/service`` core reads the beacons
either way):

* builds the deterministic self-test CNN (the ``check_compile_cache``
  model: seeded ``get_model`` → bit-identical params in every process,
  so every backend computes bit-identical answers — the property the
  fleet gate pins through the router),
* serves it over HTTP on an EPHEMERAL port (the beacon, not the env,
  carries the port back to the supervisor — no port-allocation race),
* publishes a liveness beacon each interval with the bound port, the
  SLO burn/occupancy excerpt (the autoscaler's sensors), a ``serve.*``
  counter excerpt (the fleet-merge pin's per-backend truth), and the
  compile-cache stats (how the gate proves a scaled-up backend warmed
  from the PR 15 cache with zero fresh XLA compiles),
* on SIGTERM: beacon ``draining``, zero-drop drain
  (``ModelServer.close(drain=True)`` — queued work finishes), beacon
  ``exited``, exit 0.

The compile cache arrives via ``MMLSPARK_TPU_COMPILE_CACHE`` (honored
by ``ServeConfig(compile_cache=None)``); the SLO spec via
``MMLSPARK_TPU_SERVE_FLEET_SLO`` (a JSON dict of ``SLOSpec`` fields —
the gate tightens the windows so induced burn shows within a beacon
interval or two).
"""

from __future__ import annotations

import json
import os
import signal
import threading
import time

import numpy as np

from mmlspark_tpu.core.logging_utils import get_logger
from mmlspark_tpu.serve.fleet.supervisor import ENV_MAX_QUEUE, \
    ENV_REPO, ENV_SLO

_log = get_logger(__name__)

DEPLOY_FILE = "deploy.json"  # the lifecycle deployer's command channel

MODEL_NAME = "cnn"
SELFTEST_BUCKETS = (1, 8)
ROW_DIM = 32 * 32 * 3

GEN_NAME = "lm"
GEN_VOCAB = 48
GEN_T_MAX = 64


def selftest_bundle():
    """The fleet's deterministic serve workload: the seeded ConvNet the
    ``check_compile_cache`` gate already proves bit-identical and
    cache-warmable across processes."""
    from mmlspark_tpu.models.zoo import get_model
    return get_model("ConvNet_CIFAR10", widths=(8, 16), dense_width=32)


def selftest_rows(n: int, seed: int = 7) -> np.ndarray:
    """Deterministic uint8 image rows (the dtype the model is warmed
    with — same program family on every backend)."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, 255, (n, ROW_DIM)).astype(np.uint8)


def selftest_generator():
    """A seeded causal toy LM for the ``:generate`` surface: PRNGKey(0)
    init → bit-identical params (and greedy decodes) in every backend,
    the same determinism contract as the CNN."""
    import jax
    from mmlspark_tpu.models.sequence import TransformerTagger

    model = TransformerTagger(vocab_size=GEN_VOCAB, embed_dim=16,
                              num_heads=2, num_layers=2, mlp_dim=32,
                              num_tags=GEN_VOCAB, max_len=GEN_T_MAX,
                              causal=True)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, 8), np.int32))["params"]
    return model, params


def build_server():
    """The worker's ModelServer: self-test CNN + toy causal LM, (1, 8)
    ladder, SLO from the env. Shared with the bench/gate reference
    instance so "router answer == single-process answer" compares
    equals against equals."""
    from mmlspark_tpu.data.table import DataTable
    from mmlspark_tpu.models.jax_model import JaxModel
    from mmlspark_tpu.serve import GenerateConfig, ModelServer, \
        ServeConfig

    slo = None
    raw = os.environ.get(ENV_SLO)
    if raw:
        slo = json.loads(raw)
    cfg = ServeConfig(
        buckets=SELFTEST_BUCKETS, deadline_ms=None, slo=slo,
        max_queue=int(os.environ.get(ENV_MAX_QUEUE, "128")))
    server = ModelServer(cfg)
    jm = JaxModel(model=selftest_bundle(), input_col="image",
                  output_col="scores")
    server.add_model(MODEL_NAME, jm,
                     example=DataTable({"image": [selftest_rows(1)[0]]}))
    gen_model, gen_params = selftest_generator()
    server.add_generator(GEN_NAME, gen_model, gen_params,
                         config=GenerateConfig(
                             slots=4, t_max=GEN_T_MAX,
                             prefill_buckets=(4, 8), prefill_rows=2,
                             max_new_tokens=16, max_queue=64))
    repo_root = os.environ.get(ENV_REPO)
    if repo_root:
        _serve_repo_models(server, repo_root)
    return server


def _serve_repo_models(server, repo_root: str) -> None:
    """Serve every repo model's CURRENT version (digest-verified by
    ``add_model_from_repo``; a ModelBundle auto-wraps to a JaxModel with
    the bundle's own input/output columns). A model that fails to load
    is skipped with a warning — one corrupt publish must not keep the
    whole backend from coming up; the beacon's ``versions`` map simply
    won't list it, which the deployer reads as non-convergence."""
    from mmlspark_tpu.models.repo import ModelRepo

    repo = ModelRepo(repo_root)
    for name in repo.models():
        try:
            server.add_model_from_repo(repo, name)
        except Exception as e:
            _log.warning("fleet backend: repo model %r skipped: %s",
                         name, e)


class _DeployWatcher:
    """Apply versioned hot-swap commands from the lifecycle deployer.

    The deployer (``lifecycle/deployer.py`` :class:`FleetTarget`) writes
    ``<service_dir>/deploy.json`` — ``{"seq", "model", "version",
    "repo", "backends"}`` — atomically; each backend polls it every
    beacon interval and applies each NEW seq addressed to it (scope
    ``"all"`` or an explicit bid list) via ``add_model_from_repo``:
    digests verify before anything deserializes, the flip is the
    server's own zero-drop swap. A failed apply is reported in the
    beacon (``deploy_error``) and NOT retried for the same seq — the
    beacon's ``versions`` map stays on the old version, the deployer
    reads that as non-convergence and its policy decides (hold until
    ``max_stage_ticks``, then abort → rollback)."""

    def __init__(self, info, server):
        self.info = info
        self.server = server
        self.path = os.path.join(info.service_dir, DEPLOY_FILE)
        self.seq = 0
        self.error: str | None = None

    def poll(self) -> None:
        try:
            with open(self.path, encoding="utf-8") as f:
                cmd = json.load(f)
            seq = int(cmd.get("seq", 0))
        except (OSError, ValueError, TypeError):
            return
        if seq <= self.seq:
            return
        self.seq = seq
        scope = cmd.get("backends")
        if scope != "all" and self.info.rank not in (scope or ()):
            return
        try:
            self.server.add_model_from_repo(
                str(cmd["repo"]), str(cmd["model"]),
                version=int(cmd["version"]))
            self.error = None
            _log.info("fleet backend %d: deploy seq %d → %s v%d",
                      self.info.rank, seq, cmd["model"],
                      int(cmd["version"]))
        except Exception as e:
            self.error = f"{type(e).__name__}: {e}"
            _log.warning("fleet backend %d: deploy seq %d failed: %s",
                         self.info.rank, seq, self.error)

    def describe(self) -> dict:
        out: dict = {"deploy_seq": self.seq}
        if self.error:
            out["deploy_error"] = self.error
        return out


def _beacon_sample(info, server, port: int, status: str,
                   deploy: _DeployWatcher | None = None) -> dict:
    """One beacon payload: identity + port + the autoscaler's sensors
    + the fleet-merge counter excerpt + compile-cache stats + the
    served ``{model: repo version}`` map (the deployer's rollout-
    convergence sensor)."""
    from mmlspark_tpu.core import compile_cache as _cc
    from mmlspark_tpu.obs.metrics import Counter as _ObsCounter
    from mmlspark_tpu.obs.metrics import registry as _obs_registry

    sample: dict = {
        "rank": info.rank, "pid": os.getpid(),
        "generation": info.generation,
        "ts": time.time(), "status": status,
        "host": "127.0.0.1", "port": port,
        "model": MODEL_NAME,
        "burn_short": 0.0, "occupancy": 0.0,
        "counters": [], "compile_cache": None,
        "versions": {},
    }
    if deploy is not None:
        sample.update(deploy.describe())
    try:
        sample["versions"] = {
            name: snap["version"]
            for name, snap in server.snapshot().items()
            if isinstance(snap, dict) and "version" in snap}
    except Exception:  # pragma: no cover - beacon never kills the worker
        pass
    try:
        # each beacon is one SLO sample per model (registry reads only)
        # — the sampling cadence that feeds the supervisor's
        # MetricHistory, mirroring how /slo polls drive it in-process
        slo = server.slo_snapshot()
        burns = [m.get("burn_rate_short") for m in slo.values()
                 if isinstance(m, dict)]
        occs = [m.get("occupancy_mean") for m in slo.values()
                if isinstance(m, dict)]
        sample["burn_short"] = max(
            (b for b in burns if b is not None), default=0.0)
        sample["occupancy"] = max(
            (o for o in occs if o is not None), default=0.0)
    except Exception:  # pragma: no cover - beacon never kills the worker
        pass
    try:
        for reg in [_obs_registry()] + server.metric_registries():
            for m in reg.iter_metrics():
                if isinstance(m, _ObsCounter) \
                        and m.name.startswith("serve."):
                    sample["counters"].append(
                        [m.name, dict(m.labels), m.value])
        cache = _cc.active()
        if cache is not None:
            sample["compile_cache"] = dict(cache.stats)
    except Exception:  # pragma: no cover
        pass
    return sample


def run_backend_worker(beacon_interval_s: float = 0.25) -> int:
    """The worker main: serve until SIGTERM, beaconing all the while."""
    from mmlspark_tpu.service.core import atomic_write_json
    from mmlspark_tpu.serve.http import start_http_server
    from mmlspark_tpu.train.service import ServiceWorkerInfo

    info = ServiceWorkerInfo.from_env()
    if info is None:
        raise SystemExit("not under a fleet supervisor "
                         "(MMLSPARK_TPU_SERVICE_DIR unset)")
    from mmlspark_tpu.utils.jit_cache import place_compilation_cache
    place_compilation_cache()
    os.makedirs(info.service_dir, exist_ok=True)
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())

    server = build_server()
    deploy = _DeployWatcher(info, server)
    httpd = start_http_server(server, host="127.0.0.1", port=0,
                              identity=f"backend-{info.rank}")
    port = int(httpd.server_address[1])
    _log.info("fleet backend %d (gen %d) serving on 127.0.0.1:%d",
              info.rank, info.generation, port)
    try:
        while not stop.wait(beacon_interval_s):
            deploy.poll()
            try:
                atomic_write_json(
                    info.beacon_path(),
                    _beacon_sample(info, server, port, "running",
                                   deploy=deploy))
            except Exception:  # pragma: no cover - beacon never kills
                pass           # the worker it reports on
        # zero-drop drain: announce, stop admitting, finish what's
        # queued/in flight, then the terminal beacon
        atomic_write_json(info.beacon_path(),
                          _beacon_sample(info, server, port, "draining",
                                         deploy=deploy))
        server.close(drain=True)
    finally:
        httpd.shutdown()
        httpd.server_close()
        try:
            atomic_write_json(info.beacon_path(),
                              _beacon_sample(info, server, port,
                                             "exited", deploy=deploy))
        except Exception:  # pragma: no cover - best-effort terminal
            pass
    return 0


if __name__ == "__main__":
    raise SystemExit(run_backend_worker())
