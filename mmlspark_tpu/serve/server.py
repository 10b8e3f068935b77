"""ModelServer — load, validate, warm, and serve fitted models.

Load path: a served model is any fitted table→table transformer
(``PipelineModel``, ``JaxModel``, …) or a raw :class:`ModelBundle` (wrapped
in a ``JaxModel`` on the spot). Every load runs the PR 2 pre-flight
analyzer first — a model that cannot survive ``analysis.analyze`` fails
the load with :class:`ModelLoadError` *before any device work* (no
compile, no transfer), mirroring transformSchema-at-submit in the
reference. Loads with a concrete input schema (given, or derived from the
bundle's ``input_spec``) also warm the bucket ladder: one compiled program
per (model, bucket) exists before the first request arrives.
"""

from __future__ import annotations

import copy
import dataclasses
import threading
from typing import Any, Iterable, Mapping

import numpy as np

from mmlspark_tpu.core.logging_utils import get_logger
from mmlspark_tpu.core.retry import RetryPolicy, call_with_retry
from mmlspark_tpu.data.table import DataTable
from mmlspark_tpu.obs.lockwitness import named_lock
from mmlspark_tpu.serve.batcher import DynamicBatcher, ServeRequest
from mmlspark_tpu.serve.config import ServeConfig
from mmlspark_tpu.serve.errors import (
    BadRequest, DeadlineExceeded, LaneFailed, ModelLoadError,
    ModelNotFound, Overloaded, ServeError, ServerClosed,
)
from mmlspark_tpu.serve.stats import ServerStats

_log = get_logger(__name__)


def _as_stages(model: Any) -> tuple[list, Any, Any]:
    """(stage list, cache_host, model) for any servable object.

    A ``ModelBundle`` is wrapped in a ``JaxModel`` reading column
    ``"input"`` and writing ``"scores"`` (the CLI's bundle-file path);
    a ``PipelineModel`` serves its fitted stages through its own
    compiled-segment cache, so online and offline execution share one
    compile + param upload.
    """
    from mmlspark_tpu.models.bundle import ModelBundle
    if isinstance(model, ModelBundle):
        from mmlspark_tpu.models.jax_model import JaxModel
        model = JaxModel(model=model, input_col="input",
                         output_col="scores")
    stages = getattr(model, "stages", None)
    if stages is not None and not callable(stages):
        return list(stages), model, model
    if not hasattr(model, "transform"):
        raise BadRequest(
            f"not a servable model: {type(model).__name__} (needs "
            ".transform or a ModelBundle)")
    return [model], model, model


def _derived_schema(stages: list) -> Any | None:
    """A concrete input schema derivable from the model itself: a leading
    ``JaxModel`` pins its input column to the bundle's ``input_spec``
    (as the flat vector ``coerce_input_matrix`` accepts)."""
    from mmlspark_tpu.analysis.info import ColumnInfo, TableSchema
    from mmlspark_tpu.models.jax_model import JaxModel
    if not stages or not isinstance(stages[0], JaxModel):
        return None
    bundle = stages[0].model
    if bundle is None:
        return None
    size = int(np.prod(tuple(bundle.input_spec)))
    return TableSchema({stages[0].input_col: ColumnInfo.vector(
        size, "float32")})


def _example_rows(schema: Any, n: int) -> DataTable | None:
    """Synthesize an ``n``-row table realizing ``schema`` — the warmup
    input. None when any column's layout is not concrete enough to build
    honest rows (warmup is then skipped; first request pays the compile)."""
    from mmlspark_tpu.analysis.info import (
        KIND_IMAGE, KIND_SCALAR, KIND_TEXT, KIND_VECTOR,
    )
    cols: dict[str, Any] = {}
    meta: dict[str, dict] = {}
    for name, info in schema.columns.items():
        if info.kind == KIND_IMAGE:
            shape = info.concrete_shape
            if shape is None or len(shape) != 3:
                return None
            from mmlspark_tpu.core.schema import make_image
            cols[name] = [make_image(f"warmup{i}",
                                     np.zeros(shape, np.uint8))
                          for i in range(n)]
            meta[name] = {"is_image": True}
        elif info.kind == KIND_VECTOR:
            size = info.row_size
            if size is None:
                return None
            dt = np.uint8 if info.dtype == "uint8" else np.float32
            cols[name] = [np.zeros(size, dt) for _ in range(n)]
        elif info.kind == KIND_SCALAR:
            dt = np.dtype(info.dtype or "float64")
            cols[name] = np.zeros(n, dt)
        elif info.kind == KIND_TEXT:
            cols[name] = [""] * n
        else:
            return None
    if not cols:
        return None
    table = DataTable(cols)
    for name, m in meta.items():
        table = table.with_meta(name, **m)
    return table


# the ONE parity read both the load-time low-precision calibration and
# the shadow-canary drift signal use (serve/lifecycle.py), so their
# tolerances mean the same thing
from mmlspark_tpu.serve.lifecycle import (  # noqa: E402
    max_abs_parity as _max_abs_parity,
)


class _ModelEntry:
    def __init__(self, name: str, model: Any, batcher: DynamicBatcher,
                 schema: Any | None, mesh_spec: Any | None = None,
                 slo: Any = None, health: Any = None,
                 precision: Any = None, parity: float | None = None,
                 version: Any = None):
        self.name = name
        self.model = model
        self.batcher = batcher
        self.schema = schema
        self.mesh_spec = mesh_spec
        self.slo = slo          # obs.slo.SLOTracker
        self.health = health    # obs.health.HealthMonitor
        self.precision = precision  # core.precision.PrecisionPolicy | None
        self.parity = parity    # measured max-abs vs f32 offline at load
        self.version = version  # model-repo version (or caller tag)
        self.canary: Any = None  # serve.lifecycle.CanaryState | None
        # the load call's kwargs, kept so a ladder rollout
        # (ModelServer.apply_ladder) can rebuild this entry identically
        # except for the bucket ladder
        self.load_kwargs: dict = {}
        # adaptive-ladder re-fit policy (lazy; ModelServer.ladder_tick)
        self.ladder_advisor: Any = None


class _GeneratorEntry:
    """One registered token-serving engine: the autoregressive analog of
    :class:`_ModelEntry`. No lanes, no canary — the engine owns its one
    decode loop; SLO sampling rides the same tracker machinery so
    ``/slo`` carries TTFT/ITL burn next to the batch models."""

    def __init__(self, name: str, engine: Any, slo: Any):
        self.name = name
        self.engine = engine    # serve.generate.GenerateBatcher
        self.slo = slo          # obs.slo.SLOTracker


class ModelServer:
    """Serves one or more fitted models through per-model dynamic batchers.

    Thread-safe: :meth:`submit`/:meth:`predict` may be called from any
    number of client threads (the HTTP front end is one such client).
    """

    def __init__(self, config: ServeConfig | None = None):
        from mmlspark_tpu.serve.lifecycle import DecisionJournal
        self.config = config or ServeConfig()
        if self.config.compile_cache:
            # persistent AOT compile cache (process-wide, like the obs
            # pillars): every model this server loads serializes its
            # compiled bucket programs to disk, and a later cold
            # process deserializes them instead of re-compiling. An
            # unwritable dir degrades to a warning inside configure()
            from mmlspark_tpu.core import compile_cache as _cc
            _cc.configure(self.config.compile_cache)
        self._models: dict[str, _ModelEntry] = {}
        self._generators: dict[str, _GeneratorEntry] = {}
        self._lock = named_lock("serve.server.ModelServer._lock")
        self._closed = False
        # lifecycle forensics: swap/canary/promote/rollback and lane
        # death/restart decisions — decisions.jsonl on disk when
        # ServeConfig.lifecycle_dir is set, always the in-memory tail
        self.journal = DecisionJournal(self.config.lifecycle_dir)
        # fleet plane: per-model stats registries ride the process's
        # telemetry snapshots (and the timeseries sampler) so the
        # serve.* series aggregate across the fleet; unregistered on
        # close — a dead server's registries must not keep exporting
        from mmlspark_tpu.obs import fleet as _obs_fleet
        _obs_fleet.add_registry_source(self.metric_registries)

    # -- loading --

    def _build_entry(self, name: str, model: Any,
                     schema: Any | None = None,
                     example: DataTable | None = None,
                     mesh: Any = None, shard_params: Any = None,
                     precision: Any = None, version: Any = None,
                     buckets: Any = None) -> _ModelEntry:
        """Validate, shard, warm, and calibrate one servable — the
        whole load path SHORT of registration, shared by
        :meth:`add_model` (stable loads and hot-swaps) and
        :meth:`deploy_canary` (candidate versions warming concurrently
        with live traffic). Returns a running, warmed entry that is not
        yet routed any requests; on any failure its batcher is closed
        before the raise (no leaked dispatch threads).

        1. **Validate** with the pre-flight analyzer over ``schema`` (or a
           schema derived from the model's own input contract, or an
           inexact empty schema) — error diagnostics raise
           :class:`ModelLoadError` before any device work.
        2. **Shard** (optional): ``mesh`` (or the server-wide
           ``ServeConfig.mesh``) selects the model's serving tier —
           ``dp=N`` replica fan-out and/or ``tp``/``pp`` model-parallel
           sub-meshes (:mod:`mmlspark_tpu.serve.mesh`); ``shard_params``
           optionally overrides every replica's param placement
           (``(mesh, params_tuple) → shardings``). A mesh that does
           not divide the host's device count, or a sharded segment that
           violates its SPMD contract (manual collectives on a dp
           replica; off-contract axes under tp/pp), is a typed
           :class:`ModelLoadError` — still before any device work.
        3. **Resolve precision** (optional): ``precision`` (or the
           server-wide ``ServeConfig.precision``) selects the serving
           :class:`~mmlspark_tpu.core.precision.PrecisionPolicy` —
           ``"bf16"`` activations or ``"int8w"`` weight-only int8, both
           folded into the compile-cache key so every (model, precision)
           owns its own program ladder and device param tree.
        4. **Warm** the bucket ladder when concrete example rows are
           available (``example``, or rows synthesized from the schema):
           one compiled program per bucket exists before the first
           request, on EVERY replica.
        5. **Calibrate** (low-precision loads): the quantized program's
           outputs on the sample batch are measured against the f32
           offline transform; drift past the policy's pinned tolerance
           is a typed :class:`ModelLoadError` (docs/quantization.md).
        6. **Start** the model's dispatch loop (one lane per replica).

        ``buckets`` overrides the server-wide ladder for THIS entry (a
        per-model learned ladder — :meth:`apply_ladder`); the entry's
        batcher, warmup, and calibration all run on the override.
        """
        from mmlspark_tpu.analysis import TableSchema, analyze
        from mmlspark_tpu.core.precision import PrecisionPolicy

        cfg = self.config
        if buckets is not None:
            from mmlspark_tpu.serve.ladder import validate_ladder
            try:
                ladder = validate_ladder(buckets)
            except ValueError as e:
                raise ModelLoadError(name, message=(
                    f"model {name!r}: {e}")) from e
            cfg = dataclasses.replace(self.config, buckets=ladder)
        stages, cache_host, model = _as_stages(model)
        try:
            policy = PrecisionPolicy.parse(
                precision if precision is not None
                else self.config.precision)
        except (TypeError, ValueError) as e:
            raise ModelLoadError(name, message=(
                f"model {name!r}: invalid precision policy: {e}")) from e
        if policy is not None and not policy.active:
            policy = None  # f32 = the unwrapped fast path
        if schema is None:
            schema = _derived_schema(stages)
        check_schema = schema if schema is not None \
            else TableSchema({}, exact=False)
        report = analyze(model, check_schema)
        if not report.ok:
            raise ModelLoadError(name, report)

        mesh = mesh if mesh is not None else self.config.mesh
        replicas = lockstep = mesh_spec = None
        if mesh is not None:
            from mmlspark_tpu.serve.mesh import (
                LockstepCoordinator, ServeMeshSpec, build_replicas,
            )
            mesh_spec = ServeMeshSpec.parse(mesh)
            if mesh_spec.lockstep and mesh_spec.dp > 1:
                # lockstep drains every lane before each agreed dispatch,
                # so extra DP replicas could never serve a batch — they'd
                # only cost dp× warm compiles and param HBM. Typed error
                # beats silently serializing a fan-out the caller paid for.
                raise ModelLoadError(name, message=(
                    f"model {name!r}: lockstep serving dispatches one "
                    f"agreed batch at a time, which is incompatible with "
                    f"dp={mesh_spec.dp} replica fan-out — use dp=1 for "
                    f"lockstep models, or drop lockstep for DP scaling"))
            replicas = build_replicas(name, mesh_spec,
                                      shard_params=shard_params)
            self._audit_sharded(name, stages, schema, mesh_spec, replicas,
                                policy)
            # lockstep only on request: build_replicas carves sub-meshes
            # of THIS host's devices, so no serve program today contains
            # a cross-process collective — auto-enabling on process
            # count would fence (and allgather-stall) multi-host
            # processes that serve independent local traffic. The flag
            # exists for callers that feed every process the identical
            # stream (the dryrun harness; a future cross-process mesh).
            if mesh_spec.lockstep:
                lockstep = LockstepCoordinator(name)

        # SLO tracker + health monitor: burn rates over the stats
        # registry (reads only — obs/slo.py), the hysteretic
        # ok/degraded/unhealthy machine over them (obs/health.py).
        # Sampling is on-demand (each /slo, /healthz, or slo_snapshot
        # poll), so an unpolled server pays nothing. The spec parses
        # BEFORE the batcher exists: a malformed ServeConfig.slo must
        # fail the load without leaking dispatch threads
        from mmlspark_tpu.obs.health import HealthMonitor
        from mmlspark_tpu.obs.slo import SLOSpec, SLOTracker
        try:
            spec = SLOSpec.parse(cfg.slo)
        except (TypeError, ValueError) as e:
            raise ModelLoadError(name, message=(
                f"model {name!r}: invalid SLO spec: {e}")) from e
        stats = ServerStats(
            cfg.stats_window, model=name,
            extra_labels=None if version is None
            else {"version": version})
        batcher = DynamicBatcher(name, stages, cache_host, cfg,
                                 stats, replicas=replicas,
                                 lockstep=lockstep, precision=policy)
        # lane supervision lands in the lifecycle journal: a death or
        # restart is a capacity decision, same forensics as a swap
        batcher.on_lane_event = self.journal.record
        tracker = SLOTracker(spec, stats,
                             queued_fn=lambda: batcher.queued)
        monitor = HealthMonitor.for_spec(spec)
        parity = None
        try:
            if cfg.warmup:
                warm = example
                if warm is None and schema is not None:
                    warm = _example_rows(schema, 1)
                if warm is not None and len(warm):
                    import time as _time
                    t0 = _time.perf_counter()
                    self._warm(batcher, warm)
                    # the warm-start observable: wall seconds to bring
                    # the whole ladder up (XLA compiles when cold,
                    # compile-cache deserializes when warm) — the
                    # serve.warm_wall_s gauge bench A/Bs
                    stats.record_warm_wall(_time.perf_counter() - t0)
                else:
                    _log.info("serve[%s]: no concrete input layout — "
                              "skipping warmup (first request per bucket "
                              "pays the compile)", name)
            parity = self._calibrate(name, model, batcher, policy,
                                     example, schema)
        except BaseException:
            batcher.close(drain=False)
            raise
        return _ModelEntry(name, model, batcher, schema, mesh_spec,
                           slo=tracker, health=monitor, precision=policy,
                           parity=parity, version=version)

    def add_model(self, name: str, model: Any,
                  schema: Any | None = None,
                  example: DataTable | None = None,
                  mesh: Any = None, shard_params: Any = None,
                  precision: Any = None, version: Any = None,
                  buckets: Any = None) -> None:
        """Register ``model`` under ``name`` (see :meth:`_build_entry`
        for the validate → shard → warm → calibrate load path).

        Re-registering a served name is the **hot-swap**: the new
        version loads and warms its whole bucket ladder while the live
        version keeps serving (compiles release the GIL — the PR 7 warm
        discipline), then the name flips to the new entry atomically
        and the old batcher drains — every request admitted before the
        flip is answered by the version that admitted it, and
        :meth:`submit` re-routes the flip race, so no request is ever
        dropped by a swap (``check_serve_lifecycle`` pins this).
        ``version`` tags the entry (the model-repo version, or any
        caller label): it labels the per-version stats registry and the
        journal's swap records. ``buckets`` pins a per-model ladder
        (:meth:`apply_ladder` rolls a learned one out through this same
        path)."""
        entry = self._build_entry(name, model, schema=schema,
                                  example=example, mesh=mesh,
                                  shard_params=shard_params,
                                  precision=precision, version=version,
                                  buckets=buckets)
        entry.load_kwargs = dict(schema=schema, example=example,
                                 mesh=mesh, shard_params=shard_params,
                                 precision=precision, version=version)
        old = canary = None
        with self._lock:
            closed = self._closed
            if not closed:
                old = self._models.get(name)
                if old is not None:
                    # the outgoing version's canary (if any) dies with
                    # it: a swap supersedes an in-flight rollout
                    canary, old.canary = old.canary, None
                self._models[name] = entry
        if closed:
            # teardown outside self._lock: close() joins lane threads,
            # and holding the server-wide lock across those joins would
            # stall every concurrent submit/snapshot (CC102)
            entry.batcher.close(drain=False)
            raise ServerClosed("server is closed",
                               retry_after_s=self.config.retry_after_s)
        if old is not None:
            if canary is not None:
                canary.batcher.close(drain=True)
            old.batcher.close(drain=True)
            self.journal.record("swap", {
                "model": name, "from_version": old.version,
                "to_version": version,
                "canary_superseded": canary is not None})
        _log.info("serve[%s]: loaded (buckets=%s, mesh=%s, "
                  "precision=%s, version=%s)", name,
                  entry.batcher.config.buckets,
                  entry.mesh_spec.describe() if entry.mesh_spec
                  else "default",
                  entry.precision.describe() if entry.precision
                  else "f32", version)

    def add_model_from_repo(self, repo: Any, name: str,
                            version: int | None = None,
                            schema: Any | None = None,
                            example: DataTable | None = None,
                            **kwargs: Any) -> Any:
        """Load ``name`` from a versioned
        :class:`~mmlspark_tpu.models.repo.ModelRepo` (a repo object or
        its root path) and serve it — the repo's digests verify before
        anything deserializes, so a torn or corrupt version raises the
        repo's typed error here and a currently-served version keeps
        serving untouched. Returns the verified ``ModelVersion``."""
        from mmlspark_tpu.models.repo import ModelRepo
        if isinstance(repo, str):
            repo = ModelRepo(repo)
        model, info = repo.load(name, version)
        self.add_model(name, model, schema=schema, example=example,
                       version=info.version, **kwargs)
        return info

    # -- adaptive bucket ladder (serve/ladder.py) --

    def apply_ladder(self, name: str, buckets: Any) -> None:
        """Roll a new bucket ladder out for ``name`` through the
        hot-swap path: the entry rebuilds with the new ladder (warming
        it — with the persistent compile cache live, the new rungs
        deserialize from disk instead of paying XLA compiles), then the
        name flips atomically and the old batcher drains. Zero requests
        dropped, by the same contract as a version swap; the top rung
        must equal the current max bucket so nothing admissible becomes
        inadmissible mid-flight. Journaled as a ``"ladder"`` decision."""
        from mmlspark_tpu.serve.ladder import validate_ladder
        entry = self._entry(name)
        old = entry.batcher.config.buckets
        new = validate_ladder(buckets)
        if new[-1] != old[-1]:
            raise ValueError(
                f"model {name!r}: ladder rollout must keep the top rung "
                f"{old[-1]} (got {new[-1]}) — shrinking it would refuse "
                f"requests the server admitted a moment ago")
        advisor = entry.ladder_advisor
        self.add_model(name, entry.model, buckets=new,
                       **entry.load_kwargs)
        cur = self._entry(name)
        cur.ladder_advisor = advisor  # policy state survives the flip
        self.journal.record("ladder", {
            "model": name, "from_buckets": list(old),
            "to_buckets": list(new)})

    def ladder_tick(self, name: str, budget: int | None = None,
                    advisor: Any = None) -> dict | None:
        """One adaptive-ladder evaluation for ``name``: fit a ladder to
        the observed request-size histogram (``serve.request_rows``)
        under the program budget (default: the current rung count — the
        ``programs <= len(buckets)`` discipline) and, when the window
        is SLO-clean and the fit beats the current ladder by the
        advisor's margin, roll it out via :meth:`apply_ladder`.
        On-demand like ``lifecycle_tick``: polling this is the re-fit
        cadence. Returns the decision dict, or None (no change)."""
        from mmlspark_tpu.obs.health import OK
        from mmlspark_tpu.serve.ladder import LadderAdvisor
        entry = self._entry(name)
        if advisor is not None:
            entry.ladder_advisor = advisor
        elif entry.ladder_advisor is None:
            entry.ladder_advisor = LadderAdvisor()
        _status, health = self._sample_model_health(entry)
        current = entry.batcher.config.buckets
        fitted = entry.ladder_advisor.propose(
            entry.batcher.stats.request_sizes(), current,
            slo_clean=(health["state"] == OK and not health["draining"]),
            budget=budget)
        if fitted is None:
            return None
        self.apply_ladder(name, fitted)
        return {"action": "ladder", "model": name,
                "from_buckets": list(current),
                "to_buckets": list(fitted)}

    def _audit_sharded(self, name: str, stages: list, schema: Any,
                       mesh_spec: Any, replicas: Any,
                       policy: Any = None) -> None:
        """Static SPMD gate for a sharded serve entry, at load time.

        The served segment runs on every replica's sub-mesh, so it must
        honor the sharded-serving contract *before* any compile: a
        DP-replica segment stays manual-collective-free (replicas are
        independent — a collective would deadlock the fan-out), and a
        tp/pp model-parallel segment may communicate only over its
        model-parallel axes, never ``dp``. A low-precision load audits
        the QUANTIZED composite (``policy`` threads into the plan
        replay), so the verified program is the dispatched one. Needs a
        concrete entry layout; a model with no derivable schema skips
        the audit (the analyzer already passed) and relies on the
        repo-wide ``check_spmd_clean`` gate."""
        if schema is None or not replicas.replicas:
            return
        from mmlspark_tpu.analysis.spmd import audit_plan_spmd
        from mmlspark_tpu.serve.mesh import MODEL_PARALLEL_AXES

        import jax

        expect_axes = (tuple(a for a in MODEL_PARALLEL_AXES)
                       if mesh_spec.model_parallel else None)
        try:
            audit = audit_plan_spmd(stages, schema.entry_meta,
                                    mesh=replicas.replicas[0].mesh,
                                    expect_axes=expect_axes,
                                    precision=policy)
        except jax.errors.JAXTypeError as e:
            # a stage body that needs concrete values cannot be traced
            # over ShapeDtypeStructs: not a verdict. Anything else (a
            # verifier bug, a changed jaxpr layout) must fail the load —
            # a safety check that skips itself on error fails open
            _log.warning("serve[%s]: sharded SPMD audit skipped, stage "
                         "not abstractly traceable (%s)", name, e)
            return
        if not audit.ok:
            raise ModelLoadError(name, message=(
                f"model {name!r} failed the sharded-serving SPMD audit "
                f"on mesh {mesh_spec.describe()}:\n" + audit.format()))

    def _warm(self, batcher: DynamicBatcher, example: DataTable) -> None:
        """Compile every bucket by running one padded batch per rung
        through the SAME dispatch path requests take. The rungs come
        from the BATCHER's config — a per-model ladder override warms
        its own ladder, not the server-wide default."""
        row = example.take(np.arange(1))
        for bucket in batcher.config.buckets:
            padded = row if bucket == 1 else row.concat(
                row.take(np.zeros(bucket - 1, dtype=np.int64)))
            batcher.warm(padded)

    def _calibrate(self, name: str, model: Any, batcher: DynamicBatcher,
                   policy: Any, example: DataTable | None,
                   schema: Any) -> float | None:
        """Measured max-abs parity of a low-precision serve program vs
        the f32 offline transform, on the calibration batch (the caller's
        ``example`` sample, else one schema-synthesized row). Weight
        scales need no activation statistics (symmetric per-channel
        max-abs over the weights themselves); what IS calibrated from
        data is the *observed* output drift, checked against the
        policy's pinned tolerance — drift past it fails the load with a
        typed :class:`ModelLoadError` before the model ever serves.
        Returns the measured parity (None when no policy is active or no
        concrete rows exist to calibrate with)."""
        if policy is None:
            return None
        calib = example
        if calib is None and schema is not None:
            calib = _example_rows(schema, 1)
        if calib is None or not len(calib):
            _log.info("serve[%s]: no calibration rows — %s parity "
                      "unverified at load (first requests trust the "
                      "pinned tolerance)", name, policy.describe())
            return None
        n = min(len(calib), batcher.config.max_bucket)
        calib = calib.take(np.arange(n))
        bucket = batcher.config.bucket_for(n, name)
        padded = calib if bucket == n else calib.take(
            np.arange(bucket) % n)
        try:
            # the f32 offline path, on a shallow copy that owns no
            # compiled-segment store: the reference program and its f32
            # parameters go with it, not resident in the served model's
            ref = copy.copy(model).transform(calib)
            got = batcher.probe(padded)           # the served program
        except BaseException as e:
            raise ModelLoadError(name, message=(
                f"model {name!r}: {policy.describe()} calibration run "
                f"failed: {type(e).__name__}: {e}")) from e
        if len(got) != len(padded):  # row-changing transform: serving
            #                          rejects it per batch anyway
            _log.info("serve[%s]: calibration transform changed the row "
                      "count — parity unverified", name)
            return None
        parity = _max_abs_parity(ref, got.take(np.arange(n)),
                                 set(calib.columns))
        tol = policy.resolve_tolerance()
        if parity is not None and parity > tol:
            raise ModelLoadError(name, message=(
                f"model {name!r}: {policy.describe()} serving diverges "
                f"from the f32 offline transform by max-abs {parity:.4g} "
                f"on the {n}-row calibration batch (pinned tolerance "
                f"{tol:g}) — pin a wider per-model tolerance explicitly "
                "or serve at a wider precision"))
        return parity

    # -- request surface --

    def _entry(self, name: str) -> _ModelEntry:
        with self._lock:
            entry = self._models.get(name)
            if entry is None:
                raise ModelNotFound(name, list(self._models))
            return entry

    def submit(self, name: str, table: DataTable,
               deadline_ms: float | None = None) -> ServeRequest:
        """Admit a request; returns the awaitable handle. ``deadline_ms``
        defaults to the server-wide ``ServeConfig.deadline_ms``.

        Swap-safe: a hot-swap that closes the old batcher between this
        call's entry lookup and its admission re-routes to the entry
        that now owns the name (the zero-dropped-requests contract) —
        ``ServerClosed`` only propagates when the SERVER is closing or
        the model is gone. With a rollout in flight, the canary's
        deterministic router takes its configured fraction: mirrored
        (shadow — the stable answer is returned either way) or split
        (canary — those requests get the candidate's answers)."""
        if deadline_ms is None:
            deadline_ms = self.config.deadline_ms
        while True:
            entry = self._entry(name)
            canary = entry.canary
            take = canary is not None and canary.route()
            if take and canary.mode == "canary":
                try:
                    return canary.batcher.submit(table, deadline_ms)
                except ServerClosed:
                    pass  # rolled back mid-flight: stable serves it
            try:
                req = entry.batcher.submit(table, deadline_ms)
            except ServerClosed:
                with self._lock:
                    closed = self._closed
                    cur = self._models.get(name)
                if closed or cur is None or cur is entry:
                    raise
                continue  # hot-swap raced us: retry on the new entry
            if take and canary.mode == "shadow":
                try:
                    mirror = canary.batcher.submit(table, deadline_ms)
                    canary.note_pair(req, mirror)
                except ServeError:
                    # a shadow must never affect the stable path: a
                    # mirror bounced by canary admission (overload,
                    # rollback race) is burn-visible in the canary
                    # stats, nothing more
                    pass
            return req

    def predict(self, name: str, table: DataTable,
                deadline_ms: float | None = None,
                timeout: float | None = None) -> DataTable:
        """Blocking submit+wait."""
        return self.submit(name, table, deadline_ms).result(timeout)

    # -- autoregressive token serving (serve/generate.py) --

    def add_generator(self, name: str, model: Any, params: Any,
                      config: Any = None,
                      decode_attention_fn: Any = None) -> None:
        """Register an autoregressive token-serving engine under
        ``name``: a causal :class:`~mmlspark_tpu.models.sequence.
        TransformerTagger` (+ its fitted params) served through
        continuous batching with the KV cache as plan-managed device
        state (:class:`~mmlspark_tpu.serve.generate.GenerateBatcher`).

        Generators share the server's SLO machinery — an
        :class:`~mmlspark_tpu.obs.slo.SLOTracker` over the engine's
        :class:`ServerStats` publishes the per-token gauges
        (``serve.ttft_p50_ms``/``serve.ttft_p99_ms``/
        ``serve.itl_p99_ms``) on every ``/slo`` poll, and the engine's
        registry rides ``/metrics`` and the fleet exporter. The name
        space is shared with batch models: one name, one servable."""
        from mmlspark_tpu.obs.slo import SLOSpec, SLOTracker
        from mmlspark_tpu.serve.config import GenerateConfig
        from mmlspark_tpu.serve.generate import GenerateBatcher
        cfg = config or GenerateConfig()
        try:
            spec = SLOSpec.parse(self.config.slo)
        except (TypeError, ValueError) as e:
            raise ModelLoadError(name, message=(
                f"generator {name!r}: invalid SLO spec: {e}")) from e
        stats = ServerStats(cfg.stats_window, model=name)
        engine = GenerateBatcher(name, model, params, config=cfg,
                                 stats=stats,
                                 decode_attention_fn=decode_attention_fn)
        tracker = SLOTracker(spec, stats,
                             queued_fn=lambda: engine.queued)
        entry = _GeneratorEntry(name, engine, tracker)
        reject: Exception | None = None
        old = None
        with self._lock:
            if self._closed:
                reject = ServerClosed("server is closed")
            elif name in self._models:
                reject = ModelLoadError(name, message=(
                    f"{name!r} already serves a batch model — one name, "
                    f"one servable"))
            else:
                old = self._generators.get(name)
                self._generators[name] = entry
        if reject is not None:
            engine.close(drain=False)
            raise reject
        if old is not None:
            # re-registration is the generator hot-swap: drained, so
            # every admitted stream is answered by the engine that
            # admitted it
            old.engine.close(drain=True)
            self.journal.record("swap", {"model": name, "generator": True})
        _log.info("serve[%s]: generator loaded (slots=%d, "
                  "prefill_buckets=%s, t_max=%d)", name, cfg.slots,
                  cfg.prefill_buckets, cfg.t_max)

    def _generator(self, name: str) -> _GeneratorEntry:
        with self._lock:
            entry = self._generators.get(name)
            if entry is None:
                raise ModelNotFound(name, list(self._generators))
            return entry

    def generate(self, name: str, prompt: Any,
                 max_new_tokens: int | None = None) -> Any:
        """Admit a generation request on generator ``name``; returns the
        :class:`~mmlspark_tpu.serve.generate.TokenStream` (iterate for
        tokens as they decode, or ``.result()`` for the full list)."""
        return self._generator(name).engine.submit(
            prompt, max_new_tokens=max_new_tokens)

    def generate_oneshot(self, name: str, prompt: Any,
                         max_new_tokens: int | None = None) -> list[int]:
        """Whole-sequence reference decode of one prompt through
        generator ``name``'s OWN compiled programs
        (:meth:`~mmlspark_tpu.serve.generate.GenerateBatcher.oneshot`,
        fresh buffers, engine state untouched) — the bit-identity anchor
        every continuously-batched stream is pinned against."""
        return self._generator(name).engine.oneshot(
            prompt, max_new_tokens=max_new_tokens)

    def generators(self) -> list[str]:
        with self._lock:
            return sorted(self._generators)

    # -- rollout: canary/shadow + SLO-driven promotion (lifecycle.py) --

    def deploy_canary(self, name: str, model: Any,
                      mode: str = "shadow", fraction: float = 0.25,
                      version: Any = None, schema: Any | None = None,
                      example: DataTable | None = None,
                      mesh: Any = None, shard_params: Any = None,
                      precision: Any = None, policy: Any = None,
                      parity_tolerance: float | None = None,
                      promote_after: int = 3) -> None:
        """Start a rollout of ``model`` as ``name``'s candidate version.

        The candidate goes through the full load path (validate, warm
        its own bucket ladder, calibrate) while the stable version keeps
        serving; from then on the configured ``fraction`` of admissions
        is mirrored (``mode="shadow"``: clients still get stable
        answers, outputs are diffed) or split (``mode="canary"``: those
        clients get candidate answers). Each :meth:`lifecycle_tick`
        samples the candidate's burn engine (+ shadow parity vs
        ``parity_tolerance``) and runs ``policy``
        (:class:`~mmlspark_tpu.serve.lifecycle.PromotionPolicy`,
        default derived from the server's SLO spec): fast-burn or
        parity drift auto-rolls back, ``promote_after`` consecutive
        clean windows promote the candidate to stable. Every decision
        is journaled."""
        from mmlspark_tpu.obs.slo import SLOSpec
        from mmlspark_tpu.serve.lifecycle import (
            CanaryState, PromotionPolicy,
        )
        stable = self._entry(name)  # ModelNotFound before any build
        # everything cheap validates BEFORE the expensive build: a bad
        # mode/fraction/policy must not leave a fully warmed candidate
        # batcher running with no owner
        if mode not in ("canary", "shadow"):
            raise ValueError(
                f"canary mode must be 'canary' or 'shadow': {mode!r}")
        if not 0.0 < fraction <= 1.0:
            raise ValueError(
                f"canary fraction must be in (0, 1]: {fraction}")
        if policy is None:
            policy = PromotionPolicy.for_spec(
                SLOSpec.parse(self.config.slo), promote_after)
        entry = self._build_entry(name, model, schema=schema,
                                  example=example, mesh=mesh,
                                  shard_params=shard_params,
                                  precision=precision, version=version)
        try:
            state = CanaryState(name, version, mode, fraction,
                                entry.batcher, entry.slo, policy,
                                parity_tolerance=parity_tolerance)
        except ValueError:
            entry.batcher.close(drain=False)
            raise
        state.entry = entry  # promotion flips this whole entry in
        reject: Exception | None = None
        replaced = None
        with self._lock:
            if self._closed:
                reject = ServerClosed("server is closed")
            else:
                cur = self._models.get(name)
                if cur is None:
                    reject = ModelNotFound(name, list(self._models))
                else:
                    replaced, cur.canary = cur.canary, state
        if reject is not None:
            # close the never-attached batcher outside self._lock —
            # close() joins lane threads (CC102 under the server lock)
            entry.batcher.close(drain=False)
            raise reject
        if replaced is not None:
            replaced.batcher.close(drain=True)
        self.journal.record("canary_deploy", {
            "model": name, "version": version, "mode": mode,
            "fraction": fraction,
            "stable_version": stable.version,
            "replaced": None if replaced is None else replaced.version})

    def lifecycle_tick(self, name: str) -> dict | None:
        """One promotion-policy evaluation for ``name``'s rollout (None
        when no canary is deployed): sample the canary's SLO burn + the
        shadow-parity ring into a typed signal, run the pure policy,
        execute the action. On-demand like every PR 8 sampler — polling
        this (or ``/slo``) IS the rollout's evaluation cadence."""
        entry = self._entry(name)
        canary = entry.canary
        if canary is None:
            return None
        with canary.tick_lock:
            result, drain = self._tick_locked(name, entry, canary)
        if drain is not None:
            # drain outside tick_lock: close(drain=True) joins lane
            # threads for the full drain, and holding tick_lock across
            # it would block every concurrent tick/rollback (CC102) —
            # the detach under self._lock already made the decision
            # exactly-once, so racers see a detached canary and bail
            drain.close(drain=True)
        return result

    def _tick_locked(self, name: str, entry: _ModelEntry,
                     canary: Any) -> tuple:
        """One policy evaluation under ``canary.tick_lock``; returns
        ``(result, batcher_to_drain)`` — the caller performs the drain
        after releasing the lock."""
        from mmlspark_tpu.serve.lifecycle import Hold, Promote, Rollback
        if entry.canary is not canary:
            return None, None  # a concurrent tick already decided
        sig = canary.signal()
        action = canary.policy.decide(sig, canary.ledger)
        canary.ledger.ticks += 1
        detail = {
            "model": name, "version": canary.version,
            "mode": canary.mode, "reason": action.reason,
            "burn_short": sig.burn_short, "burn_long": sig.burn_long,
            "terminal_window": sig.terminal_window,
            "parity_drift": sig.parity_drift,
            "clean_windows": canary.ledger.clean_windows,
            "ticks": canary.ledger.ticks,
        }
        if isinstance(action, Rollback):
            drain = self._end_canary(entry, canary, "rollback", detail)
            if drain is not None:
                return {"action": "rollback", **detail}, drain
            return None, None  # a racing close()/swap already detached it
        if isinstance(action, Promote):
            drain = self._promote(entry, canary, detail)
            if drain is not None:
                return {"action": "promote", **detail}, drain
            return None, None
        assert isinstance(action, Hold)
        canary.ledger.clean_windows = (
            canary.ledger.clean_windows + 1 if action.clean else 0)
        detail["clean_windows"] = canary.ledger.clean_windows
        self.journal.record("hold", detail)
        return {"action": "hold", **detail}, None

    def rollback(self, name: str, reason: str = "manual") -> dict | None:
        """Abort ``name``'s rollout now (the operator's big red
        button); None when no canary is deployed."""
        entry = self._entry(name)
        canary = entry.canary
        if canary is None:
            return None
        detail = {"model": name, "version": canary.version,
                  "mode": canary.mode, "reason": reason}
        drain = self._end_canary(entry, canary, "rollback", detail)
        if drain is not None:
            drain.close(drain=True)
            return {"action": "rollback", **detail}
        return None

    def promote(self, name: str, reason: str = "manual") -> dict | None:
        """Promote ``name``'s candidate to stable now — the flip an
        external rollout driver (the lifecycle Deployer) commands once
        its own policy is satisfied, same atomic entry-swap as a
        burn-engine promotion; None when no canary is deployed."""
        entry = self._entry(name)
        canary = entry.canary
        if canary is None:
            return None
        detail = {"model": name, "version": canary.version,
                  "mode": canary.mode, "reason": reason}
        drain = self._promote(entry, canary, detail)
        if drain is not None:
            drain.close(drain=True)
            return {"action": "promote", **detail}
        return None

    def _end_canary(self, entry: _ModelEntry, canary: Any,
                    kind: str, detail: dict) -> Any | None:
        """Atomically detach the canary; returns its batcher for the
        caller to drain with no lock held (None when another thread's
        decision already detached it — exactly one rollback/promote
        ever executes per rollout)."""
        with self._lock:
            if entry.canary is not canary:
                return None
            entry.canary = None
        self.journal.record(kind, {**detail, **canary.describe()})
        return canary.batcher

    def _promote(self, entry: _ModelEntry, canary: Any,
                 detail: dict) -> Any | None:
        """The candidate becomes stable: its (already warm) entry takes
        the name atomically, the outgoing stable drains — the same flip
        as a hot-swap, decided by the burn engine instead of an
        operator.  Returns the outgoing stable's batcher for the caller
        to drain with no lock held (None when a racing close()/swap won)."""
        with self._lock:
            if self._closed or entry.canary is not canary \
                    or self._models.get(entry.name) is not entry:
                # a racing close() owns teardown of whatever is still
                # attached — installing the promoted entry after close
                # snapshots would leak its batcher threads forever
                return None
            entry.canary = None
            promoted = canary.entry
            promoted.canary = None
            self._models[entry.name] = promoted
        self.journal.record("promote", {
            **detail, "from_version": entry.version,
            **canary.describe()})
        return entry.batcher

    def canary_status(self, name: str) -> dict | None:
        entry = self._entry(name)
        return None if entry.canary is None else entry.canary.describe()

    def lifecycle_decisions(self, kind: str | None = None) -> list[dict]:
        """The in-memory decision tail (``decisions.jsonl`` carries the
        same records on disk when ``lifecycle_dir`` is set)."""
        return self.journal.entries(kind)

    # -- introspection --

    def models(self) -> list[str]:
        with self._lock:
            return sorted(self._models)

    def stats(self, name: str) -> ServerStats:
        return self._entry(name).batcher.stats

    def compiled_programs(self, name: str) -> int | None:
        return self._entry(name).batcher.compiled_programs()

    def snapshot(self) -> dict:
        """All models' stats in one JSON-safe dict (the /v1/stats body)."""
        with self._lock:
            entries = list(self._models.values())
            gens = list(self._generators.values())
        out = {}
        for g in gens:
            snap = g.engine.stats.snapshot()
            snap["queued"] = g.engine.queued
            programs = g.engine.compiled_programs()
            if programs is not None:
                snap["programs_compiled"] = programs
            snap["generator"] = True
            out[g.name] = snap
        for e in entries:
            snap = e.batcher.stats.snapshot()
            snap["queued"] = e.batcher.queued
            programs = e.batcher.compiled_programs()
            if programs is not None:
                snap["programs_compiled"] = programs
            if e.mesh_spec is not None:
                snap["mesh"] = e.mesh_spec.describe()
            if e.precision is not None:
                snap["precision"] = e.precision.describe()
                if e.parity is not None:
                    snap["precision_parity"] = e.parity
            if e.version is not None:
                snap["version"] = e.version
            snap["lane_health"] = e.batcher.lane_health()
            canary = e.canary
            if canary is not None:
                snap["canary"] = {
                    **canary.describe(),
                    **{f"stats_{k}": v for k, v in
                       canary.batcher.stats.snapshot().items()
                       if k in ("admitted", "completed", "failed",
                                "timed_out", "rejected_overload")},
                }
            out[e.name] = snap
        return out

    def metric_registries(self) -> list:
        """Every per-model stats registry (plus nothing else) — what the
        HTTP front end hands to the Prometheus exposition alongside the
        process-wide obs registry."""
        with self._lock:
            out = []
            for g in self._generators.values():
                out.append(g.engine.stats.registry)
            for e in self._models.values():
                out.append(e.batcher.stats.registry)
                if e.canary is not None:
                    # the candidate's per-version series (distinct
                    # version label) scrape alongside the stable's
                    out.append(e.canary.batcher.stats.registry)
            return out

    # -- SLO + health surfaces (obs/slo.py + obs/health.py) --

    def _sample_model_health(self, e) -> tuple[dict, dict]:
        """One SLO sample + health-machine advance for one model:
        (status dict, health dict). The single place the per-model
        health shape is built — ``/slo`` and ``/healthz`` must never
        diverge on it. Lane supervision merges in here: a model with a
        dispatch lane down is at least DEGRADED — restarted-but-
        shrunken capacity must show on the health surface, not hide
        behind still-clean latency percentiles."""
        from mmlspark_tpu.obs.health import DEGRADED, SEVERITY
        status = e.slo.sample()
        verdict = e.health.update_describe(status)
        lanes = e.batcher.lane_health()
        state, reason = verdict["state"], verdict["reason"]
        if lanes["alive"] < lanes["lanes"] \
                and SEVERITY[state] < SEVERITY[DEGRADED]:
            down = lanes["lanes"] - lanes["alive"]
            state = DEGRADED
            reason = (f"{down}/{lanes['lanes']} dispatch lane(s) down "
                      f"({lanes['restarts']} restart(s) used)")
        return status, {"state": state, "reason": reason,
                        "draining": e.batcher.closed, "lanes": lanes}

    def slo_snapshot(self) -> dict:
        """Sample every model's SLO tracker and advance its health
        machine; the JSON-safe ``/slo`` body. Each call is one burn-rate
        sample per model (registry reads only — no device work, no
        batcher locks beyond the queue-depth read), so polling this IS
        the sampling cadence — INCLUDING the rollout loop: a model with
        a canary deployed gets one :meth:`lifecycle_tick` per poll, so
        an HTTP-only operator's ``/slo`` probes drive auto-rollback/
        promotion without any in-process caller (the decision, if any,
        rides along under ``"lifecycle"``)."""
        with self._lock:
            entries = list(self._models.values())
            gens = list(self._generators.values())
        out = {}
        for g in gens:
            # a generator's SLO sample carries the per-token gauges
            # (TTFT/ITL percentiles published into its registry) next
            # to the shared burn-rate machinery
            out[g.name] = {**g.slo.sample(), "generator": True}
        for e in entries:
            decision = None
            if e.canary is not None:
                decision = self.lifecycle_tick(e.name)
            status, health = self._sample_model_health(e)
            body = {**status, "health": health}
            if decision is not None:
                body["lifecycle"] = decision
            out[e.name] = body
        return out

    def health(self) -> dict:
        """Drain-aware readiness: the ``/healthz`` body.

        ``status`` is the worst model health state (``ok`` with no
        models — an empty server is a healthy server), ``draining``
        reflects server-wide close, and ``ready`` is the load-balancer
        verdict: accepting traffic AND not unhealthy. The HTTP layer
        maps ``ready`` to 200/503."""
        from mmlspark_tpu.obs.health import UNHEALTHY, worst
        with self._lock:
            closed = self._closed
            entries = list(self._models.values())
        model_health = {}
        for e in entries:
            _status, model_health[e.name] = self._sample_model_health(e)
        overall = worst([h["state"] for h in model_health.values()])
        draining = closed or any(h["draining"]
                                 for h in model_health.values())
        return {
            "status": "draining" if closed else overall,
            "ready": not draining and overall != UNHEALTHY,
            "draining": draining,
            "models": sorted(model_health),
            "model_health": model_health,
        }

    # -- lifecycle --

    def close(self, drain: bool = True) -> None:
        """Shut down every model's batcher. ``drain=True`` (default)
        answers all admitted requests first; no threads survive."""
        from mmlspark_tpu.obs import fleet as _obs_fleet
        _obs_fleet.remove_registry_source(self.metric_registries)
        with self._lock:
            self._closed = True
            entries = list(self._models.values())
            gens = list(self._generators.values())
        for g in gens:
            g.engine.close(drain=drain)
        for e in entries:
            canary, e.canary = e.canary, None
            if canary is not None:
                canary.batcher.close(drain=drain)
            e.batcher.close(drain=drain)

    def __enter__(self) -> "ModelServer":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()


# what a client-side retry may NEVER retry, regardless of the policy it
# was handed: an expired deadline is the caller's latency budget spent
# (retrying busts it by construction), and a malformed request or
# unknown model will fail identically every time
_NEVER_RETRY = (DeadlineExceeded, BadRequest, ModelNotFound)

#: the ``retry=True`` policy: transient serving faults only —
#: ``Overloaded`` (admission backpressure: back off and re-offer) and
#: ``LaneFailed`` (a dispatch lane died mid-flight; the supervisor
#: restarts it, a retry lands on healthy capacity)
DEFAULT_PREDICT_RETRY = RetryPolicy(
    max_attempts=3, base_delay_s=0.05, max_delay_s=2.0,
    retry_on=(Overloaded, LaneFailed))


def _retry_policy(retry: Any) -> RetryPolicy | None:
    """Coerce the ``retry=`` argument (None/False = off, True = the
    default policy, or a caller ``RetryPolicy``) and pin the
    never-retry guard INTO the predicate — a caller policy with
    ``retry_on=(ServeError,)`` still cannot re-spend an expired
    deadline or replay a bad request."""
    if retry is None or retry is False:
        return None
    policy = DEFAULT_PREDICT_RETRY if retry is True else retry
    orig = policy.retry_if
    return dataclasses.replace(
        policy,
        retry_if=lambda e: not isinstance(e, _NEVER_RETRY)
        and (orig is None or orig(e)))


class Client:
    """In-process client: the deterministic test/bench surface, mirroring
    what the HTTP front end does without sockets.

    ``retry`` (per call, or a client-wide default) retries TRANSIENT
    serving faults through :mod:`mmlspark_tpu.core.retry` — by default
    ``Overloaded`` backpressure and ``LaneFailed`` lane deaths, with
    jittered exponential backoff. ``DeadlineExceeded``/``BadRequest``/
    ``ModelNotFound`` are never retried (enforced even against a
    broader caller policy). Each attempt is a fresh submission with a
    fresh ``deadline_ms`` budget."""

    def __init__(self, server: ModelServer, retry: Any = None):
        self.server = server
        self._retry = retry

    def predict(self, model: str,
                rows: DataTable | Iterable[Mapping[str, Any]],
                deadline_ms: float | None = None,
                columns: Iterable[str] | None = None,
                timeout: float | None = None,
                retry: Any = None) -> DataTable:
        if not isinstance(rows, DataTable):
            rows = DataTable.from_rows(list(rows))
        policy = _retry_policy(retry if retry is not None
                               else self._retry)
        if policy is None:
            out = self.server.predict(model, rows, deadline_ms, timeout)
        else:
            out = call_with_retry(
                lambda: self.server.predict(model, rows, deadline_ms,
                                            timeout), policy)
        if columns is not None:
            out = out.select(*columns)
        return out

    def predict_async(self, model: str,
                      rows: DataTable | Iterable[Mapping[str, Any]],
                      deadline_ms: float | None = None,
                      retry: Any = None) -> ServeRequest:
        """Async submit; ``retry`` covers the SUBMISSION (admission
        backpressure) only — once a handle exists, waiting on it is the
        caller's, and retrying a dispatched request would risk the
        double-response the whole pipeline is built to never produce."""
        if not isinstance(rows, DataTable):
            rows = DataTable.from_rows(list(rows))
        policy = _retry_policy(retry if retry is not None
                               else self._retry)
        if policy is None:
            return self.server.submit(model, rows, deadline_ms)
        return call_with_retry(
            lambda: self.server.submit(model, rows, deadline_ms), policy)

    def generate(self, model: str, prompt: Iterable[int],
                 max_new_tokens: int | None = None,
                 stream: bool = False,
                 timeout: float | None = None,
                 retry: Any = None) -> Any:
        """Token generation on a registered generator. ``stream=True``
        returns the :class:`~mmlspark_tpu.serve.generate.TokenStream`
        (iterate for tokens as they decode); the default blocks for the
        full token list. ``retry`` covers ADMISSION only (the same
        contract as :meth:`predict_async` — a stream that exists is
        never resubmitted)."""
        policy = _retry_policy(retry if retry is not None
                               else self._retry)
        prompt = list(prompt)
        if policy is None:
            handle = self.server.generate(model, prompt, max_new_tokens)
        else:
            handle = call_with_retry(
                lambda: self.server.generate(model, prompt,
                                             max_new_tokens), policy)
        return handle if stream else handle.result(timeout)
