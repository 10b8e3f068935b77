"""serve — online model-server CLI.

Usage::

    python tools/serve.py <model-path> [--name NAME] [--host H] [--port P]
        [--buckets 1,8,32,128] [--max-queue N] [--deadline-ms D]
        [--mesh dp=N[,tp=M][,pp=K]] [--schema schema.json] [--no-warmup]
        [--obs] [--fleet DIR] [--slo-objective 0.999]
        [--slo-latency-ms P99_MS] [--compile-cache DIR]

``<model-path>`` is any of

* a directory saved with ``stage.save()`` (``metadata.json`` inside) — a
  ``PipelineModel`` or any fitted transformer;
* a single ``ModelBundle`` file (``tools/build_model_repo.py`` output) —
  wrapped in a ``JaxModel`` reading column ``input``, writing ``scores``;
* a model *repository* directory (``MANIFEST.json`` inside) — every
  manifest entry is loaded and served under its manifest name;
* with ``--repo``: a **versioned** model repository
  (``models/repo.py`` layout — per-version dirs with sha256 manifests
  and a ``CURRENT`` pointer): every model's current version is
  digest-verified and served, tagged with its version (per-version
  stats/SLO series, swap decisions journaled under
  ``ServeConfig.lifecycle_dir``). See docs/serving.md §model lifecycle.

Every model is validated by the pre-flight analyzer at load time (the
load fails fast — exit 2 with the diagnostics — before any device work),
and the bucket ladder is warmed when a concrete input schema is known
(``--schema``, or derived from the bundle's input_spec).

``--schema`` takes the same JSON column-spec file as ``tools/analyze.py``.

Every server exposes ``/healthz`` (drain-aware readiness: 200 while
ready, 503 when draining or the SLO burn rate turns the model
unhealthy), ``/livez`` (liveness: always 200 while the process answers
HTTP — restart probes go here, never at ``/healthz``) and ``/slo``
(burn rates, error-budget remaining, latency
verdict, queue-depth/occupancy/replica-skew signals) — tune the
objective with ``--slo-objective``/``--slo-latency-ms``. ``--obs``
additionally enables the span tracer so ``/metrics`` (JSON, or
Prometheus text under ``Accept: text/plain``) and ``/trace``
(Chrome-trace JSON with per-request flows) carry a live timeline.
``--fleet DIR`` exports this process's telemetry snapshots into the
fleet plane (obs/fleet.py; equivalent to ``MMLSPARK_TPU_FLEET=DIR``)
and serves the fleet-merged cross-process view on ``/fleet``. See
docs/observability.md.

Prints one JSON line when serving starts; Ctrl-C drains in-flight
requests and exits.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _load_versioned_repo(path: str, name: str | None
                         ) -> list[tuple[str, object, object]]:
    """[(serve name, model, ModelVersion), ...] from a VERSIONED model
    repo (models/repo.py layout): every model's CURRENT version, digest-
    verified before deserialization — a torn or corrupt version is a
    typed refusal at startup, never a silently-wrong served model."""
    from mmlspark_tpu.models.repo import ModelRepo
    repo = ModelRepo(path)
    names = [name] if name else repo.models()
    if not names:
        raise SystemExit(f"{path}: no published models in the repo")
    out = []
    for n in names:
        model, info = repo.load(n)
        out.append((n, model, info))
    return out


def _load_models(path: str, name: str | None) -> list[tuple[str, object]]:
    """[(serve name, model object), ...] for any supported model path."""
    from mmlspark_tpu.core.stage import PipelineStage
    from mmlspark_tpu.data.downloader import (
        MANIFEST_NAME, Repository, load_bundle_file,
    )

    if os.path.isdir(path):
        if os.path.exists(os.path.join(path, "metadata.json")):
            stage = PipelineStage.load(path)
            return [(name or os.path.basename(os.path.normpath(path)),
                     stage)]
        if os.path.exists(os.path.join(path, MANIFEST_NAME)):
            repo = Repository(path)
            out = []
            for entry in repo.read_manifest():
                bundle = load_bundle_file(os.path.join(path, entry.uri))
                out.append((entry.name, bundle))
            return out
        raise SystemExit(
            f"{path}: neither a saved stage (metadata.json) nor a model "
            f"repository ({MANIFEST_NAME})")
    bundle = load_bundle_file(path)
    return [(name or bundle.name, bundle)]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="serve", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("model", help="saved stage dir, bundle file, or "
                                  "model-repo dir")
    ap.add_argument("--name", default=None,
                    help="serve name (default: dir/bundle name); with "
                         "--repo, serve only this model from the repo")
    ap.add_argument("--repo", action="store_true",
                    help="treat <model-path> as a VERSIONED model repo "
                         "(models/repo.py: per-version dirs with sha256 "
                         "manifests + a CURRENT pointer): serve every "
                         "model's current version, digest-verified at "
                         "load. Publish a new version + re-run (or use "
                         "the deploy_canary/add_model APIs in-process) "
                         "to roll forward; see docs/serving.md §model "
                         "lifecycle")
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--port", type=int, default=8000)
    ap.add_argument("--buckets", default="1,8,32,128",
                    help="comma-separated batch bucket ladder")
    ap.add_argument("--max-queue", type=int, default=128,
                    help="queued requests per model before Overloaded")
    ap.add_argument("--deadline-ms", type=float, default=1000.0,
                    help="default per-request deadline (0 = none)")
    ap.add_argument("--mesh", default=None,
                    help="serving mesh: dp=N[,tp=M][,pp=K][,lockstep] — "
                         "N DP replicas of M×K chips each (sharded "
                         "serving, docs/serving.md). The load fails with "
                         "a typed ModelLoadError when the mesh does not "
                         "divide this host's device count")
    ap.add_argument("--schema", default=None,
                    help="JSON column-spec file (tools/analyze.py format) "
                         "used for validation + bucket warmup")
    ap.add_argument("--precision", default=None,
                    choices=["f32", "bf16", "int8w"],
                    help="serving precision policy (docs/quantization.md)"
                         ": bf16 activations, or int8 weight-only on top;"
                         " parity vs the f32 offline transform is "
                         "calibrated at load against the policy's pinned "
                         "tolerance (typed ModelLoadError on drift)")
    ap.add_argument("--precision-tolerance", type=float, default=None,
                    help="per-model max-abs parity pin for --precision "
                         "(default: the mode's documented tolerance)")
    ap.add_argument("--compile-cache", default=None, metavar="DIR",
                    help="persistent AOT compile cache (same as "
                         "MMLSPARK_TPU_COMPILE_CACHE): compiled bucket "
                         "programs serialize into DIR and later cold "
                         "starts deserialize them instead of paying XLA "
                         "compiles (docs/serving.md §compile cache). An "
                         "unwritable DIR degrades to one warning + "
                         "in-memory compiles — never a failed load")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip compiling the bucket ladder at load")
    ap.add_argument("--obs", action="store_true",
                    help="enable the obs tracer (docs/observability.md): "
                         "GET /metrics and /trace expose the registry "
                         "snapshot (JSON, or Prometheus text under "
                         "content negotiation) and the Chrome-trace "
                         "span timeline with per-request flows")
    ap.add_argument("--fleet", default=None, metavar="DIR",
                    help="export fleet telemetry snapshots into DIR "
                         "(obs/fleet.py; same as MMLSPARK_TPU_FLEET=DIR) "
                         "and serve the fleet-merged view on GET /fleet; "
                         "implies --obs")
    ap.add_argument("--slo-objective", type=float, default=0.999,
                    help="SLO success-ratio objective; its complement "
                         "is the error budget the /healthz burn-rate "
                         "state machine meters (default 0.999)")
    ap.add_argument("--slo-latency-ms", type=float, default=None,
                    help="optional p99 latency objective in ms; when "
                         "violated the model reports degraded on "
                         "/healthz and /slo")
    args = ap.parse_args(argv if argv is not None else sys.argv[1:])

    from mmlspark_tpu.serve import ModelLoadError, ModelServer, ServeConfig
    from mmlspark_tpu.serve.http import start_http_server

    if args.obs:
        from mmlspark_tpu import obs
        obs.enable()
    if args.fleet:
        from mmlspark_tpu.obs import fleet as obs_fleet
        obs_fleet.enable(args.fleet)  # enables the tracer too

    schema = None
    if args.schema:
        from mmlspark_tpu.analysis import TableSchema
        with open(args.schema, "r", encoding="utf-8") as fh:
            schema = TableSchema.from_spec(json.load(fh))

    mesh = None
    if args.mesh:
        from mmlspark_tpu.serve.mesh import ServeMeshSpec
        try:
            mesh = ServeMeshSpec.parse(args.mesh)
        except ValueError as e:
            print(str(e), file=sys.stderr)
            return 2

    from mmlspark_tpu.obs.slo import SLOSpec
    try:
        slo = SLOSpec(objective=args.slo_objective,
                      latency_ms=args.slo_latency_ms)
    except ValueError as e:
        print(str(e), file=sys.stderr)
        return 2

    precision = None
    if args.precision and args.precision != "f32":
        precision = {"mode": args.precision}
        if args.precision_tolerance is not None:
            precision["tolerance"] = args.precision_tolerance
    elif args.precision_tolerance is not None:
        # a tolerance without an active low-precision mode would be
        # silently ignored — refuse loudly instead
        print("--precision-tolerance needs --precision bf16|int8w "
              "(f32 serving is bit-exact; there is nothing to pin)",
              file=sys.stderr)
        return 2

    try:
        config = ServeConfig(
            buckets=tuple(int(b) for b in args.buckets.split(",")),
            max_queue=args.max_queue,
            deadline_ms=args.deadline_ms or None,
            warmup=not args.no_warmup,
            mesh=mesh,
            slo=slo,
            precision=precision,
            compile_cache=args.compile_cache)
    except (ModelLoadError, ValueError) as e:
        # a misordered/duplicate --buckets ladder is a typed refusal
        print(str(e), file=sys.stderr)
        return 2
    from mmlspark_tpu.utils.jit_cache import place_compilation_cache
    place_compilation_cache()
    server = ModelServer(config)
    versions = None
    provenance = None
    try:
        if args.repo:
            from mmlspark_tpu.models.repo import ModelRepoError
            try:
                loaded = _load_versioned_repo(args.model, args.name)
            except ModelRepoError as e:
                print(str(e), file=sys.stderr)
                return 2
            versions = {}
            provenance = {}
            for model_name, model, info in loaded:
                server.add_model(model_name, model, schema=schema,
                                 version=info.version)
                versions[model_name] = info.version
                if info.provenance is not None:
                    # the lifecycle Publisher's stamp: which checkpoint
                    # step, which eval tail, which train run published
                    # the version this process is about to serve
                    provenance[model_name] = info.provenance
                    print(f"serving {model_name} v{info.version} "
                          f"(checkpoint step "
                          f"{info.provenance.get('checkpoint_step')}, "
                          f"run {info.provenance.get('run_id')}, "
                          f"eval {info.provenance.get('eval')})",
                          file=sys.stderr)
        else:
            for model_name, model in _load_models(args.model, args.name):
                server.add_model(model_name, model, schema=schema)
    except ModelLoadError as e:
        print(str(e), file=sys.stderr)
        return 2

    httpd = start_http_server(server, args.host, args.port,
                              background=False)
    print(json.dumps({
        "serving": server.models(),
        "versions": versions,
        "provenance": provenance,
        "host": httpd.server_address[0],
        "port": httpd.server_address[1],
        "buckets": list(config.buckets),
        "precision": args.precision or "f32",
        "max_queue": config.max_queue,
        "deadline_ms": config.deadline_ms,
        "mesh": mesh.describe() if mesh is not None else None,
        "slo": slo.describe(),
        "compile_cache": args.compile_cache,
        "endpoints": ["/healthz", "/livez", "/slo", "/metrics",
                      "/trace", "/v1/models", "/v1/stats"],
    }), flush=True)
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        httpd.server_close()
        server.close(drain=True)  # answer everything admitted
    return 0


if __name__ == "__main__":
    sys.exit(main())
