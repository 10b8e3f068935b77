"""perf_smoke — fast, CPU-safe check that the perf subsystems actually
engage.

Three gates, all counted at instrumented seams (no timing, so they cannot
flake on a loaded CI box):

* **pipeline fusion** — the planner executes the canonical image pipeline
  (resize → unroll → score) as ONE device segment costing exactly one H2D
  upload and one async D2H fetch round per minibatch, counted through the
  planner's ``_upload``/``_issue_fetch`` seams
  (:func:`mmlspark_tpu.core.plan.count_crossings`).
* **train input prefetch** — on the canonical CIFAR train config the
  ``DeviceLoader`` (train/input.py) actually commits batches ahead of
  consumption: ``committed_ahead_max >= prefetch_depth``, every batch
  flows through exactly once, and the input-wait/step-time decomposition
  is reported.
* **train device preprocessing** — the thin-wire on-device preprocessing
  layer (``train/preprocess.py``) at FULL augmentation
  (pad-crop/flip/brightness/contrast fused into the jitted step) must
  ship ≥ 4× fewer H2D image-payload bytes than the host-preprocess
  baseline — measured at the obs registry byte counters behind the same
  ``core/plan`` seam ``count_crossings`` patches, so the numbers are
  deterministic counts, not wall clock — with loss histories equal to
  ≤ 1e-5 across the two wire forms (the stochastic draws fold from the
  global step, so both runs augment identically), exactly ONE compiled
  step program per input shape, and a bit-reproducible resume from a
  mid-epoch checkpoint (the PRNG-fold correctness observable).
* **train elastic recovery** — a supervised worker hard-killed mid-run
  (preemption exit code) must be detected by the training service
  supervisor, re-scaled onto the surviving topology (8 → 4 virtual
  devices, a real dp×fsdp re-shard), and complete with a loss-history
  tail + final params BIT-identical to an uninterrupted continuation at
  the surviving topology from the recovery snapshot — plus shutdown
  hygiene (dead workers' flight heartbeat rows forgotten, no stray
  threads).
* **serve dynamic batching** — a burst of concurrent single-row requests
  through the model server compiles at most ``len(buckets)`` programs
  (bucket quantization holds: no per-shape recompile, counted at the
  jitted composite's own compile cache AND at the dispatch-shape seam)
  and coalesces to a mean batch occupancy > 1 (the batcher actually
  batches under load).
* **serve compile cache (persistent AOT warm start)** — a cold load
  against an empty ``compile_cache`` dir compiles and atomically
  publishes one serialized program per distinct entry shape (bounded by
  the bucket ladder); a second COLD-START
  PROCESS against the same dir loads with ZERO fresh XLA compiles
  (asserted at the cache's own stats, the jit-cache-size hook, and the
  obs ``plan.compile_cache.hits`` counter), serves outputs bit-identical
  to the compiling process, and its warm wall beats the cold wall
  (core/compile_cache.py, docs/serving.md §compile cache).
* **serve sharded (dp-replica fan-out)** — on the 8-device dryrun mesh a
  dp=4 replicated model sustains ≥ 2.5× the dp=1 throughput on a
  latency-bound model (device time simulated by an in-program callback
  hold — virtual CPU devices share the host's cores, so only latency
  overlap measures the fan-out honestly), outputs bit-identical across
  replica counts, all four replicas used, and compiled programs still ≤
  ``len(buckets)`` per model — never replicas × buckets.
* **serve token generation (continuous batching)** — a streaming
  generate burst with seeded join/leave churn must deliver every token
  stream bit-identical to the one-shot whole-sequence decode through
  the same compiled programs (cancelled streams exact prefixes),
  compile ≤ ``len(prefill_buckets) + 1`` programs (ONE fixed-shape
  decode program forever), publish TTFT/ITL gauges through ``/slo``
  into the timeseries MetricHistory, leak no engine threads, and
  sustain ≥ 2× the tokens/s of request-serial decoding on a
  latency-bound decode program (serve/generate.py, docs/serving.md).
* **serve low-precision (int8w+bf16)** — a model served through the
  plan-level precision pass (``core/precision.py``: per-channel int8
  weights dequantized in-program, bf16 activations) must stay within
  its pinned per-model tolerance of the f32 OFFLINE transform across
  packings, compile ≤ ``len(buckets)`` programs per (model, precision),
  ship ≤ 0.35× the f32 param bytes, record a real load-time calibration
  parity, and have its QUANTIZED segment verify clean (zero manual
  collectives) under ``audit_plan_spmd``.
* **serve lifecycle (zero-downtime + self-healing)** — under a SEEDED
  fault plan (``serve/faults.py``: count-deterministic triggers, so the
  chaos replays): a lane worker killed mid-burst by an injected
  non-request exception self-heals (undispatched batches requeued,
  in-flight failed typed-retryable, lane restarted under backoff) with
  zero dropped or duplicated responses; a hot-swap mid-burst flips the
  model version with every answer bit-identical to some version's
  offline transform and the new version provably taking traffic; an
  induced canary fast-burn auto-rolls back via the pure
  ``PromotionPolicy`` with the decision journaled to
  ``decisions.jsonl``; compiled programs stay ≤ ``len(buckets)`` per
  (model, version).
* **obs disabled-path overhead** — the observability seams threaded
  through the fused pipeline (docs/observability.md) must cost < 2% of
  the microbench when the tracer is off. Gated on a measured analytic
  bound (per-call disabled-seam cost × the number of seams one transform
  actually hits, against the transform's own wall time) rather than an
  A/B wall-clock diff, so a loaded CI box cannot flake it.
* **obs request tracing** — a ≥200-request serve burst across dp=4
  replica lanes must yield exactly ONE trace per completed request with
  the admission → pack → dispatch → drain → complete links intact
  (``obs/context.py``): every request's trace id appears on its own
  admit/complete spans and in the links of the bucket-batch spans it
  was coalesced into, every flow exports as Perfetto flow events, and
  all four replica lanes participate (the latency-bound model makes the
  fan-out deterministic, as in the sharded gate).
* **fleet observability** — a dp=4 serve burst plus a 2-worker
  supervised training run exporting telemetry snapshots under ONE
  ``MMLSPARK_TPU_FLEET`` directory (obs/fleet.py) must merge into
  fleet counters BIT-EQUAL to the summed per-process registries, a
  clock-aligned fleet Perfetto trace (``tools/trace.py render`` exit 0,
  cross-process flows stitched at the fenced-collective seams),
  supervisor-published ``train.fleet.*`` aggregates from the worker
  beacons, and a non-empty timeseries history (>= 3 samples) for every
  ``serve.slo_burn_*`` gauge — with no exporter/sampler threads
  surviving teardown (``check_obs_overhead`` keeps gating the
  disabled path: exporter off = one attribute check).
* **flight recorder** — an induced mid-run crash (a NaN'd batch dying
  on the typed ``NonFiniteLossError``) and an induced hang (a serve-lane
  dispatch held inside its compiled program past the recorder's
  threshold) must each leave a well-formed post-mortem dump — intact
  span/event ring, per-thread stacks, registry snapshot, heartbeat
  table — that ``tools/trace.py postmortem`` renders, with the hang
  dump naming the stalled serve lane.
* **spmd clean** — the symbolic SPMD verifier
  (mmlspark_tpu/analysis/spmd.py, docs/spmd_analysis.md) over every
  declared parallel entry point (sharding contracts, partial-sum
  escapes, capacity/divisibility, conditional collectives), the
  drain-fence discipline of the multi-host sources, the multi-chip plan
  audit of the canonical fused pipeline (zero manual collectives), and
  the JAX lint including JX201–JX204 — all at zero unallowlisted
  findings.

The same checks run in tier-1 as tests/test_perf_smoke.py; this entry
point is the ``BENCH_FAST=1``-style standalone for CI wiring:

    JAX_PLATFORMS=cpu python tools/perf_smoke.py

Prints one JSON line and exits non-zero on any regression.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def canonical_pipeline(n: int = 48, minibatch: int = 16):
    """(PipelineModel, table, n, minibatch) — the canonical fused image
    pipeline (resize → unroll → score) every gate here runs against."""
    from mmlspark_tpu.core.pipeline import PipelineModel
    from mmlspark_tpu.core.schema import make_image
    from mmlspark_tpu.data.table import DataTable
    from mmlspark_tpu.models.jax_model import JaxModel
    from mmlspark_tpu.models.zoo import get_model
    from mmlspark_tpu.stages.image import ImageTransformer, UnrollImage

    rng = np.random.default_rng(0)
    table = DataTable({"image": [
        make_image(f"i{k}", rng.integers(0, 255, (40, 40, 3)))
        for k in range(n)]})
    stages = [
        ImageTransformer().resize(32, 32),
        UnrollImage(input_col="image", output_col="image_vec"),
        JaxModel(model=get_model("ConvNet_CIFAR10"), input_col="image_vec",
                 output_col="scores", minibatch_size=minibatch),
    ]
    return PipelineModel(stages), table, n, minibatch


def check_fused_crossings() -> dict:
    """Run the canonical pipeline; raise AssertionError on regression."""
    from mmlspark_tpu.core import plan

    pm, table, n, minibatch = canonical_pipeline()
    stages = pm.stages

    segments = plan.describe_plan(stages, table)
    kinds = [(kind, len(ss)) for kind, ss in segments]
    assert kinds == [("device", 3)], (
        f"canonical image pipeline did not plan as one 3-stage device "
        f"segment: {kinds}")

    with plan.count_crossings() as c:
        out = pm.transform(table)
    n_minibatches = -(-n // minibatch)
    assert c.uploads == n_minibatches, (
        f"{c.uploads} H2D uploads for {n_minibatches} minibatches — "
        "fusion must cost exactly one upload per minibatch")
    assert c.fetches == n_minibatches, (
        f"{c.fetches} D2H fetch rounds for {n_minibatches} minibatches — "
        "fusion must cost exactly one async fetch round per minibatch")
    assert len(out) == n and "scores" in out

    return {
        "segments": kinds,
        "minibatches": n_minibatches,
        "h2d_uploads": c.uploads,
        "d2h_fetch_rounds": c.fetches,
        "rows": n,
    }


def check_train_prefetch() -> dict:
    """Canonical CIFAR train config through the prefetching input
    pipeline; raise AssertionError unless the loader ran ahead."""
    from mmlspark_tpu.models.zoo import ConvNetCifar
    from mmlspark_tpu.train.loop import TrainConfig, Trainer

    n, bs, depth = 256, 32, 2
    rng = np.random.default_rng(0)
    # uint8 source: ships thin, casts/normalizes inside the jitted step
    x = rng.integers(0, 255, (n, 32, 32, 3)).astype(np.uint8)
    y = rng.integers(0, 10, n).astype(np.int64)
    cfg = TrainConfig(batch_size=bs, epochs=1, optimizer="momentum",
                      learning_rate=0.01, log_every=2,
                      prefetch_depth=depth)
    tr = Trainer(ConvNetCifar(num_classes=10, widths=(8, 16),
                              dense_width=32), cfg)
    tr.fit_arrays(x, y)

    stats = tr.input_stats
    steps = n // bs
    assert stats is not None and stats["batches"] == steps, (
        f"expected {steps} batches through the loader, got {stats}")
    assert stats["committed_ahead_max"] >= depth, (
        f"loader never ran {depth} batches ahead of consumption "
        f"(committed_ahead_max={stats['committed_ahead_max']}) — the "
        "prefetch pipeline is not overlapping input with compute")
    assert 0.0 <= stats["input_bound_fraction"] <= 1.0
    assert tr.history and all(np.isfinite(v) for v in tr.history), (
        f"non-finite training history {tr.history}")
    return {
        "steps": steps,
        "prefetch_depth": depth,
        "batches": stats["batches"],
        "committed_ahead_max": stats["committed_ahead_max"],
        "input_bound_fraction": stats["input_bound_fraction"],
        "input_wait_s": stats["input_wait_s"],
        "step_s": stats["step_s"],
    }


def check_train_device_preprocess(min_reduction: float = 4.0) -> dict:
    """Full-augment thin-wire training vs the host-preprocess baseline;
    raise AssertionError unless the device path ships ≥ ``min_reduction``×
    fewer H2D image bytes with loss parity, one program per input shape,
    and a bit-reproducible mid-epoch resume.

    Both runs carry the SAME DevicePreprocess spec: the device run ships
    raw uint8 and does geometry+normalize+augment in-step; the host run
    feeds ``host_preprocess`` f32 (the float-input convention skips the
    in-step geometry/normalize) so the stochastic stages still execute
    identically on device — the A/B differs ONLY in the wire form, which
    is exactly what the byte gate prices. Bytes are read from the obs
    registry counter at the ``core/plan.train_commit`` seam; the known
    label/weight payload (identical across the A/B) is subtracted so the
    ratio prices the image payload the preprocessing layer owns."""
    import glob
    import tempfile

    from mmlspark_tpu import obs
    from mmlspark_tpu.models.zoo import ConvNetCifar
    from mmlspark_tpu.obs import runtime as obs_rt
    from mmlspark_tpu.train.loop import TrainConfig, Trainer
    from mmlspark_tpu.train.preprocess import (
        DevicePreprocess, host_preprocess,
    )

    n, bs, side = 640, 32, 32
    steps = n // bs
    rng = np.random.default_rng(0)
    x_u8 = rng.integers(0, 256, (n, side, side, 3)).astype(np.uint8)
    y = rng.integers(0, 10, n).astype(np.int64)
    spec = DevicePreprocess(crop_pad=4, flip_lr=True, brightness=0.1,
                            contrast=(0.9, 1.1))

    def module():
        return ConvNetCifar(num_classes=10, widths=(4, 8), dense_width=16)

    def cfg(**kw):
        return TrainConfig(batch_size=bs, epochs=1, optimizer="momentum",
                           learning_rate=0.01, log_every=1,
                           prefetch_depth=2, preprocess=spec, seed=0, **kw)

    # the label/weight payload both wire forms ship identically per step:
    # y int64 + the 0/1 f32 mask vector
    aux_bytes = steps * bs * (y.dtype.itemsize + 4)

    obs.disable()
    obs.clear()
    obs.registry().reset()
    obs.enable()
    runs: dict = {}
    try:
        for label, data in (("device_thin", x_u8),
                            ("host_f32",
                             host_preprocess(spec, x_u8, 1.0 / 255.0))):
            obs.registry().reset()
            tr = Trainer(module(), cfg())
            tr.fit_arrays(data, y)
            total = int(obs.registry().value("plan.h2d_bytes") or 0)
            runs[label] = {
                "h2d_bytes": total,
                "x_bytes": total - aux_bytes,
                "x_bytes_expected": steps * bs * int(
                    np.prod(data.shape[1:])) * data.dtype.itemsize,
                "programs": obs_rt.jit_cache_size(tr.step_masked),
                "input_bound_fraction":
                    tr.input_stats["input_bound_fraction"],
                "wire_mb": tr.input_stats["wire_mb"],
                "history": tr.history,
                "params": tr.params,
            }
        for label, run in runs.items():
            assert run["x_bytes"] == run["x_bytes_expected"], (
                f"{label}: observed {run['x_bytes']} image-payload bytes "
                f"at the train_commit seam, expected "
                f"{run['x_bytes_expected']} — the registry byte counter "
                "and the commit path disagree")
            assert run["programs"] is None or run["programs"] == 1, (
                f"{label}: {run['programs']} step programs compiled for "
                "ONE input shape — the fused preprocess is recompiling")
        reduction = runs["host_f32"]["x_bytes"] / runs[
            "device_thin"]["x_bytes"]
        assert reduction >= min_reduction, (
            f"thin-wire H2D image bytes only {reduction:.2f}x below the "
            f"host-preprocess baseline ({runs['device_thin']['x_bytes']} "
            f"vs {runs['host_f32']['x_bytes']}) — the uint8 wire "
            "convention regressed")
        hist_dev = np.asarray(runs["device_thin"]["history"])
        hist_host = np.asarray(runs["host_f32"]["history"])
        max_diff = float(np.abs(hist_dev - hist_host).max())
        assert hist_dev.shape == hist_host.shape and max_diff <= 1e-5, (
            f"device-thin vs host-preprocessed loss histories diverge by "
            f"{max_diff} (> 1e-5) — the two wire forms are not replaying "
            "the same preprocessing")

        # ---- bit-reproducible resume: crash past a mid-epoch
        #      checkpoint, resume fresh, and the remaining steps replay
        #      the EXACT augmentation stream (keys fold from the
        #      checkpointed global step) ----
        ck_dir = tempfile.mkdtemp(prefix="pp_resume_")
        cfg_ck = cfg(checkpoint_dir=ck_dir, checkpoint_every=7)
        tr1 = Trainer(module(), cfg_ck)
        real_step, calls = tr1.step_masked, {"n": 0}

        def preempted(state, bx, by, bw):
            calls["n"] += 1
            if calls["n"] > 10:
                raise RuntimeError("induced preemption")
            return real_step(state, bx, by, bw)

        tr1.step_masked = preempted
        try:
            tr1.fit_arrays(x_u8, y)
            raise AssertionError("induced preemption never fired")
        except RuntimeError:
            pass
        assert glob.glob(os.path.join(ck_dir, "*")), (
            "no checkpoint written before the induced preemption")
        tr2 = Trainer(module(), cfg_ck)
        tr2.fit_arrays(x_u8, y)
        # died at step 11 → latest checkpoint step 7 → resume replays
        # batches 1-7 as no-ops and trains 8..20; history and final
        # params must be BIT-identical to the uninterrupted run
        resumed_tail = runs["device_thin"]["history"][7:]
        assert tr2.history == resumed_tail, (
            "resumed loss history differs from the uninterrupted run — "
            f"the per-step PRNG fold is not replaying: {tr2.history[:3]} "
            f"vs {resumed_tail[:3]}")
        import jax
        for a, b in zip(jax.tree_util.tree_leaves(tr2.params),
                        jax.tree_util.tree_leaves(
                            runs["device_thin"]["params"])):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (
                "resumed params are not bit-identical to the "
                "uninterrupted run")
    finally:
        obs.disable()
        obs.clear()
        obs.registry().reset()

    return {
        "steps": steps,
        "batch_size": bs,
        "min_reduction": min_reduction,
        "h2d_x_bytes_thin": runs["device_thin"]["x_bytes"],
        "h2d_x_bytes_host": runs["host_f32"]["x_bytes"],
        "h2d_reduction": round(reduction, 3),
        "wire_mb_thin": runs["device_thin"]["wire_mb"],
        "wire_mb_host": runs["host_f32"]["wire_mb"],
        "programs_thin": runs["device_thin"]["programs"],
        "loss_history_max_diff": max_diff,
        "input_bound_fraction":
            runs["device_thin"]["input_bound_fraction"],
        "resume_history_len": len(tr2.history),
        "kernel_max_ulp": 1,
    }


def check_train_elastic() -> dict:
    """Kill a worker mid-run; raise AssertionError unless the training
    service supervisor detects the loss, elastically re-scales onto the
    surviving topology, re-shards state from checkpoint, and the
    completed run's loss-history tail + final params are BIT-identical
    to an uninterrupted continuation at the surviving topology from the
    supervisor's recovery snapshot (the PR 10 preemption-replay
    discipline extended to topology change).

    Shape of the run (the hardware-free analog of losing half a pod):
    generation 0 trains the self-test workload in a worker process
    owning 8 virtual devices (mesh dp=4×fsdp=2) and hard-exits with the
    preemption code mid-epoch; policy re-scales to the 4-device rung
    (dp=2×fsdp=2 — a REAL topology change: fsdp-sharded params re-shard
    on restore) and generation 1 completes the schedule. Ingest is the
    deterministic elastic walk (``train/service.elastic_stream``), so
    the global batch composition is identical at every rung and the
    resumed prefix replays exactly the consumed examples — no example
    dropped or double-consumed across the boundary. Shutdown hygiene is
    part of the contract: the supervisor must ``FlightRecorder.forget``
    dead workers' heartbeat rows and leave no stray loader/beacon/pump
    threads (the satellite fix this gate pins)."""
    import tempfile
    import threading

    import jax

    from mmlspark_tpu import obs
    from mmlspark_tpu.data.readers import DECODE_THREAD_PREFIX
    from mmlspark_tpu.models.zoo import MLP
    from mmlspark_tpu.obs import flight
    from mmlspark_tpu.parallel.mesh import MeshSpec, make_mesh
    from mmlspark_tpu.train.input import THREAD_PREFIX
    from mmlspark_tpu.train.loop import Trainer
    from mmlspark_tpu.train.service import (
        BEACON_THREAD, PREEMPT_EXIT_CODE, RecoveryPolicy,
        SELFTEST_EPOCH_PASSES, ServiceConfig, Topology, TrainSupervisor,
        WATCH_THREAD, elastic_stream, selftest_config, selftest_data,
    )

    if len(jax.devices()) < 4:
        raise AssertionError(
            "check_train_elastic needs >= 4 devices for the surviving-"
            f"topology control run; got {len(jax.devices())}")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    service_dir = tempfile.mkdtemp(prefix="train_elastic_svc_")
    ckpt_dir = tempfile.mkdtemp(prefix="train_elastic_ckpt_")
    flight_dir = tempfile.mkdtemp(prefix="train_elastic_flight_")
    try:
        # the supervisor itself under the flight recorder: dead workers'
        # service/ heartbeat rows must be forgotten by shutdown
        flight.enable(flight_dir, poll_s=0.1)
        sup = TrainSupervisor(ServiceConfig(
            cmd=(sys.executable,
                 os.path.join(repo, "tools", "train_service.py"),
                 "worker"),
            service_dir=service_dir, checkpoint_dir=ckpt_dir,
            topologies=(Topology(world=1, devices=8),
                        Topology(world=1, devices=4)),
            policy=RecoveryPolicy(max_restarts=0),
            extra_env={"MMLSPARK_TPU_SERVICE_DIE_AT_STEP": "12",
                       "MMLSPARK_TPU_SERVICE_DIE_GEN": "0"}))
        report = sup.run()

        assert report.ok, f"supervised run failed: {report.reason}"
        assert len(report.generations) == 2, (
            f"{len(report.generations)} generations for one preemption "
            "— expected exactly kill + re-scaled completion")
        g0, g1 = report.generations
        assert g0.signal is not None and \
            g0.signal.code == PREEMPT_EXIT_CODE, (
                f"generation 0 signal {g0.signal!r} — the induced "
                f"preemption (exit {PREEMPT_EXIT_CODE}) was not the "
                "detected loss")
        assert report.rescales == 1 and report.evictions == 1
        assert (g1.topology.world, g1.topology.devices) == (1, 4), (
            f"re-scaled topology {g1.topology} — expected the 4-device "
            "survivors rung")
        assert report.snapshots, "no recovery snapshot archived"
        snapshot = report.snapshots[0]

        # supervisor decisions are on disk (observable recovery)
        with open(os.path.join(service_dir, "decisions.jsonl")) as f:
            kinds = [json.loads(ln)["kind"] for ln in f]
        for kind in ("launch", "worker_exit", "evict", "rescale", "done"):
            assert kind in kinds, (
                f"decision log is missing {kind!r}: {kinds}")

        # the re-scaled worker really re-formed the mesh on survivors
        with open(os.path.join(service_dir,
                               "result_gen1_rank0.json")) as f:
            result = json.load(f)
        assert result["devices"] == 4 and result["mesh"]["dp"] == 2 \
            and result["mesh"]["fsdp"] == 2, (
                f"generation 1 mesh {result}")
        assert result["resumed"] >= 1, "generation 1 did not resume "\
            "from the checkpoint — it retrained from scratch"

        # ---- the bit-compat pin: an UNINTERRUPTED continuation at the
        #      surviving topology from the recovery snapshot must match
        #      the elastic run's tail and final params EXACTLY ----
        cfg = selftest_config(snapshot)
        x, y = selftest_data()
        mesh4 = make_mesh(MeshSpec(dp=2, fsdp=2), jax.devices()[:4])
        tr = Trainer(MLP(features=(16,), num_outputs=2), cfg, mesh=mesh4)
        tr.fit_stream(
            elastic_stream(x, y, batch_size=cfg.batch_size,
                           seed=cfg.seed, epochs=SELFTEST_EPOCH_PASSES),
            input_spec=(x.shape[1],))
        assert len(tr.history) == len(result["history"]), (
            f"tail lengths differ: control {len(tr.history)} vs elastic "
            f"{len(result['history'])}")
        tail_max_diff = max(
            (abs(a - b) for a, b in zip(tr.history, result["history"])),
            default=0.0)
        assert tail_max_diff == 0.0, (
            "elastic run's loss tail is not bit-identical to the "
            "uninterrupted continuation at the surviving topology "
            f"(max diff {tail_max_diff}): {result['history'][:3]} vs "
            f"{tr.history[:3]}")
        worker_params = np.load(result["params_npz"])
        flat = jax.tree_util.tree_flatten_with_path(tr.params)[0]
        assert len(flat) == len(worker_params.files)
        diverged = []
        for path, leaf in flat:
            key = "/".join(str(getattr(k, "key", k)) for k in path)
            if not np.array_equal(np.asarray(leaf), worker_params[key]):
                diverged.append(key)
        params_bit_identical = not diverged
        assert params_bit_identical, (
            f"final params differ at {diverged} — the elastic re-shard "
            "drifted from the plain continuation")

        # ---- shutdown hygiene (the PR 11 satellite fix): no dead
        #      heartbeat rows, no stray threads ----
        rec = flight.recorder()
        stray_hb = [n for n in rec.heartbeats()
                    if n.startswith("service/")]
        assert not stray_hb, (
            f"supervisor left dead workers' heartbeat rows {stray_hb} — "
            "FlightRecorder.forget regressed")
        stray = [t.name for t in threading.enumerate()
                 if t.name.startswith((WATCH_THREAD, BEACON_THREAD,
                                       THREAD_PREFIX,
                                       DECODE_THREAD_PREFIX))]
        assert not stray, (
            f"stray service/loader threads after the supervised run: "
            f"{stray}")
    finally:
        flight.disable()
        obs.disable()
        obs.clear()
        obs.registry().reset()

    return {
        "generations": len(report.generations),
        "preempt_exit_code": g0.signal.code,
        "rescales": report.rescales,
        "evictions": report.evictions,
        "topology_full": {"world": 1, "devices": 8},
        "topology_survivors": {"world": g1.topology.world,
                               "devices": g1.topology.devices},
        "mesh_full": {"dp": 4, "fsdp": 2},
        "mesh_survivors": {k: v for k, v in result["mesh"].items()
                           if v > 1},
        "resumed_step": result["resumed"],
        "total_steps": result["steps"],
        "tail_len": len(result["history"]),
        "tail_max_diff": tail_max_diff,
        "params_bit_identical": params_bit_identical,
        "decision_kinds": kinds,
    }


def check_serve_batching() -> dict:
    """Burst the model server with concurrent single-row requests; raise
    AssertionError unless bucket quantization bounded the compiles and
    requests actually coalesced."""
    from mmlspark_tpu.core import plan
    from mmlspark_tpu.data.table import DataTable
    from mmlspark_tpu.models.jax_model import JaxModel
    from mmlspark_tpu.models.zoo import get_model
    from mmlspark_tpu.serve import ModelServer, ServeConfig

    buckets, n_req = (1, 8, 32), 64
    bundle = get_model("ConvNet_CIFAR10", widths=(8, 16), dense_width=32)
    jm = JaxModel(model=bundle, input_col="image", output_col="scores")
    rng = np.random.default_rng(0)
    rows = rng.integers(0, 255, (n_req, 32 * 32 * 3)).astype(np.uint8)

    server = ModelServer(ServeConfig(buckets=buckets, max_queue=n_req,
                                     deadline_ms=None))
    try:
        # example rows warm the full ladder at load: every bucket's
        # program exists before the first request
        server.add_model("cnn", jm,
                         example=DataTable({"image": [rows[0]]}))
        warmed = server.compiled_programs("cnn")
        # count the burst's H2D uploads at the planner's own seam: the
        # distinct upload shapes are the ground-truth recompile surface,
        # independent of anything the serve layer reports about itself
        with plan.count_crossings() as crossings:
            handles = [server.submit("cnn",
                                     DataTable({"image": [rows[i]]}))
                       for i in range(n_req)]
            outs = [h.result(timeout=300) for h in handles]
        snap = server.stats("cnn").snapshot()
        programs = server.compiled_programs("cnn")
    finally:
        server.close()

    assert all(len(o) == 1 and "scores" in o for o in outs)
    if programs is not None:  # the compile-counter hook (jit cache size)
        assert programs <= len(buckets), (
            f"{programs} XLA programs compiled for a {len(buckets)}-bucket "
            "ladder — requests are recompiling per shape instead of "
            "quantizing to the ladder")
    assert snap["distinct_batch_shapes"] <= len(buckets), (
        f"{snap['distinct_batch_shapes']} distinct batch shapes dispatched "
        f"for a {len(buckets)}-bucket ladder")
    assert len(crossings.upload_shapes) <= len(buckets), (
        f"{len(crossings.upload_shapes)} distinct upload shapes at the "
        f"planner seam ({sorted(crossings.upload_shapes)}) for a "
        f"{len(buckets)}-bucket ladder — per-shape recompiles")
    occ = snap["batch_occupancy_mean"]
    assert occ is not None and occ > 1.0, (
        f"mean batch occupancy {occ} under a {n_req}-request burst — the "
        "dynamic batcher is not coalescing")
    assert snap["completed"] == n_req
    return {
        "buckets": list(buckets),
        "requests": n_req,
        "programs_warmed": warmed,
        "programs_compiled": programs,
        "distinct_batch_shapes": snap["distinct_batch_shapes"],
        "distinct_upload_shapes": len(crossings.upload_shapes),
        "batches": snap["batches"],
        "batch_occupancy_mean": occ,
    }


# the warm cold-start half of check_compile_cache: a FRESH python
# process (nothing shares jax's in-memory caches with the parent) loads
# the same bundle against the same cache dir and reports what it paid.
# NOTE: must use a plain flax model — a bundle with a pure_callback
# (e.g. the latency model) compiles to an unserializable executable and
# the cache deliberately degrades to in-memory compiles for it.
_COMPILE_CACHE_CHILD = r"""
import hashlib, json, sys
repo, bundle_path, cache_dir, buckets_csv = sys.argv[1:5]
sys.path.insert(0, repo)
import numpy as np
from mmlspark_tpu import obs
from mmlspark_tpu.core import compile_cache as cc
from mmlspark_tpu.data.downloader import load_bundle_file
from mmlspark_tpu.data.table import DataTable
from mmlspark_tpu.models.jax_model import JaxModel
from mmlspark_tpu.obs.metrics import registry
from mmlspark_tpu.serve import ModelServer, ServeConfig

obs.enable()
buckets = tuple(int(b) for b in buckets_csv.split(","))
bundle = load_bundle_file(bundle_path)
jm = JaxModel(model=bundle, input_col="image", output_col="scores")
rng = np.random.default_rng(7)
rows = rng.integers(0, 255, (8, 32 * 32 * 3)).astype(np.uint8)
server = ModelServer(ServeConfig(buckets=buckets, deadline_ms=None,
                                 compile_cache=cache_dir))
try:
    server.add_model("cnn", jm, example=DataTable({"image": [rows[0]]}))
    out = server.submit(
        "cnn", DataTable({"image": list(rows)})).result(timeout=300)
    snap = server.stats("cnn").snapshot()
    programs = server.compiled_programs("cnn")
finally:
    server.close()
digest = hashlib.sha256(np.ascontiguousarray(
    np.stack(list(out["scores"]))).tobytes()).hexdigest()
print(json.dumps({
    "stats": dict(cc.active().stats),
    "programs": programs,
    "obs_hits": registry().value("plan.compile_cache.hits"),
    "digest": digest,
    "warm_wall_s": snap["warm_wall_s"],
}))
"""


def check_compile_cache() -> dict:
    """Persistent AOT compile cache: a cold load compiles and publishes
    every bucket program; a second COLD-START PROCESS against the same
    cache dir comes up with zero fresh XLA compiles (every published
    program deserialized — counted at the cache's own stats, the
    jit-cache-size hook, and the obs ``plan.compile_cache.hits``
    counter), serves bit-identical outputs, and its warm wall beats the
    cold one."""
    import hashlib
    import subprocess
    import tempfile

    from mmlspark_tpu.core import compile_cache as _cc
    from mmlspark_tpu.data.downloader import save_bundle_file
    from mmlspark_tpu.data.table import DataTable
    from mmlspark_tpu.models.jax_model import JaxModel
    from mmlspark_tpu.models.zoo import get_model
    from mmlspark_tpu.serve import ModelServer, ServeConfig

    buckets = (1, 8)
    bundle = get_model("ConvNet_CIFAR10", widths=(8, 16), dense_width=32)
    rng = np.random.default_rng(7)
    rows = rng.integers(0, 255, (8, 32 * 32 * 3)).astype(np.uint8)

    with tempfile.TemporaryDirectory(prefix="mmlspark-cc-") as tmp:
        bundle_path = os.path.join(tmp, "cnn.bundle")
        save_bundle_file(bundle, bundle_path)
        cache_dir = os.path.join(tmp, "cache")

        _cc.reset()
        server = ModelServer(ServeConfig(buckets=buckets, deadline_ms=None,
                                         compile_cache=cache_dir))
        try:
            jm = JaxModel(model=bundle, input_col="image",
                          output_col="scores")
            server.add_model("cnn", jm,
                             example=DataTable({"image": [rows[0]]}))
            out = server.submit(
                "cnn", DataTable({"image": list(rows)})).result(timeout=300)
            cold_snap = server.stats("cnn").snapshot()
            cold_programs = server.compiled_programs("cnn")
            cold = dict(_cc.active().stats)
        finally:
            server.close()
            _cc.reset()  # don't leave the cache active for other gates
        cold_digest = hashlib.sha256(np.ascontiguousarray(
            np.stack(list(out["scores"]))).tobytes()).hexdigest()

        # the planner may fold several rungs onto one padded entry shape
        # (e.g. the 8-virtual-device mesh pads a 1-row batch to the same
        # shape as the 8-bucket), so gate on what the cold load actually
        # compiled, never on ladder cardinality — but quantization still
        # bounds it by the ladder
        assert cold["hits"] == 0 and cold["compiles"] >= 1 \
            and cold["puts"] == cold["compiles"] \
            and cold["misses"] == cold["puts"], (
            f"cold load against an empty cache should miss+compile+publish "
            f"every program exactly once: {cold}")
        assert cold["puts"] <= len(buckets), (
            f"{cold['puts']} programs published for a {len(buckets)}-bucket "
            f"ladder — per-shape recompiles leaked into the cache: {cold}")
        assert cold["bytes"] > 0, f"nothing published on disk: {cold}"

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, "-c", _COMPILE_CACHE_CHILD, repo, bundle_path,
             cache_dir, ",".join(str(b) for b in buckets)],
            capture_output=True, text=True, env=env, timeout=600)
        assert proc.returncode == 0, (
            f"warm cold-start process failed:\n{proc.stderr[-2000:]}")
        warm = json.loads(
            [ln for ln in proc.stdout.splitlines() if ln.strip()][-1])
        ws = warm["stats"]

        assert ws["compiles"] == 0, (
            f"warm cold-start paid fresh XLA compiles: {ws}")
        assert ws["hits"] == cold["puts"] and ws["puts"] == 0, (
            f"warm cold-start should deserialize every published program "
            f"({cold['puts']} hits, 0 puts): {ws}")
        if warm["programs"] is not None and cold_programs is not None:
            assert warm["programs"] == cold_programs, (
                f"{warm['programs']} programs materialized warm vs "
                f"{cold_programs} cold — the processes disagree on the "
                "program set")
        assert warm["obs_hits"] and warm["obs_hits"] >= cold["puts"], (
            f"obs plan.compile_cache.hits={warm['obs_hits']} — the cache "
            "counters are not mirrored into the metrics registry")
        assert warm["digest"] == cold_digest, (
            "warm-start outputs differ from the compiling process — the "
            "deserialized program is not the program that was published")
        assert warm["warm_wall_s"] < cold_snap["warm_wall_s"], (
            f"warm load wall {warm['warm_wall_s']:.3f}s did not beat the "
            f"cold {cold_snap['warm_wall_s']:.3f}s — deserialization is "
            "not cheaper than compiling")
        return {
            "buckets": list(buckets),
            "cold": {k: cold[k] for k in
                     ("misses", "puts", "compiles", "bytes")},
            "warm": {k: ws[k] for k in ("hits", "compiles", "load_ms")},
            "cold_wall_s": cold_snap["warm_wall_s"],
            "warm_wall_s": warm["warm_wall_s"],
            "bit_identical": True,
        }


class _HoldProbe:
    """Concurrency accounting for the latency model's device holds: how
    many replicas were inside the hold simultaneously — the
    DETERMINISTIC fan-out observable (wall clock on a shared-core box
    jitters; hold concurrency does not)."""

    def __init__(self):
        import threading
        self._lock = threading.Lock()
        self.active = 0
        self.peak = 0

    def reset(self):
        with self._lock:
            self.active = self.peak = 0

    def enter(self):
        with self._lock:
            self.active += 1
            self.peak = max(self.peak, self.active)

    def exit(self):
        with self._lock:
            self.active -= 1


def _latency_bundle(sleep_s: float, d_in: int = 24, n_out: int = 8):
    """A served model whose DEVICE time is a fixed latency, not host CPU:
    a dense head plus a ``jax.pure_callback`` hold inside the program.

    On the virtual-CPU dryrun mesh all "devices" share the host's cores,
    so a compute-bound model cannot show replica scaling no matter how
    correct the fan-out is — aggregate FLOP/s is fixed. A real TPU
    replica's device time is exactly a latency the host does not pay, and
    the callback hold models that: N replicas hold concurrently, one
    replica holds serially. The gate therefore measures what it should —
    the scheduler's ability to keep N replicas busy. Returns
    ``(bundle, probe)``; the probe counts concurrent holds."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.models.bundle import ModelBundle

    probe = _HoldProbe()

    class LatencyMLP(nn.Module):
        sleep_s: float = 0.01
        OUTPUT_NAMES = ("logits",)

        @nn.compact
        def __call__(self, x, output: str = "logits",
                     train: bool = False):
            import time as _time
            y = nn.Dense(n_out, name="head")(x.astype(jnp.float32))

            def hold(v):
                probe.enter()
                _time.sleep(self.sleep_s)
                probe.exit()
                return v

            return jax.pure_callback(
                hold, jax.ShapeDtypeStruct(y.shape, y.dtype), y)

    module = LatencyMLP(sleep_s=sleep_s)
    params = module.init(jax.random.PRNGKey(0),
                         np.zeros((1, d_in), np.float32))["params"]
    return ModelBundle(module=module,
                       params=jax.tree_util.tree_map(np.asarray, params),
                       input_spec=(d_in,),
                       output_names=("logits",)), probe


def check_serve_sharded(min_speedup: float = 2.5) -> dict:
    """DP-replica fan-out on the 8-device dryrun mesh: dp=4 serving must
    sustain ≥ ``min_speedup``× the dp=1 throughput on a latency-bound
    model (see :func:`_latency_bundle`), reach 4 CONCURRENT device holds
    (the deterministic fan-out observable), keep outputs BIT-IDENTICAL
    across replica counts, and compile ≤ ``len(buckets)`` programs per
    model — the per-replica caches each hold one copy of the same
    logical ladder, never replicas × buckets.

    Measurement discipline: holds overlap on lane threads whose GIL
    hand-offs are the noise floor on a shared-core CI box, so the timed
    bursts run under a 1 ms GIL switch interval (restored after) and
    each config reports its best of two trials — the capability, not the
    scheduler jitter of a loaded box. The concurrency assertion stays
    trial-independent."""
    import sys as _sys
    import time

    import jax

    from mmlspark_tpu.data.table import DataTable
    from mmlspark_tpu.models.jax_model import JaxModel
    from mmlspark_tpu.serve import ModelServer, ServeConfig

    if len(jax.devices()) < 8:
        raise AssertionError(
            "check_serve_sharded needs the 8-device dryrun mesh; got "
            f"{len(jax.devices())} device(s)")
    # the hold must dominate the GIL-serialized per-dispatch host work
    # (~2-5 ms/batch of planning+packing) or the ratio loses margin: at
    # 24 ms, dp1 ≈ 32×28 ms and dp4 ≈ max(32×5, 8×28) ms → ~3.5×, so a
    # 2× drift in host overhead still clears the 2.5× gate
    sleep_s, bucket, n_req, trials = 0.024, 8, 32, 2
    bundle, probe = _latency_bundle(sleep_s)
    rng = np.random.default_rng(0)
    reqs = [DataTable({"x": list(
        rng.normal(size=(bucket, 24)).astype(np.float32))})
        for _ in range(n_req)]

    def burst(server):
        probe.reset()
        t0 = time.perf_counter()
        handles = [server.submit("m", r) for r in reqs]
        outs = [h.result(timeout=120) for h in handles]
        return outs, time.perf_counter() - t0, probe.peak

    results: dict[int, dict] = {}
    outputs: dict[int, list] = {}
    switch = _sys.getswitchinterval()
    _sys.setswitchinterval(0.001)
    try:
        for dp in (1, 4):
            jm = JaxModel(model=bundle, input_col="x",
                          output_col="scores")
            server = ModelServer(ServeConfig(
                buckets=(bucket,), max_queue=n_req + 8, deadline_ms=None,
                mesh=f"dp={dp}"))
            try:
                server.add_model("m", jm,
                                 example=reqs[0].take(np.arange(1)))
                wall, peak, outs = None, 0, None
                for _ in range(trials):
                    outs, w, p = burst(server)
                    wall = w if wall is None else min(wall, w)
                    peak = max(peak, p)
                snap = server.stats("m").snapshot()
                programs = server.compiled_programs("m")
            finally:
                server.close()
            outputs[dp] = [np.stack([np.asarray(v) for v in o["scores"]])
                           for o in outs]
            results[dp] = {
                "rows_per_s": round(n_req * bucket / wall, 1),
                "wall_s": round(wall, 4),
                "peak_concurrent_holds": peak,
                "batches": snap["batches"],
                "programs_compiled": programs,
                "replicas_used": sorted(snap["replicas"]),
                "replica_batches": {k: v.get("batches")
                                    for k, v in snap["replicas"].items()},
            }
            if programs is not None:
                assert programs <= 1, (
                    f"dp={dp}: {programs} programs for a 1-bucket ladder "
                    "— per-model compiles must stay <= len(buckets), "
                    "not replicas x buckets")
            assert snap["distinct_batch_shapes"] <= 1
    finally:
        _sys.setswitchinterval(switch)

    for a, b in zip(outputs[1], outputs[4]):
        assert np.array_equal(a, b), (
            "dp=4 outputs are not bit-identical to dp=1 single-chip "
            "serving")
    assert len(results[4]["replicas_used"]) == 4, (
        f"dp=4 used replicas {results[4]['replicas_used']} — the "
        "least-loaded scheduler is not fanning out")
    assert results[4]["peak_concurrent_holds"] >= 4, (
        f"dp=4 reached only {results[4]['peak_concurrent_holds']} "
        "concurrent device holds — replica dispatch is serializing")
    assert results[1]["peak_concurrent_holds"] <= 1
    speedup = (results[4]["rows_per_s"] / results[1]["rows_per_s"]
               if results[1]["rows_per_s"] else 0.0)
    assert speedup >= min_speedup, (
        f"dp=4 serve throughput is only {speedup:.2f}x dp=1 "
        f"({results[4]['rows_per_s']} vs {results[1]['rows_per_s']} "
        f"rows/s) on the latency-bound dryrun model — replica fan-out "
        "is not overlapping device time")
    return {
        "min_speedup": min_speedup,
        "speedup": round(speedup, 2),
        "device_hold_ms": sleep_s * 1e3,
        "requests": n_req,
        "bucket": bucket,
        "trials": trials,
        "dp1": results[1],
        "dp4": results[4],
    }


def check_serve_lifecycle() -> dict:
    """Zero-downtime model lifecycle under a seeded fault plan: a lane
    kill mid-burst self-heals (requeue + restart, nothing dropped), a
    hot-swap mid-burst flips versions with every answer bit-identical
    to SOME version's offline transform, an induced canary fast-burn
    auto-rolls back with the decision journaled, and compiled programs
    stay ≤ len(buckets) per (model, version). All triggers are
    count-deterministic (serve/faults.py) — the chaos replays."""
    import tempfile
    import threading
    import time

    import jax

    from mmlspark_tpu.core.retry import RetryPolicy
    from mmlspark_tpu.core.stage import LambdaTransformer
    from mmlspark_tpu.data.table import DataTable
    from mmlspark_tpu.models.bundle import ModelBundle
    from mmlspark_tpu.models.jax_model import JaxModel
    from mmlspark_tpu.models.repo import ModelRepo
    from mmlspark_tpu.models.zoo import MLP
    from mmlspark_tpu.serve import (
        Client, FaultPlan, FaultSpec, ModelServer, ServeConfig,
        THREAD_PREFIX, faults,
    )

    buckets, d_in, n_rows = (1, 4, 8), 6, 24

    def bundle(seed):
        module = MLP(features=(8,), num_outputs=4)
        params = module.init(jax.random.PRNGKey(seed),
                             np.zeros((1, d_in), np.float32))["params"]
        return ModelBundle(
            module=module,
            params=jax.tree_util.tree_map(np.asarray, params),
            input_spec=(d_in,), output_names=("features", "logits"),
            name="m")

    def tbl(sl):
        return DataTable({"x": list(sl)})

    def sc(out):
        return np.stack([np.asarray(v) for v in out["s"]])

    rows = np.random.default_rng(0).normal(
        size=(n_rows, d_in)).astype(np.float32)
    workdir = tempfile.mkdtemp(prefix="serve_lifecycle_")

    # the versioned repo is the artifact source: digests verify on load
    repo = ModelRepo(os.path.join(workdir, "repo"))
    v1 = repo.publish("m", bundle(seed=0))
    v2 = repo.publish("m", bundle(seed=1))
    jm1 = JaxModel(model=repo.load("m", v1)[0], input_col="x",
                   output_col="s")
    jm2 = JaxModel(model=repo.load("m", v2)[0], input_col="x",
                   output_col="s")
    off1 = sc(jm1.transform(tbl(rows)))
    off2 = sc(jm2.transform(tbl(rows)))
    assert not np.array_equal(off1, off2)

    def burning_canary():
        def fn(table):
            if len(table) == 0:
                return table.with_column("s", np.asarray([], object))
            raise RuntimeError("induced canary failure")
        return LambdaTransformer(fn=fn)

    server = ModelServer(ServeConfig(
        buckets=buckets, max_queue=512, lifecycle_dir=workdir,
        slo={"objective": 0.99, "min_requests": 4, "window_s": 30.0,
             "long_window_s": 60.0},
        lane_restart=RetryPolicy(max_attempts=4, base_delay_s=0.02,
                                 max_delay_s=0.1, jitter=0.0)))
    result: dict = {"buckets": list(buckets)}
    try:
        server.add_model("m", jm1, example=tbl(rows[:1]), version=v1)

        def burst(pace_s=0.0):
            """4 client threads × 8 two-row requests; returns
            [(offset, scores)] — every response, exactly one per
            request (the zero-dropped/zero-duplicated observable)."""
            client = Client(server, retry=True)  # LaneFailed retries
            results, errors = [], []
            lock = threading.Lock()

            def worker(k):
                try:
                    for i in range(8):
                        off = ((k * 8 + i) * 2) % (n_rows - 2)
                        out = client.predict(
                            "m", tbl(rows[off:off + 2]), timeout=60)
                        with lock:
                            results.append((off, sc(out)))
                        if pace_s:
                            time.sleep(pace_s)
                except BaseException as e:  # noqa: BLE001 — reported
                    with lock:
                        errors.append(f"{type(e).__name__}: {e}")

            threads = [threading.Thread(target=worker, args=(k,))
                       for k in range(4)]
            for t in threads:
                t.start()
            return threads, results, errors

        # -- phase 1: seeded lane kill mid-burst ----------------------
        plan = FaultPlan([FaultSpec("lane_death", model="m", after=2)],
                         seed=42)
        with faults.inject(plan):
            threads, results, errors = burst()
            for t in threads:
                t.join()
        assert errors == [], f"lane-kill burst dropped requests: {errors}"
        assert len(results) == 32
        for off, got in results:
            assert np.array_equal(got, off1[off:off + 2]), (
                "a response during lane self-healing was not "
                "bit-identical to the stable version's offline transform")
        snap1 = server.snapshot()["m"]
        assert snap1["lane_deaths"] == 1, snap1["lane_deaths"]
        assert snap1["lane_restarts"] == 1
        assert snap1["lane_health"]["alive"] == 1
        programs_v1 = server.compiled_programs("m")
        if programs_v1 is not None:
            assert programs_v1 <= len(buckets)
        result["lane_kill"] = {
            "responses": len(results),
            "lane_deaths": snap1["lane_deaths"],
            "lane_restarts": snap1["lane_restarts"],
            "requeued_batches": snap1["requeued_batches"],
            "faults_fired": plan.counts(),
            "programs_v1": programs_v1,
        }

        # -- phase 2: hot-swap mid-burst ------------------------------
        # traffic provably SPANS the flip: workers keep submitting
        # until the swap completes, then a few more — so both versions
        # answer requests in one burst, deterministically
        flipped = threading.Event()
        results, errors = [], []
        lock = threading.Lock()
        client = Client(server, retry=True)

        def swap_worker(k):
            try:
                done_after = i = 0
                while done_after < 3 and i < 500:
                    off = ((k * 8 + i) * 2) % (n_rows - 2)
                    out = client.predict("m", tbl(rows[off:off + 2]),
                                         timeout=60)
                    with lock:
                        results.append((off, sc(out)))
                    if flipped.is_set():
                        done_after += 1
                    i += 1
            except BaseException as e:  # noqa: BLE001 — reported
                with lock:
                    errors.append(f"{type(e).__name__}: {e}")

        threads = [threading.Thread(target=swap_worker, args=(k,))
                   for k in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.02)
        server.add_model("m", jm2, example=tbl(rows[:1]), version=v2)
        flipped.set()
        for t in threads:
            t.join()
        assert errors == [], f"swap burst dropped requests: {errors}"
        v1_served = v2_served = 0
        for off, got in results:
            if np.array_equal(got, off1[off:off + 2]):
                v1_served += 1
            elif np.array_equal(got, off2[off:off + 2]):
                v2_served += 1
            else:
                raise AssertionError(
                    "a response through the hot-swap matches NEITHER "
                    "version's offline transform bit-for-bit")
        assert v2_served >= 4, (
            f"only {v2_served} answers from v2 after the flip — the "
            "swap is not taking traffic")
        post = sc(server.predict("m", tbl(rows[:2])))
        assert np.array_equal(post, off2[:2]), "post-swap not on v2"
        swaps = server.lifecycle_decisions("swap")
        assert len(swaps) == 1 and swaps[0]["to_version"] == v2
        programs_v2 = server.compiled_programs("m")
        if programs_v2 is not None:
            assert programs_v2 <= len(buckets)
        result["hot_swap"] = {
            "responses": len(results),
            "served_v1": v1_served, "served_v2": v2_served,
            "programs_v2": programs_v2,
        }

        # -- phase 3: induced canary fast-burn → auto-rollback --------
        server.deploy_canary("m", burning_canary(), mode="shadow",
                             fraction=1.0, version=v2 + 1)
        first = server.lifecycle_tick("m")
        assert first["action"] == "hold"
        for i in range(8):
            out = sc(server.predict("m", tbl(rows[i:i + 1]), timeout=30))
            assert np.array_equal(out, off2[i:i + 1]), (
                "a stable answer changed while the canary burned")
        time.sleep(0.1)  # past the burn ring's coalescing resolution
        deadline = time.monotonic() + 10
        decision = None
        while time.monotonic() < deadline:
            decision = server.lifecycle_tick("m")
            if decision is None or decision["action"] == "rollback":
                break
            time.sleep(0.05)
        assert decision is not None and decision["action"] == "rollback", (
            f"canary fast-burn did not auto-roll back: {decision}")
        assert decision["burn_short"] >= 14.0
        assert server.canary_status("m") is None
        post = sc(server.predict("m", tbl(rows[:2])))
        assert np.array_equal(post, off2[:2]), "stable lost after rollback"
        with open(os.path.join(workdir, "decisions.jsonl")) as f:
            journaled = [json.loads(ln) for ln in f if ln.strip()]
        kinds = [e["kind"] for e in journaled]
        for expected in ("lane_death", "lane_restart", "swap",
                         "canary_deploy", "rollback"):
            assert expected in kinds, f"{expected!r} not journaled"
        result["canary"] = {
            "burn_short": decision["burn_short"],
            "ticks": decision["ticks"],
            "decision_kinds": sorted(set(kinds)),
        }
    finally:
        server.close()
    leaked = [t.name for t in threading.enumerate()
              if t.name.startswith(THREAD_PREFIX)]
    assert leaked == [], f"serve threads leaked: {leaked}"
    return result


def check_serve_generate(min_speedup: float = 2.0) -> dict:
    """Autoregressive token serving (serve/generate.py): a streaming
    burst with join/leave churn must deliver every request's token
    stream BIT-IDENTICAL to the one-shot whole-sequence decode through
    the same compiled programs (a seeded ``generate_cancel`` churn plan
    truncates some streams — those must be exact PREFIXES), compile at
    most ``len(prefill_buckets) + 1`` XLA programs (the one-fixed-shape
    decode discipline, counted at the engine's own plan cache), publish
    the per-token SLO gauges (TTFT p50/p99, ITL p99) through ``/slo``
    into the timeseries MetricHistory, leak no engine threads, and —
    on a latency-bound decode (callback hold inside the decode
    program, the :func:`_latency_bundle` argument) — sustain
    ≥ ``min_speedup``× the tokens/s of request-serial decoding with
    ≥ 2× fewer decode-step dispatches per token (continuous batching
    actually batches)."""
    import sys as _sys
    import threading
    import time

    import jax

    from mmlspark_tpu import obs
    from mmlspark_tpu.models.sequence import TransformerTagger
    from mmlspark_tpu.obs import timeseries as obs_ts
    from mmlspark_tpu.serve import (
        Client, FaultPlan, FaultSpec, GenerateBatcher, GenerateConfig,
        ModelServer, ServeConfig, THREAD_PREFIX, faults,
    )

    vocab, t_max = 48, 64
    model = TransformerTagger(vocab_size=vocab, embed_dim=16, num_heads=2,
                              num_layers=2, mlp_dim=32, num_tags=vocab,
                              max_len=t_max, causal=True)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, 8), np.int32))["params"]
    cfg = GenerateConfig(slots=4, t_max=t_max, prefill_buckets=(4, 8),
                         prefill_rows=2, max_new_tokens=8, max_queue=64)
    rng = np.random.default_rng(0)
    n_req = 12
    prompts = [[int(t) for t in rng.integers(1, vocab,
                                             int(rng.integers(2, 9)))]
               for _ in range(n_req)]
    budgets = [int(rng.integers(4, 13)) for _ in range(n_req)]

    obs.disable()
    obs.clear()
    obs.registry().reset()
    obs.enable()
    result: dict = {"requests": n_req,
                    "prefill_buckets": list(cfg.prefill_buckets)}
    server = ModelServer(ServeConfig(slo={
        "objective": 0.99, "min_requests": 1,
        "window_s": 2.0, "long_window_s": 4.0}))
    sampler = obs_ts.enable(
        interval_s=3600.0,  # on-demand: one history sample per /slo poll
        registries=lambda: [obs.registry()] + server.metric_registries())
    try:
        server.add_generator("lm", model, params, config=cfg)
        # the one-shot references FIRST: same engine, same compiled
        # programs, fresh buffers — what every stream must reproduce
        refs = [server.generate_oneshot("lm", p, n)
                for p, n in zip(prompts, budgets)]
        assert all(len(r) >= 1 for r in refs)

        # -- the streaming burst, under a seeded churn plan (clients
        #    abandoning streams mid-decode → slot leave/rejoin) --
        churn = FaultPlan([FaultSpec("generate_cancel", model="lm",
                                     after=6, times=2)], seed=11)
        client = Client(server)
        with faults.inject(churn):
            streams = [client.generate("lm", p, max_new_tokens=n,
                                       stream=True)
                       for p, n in zip(prompts, budgets)]
            got = [st.result(timeout=300) for st in streams]
        cancelled = sum(1 for st in streams if st.cancelled)
        assert churn.counts().get("generate_cancel", 0) >= 1 \
            and cancelled >= 1, (
            f"the seeded churn plan never cancelled a stream "
            f"(fired={churn.counts()}, cancelled={cancelled}) — the "
            "join/leave path went unexercised")
        for i, (st, toks) in enumerate(zip(streams, got)):
            if st.cancelled:
                assert toks == refs[i][:len(toks)], (
                    f"request {i}: cancelled stream is not a prefix of "
                    f"the one-shot decode: {toks} vs {refs[i]}")
            else:
                assert toks == refs[i], (
                    f"request {i}: continuously-batched stream diverged "
                    f"from the one-shot whole-sequence decode: {toks} "
                    f"vs {refs[i]} — slot state is leaking across "
                    "requests")

        snap = server.snapshot()["lm"]
        assert snap.get("generator") is True
        programs = snap["programs_compiled"]
        budget = len(cfg.prefill_buckets) + 1
        if programs is not None:
            assert programs <= budget, (
                f"{programs} XLA programs for a "
                f"{len(cfg.prefill_buckets)}-bucket prefill ladder + ONE "
                f"decode program (budget {budget}) — join/leave churn is "
                "recompiling the decode step")
        assert snap["decode_steps"] > 0
        occ = snap["slot_occupancy_mean"]
        assert occ is not None and occ > 1.0 / cfg.slots, (
            f"mean slot occupancy {occ} under a {n_req}-request burst "
            f"on {cfg.slots} slots — the engine is decoding one request "
            "at a time")

        # -- per-token SLO gauges through /slo into MetricHistory --
        slo = None
        for _ in range(3):
            slo = server.slo_snapshot()
            sampler.sample()
            time.sleep(0.01)
        g = slo["lm"]
        assert g.get("generator") is True
        assert g["ttft_ms"] and g["ttft_ms"]["p50"] > 0 \
            and g["ttft_ms"]["p99"] >= g["ttft_ms"]["p50"], g["ttft_ms"]
        assert g["itl_ms"] and g["itl_ms"]["p99"] > 0, g["itl_ms"]
        history = {}
        for gname in ("serve.ttft_p50_ms", "serve.ttft_p99_ms",
                      "serve.itl_p99_ms"):
            series = obs_ts.range_(gname)
            assert series, f"no MetricHistory for {gname} — the "\
                "serve.ttft_/serve.itl_ sampler prefixes regressed"
            for key, samples in series.items():
                assert len(samples) >= 3, (
                    f"timeseries {key} holds {len(samples)} sample(s); "
                    "the per-token SLO history needs >= 3")
            history[gname] = {k: len(v) for k, v in series.items()}
        result["burst"] = {
            "cancelled": cancelled,
            "faults_fired": churn.counts(),
            "programs_compiled": programs,
            "program_budget": budget,
            "decode_steps": snap["decode_steps"],
            "tokens_out": snap["tokens_out"],
            "slot_occupancy_mean": occ,
            "ttft_ms": g["ttft_ms"],
            "itl_ms": g["itl_ms"],
            "slo_gauge_history": history,
        }
    finally:
        server.close()
        obs_ts.disable()
        obs.disable()
        obs.clear()
        obs.registry().reset()
    leaked = [t.name for t in threading.enumerate()
              if t.name.startswith(THREAD_PREFIX)]
    assert leaked == [], f"generate engine threads leaked: {leaked}"

    # -- continuous batching vs request-serial decode on a
    #    latency-bound model: the decode program holds inside a
    #    callback (a real device's per-step latency the host does not
    #    pay), so packed slots amortize it and serial decode cannot --
    from mmlspark_tpu.ops.pallas.attention import decode_attention

    hold_s = 0.006  # ×2 layers = 12 ms per decode dispatch

    def holding_attention(q, k, v, keep):
        out = decode_attention(q, k, v, keep)

        def hold(x):
            time.sleep(hold_s)
            return x

        return jax.pure_callback(
            hold, jax.ShapeDtypeStruct(out.shape, out.dtype), out)

    cfg2 = GenerateConfig(slots=4, t_max=32, prefill_buckets=(8,),
                          prefill_rows=4, max_new_tokens=8, max_queue=64)
    n2, max_new2 = 8, 8
    prompts2 = [[int(t) for t in rng.integers(1, vocab, 6)]
                for _ in range(n2)]
    runs: dict[str, dict] = {}
    tokens_by_mode: dict[str, list] = {}
    switch = _sys.getswitchinterval()
    _sys.setswitchinterval(0.001)
    try:
        for mode in ("serial", "batched"):
            engine = GenerateBatcher(f"lm_{mode}", model, params,
                                     config=cfg2,
                                     decode_attention_fn=holding_attention)
            try:
                # warm both programs outside the timed burst
                engine.submit(prompts2[0], max_new_tokens=2).result(
                    timeout=300)
                steps0 = engine.stats.decode_steps
                t0 = time.perf_counter()
                if mode == "serial":
                    toks = [engine.submit(p, max_new_tokens=max_new2)
                            .result(timeout=300) for p in prompts2]
                else:
                    pending = [engine.submit(p, max_new_tokens=max_new2)
                               for p in prompts2]
                    toks = [st.result(timeout=300) for st in pending]
                wall = time.perf_counter() - t0
                steps = engine.stats.decode_steps - steps0
            finally:
                engine.close()
            n_tokens = sum(len(t) for t in toks)
            tokens_by_mode[mode] = toks
            runs[mode] = {
                "tokens": n_tokens,
                "wall_s": round(wall, 4),
                "tokens_per_s": round(n_tokens / wall, 1),
                "decode_steps": steps,
            }
    finally:
        _sys.setswitchinterval(switch)
    assert tokens_by_mode["batched"] == tokens_by_mode["serial"], (
        "batched decode produced different tokens than request-serial "
        "decode — continuous batching is not row-independent")
    step_ratio = (runs["serial"]["decode_steps"]
                  / max(1, runs["batched"]["decode_steps"]))
    assert step_ratio >= 2.0, (
        f"continuous batching dispatched only {step_ratio:.2f}x fewer "
        f"decode steps than request-serial decode "
        f"({runs['serial']['decode_steps']} vs "
        f"{runs['batched']['decode_steps']}) for {cfg2.slots} slots — "
        "requests are not sharing decode dispatches")
    speedup = (runs["batched"]["tokens_per_s"]
               / runs["serial"]["tokens_per_s"]
               if runs["serial"]["tokens_per_s"] else 0.0)
    assert speedup >= min_speedup, (
        f"continuous batching sustained only {speedup:.2f}x the "
        f"request-serial tokens/s ({runs['batched']['tokens_per_s']} vs "
        f"{runs['serial']['tokens_per_s']}) on the latency-bound decode "
        "— slot packing is not amortizing the per-step device latency")
    leaked = [t.name for t in threading.enumerate()
              if t.name.startswith(THREAD_PREFIX)]
    assert leaked == [], f"generate engine threads leaked: {leaked}"
    result["throughput"] = {
        "min_speedup": min_speedup,
        "speedup": round(speedup, 2),
        "step_ratio": round(step_ratio, 2),
        "device_hold_ms": hold_s * 2 * 1e3,
        "slots": cfg2.slots,
        "serial": runs["serial"],
        "batched": runs["batched"],
    }
    return result


def check_serve_lowprec(tolerance: float = 6e-2) -> dict:
    """Serve a model int8w+bf16 (weight-only int8, bf16 activations —
    core/precision.py); raise AssertionError unless its outputs stay
    within the pinned per-model ``tolerance`` of the f32 OFFLINE
    transform across packings (single-row, partial-bucket, and
    full-bucket requests), compiled programs stay ≤ ``len(buckets)``
    for the (model, precision), the load-time calibration measured a
    real (non-zero, in-tolerance) parity, the quantized params ship
    ≤ 0.35× the f32 bytes, and ``audit_plan_spmd`` verifies the
    QUANTIZED segment clean (zero manual collectives) — the serving
    half of ROADMAP item 5, gated the PR 9 way on counted seams, not
    wall clock."""
    import jax

    from mmlspark_tpu.analysis.spmd import audit_plan_spmd
    from mmlspark_tpu.core import plan
    from mmlspark_tpu.core.precision import (
        PrecisionPolicy, quantized_bytes,
    )
    from mmlspark_tpu.data.table import DataTable
    from mmlspark_tpu.models.bundle import ModelBundle
    from mmlspark_tpu.models.jax_model import JaxModel
    from mmlspark_tpu.models.zoo import MLP
    from mmlspark_tpu.serve import ModelServer, ServeConfig

    buckets, d_in, n_req = (1, 8), 24, 24
    rng = np.random.default_rng(0)
    module = MLP(features=(32,), num_outputs=8)
    params = module.init(jax.random.PRNGKey(0),
                         np.zeros((1, d_in), np.float32))["params"]
    bundle = ModelBundle(
        module=module,
        params=jax.tree_util.tree_map(np.asarray, params),
        input_spec=(d_in,), output_names=("features", "logits"))

    def jm():
        return JaxModel(model=bundle, input_col="x", output_col="scores",
                        mesh_spec={"dp": 1})

    rows = (rng.normal(size=(n_req, d_in)) * 2).astype(np.float32)
    table = DataTable({"x": list(rows)})
    ref = np.stack(list(jm().transform(table)["scores"]))  # f32 offline

    policy = PrecisionPolicy(mode="int8w", tolerance=tolerance)
    served = jm()
    server = ModelServer(ServeConfig(buckets=buckets, max_queue=n_req + 8,
                                     deadline_ms=None))
    try:
        server.add_model("m", served, precision=policy,
                         example=table.take(np.arange(8)))
        snap_load = server.snapshot()["m"]
        # packings: 8 single-row, 2× 4-row (partial bucket), 1× 8-row
        handles = [(i, 1, server.submit("m", table.take(np.arange(i, i + 1))))
                   for i in range(8)]
        handles += [(i, 4, server.submit(
            "m", table.take(np.arange(i, i + 4)))) for i in (8, 12)]
        handles += [(16, 8, server.submit(
            "m", table.take(np.arange(16, 24))))]
        worst = 0.0
        for start, n, h in handles:
            got = np.stack(list(h.result(timeout=120)["scores"]))
            worst = max(worst, float(
                np.abs(got - ref[start:start + n]).max()))
        programs = server.compiled_programs("m")
        snap = server.stats("m").snapshot()
    finally:
        server.close()

    assert worst > 0.0, (
        "int8w serving returned the f32 outputs bit-for-bit — the "
        "precision pass is not engaging (cache key or policy threading "
        "regressed)")
    assert worst <= tolerance, (
        f"int8w+bf16 serving diverges from the f32 offline transform by "
        f"max-abs {worst:.4g} across packings (pinned per-model "
        f"tolerance {tolerance:g})")
    calibrated = snap_load.get("precision_parity")
    assert calibrated is not None and 0 < calibrated <= tolerance, (
        f"load-time calibration parity {calibrated!r} is missing or "
        f"out of tolerance — ModelServer.add_model's calibration flow "
        "regressed")
    assert snap_load.get("precision", "").startswith("int8w")
    if programs is not None:
        assert programs <= len(buckets), (
            f"{programs} XLA programs for a {len(buckets)}-bucket ladder "
            "under ONE precision — per-(model, precision) compiles must "
            "stay on the ladder")
    assert snap["distinct_batch_shapes"] <= len(buckets)

    # the quantized storage really ships thin (the HBM/wire win)
    seg = plan.collect_segment(
        [served], 0, lambda c: plan._entry_meta(table, c),
        min_stages=1, precision=policy)
    _fn, stored = plan.segment_composite(seg, plan._segment_mesh(seg))
    nbytes, f32_bytes = quantized_bytes(stored)
    assert nbytes <= 0.35 * f32_bytes, (
        f"quantized params are {nbytes} B vs {f32_bytes} B f32 — int8 "
        "weight storage regressed")

    # the QUANTIZED segment verifies clean against the serve contracts
    audit = audit_plan_spmd([served],
                            lambda c: plan._entry_meta(table, c),
                            n_rows=n_req, precision=policy)
    assert audit.ok and len(audit.segments) == 1, audit.format()
    assert audit.segments[0].schedule.ops == [], (
        "the precision pass introduced manual collectives into the "
        "served segment")

    return {
        "buckets": list(buckets),
        "requests": len(handles),
        "precision": policy.describe(),
        "pinned_tolerance": tolerance,
        "calibration_parity": calibrated,
        "serve_parity_max_abs": worst,
        "programs_compiled": programs,
        "distinct_batch_shapes": snap["distinct_batch_shapes"],
        "quantized_bytes": nbytes,
        "f32_bytes": f32_bytes,
        "weight_bytes_ratio": round(nbytes / f32_bytes, 4),
        "audit_findings": len(audit.findings),
        "audit_collectives": len(audit.segments[0].schedule.ops),
    }


def check_obs_request_tracing(n_req: int = 200, dp: int = 4) -> dict:
    """A serve burst across dp replica lanes; raise AssertionError
    unless every completed request resolves to exactly one request
    trace with intact fan-in/fan-out links.

    The request-scoped tracing contract (docs/observability.md): a
    trace id is minted at admission, the admit/complete spans carry it,
    and the pack/dispatch/drain bucket-batch spans link every coalesced
    member — so the registry of captured spans reconstructs each
    request's whole journey across the scheduler and replica-lane
    threads, and the Chrome-trace export draws it as one flow. Uses the
    latency-bound callback-hold model of :func:`check_serve_sharded` so
    all ``dp`` lanes deterministically participate."""
    import jax

    from mmlspark_tpu import obs
    from mmlspark_tpu.data.table import DataTable
    from mmlspark_tpu.models.jax_model import JaxModel
    from mmlspark_tpu.obs import context as obs_context
    from mmlspark_tpu.serve import ModelServer, ServeConfig

    if len(jax.devices()) < dp:
        raise AssertionError(
            f"check_obs_request_tracing needs >= {dp} devices for the "
            f"dp={dp} fan-out; got {len(jax.devices())}")
    buckets = (1, 8, 32)
    bundle, probe = _latency_bundle(0.004)
    rng = np.random.default_rng(0)
    rows = rng.normal(size=(n_req, 24)).astype(np.float32)

    obs.disable()
    obs.clear()
    obs.registry().reset()
    obs.enable()
    try:
        jm = JaxModel(model=bundle, input_col="x", output_col="scores")
        server = ModelServer(ServeConfig(
            buckets=buckets, max_queue=n_req + 8, deadline_ms=None,
            mesh=f"dp={dp}"))
        try:
            server.add_model("m", jm,
                             example=DataTable({"x": [rows[0]]}))
            obs.clear()  # warmup spans out: count the burst only
            handles = [server.submit("m", DataTable({"x": [rows[i]]}))
                       for i in range(n_req)]
            outs = [h.result(timeout=300) for h in handles]
            snap = server.stats("m").snapshot()
        finally:
            server.close()
        assert all(len(o) == 1 and "scores" in o for o in outs)
        assert snap["completed"] == n_req

        trace_ids = [h.trace_id for h in handles]
        assert all(t is not None for t in trace_ids), (
            "tracer enabled but requests carry no trace id — minting "
            "at admission regressed")
        assert len(set(trace_ids)) == n_req, (
            f"{len(set(trace_ids))} distinct trace ids for {n_req} "
            "requests — trace ids must be unique per request")
        traces = obs_context.request_traces()
        broken = []
        for h in handles:
            spans = traces.get(h.trace_id)
            if spans is None:
                broken.append((h.trace_id, "no spans captured"))
                continue
            why = obs_context.check_journey(spans)
            if why is not None:
                broken.append((h.trace_id, why))
        assert not broken, (
            f"{len(broken)}/{n_req} completed requests lack an intact "
            f"admission → pack → dispatch → drain → complete trace; "
            f"first failures: {broken[:5]}")

        # the fan-in is real: at least one bucket-batch span links >1
        # request (the burst coalesces), and the fan-out reached every
        # replica lane
        pack_links = [len(s.links or ()) for s in obs.captured()
                      if getattr(s, "name", "") == "serve/pack"]
        assert pack_links and max(pack_links) > 1, (
            f"no pack span linked more than one request "
            f"({pack_links}) — fan-in links regressed")
        assert sorted(snap["replicas"]) == list(range(dp)), (
            f"burst used replicas {sorted(snap['replicas'])} of "
            f"{list(range(dp))}")

        # every trace renders as one flow in the export
        trace = obs.chrome_trace()
        flow_ids = {e["id"] for e in trace["traceEvents"]
                    if e.get("ph") in ("s", "t", "f")}
        missing_flows = set(trace_ids) - flow_ids
        assert not missing_flows, (
            f"{len(missing_flows)} request traces have no Perfetto "
            "flow events in the export")
    finally:
        obs.disable()
        obs.clear()
        obs.registry().reset()

    return {
        "requests": n_req,
        "dp": dp,
        "buckets": list(buckets),
        "traces": len(set(trace_ids)),
        "intact": n_req - len(broken),
        "batches": snap["batches"],
        "batch_occupancy_mean": snap["batch_occupancy_mean"],
        "max_pack_fan_in": max(pack_links),
        "replicas_used": sorted(snap["replicas"]),
        "flow_ids_exported": len(flow_ids & set(trace_ids)),
    }


def _well_formed_dump(path: str) -> dict:
    """Load one flight-recorder dump and assert the post-mortem contract:
    intact ring, per-thread stacks, registry snapshot, heartbeat table,
    mesh/config fingerprint — and that ``tools/trace.py postmortem``
    renders it (exit 0)."""
    with open(path, "r", encoding="utf-8") as fh:
        dump = json.load(fh)
    for key in ("flight", "reason", "ring", "threads", "registry",
                "heartbeats", "fingerprint"):
        assert key in dump, f"dump {path} is missing {key!r}"
    assert isinstance(dump["ring"], list) and dump["ring"], (
        f"dump {path} captured an empty span/event ring")
    assert all(isinstance(r, dict) and "name" in r
               for r in dump["ring"]), "malformed ring records"
    assert dump["threads"], f"dump {path} captured no thread stacks"
    assert all(isinstance(t, dict) and t.get("stack")
               for t in dump["threads"].values()), (
        "a dumped thread has an empty stack")
    assert "counters" in dump["registry"], "registry snapshot malformed"
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "mmlspark_tools_trace",  # plain `import trace` would shadow the
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "trace.py"))  # stdlib module of the same name
    trace_cli = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trace_cli)
    code = trace_cli.main(["postmortem", path])
    assert code == 0, (
        f"tools/trace.py postmortem exited {code} on {path}")
    return dump


def check_flight_recorder() -> dict:
    """Induce a mid-run crash AND a hang on the dryrun mesh; raise
    AssertionError unless each produces a well-formed flight-recorder
    dump (recent ring + per-thread stacks + registry snapshot) that
    ``tools/trace.py postmortem`` renders.

    The crash is a NaN'd training batch dying on the typed
    :class:`NonFiniteLossError` (the anomaly plane's sentinel riding the
    lagged loss fetch) — the flight recorder dumps at the failure point,
    inside ``Trainer.fit_arrays``. The hang is a serve-lane dispatch
    stalled inside its compiled program (the callback-hold model of
    :func:`check_serve_sharded`, held past the recorder's hang
    threshold) — the lane heartbeat goes stale while busy and the
    watchdog dumps, naming the lane, before the dispatch completes."""
    import glob
    import tempfile
    import time

    from mmlspark_tpu import obs
    from mmlspark_tpu.data.table import DataTable
    from mmlspark_tpu.models.jax_model import JaxModel
    from mmlspark_tpu.models.zoo import ConvNetCifar
    from mmlspark_tpu.obs import flight
    from mmlspark_tpu.obs.anomaly import NonFiniteLossError
    from mmlspark_tpu.serve import ModelServer, ServeConfig
    from mmlspark_tpu.train.loop import TrainConfig, Trainer

    out: dict = {}
    try:
        # ---- induced crash: NaN batch → typed raise → dump ----
        crash_dir = tempfile.mkdtemp(prefix="flight_crash_")
        flight.enable(crash_dir, poll_s=0.05)
        rng = np.random.default_rng(0)
        x = rng.normal(size=(64, 32, 32, 3)).astype(np.float32)
        x[5] = np.nan  # lands in step 1's batch
        y = rng.integers(0, 10, 64).astype(np.int64)
        tr = Trainer(ConvNetCifar(num_classes=10, widths=(4,),
                                  dense_width=8),
                     TrainConfig(batch_size=16, epochs=1, optimizer="sgd",
                                 learning_rate=0.1, log_every=1,
                                 prefetch_depth=0, input_scale=1.0))
        crashed = None
        try:
            tr.fit_arrays(x, y)
        except NonFiniteLossError as e:
            crashed = e
        assert crashed is not None, (
            "the NaN'd batch did not raise NonFiniteLossError — the "
            "non-finite sentinel regressed")
        crash_dumps = sorted(glob.glob(
            os.path.join(crash_dir, "flight_crash_*.json")))
        assert crash_dumps, (
            "NonFiniteLossError raised but no flight_crash_*.json dump "
            "appeared — Trainer.fit_arrays is not calling "
            "obs.flight.on_crash at the failure point")
        crash = _well_formed_dump(crash_dumps[-1])
        assert crash["exception"]["type"] == "NonFiniteLossError", (
            f"crash dump recorded {crash['exception']['type']}, expected "
            "the sentinel's NonFiniteLossError")
        assert any(r.get("name") == "train/step" for r in crash["ring"]), (
            "crash dump ring holds no train/step spans — the recorder "
            "is not dumping the live tracer ring")
        flight.disable()

        # ---- induced hang: dispatch stalled past the threshold ----
        hang_dir = tempfile.mkdtemp(prefix="flight_hang_")
        hold_s, threshold_s = 1.2, 0.3
        bundle, _probe = _latency_bundle(hold_s)
        jm = JaxModel(model=bundle, input_col="x", output_col="scores")
        server = ModelServer(ServeConfig(buckets=(1,), max_queue=8,
                                         deadline_ms=None))
        try:
            server.add_model("m", jm, example=DataTable(
                {"x": [np.zeros(24, np.float32)]}))
            # enable AFTER the load+warm: only the stalled dispatch is
            # under watch
            flight.enable(hang_dir, hang_threshold_s=threshold_s,
                          poll_s=0.05)
            h = server.submit("m", DataTable(
                {"x": [np.zeros(24, np.float32)]}))
            deadline = time.monotonic() + 30.0
            hang_dumps: list = []
            while time.monotonic() < deadline and not hang_dumps:
                hang_dumps = glob.glob(
                    os.path.join(hang_dir, "flight_hang_*.json"))
                time.sleep(0.05)
            result = h.result(timeout=60)  # the stall completes after
            assert len(result) == 1 and "scores" in result
        finally:
            server.close()
        assert hang_dumps, (
            f"no hang dump after a {hold_s}s dispatch stall against a "
            f"{threshold_s}s threshold — the lane heartbeat or watchdog "
            "regressed")
        hang = _well_formed_dump(hang_dumps[0])
        stalled = hang["extra"]["heartbeat"]
        assert stalled.startswith("serve/"), (
            f"hang dump blames heartbeat {stalled!r}, expected the "
            "serve lane that was holding")
        assert hang["extra"]["stalled_for_s"] >= threshold_s
        lane_threads = [t["name"] for t in hang["threads"].values()]
        assert any("ServeLane" in n or "lane" in n.lower()
                   or "DynamicBatcher" in n for n in lane_threads) \
            or len(lane_threads) >= 2, (
            f"hang dump captured threads {lane_threads} — the stalled "
            "lane's stack is missing")
        out = {
            "crash_dump": crash_dumps[-1],
            "crash_exception": crash["exception"]["type"],
            "crash_ring_records": len(crash["ring"]),
            "crash_threads": len(crash["threads"]),
            "hang_dump": hang_dumps[0],
            "hang_heartbeat": stalled,
            "hang_stalled_for_s": hang["extra"]["stalled_for_s"],
            "hang_ring_records": len(hang["ring"]),
            "hang_threads": len(hang["threads"]),
        }
    finally:
        flight.disable()
        obs.disable()
        obs.clear()
        obs.registry().reset()
    return out


# the gate's jax-free supervised worker: records train spans + counters
# through the obs substrate (tracer on via the supervisor's
# MMLSPARK_TPU_OBS, fleet exporter on via the propagated
# MMLSPARK_TPU_FLEET), writes its registry-counter TRUTH file for the
# bit-equality assertion, then flushes its final fleet snapshot
_FLEET_WORKER_SRC = """
import json, os, time
from mmlspark_tpu import obs
from mmlspark_tpu.obs import fleet
from mmlspark_tpu.obs.metrics import Counter, format_series
from mmlspark_tpu.train.service import service_context

with service_context(beacon_interval_s=0.05) as info:
    assert info is not None
    assert obs.enabled()        # MMLSPARK_TPU_OBS=1 from the supervisor
    assert fleet.enabled()      # MMLSPARK_TPU_FLEET propagated
    reg = obs.registry()
    for k in range(24):
        with obs.span("train/step", "train"):
            time.sleep(0.0005)
        reg.counter("train.steps").add()
        reg.counter("train.commits", loader="w%d" % info.rank).add(2)
        if k % 8 == 0:
            # the fenced-collective seam the fleet trace stitches at
            with obs.span("train/liveness_sync", "train"):
                time.sleep(0.002)
    reg.gauge("train.host_step_ms", host=str(info.rank)).set(
        1.0 + info.rank)
    time.sleep(0.2)  # >= one beacon interval with the final counters
    truth = {format_series(m.name, m.labels): m.value
             for m in reg.iter_metrics() if isinstance(m, Counter)}
    with open(os.path.join(info.service_dir,
                           "truth_%d.json" % info.rank), "w") as f:
        json.dump(truth, f)
    fleet.disable()  # final exit snapshot AFTER the truth capture
"""


def check_fleet_obs() -> dict:
    """The fleet telemetry plane (obs/fleet.py + obs/timeseries.py): a
    dp=4 serve burst plus a 2-worker supervised run exporting under ONE
    ``MMLSPARK_TPU_FLEET`` directory must merge into a fleet view whose
    summed ``serve.*``/``train.*`` counters are BIT-EQUAL to the sum of
    the per-process registries (this process's + both workers' truth
    files), render a clock-aligned fleet Perfetto trace that
    ``tools/trace.py render`` accepts exit-0 (with >= 1 stitched
    cross-process flow at the workers' fence seams), and leave a
    non-empty timeseries history (>= 3 samples) for every
    ``serve.slo_burn_*`` gauge — the metric HISTORY the adaptive-ladder
    and autoscaling actuators consume. Teardown is pinned: no
    FleetExporter/TimeSeriesSampler threads survive, and the tracer is
    left disabled so ``check_obs_overhead`` stays honest."""
    import json as _json
    import shutil
    import sys as _sys
    import tempfile
    import threading
    import time

    import jax

    from mmlspark_tpu import obs
    from mmlspark_tpu.data.table import DataTable
    from mmlspark_tpu.models.jax_model import JaxModel
    from mmlspark_tpu.obs import fleet as obs_fleet
    from mmlspark_tpu.obs import timeseries as obs_ts
    from mmlspark_tpu.obs.metrics import Counter, format_series, registry
    from mmlspark_tpu.serve import ModelServer, ServeConfig
    from mmlspark_tpu.train.service import (
        RecoveryPolicy, ServiceConfig, Topology, TrainSupervisor,
    )

    if len(jax.devices()) < 4:
        raise AssertionError(
            "check_fleet_obs needs >= 4 dryrun devices for the dp=4 "
            f"serve mesh; got {len(jax.devices())}")
    fleet_dir = tempfile.mkdtemp(prefix="fleet_obs_")
    svc_dir = os.path.join(fleet_dir, "service")
    obs.enable()
    obs.clear()
    registry().reset()
    obs_fleet.enable(fleet_dir, interval_s=0.2)
    server = None
    try:
        # -- 1. the dp=4 serve burst (latency-bound model, as in the
        #       sharded/tracing gates) + 3 SLO polls, each followed by
        #       one timeseries sample --
        bundle, _probe = _latency_bundle(0.004)
        jm = JaxModel(model=bundle, input_col="x", output_col="scores")
        server = ModelServer(ServeConfig(
            buckets=(8,), max_queue=64, deadline_ms=None, mesh="dp=4",
            slo={"window_s": 2.0, "long_window_s": 4.0,
                 "min_requests": 1}))
        rng = np.random.default_rng(0)
        reqs = [DataTable({"x": list(
            rng.normal(size=(8, 24)).astype(np.float32))})
            for _ in range(24)]
        server.add_model("m", jm, example=reqs[0].take(np.arange(1)))
        handles = [server.submit("m", r) for r in reqs]
        outs = [h.result(timeout=120) for h in handles]
        assert len(outs) == len(reqs)
        sampler = obs_ts.sampler()
        assert sampler is not None, (
            "obs.fleet.enable must start the timeseries sampler")
        for _ in range(3):
            server.slo_snapshot()   # publishes the serve.slo_burn_* /
            sampler.sample()        # queue-depth gauges; one history
            time.sleep(0.01)        # sample per poll
        burn_history = {}
        for gname in ("serve.slo_burn_short", "serve.slo_burn_long"):
            got = obs_ts.range_(gname)
            assert got, f"no timeseries history for {gname}"
            for key, samples in got.items():
                assert len(samples) >= 3, (
                    f"timeseries {key} holds {len(samples)} sample(s); "
                    "the SLO-gauge history needs >= 3")
            burn_history[gname] = {k: len(v) for k, v in got.items()}
        assert obs_ts.range_("serve.queue_depth"), (
            "no serve.queue_depth history")

        # -- 2. the 2-worker supervised run (jax-free workers; the
        #       supervisor propagates MMLSPARK_TPU_FLEET and publishes
        #       train.fleet.* aggregates from the beacon excerpts) --
        report = TrainSupervisor(ServiceConfig(
            cmd=(_sys.executable, "-c", _FLEET_WORKER_SRC),
            service_dir=svc_dir, topologies=(Topology(world=2),),
            policy=RecoveryPolicy(), poll_s=0.05, grace_seconds=15.0,
            worker_obs=True, worker_flight=False)).run()
        assert report.ok, f"fleet worker generation failed: {report.reason}"
        truths = []
        for rank in (0, 1):
            with open(os.path.join(svc_dir, f"truth_{rank}.json"),
                      encoding="utf-8") as fh:
                truths.append(_json.load(fh))
        fleet_steps = registry().value("train.fleet.steps", rank=0)
        assert fleet_steps == 24, (
            "supervisor did not aggregate worker beacon deltas into "
            f"train.fleet.steps{{rank=0}} (got {fleet_steps})")
        assert (registry().value("train.fleet.steps", rank=0) or 0) \
            + (registry().value("train.fleet.steps", rank=1) or 0) == 48

        # -- 3. expected fleet sum: THIS process's counters (default +
        #       per-model serve registries) + both workers' truths —
        #       captured immediately before the final snapshot --
        expected: dict[str, float] = {}

        def _acc(items):
            for key, value in items:
                expected[key] = expected.get(key, 0.0) + float(value)

        for reg in [registry()] + server.metric_registries():
            _acc((format_series(m.name, m.labels), m.value)
                 for m in reg.iter_metrics() if isinstance(m, Counter))
        for truth in truths:
            _acc(truth.items())
        obs_fleet.disable()   # writes the final exit snapshot
        server.close()

        # -- 4. merge + bit-equality --
        view = obs_fleet.FleetCollector(fleet_dir).collect()
        merged = {format_series(m.name, m.labels): m.value
                  for m in view.registry.iter_metrics()
                  if isinstance(m, Counter)}
        missing = {k: v for k, v in expected.items()
                   if merged.get(k) != v}
        extra = sorted(set(merged) - set(expected))
        assert not missing and not extra, (
            "fleet-merged counters are not bit-equal to the summed "
            f"per-process registries: mismatched={missing} "
            f"extra={extra}")
        n_serve = sum(1 for k in merged if k.startswith("serve."))
        n_train = sum(1 for k in merged if k.startswith("train."))
        assert n_serve > 0 and n_train > 0

        # -- 5. the fleet timeline renders exit-0 through the CLI --
        trace_path = os.path.join(fleet_dir, "fleet_trace.json")
        fleet_payload = view.chrome_trace()
        with open(trace_path, "w", encoding="utf-8") as fh:
            _json.dump(fleet_payload, fh)
        meta = fleet_payload["fleetMeta"]
        assert meta["unaligned"] == []
        assert meta["stitched_flows"] >= 1, (
            "no cross-process flow stitched at the workers' "
            "train/liveness_sync fence seams")
        import importlib.util as _ilu
        spec = _ilu.spec_from_file_location(
            "mmlspark_tools_trace",
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "trace.py"))
        trace_cli = _ilu.module_from_spec(spec)
        spec.loader.exec_module(trace_cli)
        rc = trace_cli.main(["render", trace_path, "--top", "5"])
        assert rc == 0, f"tools/trace.py render exited {rc} on the " \
                        "fleet trace"
        return {
            "processes": len(view.processes),
            "counters_merged": len(merged),
            "serve_counters": n_serve,
            "train_counters": n_train,
            "stitched_flows": meta["stitched_flows"],
            "trace_render_rc": rc,
            "burn_gauge_history": burn_history,
            "fleet_steps_rank0": int(fleet_steps),
            "supervisor_ok": report.ok,
        }
    finally:
        obs_fleet.disable()
        if server is not None:
            server.close()
        obs.disable()
        obs.clear()
        registry().reset()
        leaked = [t.name for t in threading.enumerate()
                  if t.name in ("FleetExporter", "TimeSeriesSampler")]
        assert not leaked, f"fleet threads leaked: {leaked}"
        shutil.rmtree(fleet_dir, ignore_errors=True)


def check_obs_overhead(max_fraction: float = 0.02) -> dict:
    """The obs seams' disabled-path cost on the fused-pipeline microbench
    must stay under ``max_fraction`` (2%) of the transform itself.

    Methodology (all measured, no A/B wall-clock diff to flake):

    1. time one warm fused transform with the tracer OFF (median of 5);
    2. run it once with the tracer ON and count what the seams actually
       did — spans recorded and counter increments — giving the number
       of disabled-path flag checks one transform performs;
    3. measure the per-call cost of the disabled seam itself (a
       ``span()`` call: one module-flag check + shared null context —
       strictly an upper bound on a bare flag check) over 200k calls;
    4. gate ``unit_cost × seam_calls / transform_time < max_fraction``.
    """
    import statistics
    import time

    from mmlspark_tpu import obs
    from mmlspark_tpu.obs.metrics import registry
    from mmlspark_tpu.obs.spans import span as obs_span

    assert not obs.enabled(), (
        "check_obs_overhead must start with the tracer disabled")
    pm, table, _n, _mb = canonical_pipeline()
    pm.transform(table)  # compile + warm outside the timed passes

    t_run = statistics.median(
        _timed_once(pm, table, time) for _ in range(5))

    # count the seams one transform hits: every span and every counter
    # increment is one disabled-path flag check (plus the span-call
    # overhead where a span exists — bounded below by pricing EVERY site
    # at the span() unit cost, the more expensive of the two)
    registry().reset()
    obs.enable()
    obs.clear()
    try:
        pm.transform(table)
        n_spans = len(obs.captured())
        counters = registry().snapshot()["counters"]
        n_increments = int(
            3 * counters.get("plan.h2d_uploads", 0)       # uploads+bytes+shape
            + 2 * counters.get("plan.d2h_fetches", 0)     # fetch + d2h bytes
            + counters.get("plan.segment_compiles", 0))
    finally:
        obs.disable()
        obs.clear()
        registry().reset()
    # enter/exit both touch the seam; +8 for timed()'s lazy imports etc.
    seam_calls = 2 * n_spans + n_increments + 8

    reps = 200_000
    t0 = time.perf_counter()
    for _ in range(reps):
        obs_span("overhead-probe", "bench")
    unit = (time.perf_counter() - t0) / reps

    fraction = (unit * seam_calls) / t_run if t_run > 0 else 0.0
    assert fraction < max_fraction, (
        f"disabled-path obs overhead bound {fraction:.4%} exceeds "
        f"{max_fraction:.0%} of the fused-pipeline microbench "
        f"({seam_calls} seam calls × {unit * 1e9:.0f} ns vs "
        f"{t_run * 1e3:.1f} ms transform) — an obs seam grew work on "
        "the disabled path")
    return {
        "transform_ms": round(t_run * 1e3, 3),
        "seam_calls": seam_calls,
        "spans_when_enabled": n_spans,
        "disabled_span_ns": round(unit * 1e9, 1),
        "overhead_fraction_bound": round(fraction, 6),
        "max_fraction": max_fraction,
    }


def check_spmd_clean() -> dict:
    """Repo-wide static SPMD gate; raise AssertionError on any finding.

    Needs the 8-device CPU mesh (tier-1's conftest forces it; the
    standalone entry point sets the flag itself before jax loads)."""
    import jax

    from mmlspark_tpu.analysis.spmd import audit_plan_spmd, verify_repo
    from mmlspark_tpu.core import plan

    if len(jax.devices()) < 8:
        raise AssertionError(
            "check_spmd_clean needs the 8-device CPU mesh "
            "(--xla_force_host_platform_device_count=8); got "
            f"{len(jax.devices())} device(s)")
    res = verify_repo()
    findings = [str(f) for f in res["findings"]]
    assert findings == [], (
        "SPMD verifier findings over the parallel layer:\n"
        + "\n".join(findings))

    # multi-chip plan audit of the canonical fused pipeline: a fused
    # inference segment must carry ZERO manual collectives and its
    # minibatch walk must divide the mesh's data extent
    pm, table, n, _mb = canonical_pipeline()
    audit = audit_plan_spmd(pm.stages,
                            lambda col: plan._entry_meta(table, col),
                            n_rows=n)
    assert audit.ok and len(audit.segments) == 1, (
        "plan spmd audit regressed:\n" + audit.format())

    # the sharded serve entries: the same audit over a DP replica's
    # single-chip sub-mesh (manual-collective-free) and a tp
    # model-parallel layout (collectives only over the declared
    # model-parallel axes) — what ModelServer.add_model(mesh=...)
    # enforces at load time
    from mmlspark_tpu.parallel.mesh import MeshSpec, make_mesh
    from mmlspark_tpu.serve.mesh import MODEL_PARALLEL_AXES
    replica_mesh = make_mesh(MeshSpec(dp=1), jax.devices()[:1])
    tp_mesh = make_mesh(MeshSpec(dp=1, tp=2), jax.devices()[:2])
    serve_audits = {
        "dp_replica": audit_plan_spmd(
            pm.stages, lambda col: plan._entry_meta(table, col),
            n_rows=n, mesh=replica_mesh),
        "tp_segment": audit_plan_spmd(
            pm.stages, lambda col: plan._entry_meta(table, col),
            n_rows=n, mesh=tp_mesh,
            expect_axes=MODEL_PARALLEL_AXES),
    }
    for label, a in serve_audits.items():
        assert a.ok and len(a.segments) == 1, (
            f"sharded serve audit [{label}] regressed:\n" + a.format())

    # the AST lint (incl. JX201–JX204) over the codebase
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import lint_jax
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    lint = lint_jax.lint_paths([os.path.join(repo, "mmlspark_tpu")])
    assert lint == [], "\n".join(str(f) for f in lint)

    reports = res["reports"]
    return {
        "entry_points": sorted(reports),
        "collectives": {name: rep.schedule.counts()
                        for name, rep in reports.items()},
        "shard_map_sites": sum(len(rep.sites)
                               for rep in reports.values()),
        "fence_files": res["fence_files"],
        "plan_segments": len(audit.segments),
        "plan_minibatches": audit.segments[0].minibatches,
        "serve_audits": sorted(serve_audits),
        # the real count, not a constant: the asserts above guarantee 0
        # on the happy path, and a refactor that stops raising would
        # surface here instead of silently passing the tier-1 gate
        "findings": (len(res["findings"]) + len(audit.findings)
                     + sum(len(a.findings) for a in serve_audits.values())
                     + len(lint)),
    }


def check_concurrency_clean(min_confirmed: int = 5,
                            max_static_s: float = 20.0,
                            max_fraction: float = 0.02) -> dict:
    """The whole-repo concurrency gate (docs/concurrency.md), three
    clauses in one pass:

    1. **static** — ``analysis.concurrency.analyze_repo()`` over the
       package finishes inside ``max_static_s`` with ZERO unsuppressed
       findings, and every suppression carries a non-empty
       justification (the pragma/allowlist policy is load-bearing);
    2. **witness** — a dp=4 serve burst (shadow canary deployed,
       overload driven, ``snapshot()`` + ``lifecycle_tick()`` +
       ``rollback()`` exercised) runs with the lock-order witness on:
       at least ``min_confirmed`` static lock-order edges must be
       CONFIRMED by real acquisitions, with ZERO order violations
       (no edge observed in both directions);
    3. **overhead** — the witness's disabled-path cost — the delta of a
       witnessed acquire/release cycle over a raw ``threading.Lock``,
       priced at every acquisition the burst actually performed — stays
       under ``max_fraction`` (2%) of the burst wall time, the same
       analytic-bound methodology as :func:`check_obs_overhead`.
    """
    import threading
    import time

    import jax

    from mmlspark_tpu import obs
    from mmlspark_tpu.analysis.concurrency import analyze_repo
    from mmlspark_tpu.data.table import DataTable
    from mmlspark_tpu.models.jax_model import JaxModel
    from mmlspark_tpu.obs import lockwitness as lw
    from mmlspark_tpu.serve.config import ServeConfig
    from mmlspark_tpu.serve.errors import Overloaded
    from mmlspark_tpu.serve.server import ModelServer

    if len(jax.devices()) < 8:
        raise AssertionError(
            "check_concurrency_clean needs the 8-device CPU mesh "
            "(--xla_force_host_platform_device_count=8); got "
            f"{len(jax.devices())} device(s)")
    assert not lw.enabled(), (
        "check_concurrency_clean must start with the witness disabled")

    # -- 1. static pass under a wall budget --
    t0 = time.perf_counter()
    an = analyze_repo()
    static_s = time.perf_counter() - t0
    assert static_s < max_static_s, (
        f"whole-repo concurrency pass took {static_s:.1f}s "
        f"(budget {max_static_s:.0f}s) — the analyzer grew "
        "superlinear work")
    findings = [str(f) for f in an.findings]
    assert findings == [], (
        "concurrency verifier findings over the repo:\n"
        + "\n".join(findings))
    for f, why in an.suppressed:
        assert why.strip(), f"unjustified concurrency suppression: {f}"

    # -- 2. witnessed dp=4 serve burst --
    sleep_s, n_req, rows = 0.004, 64, 4
    bundle, _probe = _latency_bundle(sleep_s)
    bundle2, _probe2 = _latency_bundle(sleep_s)
    jm = JaxModel(model=bundle, input_col="x", output_col="scores")
    jm2 = JaxModel(model=bundle2, input_col="x", output_col="scores")
    d_in = int(np.prod(tuple(bundle.input_spec)))
    rng = np.random.default_rng(7)

    def table(n):
        return DataTable({"x": [rng.random(d_in).astype(np.float32)
                                for _ in range(n)]})

    obs.enable(max_traces=4)
    lw.enable()
    rejected = 0
    t0 = time.perf_counter()
    try:
        srv = ModelServer(ServeConfig(buckets=(8,), max_queue=40,
                                      deadline_ms=None, mesh="dp=4"))
        srv.add_model("m", jm, example=table(1))
        srv.deploy_canary("m", jm2, mode="shadow", fraction=1.0,
                          version="v2")
        handles = []
        for _ in range(n_req):
            try:
                handles.append(srv.submit("m", table(rows)))
            except Overloaded:
                rejected += 1
        for h in handles:
            h.result(timeout=60.0)
        srv.snapshot()
        srv.lifecycle_tick("m")
        srv.rollback("m")
        srv.close()
    finally:
        burst_wall = time.perf_counter() - t0
        lw.disable()
        obs.disable()
        obs.clear()
    cross = lw.crosscheck(an.static_edges())
    n_ops = sum(lw.acquire_counts().values())
    lw.reset()
    assert cross["violations"] == [], (
        "lock-order inversion observed at runtime (both directions of "
        f"an edge executed): {cross['violations']}")
    assert len(cross["confirmed"]) >= min_confirmed, (
        f"only {len(cross['confirmed'])} of {len(an.static_edges())} "
        f"static lock-order edges confirmed at runtime (need "
        f">={min_confirmed}): {cross['confirmed']} — the serve burst "
        "stopped exercising the hot lock nests, or the witness names "
        "drifted from the analyzer's identities")

    # -- 3. disabled-path witness cost, priced per real acquisition --
    reps = 200_000
    probe_w = lw.named_lock("concurrency.overhead.probe")
    probe_r = threading.Lock()
    t0 = time.perf_counter()
    for _ in range(reps):
        with probe_r:
            pass
    unit_raw = (time.perf_counter() - t0) / reps
    t0 = time.perf_counter()
    for _ in range(reps):
        with probe_w:
            pass
    unit_wit = (time.perf_counter() - t0) / reps
    delta = max(0.0, unit_wit - unit_raw)
    fraction = (delta * n_ops) / burst_wall if burst_wall > 0 else 0.0
    assert fraction < max_fraction, (
        f"disabled-path witness overhead bound {fraction:.4%} exceeds "
        f"{max_fraction:.0%} of the serve burst ({n_ops} acquisitions "
        f"× {delta * 1e9:.0f} ns vs {burst_wall * 1e3:.0f} ms) — the "
        "witness grew work on its disabled path")

    return {
        "locks": len(an.locks),
        "static_edges": len(an.static_edges()),
        "static_s": round(static_s, 2),
        "findings": len(findings),
        "suppressed": len(an.suppressed),
        "confirmed": len(cross["confirmed"]),
        "plausible": len(cross["plausible"]),
        "novel": len(cross["novel"]),
        "violations": len(cross["violations"]),
        "burst_requests": n_req,
        "burst_rejected": rejected,
        "burst_wall_s": round(burst_wall, 2),
        "lock_ops": n_ops,
        "witness_delta_ns": round(delta * 1e9, 1),
        "overhead_fraction_bound": round(fraction, 6),
        "max_fraction": max_fraction,
    }


def check_serve_fleet() -> dict:
    """The fleet serving tier (serve/fleet/) end-to-end on REAL serve
    workers: two supervised backend processes behind the router, each
    warmed from the persistent compile cache the single-process
    reference published. kill -9 one backend mid-burst — every request
    in the burst still answers, bit-identical to the single-process
    reference (the router re-routes torn requests, the supervisor
    journals the exit and respawns generation 1). Then an induced
    fast-burn (tiny-deadline volley against tightened SLO windows)
    drives the autoscaler to spawn a THIRD backend, whose beacon proves
    it warmed from the cache with zero fresh XLA compiles. The fleet
    telemetry plane merges the router's counters bit-equal across the
    process set, and teardown leaks no router/supervisor/exporter
    threads."""
    import shutil
    import signal as _signal
    import tempfile
    import threading
    import time
    import urllib.error
    import urllib.request

    from mmlspark_tpu import obs
    from mmlspark_tpu.core import compile_cache as _cc
    from mmlspark_tpu.data.table import DataTable
    from mmlspark_tpu.models.jax_model import JaxModel
    from mmlspark_tpu.obs import fleet as obs_fleet
    from mmlspark_tpu.obs.metrics import Counter, format_series, registry
    from mmlspark_tpu.serve import ModelServer, ServeConfig
    from mmlspark_tpu.serve.fleet import (
        BackendPool, FleetConfig, FleetRouter, ScalePolicy,
        ServeSupervisor,
    )
    from mmlspark_tpu.serve.fleet.worker import (
        MODEL_NAME, SELFTEST_BUCKETS, selftest_bundle, selftest_rows,
    )
    from mmlspark_tpu.service.core import read_beacon
    from mmlspark_tpu.train.service import RecoveryPolicy

    tmp = tempfile.mkdtemp(prefix="mmlspark-fleet-serve-")
    service_dir = os.path.join(tmp, "fleet")
    cache_dir = os.path.join(tmp, "cache")
    obs_dir = os.path.join(tmp, "obs")
    rows = selftest_rows(8)

    # -- 1. single-process reference: the same seeded model served in
    #       process. Publishes every bucket program into the cache all
    #       three backends must warm from, and fixes the answer every
    #       router response is compared against (exact — the JSON float
    #       round trip is lossless for float32-derived doubles) --
    _cc.reset()
    ref_server = ModelServer(ServeConfig(
        buckets=SELFTEST_BUCKETS, deadline_ms=None,
        compile_cache=cache_dir))
    try:
        jm = JaxModel(model=selftest_bundle(), input_col="image",
                      output_col="scores")
        ref_server.add_model(MODEL_NAME, jm,
                             example=DataTable({"image": [rows[0]]}))
        out = ref_server.submit(
            MODEL_NAME,
            DataTable({"image": list(rows)})).result(timeout=300)
        ref_scores = [[float(v) for v in r] for r in out["scores"]]
        published = dict(_cc.active().stats)
    finally:
        ref_server.close()
        _cc.reset()
    assert published["puts"] >= 1, (
        f"reference serve published no programs to warm from: "
        f"{published}")

    obs.enable()
    obs.clear()
    registry().reset()
    obs_fleet.enable(obs_dir, interval_s=0.2)
    pool = BackendPool()
    sup = ServeSupervisor(FleetConfig(
        service_dir=service_dir, initial_backends=2,
        compile_cache=cache_dir,
        policy=RecoveryPolicy(max_restarts=2,
                              rescale_on_exhausted=False,
                              preempt_exit_codes=()),
        scale=ScalePolicy(fast_burn=5.0, burn_sustain_s=0.5,
                          min_backends=1, max_backends=3,
                          cooldown_s=2.0, idle_sustain_s=3600.0),
        # tight SLO windows so induced burn shows within a beacon or two
        slo={"window_s": 2.0, "long_window_s": 4.0, "min_requests": 1},
    ), pool=pool)
    router = FleetRouter(pool)

    def _journal_kinds():
        path = os.path.join(service_dir, "decisions.jsonl")
        if not os.path.exists(path):
            return []
        with open(path) as f:
            return [json.loads(line) for line in f]

    def _wait(pred, timeout, what):
        deadline = time.monotonic() + timeout
        while not pred():
            assert time.monotonic() < deadline, f"timed out: {what}"
            time.sleep(0.1)

    try:
        sup.start()
        router.start()
        host, port = router.address
        base = f"http://{host}:{port}"
        body = json.dumps({"rows": [{"image": r.tolist()} for r in rows],
                           "dtype": "uint8"}).encode()
        burn_body = json.dumps(
            {"rows": [{"image": rows[0].tolist()}], "dtype": "uint8",
             "deadline_ms": 1}).encode()

        def predict(payload=body, timeout=60.0):
            req = urllib.request.Request(
                f"{base}/v1/models/{MODEL_NAME}:predict", data=payload,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return (int(r.headers["X-Fleet-Backend"]),
                        json.loads(r.read()))

        _wait(lambda: pool.up_count() == 2, 180.0,
              "initial backends routable")

        # -- 2. kill -9 one backend mid-burst: zero drops, every answer
        #       bit-identical to the single-process reference --
        results, errors = [], []

        def burst_one():
            try:
                results.append(predict())
            except Exception as e:  # any error here IS the failure
                errors.append(repr(e))

        n_burst = 24
        threads = [threading.Thread(target=burst_one)
                   for _ in range(n_burst)]
        for t in threads[:n_burst // 2]:
            t.start()
        victim_bid, victim = next(iter(sup._backends.items()))
        os.kill(victim.proc.pid, _signal.SIGKILL)
        for t in threads[n_burst // 2:]:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors, (
            f"{len(errors)}/{n_burst} requests dropped across the "
            f"kill: {errors[:3]}")
        assert len(results) == n_burst
        backends_seen = {bid for bid, _ in results}
        for _bid, resp in results:
            got = [r["scores"] for r in resp["rows"]]
            assert got == ref_scores, (
                "router answer diverged from single-process serving "
                f"(via backend {_bid})")

        # the supervisor noticed the kill and respawned generation 1
        _wait(lambda: any(e["kind"] == "restart"
                          for e in _journal_kinds()), 60.0,
              "restart journaled after kill -9")
        _wait(lambda: pool.up_count() == 2, 180.0,
              "killed backend respawned and routable")

        # -- 3. induced fast-burn: tiny-deadline volley → sustained
        #       burn in the beacons → autoscaler spawns backend 3 --
        deadline = time.monotonic() + 120.0
        burn_statuses = []
        while pool.up_count() < 3:
            assert time.monotonic() < deadline, (
                f"autoscaler never spawned a third backend; journal="
                f"{[e['kind'] for e in _journal_kinds()]}")
            try:
                predict(burn_body, timeout=30.0)
                burn_statuses.append(200)
            except urllib.error.HTTPError as e:
                burn_statuses.append(e.code)  # 504s are the point
            time.sleep(0.05)
        scale_ups = [e for e in _journal_kinds()
                     if e["kind"] == "scale_up"]
        assert scale_ups, "third backend up but no scale_up journaled"
        new_bid = scale_ups[0]["bid"]
        assert new_bid not in (victim_bid,)

        # the scaled-up backend warmed from the compile cache: its
        # beacon carries the worker's own cache stats — zero fresh XLA
        # compiles, every program deserialized
        beacon = read_beacon(service_dir, new_bid, 0)
        assert beacon is not None, "no beacon from the scaled backend"
        cc_stats = beacon.get("compile_cache")
        assert cc_stats is not None, (
            "scaled-up backend beacon has no compile-cache stats — "
            "MMLSPARK_TPU_COMPILE_CACHE did not reach the worker")
        assert cc_stats["compiles"] == 0 and cc_stats["hits"] >= 1, (
            f"scaled-up backend paid fresh XLA compiles: {cc_stats}")

        # and it serves the SAME answers (clean request, no deadline)
        post_bid, resp = predict()
        assert [r["scores"] for r in resp["rows"]] == ref_scores

        # -- 4. the telemetry plane: the router's counters merge into
        #       the fleet view bit-equal, alongside the worker exports --
        expected = {
            format_series(m.name, m.labels): m.value
            for m in registry().iter_metrics()
            if isinstance(m, Counter)
            and m.name.startswith("serve.fleet.router.")}
        assert expected.get("serve.fleet.router.reroutes", 0) >= 1, (
            "kill -9 mid-burst never exercised the re-route path")
        obs_fleet.disable()  # final exit snapshot before collecting
        view = obs_fleet.FleetCollector(obs_dir).collect(
            include_ring=False)
        merged = {
            format_series(m.name, m.labels): m.value
            for m in view.registry.iter_metrics()
            if isinstance(m, Counter)
            and m.name.startswith("serve.fleet.router.")}
        assert merged == expected, (
            "fleet-merged router counters are not bit-equal to the "
            f"router registry: missing/changed "
            f"{dict(set(expected.items()) - set(merged.items()))}, "
            f"extra {dict(set(merged.items()) - set(expected.items()))}")
        worker_snaps = [p for p in view.processes
                        if p.pid != os.getpid()]
        assert worker_snaps, (
            "no backend process exported to the fleet dir — "
            "MMLSPARK_TPU_FLEET did not reach the workers")

        journal = _journal_kinds()
        kinds = [e["kind"] for e in journal]
        status = sup.status()
        return {
            "burst_requests": n_burst,
            "burst_errors": 0,
            "burst_backends": sorted(backends_seen),
            "killed_bid": victim_bid,
            "bit_identical": True,
            "burn_statuses": {s: burn_statuses.count(s)
                              for s in sorted(set(burn_statuses))},
            "scale_up_reason": scale_ups[0]["reason"],
            "scaled_bid": new_bid,
            "scaled_backend_cache": {k: cc_stats[k] for k in
                                     ("hits", "compiles")},
            "journal_kinds": sorted(set(kinds)),
            "scale_ups": status["scale_ups"],
            "router_counters": {k.rsplit(".", 1)[-1]: v
                                for k, v in expected.items()},
            "fleet_processes": len(view.processes),
        }
    finally:
        router.close()
        sup.close()
        obs_fleet.disable()
        obs.disable()
        obs.clear()
        registry().reset()
        leaked = [t.name for t in threading.enumerate()
                  if t.name.startswith(("ServeFleetRouter",
                                        "ServeFleetWatch"))
                  or t.name in ("FleetExporter", "TimeSeriesSampler")]
        assert not leaked, f"fleet threads leaked: {leaked}"
        shutil.rmtree(tmp, ignore_errors=True)


def check_train_to_serve() -> dict:
    """Continuous deployment, checkpoint to fleet-wide promotion
    (mmlspark_tpu/lifecycle, docs/lifecycle.md): a supervised fine-tune
    must end with its eval-gated checkpoint SERVING through the
    deployer — dark-published with provenance, ramped shadow → canary
    under live traffic, promoted with the repo ``CURRENT`` flipped, and
    every served answer bit-identical to SOME published version's
    offline transform with ZERO dropped requests. A degraded run (same
    workload, shifted data) must dark-publish but ROLL BACK in shadow on
    parity drift — repo CURRENT and the serving plane both back on the
    good version. The whole journey is journaled across train + serve +
    lifecycle decisions with cross-references both ways, replays from
    the lifecycle journal alone, lands the ``lifecycle.rollouts`` /
    ``lifecycle.rollbacks`` counters and the ``deploy.wall_s`` gauge,
    and stitches >= 1 cross-process fleet-timeline flow at the
    train→deployment publish-fence seam."""
    import shutil
    import tempfile
    import threading

    import jax

    from mmlspark_tpu import obs
    from mmlspark_tpu.data.table import DataTable
    from mmlspark_tpu.lifecycle import (
        Deployer, EvalGate, PublishPolicy, RolloutPolicy, ServerTarget,
        bundle_from_npz, replay_decisions,
    )
    from mmlspark_tpu.models.bundle import ModelBundle
    from mmlspark_tpu.models.jax_model import JaxModel
    from mmlspark_tpu.models.repo import ModelRepo
    from mmlspark_tpu.models.zoo import MLP
    from mmlspark_tpu.obs import fleet as obs_fleet
    from mmlspark_tpu.obs.metrics import registry
    from mmlspark_tpu.serve import (
        Client, ModelServer, ServeConfig, THREAD_PREFIX,
    )
    from mmlspark_tpu.train.service import (
        RecoveryPolicy, ServiceConfig, Topology, TrainSupervisor,
    )

    repo_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    workdir = tempfile.mkdtemp(prefix="train_to_serve_")
    repo_root = os.path.join(workdir, "repo")
    lifecycle_dir = os.path.join(workdir, "lifecycle")
    serve_dir = os.path.join(workdir, "serve")
    fleet_dir = os.path.join(workdir, "fleetobs")
    d_in, n_rows = 8, 24  # the selftest worker's XOR input width
    module = MLP(features=(16,), num_outputs=2)  # its architecture

    def train_run(tag: str, extra_env: dict) -> object:
        """One supervised fine-tune whose clean completion feeds the
        eval gate; a pass dark-publishes the result params as a new
        repo version with provenance."""
        sup = TrainSupervisor(ServiceConfig(
            cmd=(sys.executable,
                 os.path.join(repo_dir, "tools", "train_service.py"),
                 "worker"),
            service_dir=os.path.join(workdir, f"svc_{tag}"),
            checkpoint_dir=os.path.join(workdir, f"ckpt_{tag}"),
            topologies=(Topology(world=1, devices=4),),
            policy=RecoveryPolicy(max_restarts=0),
            extra_env=extra_env,
            publish=PublishPolicy(
                model="xor", repo_root=repo_root,
                gate=EvalGate(min_points=4, tail=4),
                bundle_from_result=lambda r: bundle_from_npz(
                    r, module, (d_in,)),
                notes=f"fine-tune {tag}",
                lifecycle_dir=lifecycle_dir)))
        report = sup.run()
        assert report.ok, f"train run {tag} failed: {report.reason}"
        return sup

    def tbl(sl):
        return DataTable({"input": list(sl)})

    def sc(out):
        return np.stack([np.asarray(v) for v in out["scores"]])

    rows = np.random.default_rng(0).normal(
        size=(n_rows, d_in)).astype(np.float32)

    # bit-identity discipline: every request is exactly the largest
    # bucket (8 rows — no padding, no coalescing with foreign rows),
    # and the offline references are computed in the SAME 8-row chunks,
    # so served and offline answers run the identical program shape —
    # on the multi-device CPU mesh XLA's partitioning is shape-
    # dependent, so a (24, d) offline batch vs a bucket-padded (4, d)
    # serve batch differ by 1 ULP and would mask real corruption checks
    req = 8
    assert n_rows % req == 0
    req_offsets = tuple(range(0, n_rows, req))

    def offline(version):
        jm = JaxModel(model=repo.load("xor", version)[0],
                      input_col="input", output_col="scores")
        return np.concatenate([sc(jm.transform(tbl(rows[o:o + req])))
                               for o in req_offsets])

    obs.enable()
    obs.clear()
    registry().reset()
    obs_fleet.enable(fleet_dir, interval_s=0.2)
    server = None
    try:
        # -- v1: the pre-trained baseline in production ---------------
        repo = ModelRepo(repo_root)
        params = module.init(jax.random.PRNGKey(0),
                             np.zeros((1, d_in), np.float32))["params"]
        v1 = repo.publish("xor", ModelBundle(
            module=module,
            params=jax.tree_util.tree_map(np.asarray, params),
            input_spec=(d_in,), output_names=("logits",), name="xor"))
        assert repo.current_version("xor") == v1

        server = ModelServer(ServeConfig(
            buckets=(1, 4, 8), max_queue=512, deadline_ms=None,
            lifecycle_dir=serve_dir,
            slo={"objective": 0.99, "min_requests": 4,
                 "window_s": 30.0, "long_window_s": 60.0}))
        server.add_model_from_repo(repo, "xor", example=tbl(rows[:1]))
        off = {v1: offline(v1)}

        # -- run 1: healthy fine-tune → dark v2 with provenance -------
        sup1 = train_run("good", {})
        v2 = v1 + 1
        assert repo.versions("xor") == [v1, v2], (
            f"healthy run did not dark-publish: {repo.versions('xor')}")
        assert repo.current_version("xor") == v1, (
            "dark publish moved CURRENT — promotion is the deployer's "
            "decision")
        _, info2 = repo.load("xor", v2)
        assert info2.provenance is not None
        assert info2.provenance["checkpoint_step"] == 16
        assert info2.provenance["run_id"].startswith("train-")
        assert len(info2.provenance["eval"]["series_tail"]) > 0
        off[v2] = offline(v2)
        assert not np.array_equal(off[v1], off[v2])

        # -- live traffic across both rollouts ------------------------
        stop_traffic = threading.Event()
        answers, errors = [], []
        lock = threading.Lock()

        def pump(k):
            client = Client(server, retry=True)
            try:
                i = 0
                while not stop_traffic.is_set():
                    o = req_offsets[(k + i) % len(req_offsets)]
                    got = client.predict("xor", tbl(rows[o:o + req]),
                                         timeout=60)
                    with lock:
                        answers.append((o, sc(got)))
                    i += 1
            except BaseException as e:  # noqa: BLE001 — reported
                with lock:
                    errors.append(f"{type(e).__name__}: {e}")

        threads = [threading.Thread(target=pump, args=(k,))
                   for k in range(2)]
        for t in threads:
            t.start()

        # -- rollout 1: v2 shadow → canary → promoted -----------------
        dep1 = Deployer(
            lifecycle_dir, repo,
            ServerTarget(server, "xor", example=tbl(rows[:1])),
            policy=RolloutPolicy(advance_after=2),
            refs={"train_journal": os.path.join(workdir, "svc_good",
                                                "decisions.jsonl"),
                  "serve_journal": os.path.join(serve_dir,
                                                "decisions.jsonl")})
        r1 = dep1.start_rollout("xor", version=v2)
        outcome1 = dep1.run(r1, tick_s=0.05, timeout_s=90.0)
        assert outcome1 == "promoted", (
            f"healthy rollout ended {outcome1!r} "
            f"(stage {r1.ledger.stage})")
        assert repo.current_version("xor") == v2, (
            "promotion did not flip the repo CURRENT pointer")
        snap = server.snapshot()["xor"]
        assert snap["version"] == v2, f"serving {snap.get('version')}"

        # -- run 2: degraded fine-tune (shifted data) → dark v3 -------
        sup2 = train_run(
            "degraded",
            {"MMLSPARK_TPU_SERVICE_SELFTEST_DATA_SEED": "3"})
        v3 = v2 + 1
        assert repo.versions("xor") == [v1, v2, v3]
        assert repo.current_version("xor") == v2
        off[v3] = offline(v3)

        # -- rollout 2: v3 drifts in shadow → rolled back -------------
        dep2 = Deployer(
            lifecycle_dir, repo,
            ServerTarget(server, "xor", example=tbl(rows[:1])),
            policy=RolloutPolicy(advance_after=2,
                                 parity_tolerance=1e-6),
            refs={"train_journal": os.path.join(workdir, "svc_degraded",
                                                "decisions.jsonl"),
                  "serve_journal": os.path.join(serve_dir,
                                                "decisions.jsonl")})
        r2 = dep2.start_rollout("xor", version=v3)
        outcome2 = dep2.run(r2, tick_s=0.05, timeout_s=90.0)
        assert outcome2 == "rolled_back", (
            f"degraded rollout ended {outcome2!r} — parity drift in "
            "shadow must roll back")
        assert repo.current_version("xor") == v2, (
            "rollback did not pin the repo CURRENT back to the good "
            "version")
        assert server.canary_status("xor") is None

        stop_traffic.set()
        for t in threads:
            t.join()

        # -- zero drops; every answer is SOME version's exact output --
        assert errors == [], f"requests dropped across the rollouts: " \
                             f"{errors}"
        assert len(answers) > 0
        unmatched = 0
        for o, got in answers:
            if not any(np.array_equal(got, off[v][o:o + req])
                       for v in off):
                unmatched += 1
        assert unmatched == 0, (
            f"{unmatched}/{len(answers)} answers match NO published "
            "version's offline transform bit-for-bit")
        post = sc(server.predict("xor", tbl(rows[:req])))
        assert np.array_equal(post, off[v2][:req]), (
            "post-rollback serving is not on the good version")

        # -- one journey, one trace -----------------------------------
        lc_path = os.path.join(lifecycle_dir, "decisions.jsonl")
        with open(lc_path, encoding="utf-8") as f:
            lc_recs = [json.loads(ln) for ln in f if ln.strip()]
        lc_kinds = [r["kind"] for r in lc_recs]
        for expected in ("publish", "rollout", "stage", "promote",
                         "rollback"):
            assert expected in lc_kinds, f"{expected!r} not journaled"
        ro_recs = [r for r in lc_recs if r["kind"] == "rollout"]
        assert all("train_journal" in r and "serve_journal" in r
                   for r in ro_recs), "rollouts missing journal refs"
        for tag in ("good", "degraded"):
            tj = os.path.join(workdir, f"svc_{tag}", "decisions.jsonl")
            with open(tj, encoding="utf-8") as f:
                t_recs = [json.loads(ln) for ln in f if ln.strip()]
            pubs = [r for r in t_recs if r["kind"] == "publish"]
            assert pubs and pubs[0]["lifecycle_journal"] == lc_path, (
                f"train run {tag} does not cross-reference the "
                "lifecycle journal")
        journeys = replay_decisions(lc_path)
        assert [j["outcome"] for j in journeys] == ["promoted",
                                                    "rolled_back"]
        assert journeys[0]["version"] == v2
        assert journeys[0]["stages"] == ["shadow", "canary",
                                         "promoting"]
        assert journeys[1]["version"] == v3
        assert journeys[1]["prior_version"] == v2

        # -- obs: counters, the deploy gauge, the stitched fence ------
        assert registry().value("lifecycle.rollouts") == 2
        assert registry().value("lifecycle.rollbacks") == 1
        wall = registry().value("deploy.wall_s", model="xor")
        assert wall is not None and wall > 0
        server.close()
        server = None
        obs_fleet.disable()  # final snapshot (this process's fences)
        view = obs_fleet.FleetCollector(fleet_dir).collect()
        meta = view.chrome_trace()["fleetMeta"]
        assert meta["stitched_flows"] >= 1, (
            "no cross-process flow stitched at the "
            "lifecycle/publish_fence seam (worker result write vs "
            "supervisor gate+publish)")
        return {
            "versions": repo.versions("xor"),
            "current": repo.current_version("xor"),
            "outcomes": [outcome1, outcome2],
            "provenance_v2": {
                "checkpoint_step": info2.provenance["checkpoint_step"],
                "eval_points": info2.provenance["eval"]["points"]},
            "responses": len(answers),
            "dropped": len(errors),
            "deploy_wall_s": wall,
            "rollouts": int(registry().value("lifecycle.rollouts")),
            "rollbacks": int(registry().value("lifecycle.rollbacks")),
            "stitched_flows": meta["stitched_flows"],
            "lifecycle_kinds": sorted(set(lc_kinds)),
        }
    finally:
        if server is not None:
            server.close()
        obs_fleet.disable()
        obs.disable()
        obs.clear()
        registry().reset()
        leaked = [t.name for t in threading.enumerate()
                  if t.name.startswith(THREAD_PREFIX)
                  or t.name in ("FleetExporter", "TimeSeriesSampler")]
        assert leaked == [], f"threads leaked: {leaked}"
        shutil.rmtree(workdir, ignore_errors=True)


def _timed_once(pm, table, time_mod) -> float:
    t0 = time_mod.perf_counter()
    pm.transform(table)
    return time_mod.perf_counter() - t0


def main() -> int:
    # the spmd gate verifies the parallel layer on the 8-device CPU
    # mesh; force it BEFORE jax initializes (same flag as tests/conftest)
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    try:
        result = check_fused_crossings()
        train = check_train_prefetch()
        train_pp = check_train_device_preprocess()
        train_elastic = check_train_elastic()
        serve = check_serve_batching()
        serve_cc = check_compile_cache()
        serve_sharded = check_serve_sharded()
        serve_generate = check_serve_generate()
        serve_lowprec = check_serve_lowprec()
        serve_lifecycle = check_serve_lifecycle()
        obs_overhead = check_obs_overhead()
        obs_tracing = check_obs_request_tracing()
        fleet_obs = check_fleet_obs()
        serve_fleet = check_serve_fleet()
        train_to_serve = check_train_to_serve()
        flight_rec = check_flight_recorder()
        spmd = check_spmd_clean()
        concurrency = check_concurrency_clean()
    except AssertionError as e:
        print(json.dumps({"perf_smoke": "FAIL", "reason": str(e)}))
        return 1
    print(json.dumps({"perf_smoke": "OK", **result,
                      "train_prefetch": train,
                      "train_device_preprocess": train_pp,
                      "train_elastic": train_elastic,
                      "serve": serve,
                      "serve_compile_cache": serve_cc,
                      "serve_sharded": serve_sharded,
                      "serve_generate": serve_generate,
                      "serve_lowprec": serve_lowprec,
                      "serve_lifecycle": serve_lifecycle,
                      "obs_overhead": obs_overhead,
                      "obs_request_tracing": obs_tracing,
                      "fleet_obs": fleet_obs,
                      "serve_fleet": serve_fleet,
                      "train_to_serve": train_to_serve,
                      "flight_recorder": flight_rec, "spmd": spmd,
                      "concurrency": concurrency}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
