"""Benchmark: CIFAR-10 ConvNet train throughput on the local accelerator.

Prints ONE JSON line: {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}.

The reference publishes no performance numbers (BASELINE.md), so
``vs_baseline`` is reported against the driver-defined north star:
achieved MFU / 0.60 target MFU on the CIFAR-10 CNN featurize+train path.

This is the pre-cell-table monolith (ROADMAP Design 6 replaces it). What
it guarantees today is only that it cannot pass off a broken or
chip-less run as a result: a device whose ``device_kind`` is not in the
peaks table is an error before anything is timed (so a CPU box, or a
failed TPU bring-up, never writes CPU timings under device-metric
names), every timed window ends in ``block_until_ready``, and a block
that raises is named in ``failed_blocks`` and makes the exit code 1 (the
JSON line is still printed). One process: nothing here starts a child
that needs the chip — the fleet block, which did, is out until the
benchmark PR gives it a parent that stays off the device.

``python bench.py --check`` additionally runs the perf-regression
sentinel (tools/bench_check.py) over this line vs a directory of
archived bench lines: the verdict lands in the JSON line
(``bench_check_verdict``) and a regression exits 2 with the named
report on stderr.
"""

from __future__ import annotations

import json
import time

import numpy as np

# the driver-facing series identity — shared by the success and error
# records so a failed round can never mislabel its metric
METRIC_NAME = "images/sec/chip (CIFAR-10 CNN train)"
METRIC_UNIT = "images/s/chip"


def conv_flops_per_example(module, input_spec) -> float:
    """Analytic forward FLOPs for the ConvNet (2*MACs); backward ≈ 2x fwd."""
    h, w, cin = input_spec
    flops = 0.0
    for width in module.widths:
        for _ in range(2):  # two convs per block
            flops += 2 * h * w * 3 * 3 * cin * width
            cin = width
        h, w = h // 2, w // 2
    flat = h * w * cin
    flops += 2 * flat * module.dense_width
    flops += 2 * module.dense_width * module.num_classes
    return flops


# bf16 peak FLOP/s per chip, keyed by a substring of ``device_kind``
# (Google Cloud TPU documentation, per-generation system architecture
# pages). A device that is not here is an error, never a default
PEAK_BF16_FLOPS = {
    "v5 lite": 197e12, "v5e": 197e12, "v4": 275e12,
    "v5p": 459e12, "v6": 918e12, "v6e": 918e12,
}


def peak_flops_per_chip() -> float:
    """bf16 peak for the local accelerator. Raises on a device the peaks
    table does not know — a CPU box, or a TPU bring-up that fell back to
    the CPU, must fail the run rather than write host timings under
    device-metric names."""
    import jax
    dev = jax.devices()[0]
    kind = dev.device_kind.lower()
    for k, v in PEAK_BF16_FLOPS.items():
        if k in kind:
            return v
    raise RuntimeError(
        f"bench: device platform={dev.platform!r} kind="
        f"{dev.device_kind!r} is not in the peaks table "
        f"({sorted(PEAK_BF16_FLOPS)}); this benchmark runs on a TPU "
        "(through the chip tool), never on the CPU box")


def compiled_flops(jitted_fn, *args) -> float:
    """Per-call FLOPs from XLA's own cost model.

    Pass the ALREADY-jitted callable used for timing so the lowering hits
    the jit cache instead of recompiling the model a second time."""
    return float(jitted_fn.lower(*args).compile().cost_analysis()["flops"])


def _bench_loop(run_once, passes: int = 5, steps: int = 15) -> float:
    """Seconds per call: the median over ``passes`` timed windows of
    ``steps`` calls. Dispatch is asynchronous, so every window ends in
    ``block_until_ready`` on its last call's output (each call depends on
    the previous one through donated state or the device queue's order);
    the median, never the min, is the aggregate — min selects
    underestimates."""
    import jax
    jax.block_until_ready(run_once())  # warm-up outside the windows

    dts = []
    for _ in range(passes):
        t0 = time.perf_counter()
        for _ in range(steps):
            out = run_once()
        jax.block_until_ready(out)
        dts.append((time.perf_counter() - t0) / steps)
    dts.sort()
    return dts[len(dts) // 2]


def bench_flagship_models(rng, n_dev: int, peak: float,
                          failed: list) -> dict:
    """BASELINE configs 3-5: ResNet-50 featurize, BiLSTM-613 tagging,
    ViT-B/16 fine-tune step (single-chip; DP scales via the mesh). A
    config that raises is appended to ``failed``."""
    import jax
    import jax.numpy as jnp

    out: dict = {}

    # --- config 3: ResNet-50 image featurization (img/s + MFU) ---
    # The featurize task is frozen-backbone inference, so the benchmarked
    # model is the zoo's *inference variant*: frozen BatchNorm folded into
    # the conv weights (the reference's zoo ResNet-50 is a BN network whose
    # inference-time norm cost folds away — Schema.scala:54-74), bf16
    # params, space-to-depth stem. Same math as the unfolded net
    # (numerics-parity-tested, tests/test_models.py).
    try:
        from mmlspark_tpu.models.zoo import get_model
        bundle = get_model("ResNet50_Infer", num_classes=10, input_size=224)
        params = jax.device_put(bundle.params, jax.devices()[0])
        batch = 256
        x = jnp.asarray(rng.integers(0, 255, (batch, 224, 224, 3)
                                     ).astype(np.float32))

        def fwd(p, xb):
            return bundle.module.apply({"params": p}, xb, output="features")

        fn = jax.jit(fwd)
        fn(params, x).block_until_ready()  # compile
        dt = _bench_loop(lambda: fn(params, x))
        out["resnet50_featurize_images_per_s_per_chip"] = round(
            batch / dt, 1)
        out["resnet50_featurize_variant"] = "folded-frozen-bn+s2d+bf16"
        out["resnet50_featurize_mfu"] = round(
            compiled_flops(fn, params, x) / dt / peak, 4)
    except Exception as e:
        failed.append("resnet50_featurize")
        out["resnet50_featurize_images_per_s_per_chip"] = f"error: {e}"

    # --- config 4: BiLSTM tagger at the reference's 613-token pad ---
    try:
        from mmlspark_tpu.models.zoo import get_model
        bundle = get_model("BiLSTM_MedTag", vocab_size=8192, num_tags=16,
                           max_len=613)
        params = jax.device_put(bundle.params, jax.devices()[0])
        batch = 64
        toks = jnp.asarray(rng.integers(1, 8192, (batch, 613)
                                        ).astype(np.int32))

        def tag(p, tb):
            return bundle.module.apply({"params": p}, tb)

        fn = jax.jit(tag)
        fn(params, toks).block_until_ready()
        dt = _bench_loop(lambda: fn(params, toks))
        out["bilstm613_tokens_per_s_per_chip"] = round(
            batch * 613 / dt, 1)
        out["bilstm613_sentences_per_s_per_chip"] = round(batch / dt, 1)
    except Exception as e:
        failed.append("bilstm613")
        out["bilstm613_tokens_per_s_per_chip"] = f"error: {e}"

    # --- config 5: ViT-B/16 fine-tune step time + MFU ---
    try:
        from mmlspark_tpu.models.zoo import get_model
        from mmlspark_tpu.train.loop import TrainConfig, Trainer

        bundle = get_model("ViT_B16", num_classes=10)
        module = bundle.module
        batch = 64
        # master-free bf16 fine-tune (param_dtype) + momentum: the
        # round-4 winning config on v5e (remat and larger batches both
        # lost then; not re-measured on current code)
        cfg = TrainConfig(batch_size=batch, epochs=1, optimizer="momentum",
                          learning_rate=1e-3, log_every=10**9,
                          param_dtype="bfloat16")
        trainer = Trainer(module, cfg)
        trainer.state = trainer.init_state((224, 224, 3))
        data = trainer.data_target()
        xb = jax.device_put(rng.normal(size=(batch, 224, 224, 3)
                                       ).astype(np.float32), data)
        yb = jax.device_put(rng.integers(0, 10, batch), data)
        box = {"state": trainer.state}

        def once():
            box["state"], m = trainer.step(box["state"], xb, yb)
            return m["loss"]

        step_s = _bench_loop(once)  # its warm-up call pays the compile
        out["vit_b16_finetune_step_ms"] = round(step_s * 1e3, 2)
        out["vit_b16_finetune_images_per_s_per_chip"] = round(
            batch / step_s / n_dev, 1)
        # fwd+bwd ≈ 3x forward FLOPs (XLA cost model on the fwd)

        def fwd(p, x):
            return module.apply({"params": p}, x, train=True)
        flops = compiled_flops(jax.jit(fwd), box["state"]["params"], xb)
        out["vit_b16_finetune_mfu"] = round(
            3 * flops / step_s / (peak * n_dev), 4)
    except Exception as e:
        failed.append("vit_b16_finetune")
        out["vit_b16_finetune_step_ms"] = f"error: {e}"

    return out


def bench_serve(jm, rng, n_total: int = 192) -> dict:
    """Serve-layer A/B: dynamic bucket-ladder batching vs batch-size-1,
    each at 1/8/64 concurrent requesters over the in-process client.

    Single-row uint8 image requests against the same ConvNet JaxModel the
    inference metrics use; the model object is shared across all runs so
    warmup compiles are paid once (the plan cache persists on the stage).
    """
    import threading

    from mmlspark_tpu.data.table import DataTable
    from mmlspark_tpu.serve import Client, ModelServer, ServeConfig

    imgs = rng.integers(0, 255, size=(n_total, 32 * 32 * 3)
                        ).astype(np.uint8)
    tables = [DataTable({"image": [imgs[i]]}) for i in range(n_total)]
    out: dict = {}
    for label, buckets in (("dynamic", (1, 8, 32, 128)), ("batch1", (1,))):
        for conc in (1, 8, 64):
            server = ModelServer(ServeConfig(
                buckets=buckets, max_queue=n_total + conc,
                deadline_ms=None))
            server.add_model("m", jm, example=tables[0])
            client = Client(server)
            errors: list[str] = []

            def worker(k: int) -> None:
                try:
                    for i in range(k, n_total, conc):
                        client.predict("m", tables[i], timeout=600)
                except BaseException as e:  # noqa: BLE001 — reported
                    errors.append(f"{type(e).__name__}: {e}")

            threads = [threading.Thread(target=worker, args=(k,))
                       for k in range(conc)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            snap = server.stats("m").snapshot()
            server.close()
            key = f"{label}_c{conc}"
            if errors:
                out[key] = {"error": errors[0]}
                continue
            e2e = snap.get("e2e_ms") or {}
            out[key] = {
                "rows_per_s": round(n_total / wall, 1),
                "p50_ms": e2e.get("p50"),
                "p99_ms": e2e.get("p99"),
                "occupancy_mean": snap.get("batch_occupancy_mean"),
                "batches": snap.get("batches"),
            }
    return out


def bench_serve_precision(jm, rng, n_total: int = 128,
                          conc: int = 8) -> dict:
    """Serve precision A/B (round 12): the same ConvNet served f32 vs
    bf16 vs int8w through the plan-level precision pass
    (core/precision.py, docs/quantization.md) — rows/s and p99 from the
    server stats, max-abs parity vs the f32 OFFLINE transform, and the
    compute/transfer/idle split of a small traced pass per precision
    (obs device pillar), which main() archives into BENCH_OBS.json.

    On a CPU box the bf16/int8w kernels emulate (no MXU bf16 pass, no
    int8 HBM), so rows/s deltas here are labeled-regime numbers like
    Rounds 6-9 — the honest cross-regime observables are the parity and
    the weight-byte ratio; real-chip rounds read the throughput."""
    import threading

    from mmlspark_tpu import obs
    from mmlspark_tpu.core import plan as plan_lib
    from mmlspark_tpu.core.precision import (
        PrecisionPolicy, quantized_bytes,
    )
    from mmlspark_tpu.data.table import DataTable
    from mmlspark_tpu.models.jax_model import JaxModel
    from mmlspark_tpu.serve import Client, ModelServer, ServeConfig

    imgs = rng.integers(0, 255, size=(n_total, 32 * 32 * 3)
                        ).astype(np.uint8)
    tables = [DataTable({"image": [imgs[i]]}) for i in range(n_total)]
    # the f32 offline anchor (the parity-contract side of every policy)
    full = DataTable({"image": list(imgs)})
    ref = np.stack(list(jm.transform(full)["scores"]))
    out: dict = {}
    # per-model pinned tolerances (docs/quantization.md): the ConvNet's
    # logits span ~±75, so int8w's ~1.4% relative error needs an
    # absolute pin of 2.0; bf16 is BIT-identical here — the module
    # already computes in bf16, so pre-narrowed params round identically
    # and the policy is a pure wire/HBM win
    policies = {"f32": None, "bf16": "bf16",
                "int8w": {"mode": "int8w", "tolerance": 2.0}}
    for label, precision in policies.items():
        served = JaxModel(model=jm.model, input_col="image",
                          output_col="scores", minibatch_size=1024)
        server = ModelServer(ServeConfig(
            buckets=(1, 8, 32, 128), max_queue=n_total + conc,
            deadline_ms=None, precision=precision))
        try:
            server.add_model("m", served, example=tables[0])
            client = Client(server)
            errors: list[str] = []
            got: dict[int, np.ndarray] = {}

            def worker(k: int) -> None:
                try:
                    for i in range(k, n_total, conc):
                        res = client.predict("m", tables[i], timeout=600)
                        got[i] = np.asarray(res["scores"][0])
                except BaseException as e:  # noqa: BLE001 — reported
                    errors.append(f"{type(e).__name__}: {e}")

            threads = [threading.Thread(target=worker, args=(k,))
                       for k in range(conc)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            snap = server.stats("m").snapshot()
            load_snap = server.snapshot()["m"]
            if errors:
                out[label] = {"error": errors[0]}
                continue
            parity = max(float(np.abs(got[i] - ref[i]).max())
                         for i in got)
            # traced pass: the host's phase split per precision (obs
            # boundary spans), archived in BENCH_OBS.json
            obs.registry().reset()
            obs.clear()  # boundary spans record with the tracer off too
            obs.enable(device=True)
            try:
                for i in range(8):
                    client.predict("m", tables[i], timeout=600)
                split = obs.host_phase_split()
            finally:
                obs.disable()
                obs.clear()
                obs.registry().reset()
            e2e = snap.get("e2e_ms") or {}
            rec = {
                "serve_rows_per_s": round(n_total / wall, 1),
                "serve_p99_ms": e2e.get("p99"),
                "parity_max_abs": parity,
                "occupancy_mean": snap.get("batch_occupancy_mean"),
                "host_split": split,
            }
            if precision is not None:
                rec["calibration_parity"] = load_snap.get(
                    "precision_parity")
                pol = PrecisionPolicy.parse(precision)
                rec["pinned_tolerance"] = pol.resolve_tolerance()
                seg = plan_lib.collect_segment(
                    [served], 0,
                    lambda c: plan_lib._entry_meta(full, c),
                    min_stages=1, precision=pol)
                _fn, stored = plan_lib.segment_composite(
                    seg, plan_lib._segment_mesh(seg))
                nb, fb = quantized_bytes(stored)
                rec["weight_bytes_ratio"] = round(nb / fb, 4)
            out[label] = rec
        finally:
            server.close()
    return out


def bench_serve_swap(rng, n_total: int = 160, conc: int = 8) -> dict:
    """Hot-swap under load A/B (round 13): client-observed latency with
    a version hot-swap landing mid-window vs an identical steady-state
    window, plus the dropped-request count (the zero-downtime claim,
    measured). Client-side timing, not ServerStats — the swap replaces
    the stats registry with the new version's, and the number that
    matters spans both."""
    import threading

    from mmlspark_tpu.data.table import DataTable
    from mmlspark_tpu.models.jax_model import JaxModel
    from mmlspark_tpu.models.zoo import get_model
    from mmlspark_tpu.serve import Client, ModelServer, ServeConfig

    imgs = rng.integers(0, 255, size=(n_total, 32 * 32 * 3)
                        ).astype(np.uint8)
    tables = [DataTable({"image": [imgs[i]]}) for i in range(n_total)]

    def model(seed):
        return JaxModel(model=get_model("ConvNet_CIFAR10", widths=(8, 16),
                                        dense_width=32, seed=seed),
                        input_col="image", output_col="scores")

    out: dict = {}
    for label in ("steady", "swap"):
        server = ModelServer(ServeConfig(
            buckets=(1, 8, 32), max_queue=n_total + conc,
            deadline_ms=None))
        server.add_model("m", model(seed=0), example=tables[0],
                         version=1)
        client = Client(server)
        lat: list[float] = []
        errors: list[str] = []
        lock = threading.Lock()

        def worker(k: int) -> None:
            # per-REQUEST error capture: one failure must count as one
            # dropped request and the rest of the window still run —
            # aborting the worker would shrink the sample and
            # under-report the very outage this A/B exists to measure
            for i in range(k, n_total, conc):
                t0 = time.perf_counter()
                try:
                    client.predict("m", tables[i], timeout=600)
                except BaseException as e:  # noqa: BLE001 — counted
                    with lock:
                        errors.append(f"{type(e).__name__}: {e}")
                    continue
                dt = time.perf_counter() - t0
                with lock:
                    lat.append(dt)

        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(conc)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        swap_wall_ms = None
        if label == "swap":
            # land the swap inside the window: v2 loads + warms its
            # ladder while v1 serves, then the name flips atomically
            time.sleep(0.05)
            s0 = time.perf_counter()
            server.add_model("m", model(seed=1), example=tables[0],
                             version=2)
            swap_wall_ms = round((time.perf_counter() - s0) * 1e3, 1)
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        server.close()
        entry = {
            "rows_per_s": round(len(lat) / wall, 1),
            "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 2),
            "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 2),
            "dropped": len(errors),
        }
        if swap_wall_ms is not None:
            entry["swap_wall_ms"] = swap_wall_ms
        if errors:
            entry["first_error"] = errors[0]
        out[label] = entry
    steady99, swap99 = out["steady"]["p99_ms"], out["swap"]["p99_ms"]
    out["p99_ratio_swap_vs_steady"] = (
        round(swap99 / steady99, 3) if steady99 else None)
    return out


def bench_serve_generate(rng, n_req: int = 32, max_new: int = 16) -> dict:
    """Token-serving bench (round 18): a streaming generate burst
    through the continuous-batching engine (serve/generate.py) — wall
    tokens/s, TTFT p50/p99 and ITL p99 from the engine's ServerStats,
    mean slot occupancy, and the compiled-program count against the
    ``len(prefill_buckets) + 1`` budget.

    A small causal TransformerTagger on CPU is a labeled-regime number
    like the precision A/B — the cross-regime observables are the
    program budget and occupancy; real-chip rounds read the
    throughput/latency. Warmup goes through ``generate_oneshot`` (the
    same compiled programs, no stats), so the burst percentiles never
    include compile time."""
    import jax

    from mmlspark_tpu.models.sequence import TransformerTagger
    from mmlspark_tpu.serve import (
        Client, GenerateConfig, ModelServer, ServeConfig,
    )

    vocab, t_max = 128, 128
    model = TransformerTagger(vocab_size=vocab, embed_dim=32, num_heads=2,
                              num_layers=2, mlp_dim=64, num_tags=vocab,
                              max_len=t_max, causal=True)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, 8), np.int32))["params"]
    cfg = GenerateConfig(slots=8, t_max=t_max, prefill_buckets=(8, 32),
                         prefill_rows=4, max_new_tokens=max_new,
                         max_queue=n_req + 8)
    prompts = [[int(t) for t in rng.integers(1, vocab,
                                             int(rng.integers(4, 30)))]
               for _ in range(n_req)]
    server = ModelServer(ServeConfig())
    try:
        server.add_generator("lm", model, params, config=cfg)
        for blen in cfg.prefill_buckets:  # warm the ladder + decode
            server.generate_oneshot(
                "lm", [int(t) for t in rng.integers(1, vocab, blen - 1)],
                max_new_tokens=2)
        client = Client(server)
        t0 = time.perf_counter()
        streams = [client.generate("lm", p, stream=True) for p in prompts]
        toks = [st.result(timeout=600) for st in streams]
        wall = time.perf_counter() - t0
        snap = server.snapshot()["lm"]
        programs = snap["programs_compiled"]
    finally:
        server.close()
    n_tokens = sum(len(t) for t in toks)
    ttft = snap.get("ttft_ms") or {}
    itl = snap.get("itl_ms") or {}
    return {
        "requests": n_req,
        "max_new_tokens": max_new,
        "tokens": n_tokens,
        "tokens_per_s": round(n_tokens / wall, 1),
        "ttft_p50_ms": ttft.get("p50"),
        "ttft_p99_ms": ttft.get("p99"),
        "itl_p99_ms": itl.get("p99"),
        "slot_occupancy_mean": snap.get("slot_occupancy_mean"),
        "decode_steps": snap.get("decode_steps"),
        "programs_compiled": programs,
        "program_budget": len(cfg.prefill_buckets) + 1,
    }


def bench_serve_sharded(jm, rng, n_total: int = 192,
                        conc: int = 8) -> dict:
    """Sharded-serving scaling A/B: one chip (``dp=1``) vs DP-replica
    fan-out over every local chip (``dp=N``), same request stream, same
    bucket ladder, ``conc`` concurrent requesters.

    On real multi-chip hosts the N-replica run multiplies the Round-8
    single-chip numbers (each replica owns its chip, params uploaded once
    per replica); on a single-device (or virtual-CPU) box the A/B
    degenerates and the honest scaling evidence is the latency-bound
    dryrun gate (``tools/perf_smoke.py check_serve_sharded``) — the
    record labels which regime it measured via ``n_devices``.
    """
    import threading

    import jax

    from mmlspark_tpu.data.table import DataTable
    from mmlspark_tpu.serve import Client, ModelServer, ServeConfig

    n_dev = len(jax.local_devices())
    meshes = [("dp1", "dp=1")]
    if n_dev > 1:
        meshes.append((f"dp{n_dev}", f"dp={n_dev}"))
    imgs = rng.integers(0, 255, size=(n_total, 32 * 32 * 3)
                        ).astype(np.uint8)
    tables = [DataTable({"image": [imgs[i]]}) for i in range(n_total)]
    out: dict = {"n_devices": n_dev}
    for label, mesh in meshes:
        server = ModelServer(ServeConfig(
            buckets=(1, 8, 32, 128), max_queue=n_total + conc,
            deadline_ms=None, mesh=mesh))
        server.add_model("m", jm, example=tables[0])
        client = Client(server)
        errors: list[str] = []

        def worker(k: int) -> None:
            try:
                for i in range(k, n_total, conc):
                    client.predict("m", tables[i], timeout=600)
            except BaseException as e:  # noqa: BLE001 — reported
                errors.append(f"{type(e).__name__}: {e}")

        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(conc)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        snap = server.stats("m").snapshot()
        programs = server.compiled_programs("m")
        server.close()
        if errors:
            out[label] = {"error": errors[0]}
            continue
        e2e = snap.get("e2e_ms") or {}
        out[label] = {
            "rows_per_s": round(n_total / wall, 1),
            "p99_ms": e2e.get("p99"),
            "batches": snap.get("batches"),
            "programs_compiled": programs,
            "replica_batches": {k: v.get("batches")
                                for k, v in snap["replicas"].items()},
        }
    first, last = out[meshes[0][0]], out[meshes[-1][0]]
    if (len(meshes) > 1 and isinstance(first.get("rows_per_s"), float)
            and isinstance(last.get("rows_per_s"), float)
            and first["rows_per_s"]):
        out["speedup"] = round(last["rows_per_s"] / first["rows_per_s"],
                               2)
    return out


def _aot_scratch(name: str) -> str:
    """An EMPTY directory for one cold-vs-warm A/B of the repo's AOT
    cache (core/compile_cache.py), at a fixed place inside the checkout
    (under the git-ignored jax cache dir) — never a temp dir, a pid or a
    timestamp. Emptied here because "cold" means it starts empty."""
    import os
    import shutil

    from mmlspark_tpu.utils.jit_cache import DEFAULT_DIR
    path = os.path.join(DEFAULT_DIR, "bench_aot", name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def bench_serve_load_wall(rng) -> dict:
    """Model-load wall A/B through the persistent AOT compile cache
    (core/compile_cache.py, docs/serving.md §compile cache): the same
    ConvNet loaded twice against one cache dir — cold (empty cache:
    every bucket program XLA-compiles and publishes) vs warm (every
    program deserializes). Fresh bundle/model objects per load, so the
    warm pass cannot ride the in-process plan cache; the cross-PROCESS
    version of this claim is gated in perf_smoke check_compile_cache.
    Walls include analyzer validation + full-ladder warmup — the number
    a fleet restart actually waits on. (jax's own persistent cache is on
    underneath, so after the first bench run on a machine the "cold"
    pass's XLA compiles are themselves cache reads.)"""
    from mmlspark_tpu.core import compile_cache as cc
    from mmlspark_tpu.data.table import DataTable
    from mmlspark_tpu.models.jax_model import JaxModel
    from mmlspark_tpu.models.zoo import get_model
    from mmlspark_tpu.serve import ModelServer, ServeConfig

    img = rng.integers(0, 255, size=(32 * 32 * 3,)).astype(np.uint8)
    tmp = _aot_scratch("serve_load_wall")
    out: dict = {}
    try:
        for label in ("cold", "warm"):
            cc.reset()
            bundle = get_model("ConvNet_CIFAR10")
            jm = JaxModel(model=bundle, input_col="image",
                          output_col="scores")
            server = ModelServer(ServeConfig(
                buckets=(1, 8, 32, 128), deadline_ms=None,
                compile_cache=tmp))
            t0 = time.perf_counter()
            server.add_model("m", jm,
                             example=DataTable({"image": [img]}))
            wall = time.perf_counter() - t0
            stats = dict(cc.active().stats)
            server.close()
            out[label] = {
                "load_wall_s": round(wall, 3),
                "hits": stats["hits"],
                "misses": stats["misses"],
                "puts": stats["puts"],
                "xla_compiles": stats["compiles"],
                "deserialize_ms": round(stats["load_ms"], 1),
            }
        out["cache_bytes"] = stats["bytes"]
        cold_w = out["cold"]["load_wall_s"]
        if cold_w:
            out["speedup"] = round(cold_w / max(
                out["warm"]["load_wall_s"], 1e-9), 2)
    finally:
        cc.reset()
    return out


def bench_deploy(rng) -> dict:
    """Checkpoint→serving wall through the lifecycle deployer
    (mmlspark_tpu/lifecycle, docs/lifecycle.md): the time from
    ``start_rollout`` on an already-published version to PROMOTED —
    shadow warmup, canary ramp under a trickle of live traffic, repo
    ``CURRENT`` flip — cold (empty compile cache: every candidate
    bucket program XLA-compiles during the shadow deploy) vs warm (the
    same rollout against the cache the cold pass populated). Fresh
    server/bundle objects per pass, same repo artifacts; bench_check
    gates warm <= cold WITHIN this line — absolute deploy walls are box
    weather, the cache either cuts the candidate warmup or it doesn't."""
    from mmlspark_tpu.core import compile_cache as cc
    from mmlspark_tpu.data.table import DataTable
    from mmlspark_tpu.lifecycle import Deployer, RolloutPolicy, ServerTarget
    from mmlspark_tpu.models.bundle import ModelBundle
    from mmlspark_tpu.models.repo import ModelRepo
    from mmlspark_tpu.models.zoo import MLP
    from mmlspark_tpu.serve import Client, ModelServer, ServeConfig

    import jax

    d_in = 32
    module = MLP(features=(64, 64), num_outputs=8)
    rows = rng.normal(size=(8, d_in)).astype(np.float32)
    example = DataTable({"input": list(rows[:1])})
    tmp = _aot_scratch("deploy")
    out: dict = {}
    try:
        repo = ModelRepo(f"{tmp}/repo")
        for seed in (0, 1):
            params = module.init(
                jax.random.PRNGKey(seed),
                np.zeros((1, d_in), np.float32))["params"]
            repo.publish("m", ModelBundle(
                module=module,
                params=jax.tree_util.tree_map(np.asarray, params),
                input_spec=(d_in,), output_names=("logits",), name="m"))
        for label in ("cold", "warm"):
            cc.reset()
            repo.set_current("m", 1)
            server = ModelServer(ServeConfig(
                buckets=(1, 8), deadline_ms=None,
                compile_cache=f"{tmp}/cc"))
            server.add_model_from_repo(repo, "m", version=1,
                                       example=example)
            client = Client(server)
            deployer = Deployer(
                f"{tmp}/lifecycle_{label}", repo,
                ServerTarget(server, "m", example=example),
                policy=RolloutPolicy(advance_after=1))
            t0 = time.perf_counter()
            rollout = deployer.start_rollout("m", version=2)
            while not rollout.done:
                # the trickle of live traffic every ramp stage needs
                # for a verdict (no canary evidence ⇒ the policy holds)
                for _ in range(2):
                    client.predict("m", DataTable({"input": list(rows)}),
                                   timeout=30)
                deployer.tick(rollout)
            wall = time.perf_counter() - t0
            stats = dict(cc.active().stats)
            server.close()
            out[label] = {
                "deploy_wall_s": round(wall, 3),
                "outcome": rollout.outcome,
                "ticks": rollout.ledger.ticks,
                "xla_compiles": stats["compiles"],
                "cache_hits": stats["hits"],
            }
        cold_w = out["cold"]["deploy_wall_s"]
        if cold_w:
            out["speedup"] = round(cold_w / max(
                out["warm"]["deploy_wall_s"], 1e-9), 2)
    finally:
        cc.reset()
    return out


def main() -> int:
    import jax

    from mmlspark_tpu.models.zoo import ConvNetCifar
    from mmlspark_tpu.train.loop import TrainConfig, Trainer
    from mmlspark_tpu.utils.jit_cache import place_compilation_cache

    place_compilation_cache()
    # first device query of the process: an unknown device (the CPU box,
    # a TPU bring-up that fell back) stops the run here, before anything
    # is timed
    peak = peak_flops_per_chip()
    dev0 = jax.devices()[0]
    device = dev0.device_kind
    print(f"bench: platform={dev0.platform} device_kind={device} "
          f"count={jax.device_count()}", flush=True)
    # every block below that raises lands here by name; a non-empty list
    # is exit code 1 (the JSON line is still printed)
    failed: list[str] = []

    batch = 1024  # large enough that compute dominates dispatch latency
    module = ConvNetCifar()
    cfg = TrainConfig(batch_size=batch, epochs=1, optimizer="momentum",
                      learning_rate=0.01, log_every=10**9)
    trainer = Trainer(module, cfg)

    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, 32, 32, 3)).astype(np.float32)
    y = rng.integers(0, 10, size=batch)

    trainer.state = trainer.init_state(x.shape[1:])
    # batches must be committed to the dp sharding: the jit infers shardings
    # from its args, so an uncommitted numpy batch would replicate (each chip
    # redundantly computing the full batch) and skew per-chip throughput.
    # (On a 1-device mesh data_target is the bare device — plain transfers.)
    data = trainer.data_target()
    x = jax.device_put(x, data)
    y = jax.device_put(y, data)
    box = {"state": trainer.state}

    def once():
        box["state"], m = trainer.step(box["state"], x, y)
        return m["loss"]

    step_dt = _bench_loop(once, steps=50)  # its warm-up call compiles

    n_dev = jax.device_count()
    images_per_s_per_chip = batch / step_dt / n_dev
    # fwd + bwd ≈ 3x forward FLOPs
    step_flops = 3 * conv_flops_per_example(module, (32, 32, 3)) * batch
    mfu = step_flops / step_dt / (peak * n_dev)
    vs_baseline = round(mfu / 0.60, 4)

    # device-health calibration: an 8k³ bf16 matmul should run near the
    # chip's nominal peak; a low MFU next to a low mxu_matmul_tf_s is a
    # sick chip, not a code regression
    mxu_tf_s = None
    try:
        import jax.numpy as jnp
        mm = jnp.asarray(rng.standard_normal((8192, 8192), np.float32),
                         jnp.bfloat16)
        g = jax.jit(lambda a, b: a @ b)
        mdt = _bench_loop(lambda: g(mm, mm), steps=5)
        mxu_tf_s = round(2 * 8192**3 / mdt / 1e12, 1)
    except Exception as e:
        failed.append("mxu_matmul")
        mxu_tf_s = f"error: {e}"

    # second BASELINE.json metric: Spark→TPU batch p50 latency through the
    # Arrow offload bridge (partition → padded device batch → scored rows),
    # plus raw batched-inference throughput (notebook-301 scoring path)
    bridge_p50 = None
    infer_ips = None
    table = None
    jm = None
    try:
        from mmlspark_tpu.data.table import DataTable
        from mmlspark_tpu.models.jax_model import JaxModel
        from mmlspark_tpu.models.zoo import get_model

        bundle = get_model("ConvNet_CIFAR10")
        jm = JaxModel(model=bundle, input_col="image", output_col="scores",
                      minibatch_size=1024)
        n_inf = 8192
        # decoded image bytes are uint8 — ship them thin, upcast on device
        imgs = rng.integers(0, 255, size=(n_inf, 32, 32, 3)
                            ).astype(np.uint8)
        table = DataTable({"image": list(imgs.reshape(n_inf, -1))})
        jm.transform(table)  # compile + param upload
        infer_dt = None
        for _ in range(2):  # best-of-2: host I/O share is noisy
            t0 = time.perf_counter()
            jm.transform(table)
            dt_i = time.perf_counter() - t0
            infer_dt = dt_i if infer_dt is None else min(infer_dt, dt_i)
        infer_ips = round(n_inf / infer_dt / n_dev, 1)
    except Exception as e:
        failed.append("inference")
        infer_ips = f"error: {e}"

    bridge_decomp: dict | None = None
    bridge_rows_s = None
    try:
        if table is None or jm is None:
            raise RuntimeError("inference setup failed, bridge skipped")
        from mmlspark_tpu.bridge import ArrowBatchBridge
        from mmlspark_tpu.bridge.offload import stream_table

        small = table.take(np.arange(2048))
        # warmup with the SAME chunking so the timed pass never compiles
        warmup = ArrowBatchBridge(jm)
        for _ in warmup.process(stream_table(small, 128)):
            pass
        # 16 timed batches: a p50 over 4 samples swung ±60% run to run.
        # workers=2 (the spark_transform default) overlaps marshal with
        # the device round-trip; wall-clock throughput (rows/s) reflects
        # the overlap, the per-batch p50 does not
        bridge2 = ArrowBatchBridge(jm)
        t0 = time.perf_counter()
        for _ in bridge2.process(stream_table(small, 128)):
            pass
        bridge_rows_s = round(len(small) / (time.perf_counter() - t0), 1)
        bridge_p50 = round(bridge2.p50_latency_ms(), 2)
        d = bridge2.p50_decomposition()
        bridge_decomp = {k: round(v, 2) for k, v in d.items()} if d else None
    except Exception as e:
        failed.append("bridge")
        bridge_p50 = f"error: {e}"

    # fused-vs-unfused pipeline execution (round 6): the canonical 3-stage
    # image pipeline (resize → unroll → score) through the pipeline planner
    # (ONE compiled program, one H2D upload of the raw uint8 batch + one
    # async fetch per minibatch) against the stage-by-stage host path. The
    # crossing counts make the fusion visible independently of link drift.
    pipe_rows_s = None
    pipe_rows_s_unfused = None
    pipe_crossings = None
    obs_snapshot = None
    try:
        if jm is None:
            raise RuntimeError("inference setup failed, pipeline skipped")
        from mmlspark_tpu.core import plan as plan_lib
        from mmlspark_tpu.core.pipeline import PipelineModel
        from mmlspark_tpu.core.schema import make_image
        from mmlspark_tpu.data.table import DataTable
        from mmlspark_tpu.stages.image import ImageTransformer, UnrollImage

        n_pipe = 2048
        src = rng.integers(0, 255, size=(n_pipe, 48, 48, 3)).astype(np.uint8)
        ptable = DataTable({"image": [make_image(f"i{k}", src[k])
                                      for k in range(n_pipe)]})
        stages = [
            ImageTransformer().resize(32, 32),
            UnrollImage(input_col="image", output_col="image_vec"),
            JaxModel(model=jm.model, input_col="image_vec",
                     output_col="scores", minibatch_size=1024),
        ]
        pm = PipelineModel(stages)
        # warm both paths at the SAME minibatch shape so the timed passes
        # never compile (1024 rows → one full-size minibatch)
        warm = ptable.take(np.arange(1024))
        pm.transform(warm)
        cur = warm
        for s in stages:
            cur = s.transform(cur)
        # the timed fused pass runs UNTRACED (tracer-on would bias the
        # fused-vs-unfused A/B with span/counter work the baseline never
        # pays); a separate small traced pass below cross-checks that the
        # obs registry reads EXACTLY what the seam-patching counter reads
        # (one substrate — docs/observability.md), so every bench run
        # double-checks the numbers the runtime exports
        with plan_lib.count_crossings() as cnt:
            t0 = time.perf_counter()
            pm.transform(ptable)
            fused_dt = time.perf_counter() - t0
        pipe_crossings = {"fused_h2d": cnt.uploads, "fused_d2h": cnt.fetches,
                          "fused_h2d_mb": round(cnt.upload_bytes / 2**20, 2)}
        from mmlspark_tpu import obs
        obs.registry().reset()
        obs.clear()  # boundary spans record with the tracer off too
        # device=True: the traced pass also captures per-segment compile
        # cost + XLA cost/memory gauges (plan.segment.*); the host's
        # phase split comes from the always-on boundary spans (not a
        # device busy/idle share — that needs a profiler trace)
        obs.enable(device=True)
        try:
            with plan_lib.count_crossings() as chk:
                pm.transform(warm)  # untimed: the obs-agreement pass
        finally:
            obs.disable()
        # keep the WHOLE registry view of the traced pass: it is
        # archived next to the bench record (BENCH_OBS.json) so the
        # bench trajectory accumulates comparable telemetry — same
        # snapshot schema as the /metrics endpoint
        obs_snapshot = obs.registry().snapshot()
        obs_counters = obs_snapshot["counters"]
        host_split = obs.host_phase_split()
        obs.clear()
        obs.registry().reset()
        obs.device.reset()
        pipe_crossings["obs_agrees"] = (
            obs_counters.get("plan.h2d_uploads", 0) == chk.uploads
            and obs_counters.get("plan.d2h_fetches", 0) == chk.fetches
            and obs_counters.get("plan.h2d_bytes", 0) == chk.upload_bytes)
        pipe_crossings["host_split"] = host_split
        pipe_crossings["segment_gauges"] = {
            k: v for k, v in obs_snapshot["gauges"].items()
            if k.startswith("plan.segment.")}
        with plan_lib.count_crossings() as cnt:
            t0 = time.perf_counter()
            cur = ptable
            for s in stages:
                cur = s.transform(cur)
            unfused_dt = time.perf_counter() - t0
        pipe_crossings["unfused_h2d"] = cnt.uploads
        pipe_crossings["unfused_d2h"] = cnt.fetches
        pipe_crossings["unfused_h2d_mb"] = round(cnt.upload_bytes / 2**20, 2)
        pipe_rows_s = round(n_pipe / fused_dt, 1)
        pipe_rows_s_unfused = round(n_pipe / unfused_dt, 1)
    except Exception as e:
        failed.append("pipeline")
        pipe_rows_s = f"error: {e}"

    # train input pipeline (round 7): prefetch on/off A/B on the canonical
    # CIFAR train config. With prefetch the batch gather + H2D commit run
    # on a background thread up to prefetch_depth steps ahead
    # (train/input.DeviceLoader), so steady-state step wall-clock is
    # max(H2D, compute) instead of the sum; the uint8 batches ship thin
    # and cast/normalize inside the jitted step. Numerics are bit-identical
    # across the A/B (asserted in tests/test_train_input.py); the wait
    # fractions make the split self-attributing under link drift
    train_ab: dict | None = None
    try:
        n_tr, bs_tr = 2048, 256
        x_tr = rng.integers(0, 255, size=(n_tr, 32, 32, 3)).astype(np.uint8)
        y_tr = rng.integers(0, 10, size=n_tr).astype(np.int64)
        train_ab = {}
        for label, depth in (("prefetch", 2), ("sync", 0)):
            cfg_tr = TrainConfig(batch_size=bs_tr, epochs=1,
                                 optimizer="momentum", learning_rate=0.01,
                                 log_every=10**9, prefetch_depth=depth,
                                 seed=0)
            tr = Trainer(ConvNetCifar(), cfg_tr)
            # warm pass compiles step_masked at the timed batch shape
            tr.fit_arrays(x_tr[:2 * bs_tr], y_tr[:2 * bs_tr])
            t0 = time.perf_counter()
            tr.fit_arrays(x_tr, y_tr)
            dt = time.perf_counter() - t0
            s = tr.input_stats or {}
            train_ab[label] = {
                "images_per_s_per_chip": round(n_tr / dt / n_dev, 1),
                "input_bound_fraction": s.get("input_bound_fraction"),
                "input_wait_s": s.get("input_wait_s"),
                "step_s": s.get("step_s"),
                "assemble_s": s.get("assemble_s"),
                "commit_s": s.get("commit_s"),
                "committed_ahead_max": s.get("committed_ahead_max"),
            }
    except Exception as e:
        failed.append("train_input_ab")
        train_ab = {"error": f"{type(e).__name__}: {e}"}

    # on-device preprocessing (round 10): host-preprocessed f32 batches
    # vs thin uint8 + DevicePreprocess fused into the jitted step, at
    # full augmentation (pad-crop/flip/brightness/contrast). Both runs
    # execute the SAME stochastic stages on device (draws fold from the
    # global step), so the A/B isolates the wire form: f32 final-width
    # pixels vs uint8 source pixels with geometry replayed in-step. The
    # crossing byte counts (train_commit seam) make the cut visible
    # independently of link drift
    train_pp_ab: dict | None = None
    try:
        from mmlspark_tpu.core import plan as plan_lib2
        from mmlspark_tpu.train.preprocess import (
            DevicePreprocess, host_preprocess,
        )
        spec = DevicePreprocess(crop_pad=4, flip_lr=True, brightness=0.1,
                                contrast=(0.9, 1.1))
        n_pp, bs_pp = 2048, 256
        x_pp = rng.integers(0, 255, size=(n_pp, 32, 32, 3)
                            ).astype(np.uint8)
        y_pp = rng.integers(0, 10, size=n_pp).astype(np.int64)
        train_pp_ab = {}
        for label, data in (("device_thin", x_pp),
                            ("host_f32",
                             host_preprocess(spec, x_pp, 1.0 / 255.0))):
            cfg_pp = TrainConfig(batch_size=bs_pp, epochs=1,
                                 optimizer="momentum", learning_rate=0.01,
                                 log_every=10**9, prefetch_depth=2,
                                 preprocess=spec, seed=0)
            tr = Trainer(ConvNetCifar(), cfg_pp)
            tr.fit_arrays(data[:2 * bs_pp], y_pp[:2 * bs_pp])  # warm
            with plan_lib2.count_crossings() as cnt:
                t0 = time.perf_counter()
                tr.fit_arrays(data, y_pp)
                dt = time.perf_counter() - t0
            s = tr.input_stats or {}
            train_pp_ab[label] = {
                "images_per_s_per_chip": round(n_pp / dt / n_dev, 1),
                "h2d_mb": round(cnt.upload_bytes / 2**20, 2),
                "wire_mb": s.get("wire_mb"),
                "input_bound_fraction": s.get("input_bound_fraction"),
            }
        thin_mb = train_pp_ab["device_thin"]["h2d_mb"]
        host_mb = train_pp_ab["host_f32"]["h2d_mb"]
        train_pp_ab["h2d_reduction"] = (round(host_mb / thin_mb, 2)
                                        if thin_mb else None)
    except Exception as e:
        failed.append("train_preprocess_ab")
        train_pp_ab = {"error": f"{type(e).__name__}: {e}"}

    # online serving (round 8): the dynamic-batching model server through
    # the in-process client at 1/8/64 concurrent requesters, A/B dynamic
    # batching (the bucket ladder) vs batch-size-1 (buckets=(1,): every
    # request its own dispatch). rows/s is wall-clock completion rate,
    # p99 the per-request end-to-end latency from ServerStats — under
    # concurrency the ladder converts queue depth into batch occupancy
    # instead of a serialized dispatch train
    serve_ab: dict | None = None
    try:
        if jm is None:
            raise RuntimeError("inference setup failed, serve skipped")
        serve_ab = bench_serve(jm, rng)
    except Exception as e:
        failed.append("serve_ab")
        serve_ab = {"error": f"{type(e).__name__}: {e}"}

    # sharded serving (round 9): dp=1 vs dp=N replica fan-out — every
    # added chip should multiply the round-8 per-chip serve numbers
    # (replica scheduler + per-replica param upload; docs/serving.md)
    serve_sharded: dict | None = None
    try:
        if jm is None:
            raise RuntimeError("inference setup failed, serve skipped")
        serve_sharded = bench_serve_sharded(jm, rng)
    except Exception as e:
        failed.append("serve_sharded")
        serve_sharded = {"error": f"{type(e).__name__}: {e}"}

    # serve precision A/B (round 12): f32 vs bf16 vs int8w through the
    # plan-level precision pass — parity vs the f32 offline transform,
    # rows/s + p99 per policy, and the traced compute/transfer/idle
    # split per precision (archived in BENCH_OBS.json)
    serve_precision: dict | None = None
    try:
        if jm is None:
            raise RuntimeError("inference setup failed, serve skipped")
        serve_precision = bench_serve_precision(jm, rng)
    except Exception as e:
        failed.append("serve_precision")
        serve_precision = {"error": f"{type(e).__name__}: {e}"}

    # hot-swap under load (round 13): a version flip mid-window vs an
    # identical steady window — client-observed p99 and the
    # dropped-request count (the zero-downtime lifecycle, measured)
    serve_swap: dict | None = None
    try:
        if jm is None:
            raise RuntimeError("inference setup failed, serve skipped")
        serve_swap = bench_serve_swap(rng)
    except Exception as e:
        failed.append("serve_swap")
        serve_swap = {"error": f"{type(e).__name__}: {e}"}

    # token serving (round 18): streaming generate burst through the
    # continuous-batching engine — tokens/s, TTFT/ITL percentiles, slot
    # occupancy, and the compiled-program budget (docs/serving.md
    # §token streaming)
    serve_generate: dict | None = None
    try:
        serve_generate = bench_serve_generate(rng)
    except Exception as e:
        failed.append("serve_generate")
        serve_generate = {"error": f"{type(e).__name__}: {e}"}

    # compile-cache load-wall A/B (round 18): cold (compile + publish)
    # vs warm (deserialize) model load against one cache dir — the
    # restart wall a fleet actually pays (docs/serving.md §compile
    # cache); bench_check gates warm <= cold WITHIN this line, never
    # across rounds (absolute load walls are box weather)
    serve_load_wall: dict | None = None
    try:
        serve_load_wall = bench_serve_load_wall(rng)
    except Exception as e:
        failed.append("serve_load_wall")
        serve_load_wall = {"error": f"{type(e).__name__}: {e}"}

    # continuous deployment (round 20): checkpoint→serving wall through
    # the lifecycle deployer, cold vs compile-cache-warm candidate
    # warmup — the promotion latency a fleet rollout actually pays
    # (docs/lifecycle.md); bench_check gates warm <= cold within-line
    deploy: dict | None = None
    try:
        deploy = bench_deploy(rng)
    except Exception as e:
        failed.append("deploy")
        deploy = {"error": f"{type(e).__name__}: {e}"}

    # BASELINE configs 3-5 (flagship models); skip with BENCH_FAST=1
    import os
    extra: dict = {}
    if os.environ.get("BENCH_FAST", "0") == "0":
        extra = bench_flagship_models(rng, n_dev, peak, failed)

    # write the obs registry snapshot of the traced fused pass next to
    # this script (git-ignored): stdout carries only the one JSON line,
    # this file the crossing/byte/compile counters and span histograms
    # in the schema the /metrics endpoint serves. Best-effort — a
    # read-only checkout must not fail the bench
    obs_archive = None
    if obs_snapshot is not None:
        try:
            obs_archive = os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "BENCH_OBS.json")
            with open(obs_archive, "w", encoding="utf-8") as fh:
                json.dump({
                    "metric": METRIC_NAME,
                    "device": device,
                    "obs_registry": obs_snapshot,
                    "pipeline_crossings": pipe_crossings,
                    "serve_stats": {
                        k: v for k, v in (serve_ab or {}).items()
                        if isinstance(v, dict)},
                    "serve_sharded": serve_sharded,
                    # compute/transfer/idle split per serving precision
                    # (the obs device pillar's traced pass per policy)
                    "serve_precision": serve_precision,
                }, fh, indent=2, default=str)
        except OSError:
            obs_archive = None

    line = {
        "metric": METRIC_NAME,
        "value": round(images_per_s_per_chip, 1),
        "unit": METRIC_UNIT,
        "vs_baseline": vs_baseline,
        "device": device,
        "platform": dev0.platform,
        "device_count": n_dev,
        "failed_blocks": failed,
        "bridge_batch_p50_ms": bridge_p50,
        "bridge_p50_marshal_ms": (bridge_decomp or {}).get("marshal_ms"),
        "bridge_p50_score_ms": (bridge_decomp or {}).get("score_ms"),
        "bridge_rows_per_s": bridge_rows_s,
        "inference_images_per_s_per_chip": infer_ips,
        "pipeline_rows_per_s": pipe_rows_s,
        "pipeline_rows_per_s_unfused": pipe_rows_s_unfused,
        "pipeline_crossings": pipe_crossings,
        "train_prefetch_images_per_s_per_chip": (train_ab or {}).get(
            "prefetch", {}).get("images_per_s_per_chip"),
        "train_sync_images_per_s_per_chip": (train_ab or {}).get(
            "sync", {}).get("images_per_s_per_chip"),
        "train_input_bound_fraction": (train_ab or {}).get(
            "prefetch", {}).get("input_bound_fraction"),
        "train_input_ab": train_ab,
        "train_preprocess_images_per_s_per_chip": (train_pp_ab or {}).get(
            "device_thin", {}).get("images_per_s_per_chip"),
        "train_preprocess_host_images_per_s_per_chip": (
            train_pp_ab or {}).get("host_f32", {}).get(
            "images_per_s_per_chip"),
        "train_preprocess_h2d_reduction": (train_pp_ab or {}).get(
            "h2d_reduction"),
        "train_preprocess_input_bound_fraction": (train_pp_ab or {}).get(
            "device_thin", {}).get("input_bound_fraction"),
        "train_preprocess_ab": train_pp_ab,
        "serve_rows_per_s": (serve_ab or {}).get(
            "dynamic_c8", {}).get("rows_per_s"),
        "serve_p99_ms": (serve_ab or {}).get(
            "dynamic_c8", {}).get("p99_ms"),
        "serve_ab": serve_ab,
        "serve_sharded": serve_sharded,
        "serve_sharded_speedup": (serve_sharded or {}).get("speedup"),
        "serve_swap": serve_swap,
        "serve_swap_p99_ms_steady": (serve_swap or {}).get(
            "steady", {}).get("p99_ms"),
        "serve_swap_p99_ms_during": (serve_swap or {}).get(
            "swap", {}).get("p99_ms"),
        "serve_swap_dropped": (serve_swap or {}).get(
            "swap", {}).get("dropped"),
        "serve_generate": serve_generate,
        "serve_generate_tokens_per_s": (serve_generate or {}).get(
            "tokens_per_s"),
        "serve_generate_ttft_p50_ms": (serve_generate or {}).get(
            "ttft_p50_ms"),
        "serve_generate_ttft_p99_ms": (serve_generate or {}).get(
            "ttft_p99_ms"),
        "serve_generate_itl_p99_ms": (serve_generate or {}).get(
            "itl_p99_ms"),
        "serve_generate_slot_occupancy": (serve_generate or {}).get(
            "slot_occupancy_mean"),
        "serve_load_wall_cold_s": (serve_load_wall or {}).get(
            "cold", {}).get("load_wall_s"),
        "serve_load_wall_warm_s": (serve_load_wall or {}).get(
            "warm", {}).get("load_wall_s"),
        "serve_load_wall": serve_load_wall,
        "deploy": deploy,
        "deploy_wall_cold_s": (deploy or {}).get(
            "cold", {}).get("deploy_wall_s"),
        "deploy_wall_warm_s": (deploy or {}).get(
            "warm", {}).get("deploy_wall_s"),
        "serve_precision_ab": serve_precision,
        **{f"serve_rows_per_s_{p}": (serve_precision or {}).get(
            p, {}).get("serve_rows_per_s") for p in ("f32", "bf16",
                                                     "int8w")},
        **{f"serve_p99_ms_{p}": (serve_precision or {}).get(
            p, {}).get("serve_p99_ms") for p in ("f32", "bf16",
                                                 "int8w")},
        **{f"serve_parity_max_abs_{p}": (serve_precision or {}).get(
            p, {}).get("parity_max_abs") for p in ("bf16", "int8w")},
        "mxu_matmul_tf_s": mxu_tf_s,
        "obs_snapshot_path": obs_archive,
        "obs_counters": (obs_snapshot["counters"]
                         if obs_snapshot else None),
        **extra,
    }

    # a serve A/B records a failed arm as {"error": ...} under its label
    # instead of raising: those count as failed blocks too
    for name, block in (("serve_ab", serve_ab),
                        ("serve_sharded", serve_sharded),
                        ("serve_precision", serve_precision),
                        ("serve_swap", serve_swap)):
        for label, arm in (block or {}).items():
            if isinstance(arm, dict) and "error" in arm \
                    and name not in failed:
                failed.append(f"{name}.{label}")

    # --check: the perf-regression sentinel (tools/bench_check.py) runs
    # over this line vs a directory of archived bench lines AFTER the
    # obs archiving above, and its verdict rides IN the JSON line
    rc = 1 if failed else 0
    import sys
    if "--check" in sys.argv:
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tools"))
        import bench_check
        repo = os.path.dirname(os.path.abspath(__file__))
        report = bench_check.check_line(line,
                                        bench_check.load_rounds(repo))
        line["bench_check_verdict"] = report["verdict"]
        line["bench_check_regressions"] = [
            r["key"] for r in report["regressions"]]
        if report["verdict"] == "regressed":
            rc = 2
            print(bench_check.format_report(report), file=sys.stderr)

    print(json.dumps(line))
    if failed:
        print(f"bench: FAILED blocks: {failed}", file=sys.stderr)
    return rc


def _main_guarded() -> None:
    """ONE JSON line on stdout, always — a failure outside every block
    (no known device, a crash in the headline train loop) still leaves
    an error-labeled record, and the exception propagates: the exit code
    is non-zero."""
    try:
        rc = main()
    except BaseException as e:  # noqa: BLE001 — last-resort driver record
        print(json.dumps({
            "metric": METRIC_NAME,
            "value": None, "unit": METRIC_UNIT, "vs_baseline": None,
            "error": f"{type(e).__name__}: {e}",
        }))
        raise
    if rc:
        raise SystemExit(rc)


if __name__ == "__main__":
    _main_guarded()
