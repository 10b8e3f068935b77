#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Run from the root of a checkout, on a machine with a TPU::

    python3 chip_smoke.py            # chiprun [--chips 4] -- python3 chip_smoke.py

ONE process drives the system's three device entry points through the
constructors a user would call, at the full width of models the repo
supports (depth cut only where stated, weights random from a seed, data
generated here — the machine has no network and the copy is not a git
checkout), then compiles every Pallas kernel in the tree with the real
compiler:

* **train** — ``Trainer(ViT_B16, momentum, bf16 params, batch 64)``,
  ``fit_arrays`` over 4 batches of 224×224×3;
* **serve** — ``ModelServer`` + ``JaxModel(ResNet50_Infer)`` over a warmed
  bucket ladder, requests of 1–8 uint8 rows through ``Client`` and one
  over HTTP, answers checked against the offline ``JaxModel.transform``;
* **generate** — ``ModelServer.add_generator`` with a causal
  ``TransformerTagger`` (embed 512, 8 heads of 64, 4 layers, vocab 8192,
  ``t_max`` 1024, 8 slots, bf16), prompts of mixed length streamed for 16
  new tokens each; prefill-then-decode logits checked against a plain
  float32 forward; the compiled decode program must contain the Pallas
  kernel;
* **kernels** — ``decode_attention``, ``flash_attention`` (ViT-B tile,
  causal T=1024, and the tiled kernel at a causal T=4096 and a masked
  T=2100), ``attention_block_update``, ``group_norm``, the selective
  scan at one row of 16,384 positions x 5,120 channels, the Mamba-2 scan
  at one row of 16,384 positions x 64 heads of 64 and the short causal
  convolution at both families' shapes, each against its XLA reference.

With more than one device visible the multi-device branches switch on:
the train phase also runs over the default ``dp`` mesh (one batch shard
and bytes in use on every device, loss sequence compared with the
one-device run) and the serve phase once more with ``mesh="dp=<n>"``
(every replica must serve a batch).

Every check is a hard failure: the phase is named and the exit code is
non-zero; nothing is caught and carried past. The script exits non-zero
and prints no result when JAX finds no TPU — it never falls back to the
CPU — and in a directory that holds nothing else of the repo. It starts
no process that needs the chip (the only child is the one-shot ``g++``
build of the native image library). Comparisons use tolerances, each
written down with its reason; nothing here is a timing, a rate or a
utilization (set-up wall seconds are printed as facts of the run).

The last line of stdout is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# ---- sizes (one place) ----

TRAIN_BATCH = 64
TRAIN_BATCHES = 4
IMAGE = 224

SERVE_BUCKETS = (1, 8, 32)
SERVE_REPLICA_BUCKETS = (1, 8)     # the dp=<n> pass: n × this many compiles
SERVE_REQUEST_ROWS = (1, 3, 8, 2, 5)

GEN = dict(vocab=8192, embed=512, heads=8, layers=4, mlp=2048,
           t_max=1024, slots=8, prefill_buckets=(32, 128), prefill_rows=4,
           new_tokens=16)
GEN_PROMPT_LENGTHS = (5, 17, 32, 60, 100, 128, 9, 40, 77, 3)

# ---- tolerances, each with its reason ----

# 1-device vs n-device loss sequence (absolute, on losses near ln 10 ≈ 2.3):
# the same batches in the same order, but bf16 parameters and activations
# round differently when each device sees batch/n rows, and the gradient
# all-reduce sums in another order; four momentum steps in bf16 let that
# drift compound. Observed 0.00042 on a four-chip v5e host (PERF.md), so
# ~24x headroom — and far below anything a wrong sharding (a replicated
# batch, a missed all-reduce) would produce.
TRAIN_LOSS_ATOL = 0.01

# served vs offline ResNet-50 logits: the same bf16 program family at a
# different padded batch size — XLA picks batch-shape-dependent conv
# tilings, so bf16 accumulations round differently (2^-8 relative per
# rounding, ~50 layers deep). Relative to the largest reference logit;
# observed 0.0023 on one v5e chip (PERF.md), so ~9x headroom.
SERVE_RTOL = 0.02

# bf16 prefill-then-decode logits vs the float32 ("highest") full forward:
# bf16 carries 8 mantissa bits through 4 layers of matmul, LayerNorm and
# residual adds. Relative to the largest reference logit; observed 0.0093
# on one v5e chip (PERF.md), so ~4x headroom.
GEN_LOGITS_RTOL = 0.04

# kernel vs XLA reference at matmul precision "highest", inputs exactly
# representable in bf16: Q·Kᵀ products are then exact in either, and what
# remains is the rounding of the softmax weights in Mosaic's default f32
# matmul, which is a reduced-precision MXU pass (observed 1.3e-3..2.6e-3
# on outputs of magnitude ~1 — the size of one bf16 rounding, 2^-9).
KERNEL_ATOL = 2e-2
KERNEL_RTOL = 2e-2
# GroupNorm emits bf16: one output rounding (2^-8 relative) on values up
# to ~4 sigma, both sides accumulating statistics in f32.
GN_ATOL = 6e-2
# the selective scan's gated output in bfloat16, values up to ~20
SCAN_ATOL = 5e-2
# the Mamba-2 scan: products of a chunk's 128 terms on bfloat16 operands
# (the decay-weighted C.B and dt x x rounded to 2^-9 each), a bfloat16
# output, values up to ~10 (read on the chip: 0.125 at most on this draw)
SSD_ATOL = 1.5e-1


class SmokeFailure(Exception):
    """A check that did not hold."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


# ---- persistent-cache accounting (jax.monitoring events) ----
# "requests" = compiles that consulted the persistent cache, "hits" = those
# it answered. (jax's own cache_misses event counts entries WRITTEN, which
# skips compiles under its 1 s write threshold, so it is not used here.)

CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                "/jax/compilation_cache/compile_requests_use_cache":
                    "requests"}
cache_counts = {"hits": 0, "requests": 0}


def _on_jax_event(event: str, **_: object) -> None:
    key = CACHE_EVENTS.get(event)
    if key:
        cache_counts[key] += 1


def run_phase(name: str, fn, *args) -> dict:
    """Run one phase; print its facts with the wall seconds and the
    persistent-cache hits/misses it caused. A failure names the phase and
    propagates (non-zero exit)."""
    say(f"phase {name}: start")
    t0 = time.perf_counter()
    before = dict(cache_counts)
    try:
        facts = fn(*args)
    except BaseException:
        say(f"FAIL phase={name}")
        raise
    facts["wall_s"] = round(time.perf_counter() - t0, 1)
    facts["cache_hits"] = cache_counts["hits"] - before["hits"]
    facts["cache_requests"] = cache_counts["requests"] - before["requests"]
    say(f"phase {name}: ok {json.dumps(facts, sort_keys=True)}")
    return facts


def has_mosaic_call(compiled) -> bool:
    """Does a compiled program contain a Pallas TPU kernel? Mosaic
    kernels appear in the optimized HLO as ``tpu_custom_call``."""
    return "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def _train_once(devices, mesh_spec, x, y) -> dict:
    """One ``Trainer.fit_arrays`` run on ``devices`` (through
    ``mesh_spec``); returns the loss history and what was observed of the
    state's and the batches' placement."""
    import jax
    import numpy as np

    from mmlspark_tpu.models.zoo import get_model
    from mmlspark_tpu.train.loop import TrainConfig, Trainer

    module = get_model("ViT_B16", num_classes=10).module
    cfg = TrainConfig(batch_size=TRAIN_BATCH, epochs=1,
                      optimizer="momentum", learning_rate=1e-3,
                      log_every=1, param_dtype="bfloat16", seed=0,
                      mesh_spec=mesh_spec)
    trainer = Trainer(module, cfg)
    check(set(trainer.mesh.devices.flat) == set(devices),
          f"trainer mesh covers {trainer.mesh.devices.size} device(s), "
          f"wanted {len(devices)}")
    trainer.state = trainer.init_state((IMAGE, IMAGE, 3))
    # the step donates its state, so snapshot to the host first
    before = [np.asarray(leaf) for leaf in
              jax.tree_util.tree_leaves(trainer.state["params"])]

    batch_shards: list = []     # per step: [(device, rows), ...]
    step = trainer.step_masked

    def recording_step(state, dx, dy, dw):
        batch_shards.append([(s.device, s.data.shape[0])
                             for s in dx.addressable_shards])
        return step(state, dx, dy, dw)

    trainer.step_masked = recording_step
    hits0 = cache_counts["hits"]
    trainer.fit_arrays(x, y)        # compiles ONE program: the train step
    step_from_cache = cache_counts["hits"] > hits0

    losses = [float(v) for v in trainer.history]
    check(len(losses) == TRAIN_BATCHES,
          f"{len(losses)} logged losses for {TRAIN_BATCHES} batches")
    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")

    leaves = jax.tree_util.tree_leaves(trainer.state)
    off = [d for leaf in leaves for d in leaf.devices()
           if d.platform != "tpu" or d not in devices]
    check(not off, f"state leaves committed off the mesh: {off[:3]}")
    after = [np.asarray(leaf) for leaf in
             jax.tree_util.tree_leaves(trainer.state["params"])]
    changed = sum(1 for a, b in zip(before, after)
                  if not np.array_equal(a, b))
    check(changed > 0, "no parameter changed after training")

    check(len(batch_shards) == TRAIN_BATCHES,
          f"{len(batch_shards)} steps ran for {TRAIN_BATCHES} batches")
    for shards in batch_shards:
        check({d for d, _ in shards} == set(devices)
              and len(shards) == len(devices),
              "batch does not have one addressable shard on each "
              f"device: {shards}")
        check(all(rows == TRAIN_BATCH // len(devices)
                  for _, rows in shards),
              f"uneven batch shards: {shards}")
    in_use = {str(d): (d.memory_stats() or {}).get("bytes_in_use", 0)
              for d in devices}
    check(all(v > 0 for v in in_use.values()),
          f"a device reports no bytes in use: {in_use}")
    return {"losses": losses, "params_changed": changed,
            "param_leaves": len(before), "bytes_in_use": in_use,
            "step_from_cache": step_from_cache}


def phase_train(devices) -> dict:
    import numpy as np

    rng = np.random.default_rng(0)
    n = TRAIN_BATCH * TRAIN_BATCHES
    x = rng.normal(size=(n, IMAGE, IMAGE, 3)).astype(np.float32)
    y = rng.integers(0, 10, size=n)

    one = _train_once(devices[:1], {"dp": 1}, x, y)
    facts = {"devices": 1, "losses": [round(v, 4) for v in one["losses"]],
             "params_changed": f"{one['params_changed']}/"
                               f"{one['param_leaves']}",
             "train_step_from_persistent_cache": one["step_from_cache"]}
    if len(devices) > 1:
        # the default mesh: pure dp over every device
        many = _train_once(devices, None, x, y)
        diff = max(abs(a - b) for a, b in zip(one["losses"],
                                              many["losses"]))
        check(diff <= TRAIN_LOSS_ATOL,
              f"1-device vs {len(devices)}-device losses differ by "
              f"{diff:.4f} > {TRAIN_LOSS_ATOL}: {one['losses']} vs "
              f"{many['losses']}")
        facts.update({
            "devices": len(devices),
            "losses_dp": [round(v, 4) for v in many["losses"]],
            "loss_max_abs_diff_1_vs_n": round(diff, 5),
            "loss_atol": TRAIN_LOSS_ATOL,
            "batch_shard_on_each_device": True,
            "bytes_in_use": many["bytes_in_use"]})
    return facts


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def _http_predict(port: int, name: str, row) -> list:
    import urllib.request

    body = json.dumps({"rows": [{"image": row.tolist()}],
                       "dtype": "uint8"}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/models/{name}:predict", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=300) as resp:
        check(resp.status == 200, f"HTTP predict status {resp.status}")
        return json.loads(resp.read())["rows"]


def _check_scores(got, want, what: str) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape,
          f"{what}: shape {got.shape} != reference {want.shape}")
    check(bool(np.isfinite(got).all()), f"{what}: non-finite scores")
    err = float(np.abs(got - want).max())
    bound = SERVE_RTOL * float(np.abs(want).max())
    check(err <= bound, f"{what}: max abs error {err:.4g} > {bound:.4g} "
          f"({SERVE_RTOL} of the largest reference logit)")
    return err


def _serve_pass(jm, rows, reference, buckets, mesh, n_replicas) -> dict:
    import numpy as np

    from mmlspark_tpu.data.table import DataTable
    from mmlspark_tpu.serve import Client, ModelServer, ServeConfig
    from mmlspark_tpu.serve.http import start_http_server

    name = "resnet50"
    server = ModelServer(ServeConfig(buckets=buckets, deadline_ms=None,
                                     mesh=mesh))
    httpd = None
    try:
        # add_model warms the whole ladder (on every replica)
        hits0 = cache_counts["hits"]
        server.add_model(name, jm, example=DataTable({"image": [rows[0]]}))
        ladder_hits = cache_counts["hits"] - hits0
        client = Client(server)
        worst = 0.0
        start = 0
        for k in SERVE_REQUEST_ROWS:
            idx = np.arange(start, start + k) % len(rows)
            start += k
            out = client.predict(
                name, DataTable({"image": [rows[i] for i in idx]}),
                timeout=300)
            worst = max(worst, _check_scores(
                np.stack(out["scores"]), reference[idx],
                f"Client request of {k} row(s)"))
        httpd = start_http_server(server, host="127.0.0.1", port=0)
        answer = _http_predict(int(httpd.server_address[1]), name, rows[0])
        worst = max(worst, _check_scores(
            np.asarray([answer[0]["scores"]]), reference[:1],
            "HTTP request"))

        used: dict = {}
        if n_replicas > 1:
            # the scheduler hands a batch to the least-loaded lane, so
            # only concurrent full-bucket requests reach every replica
            full = buckets[-1]
            table = DataTable({"image": [rows[i % len(rows)]
                                         for i in range(full)]})
            for _ in range(5):
                pending = [client.predict_async(name, table)
                           for _ in range(4 * n_replicas)]
                for req in pending:
                    out = req.result(300)
                    _check_scores(np.stack(out["scores"]),
                                  reference[np.arange(full) % len(rows)],
                                  "replica burst")
                used = {k: v.get("batches", 0) for k, v in
                        server.stats(name).snapshot()["replicas"].items()}
                if len(used) == n_replicas and all(used.values()):
                    break
            check(len(used) == n_replicas and all(used.values()),
                  f"a replica served no batch after 5 bursts: {used}")

        programs = server.compiled_programs(name)
        check(programs is not None and programs <= len(buckets),
              f"{programs} compiled programs for a ladder of "
              f"{len(buckets)}")
        facts = {"buckets": list(buckets), "compiled_programs": programs,
                 "ladder_programs_from_persistent_cache": ladder_hits,
                 "max_abs_err": round(worst, 5)}
        if used:
            facts["replica_batches"] = used
        return facts
    finally:
        if httpd is not None:
            httpd.shutdown()
            httpd.server_close()
        server.close()


def phase_serve(devices) -> dict:
    import numpy as np

    from mmlspark_tpu.data.table import DataTable
    from mmlspark_tpu.models.jax_model import JaxModel
    from mmlspark_tpu.models.zoo import get_model

    rng = np.random.default_rng(1)
    rows = rng.integers(0, 256, size=(16, IMAGE * IMAGE * 3)
                        ).astype(np.uint8)
    bundle = get_model("ResNet50_Infer", input_size=IMAGE)
    jm = JaxModel(model=bundle, input_col="image", output_col="scores")
    # the reference comes from a stage of its own: an offline call's program
    # lands in its stage's compiled-segment store, and the served stage's
    # store (what compiled_programs counts) is to hold the ladder alone
    offline = JaxModel(model=bundle, input_col="image", output_col="scores"
                       ).transform(DataTable({"image": list(rows)}))
    reference = np.stack(offline["scores"]).astype(np.float32)
    check(bool(np.isfinite(reference).all()),
          "offline transform produced non-finite scores")

    facts = {"rtol_of_max_logit": SERVE_RTOL,
             "max_ref_logit": round(float(np.abs(reference).max()), 4),
             "default": _serve_pass(jm, rows, reference, SERVE_BUCKETS,
                                    None, 1)}
    n = len(devices)
    if n > 1:
        facts[f"dp={n}"] = _serve_pass(jm, rows, reference,
                                       SERVE_REPLICA_BUCKETS, f"dp={n}", n)
    return facts


# ---------------------------------------------------------------------------
# generate
# ---------------------------------------------------------------------------

def _bf16_params(params):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, params)


def _logits_check(model, params32, params16) -> dict:
    """Prefill-then-decode logits of ONE sequence through the cache path
    (the model code the engine's two programs run, default
    ``decode_attention`` → the Pallas kernel) against a plain full forward
    in float32 at matmul precision "highest"."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    L, steps, T = 40, 8, GEN["t_max"]
    rng = np.random.default_rng(3)
    seq = rng.integers(1, GEN["vocab"], size=(1, L + steps)).astype(np.int32)

    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(
            lambda p, t: model.apply({"params": p}, t))(params32, seq),
            np.float32)[0]                       # [L+steps, vocab]

    @jax.jit
    def prefill(p, toks):
        return model.apply({"params": p}, toks, return_cache=True)

    @jax.jit
    def decode(p, tok, ck, cv, pos):
        return model.apply({"params": p}, tok, cache=(ck, cv),
                           positions=pos)

    logits, (pk, pv) = prefill(params16, seq[:, :L])
    got = [np.asarray(logits, np.float32)[0]]    # [L, vocab]
    shape = (1, GEN["layers"], GEN["heads"], T,
             GEN["embed"] // GEN["heads"])
    ck = jnp.zeros(shape, pk.dtype).at[:, :, :, :L].set(pk)
    cv = jnp.zeros(shape, pv.dtype).at[:, :, :, :L].set(pv)
    for s in range(steps):
        pos = jnp.asarray([L + s], jnp.int32)
        step_logits, (ck, cv) = decode(params16, seq[:, L + s:L + s + 1],
                                       ck, cv, pos)
        got.append(np.asarray(step_logits, np.float32))
    got = np.concatenate(got)
    check(got.shape == ref.shape, f"logits {got.shape} vs {ref.shape}")
    check(bool(np.isfinite(got).all()), "non-finite logits")
    scale = float(np.abs(ref).max())
    err_prefill = float(np.abs(got[:L] - ref[:L]).max())
    err_decode = float(np.abs(got[L:] - ref[L:]).max())
    bound = GEN_LOGITS_RTOL * scale
    check(max(err_prefill, err_decode) <= bound,
          f"logits off the f32 reference: prefill {err_prefill:.4g}, "
          f"decode {err_decode:.4g} > {bound:.4g} "
          f"({GEN_LOGITS_RTOL} of max |logit| {scale:.4g})")
    agree = float(np.mean(got.argmax(-1) == ref.argmax(-1)))
    return {"logits_max_abs_err_prefill": round(err_prefill, 5),
            "logits_max_abs_err_decode": round(err_decode, 5),
            "logits_bound": round(bound, 5),
            "max_ref_logit": round(scale, 4),
            "argmax_agreement": round(agree, 3)}


def phase_generate(devices) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.models.sequence import TransformerTagger
    from mmlspark_tpu.serve import GenerateConfig, ModelServer, ServeConfig

    model = TransformerTagger(
        vocab_size=GEN["vocab"], embed_dim=GEN["embed"],
        num_heads=GEN["heads"], num_layers=GEN["layers"],
        mlp_dim=GEN["mlp"], num_tags=GEN["vocab"], max_len=GEN["t_max"],
        causal=True)
    params32 = model.init(jax.random.PRNGKey(0),
                          np.zeros((1, 8), np.int32))["params"]
    params16 = _bf16_params(params32)
    cfg = GenerateConfig(
        slots=GEN["slots"], t_max=GEN["t_max"],
        prefill_buckets=GEN["prefill_buckets"],
        prefill_rows=GEN["prefill_rows"],
        max_new_tokens=GEN["new_tokens"], max_queue=64)

    rng = np.random.default_rng(2)
    prompts = [[int(t) for t in rng.integers(1, GEN["vocab"], n)]
               for n in GEN_PROMPT_LENGTHS]
    server = ModelServer(ServeConfig())
    try:
        server.add_generator("lm", model, params16, config=cfg)
        hits0 = cache_counts["hits"]
        streams = [server.generate("lm", p) for p in prompts]
        outs = []
        for stream in streams:
            toks = []
            for tok in stream:        # streamed: token by token
                toks.append(tok)
            outs.append(toks)
        # the engine compiled its prefill ladder and its decode program in
        # that window, and nothing else
        engine_hits = cache_counts["hits"] - hits0
        for n, toks in zip(GEN_PROMPT_LENGTHS, outs):
            check(len(toks) == GEN["new_tokens"],
                  f"prompt of {n}: {len(toks)} tokens streamed, wanted "
                  f"{GEN['new_tokens']}")
            check(all(0 <= t < GEN["vocab"] for t in toks),
                  f"prompt of {n}: token outside the vocabulary")
        snap = server.snapshot()["lm"]
        programs = snap["programs_compiled"]
        budget = len(cfg.prefill_buckets) + 1
        check(programs is not None and programs <= budget,
              f"{programs} compiled programs, budget {budget}")

        # the decode program the engine runs must contain the kernel: if
        # the wrapper took the reference path this fails
        engine = server._generator("lm").engine
        bufs = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            engine._state.buffers)
        check(bufs["k"].dtype == jnp.bfloat16,
              f"KV cache dtype {bufs['k'].dtype}, wanted bfloat16")
        S = cfg.slots
        i32 = jax.ShapeDtypeStruct((S,), jnp.int32)
        flag = jax.ShapeDtypeStruct((S,), jnp.bool_)
        compiled = engine._decode.jitted.lower(
            bufs, params16, i32, i32, flag, i32, flag).compile()
        check(has_mosaic_call(compiled),
              "the compiled generate.decode program has no Mosaic custom "
              "call: decode_attention took its reference path")
    finally:
        server.close()

    facts = {"prompts": len(prompts), "tokens_each": GEN["new_tokens"],
             "compiled_programs": programs, "program_budget": budget,
             "decode_steps": snap.get("decode_steps"),
             "decode_program_has_mosaic_call": True,
             "engine_programs_from_persistent_cache": engine_hits,
             "kv_cache_dtype": "bfloat16"}
    facts.update(_logits_check(model, params32, params16))
    return facts


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _bf16_exact(rng, shape, dtype):
    """Standard-normal values rounded to bf16 (exactly representable in
    it), delivered in ``dtype``."""
    import jax.numpy as jnp

    return jnp.asarray(rng.standard_normal(shape, dtype="float32"),
                       jnp.bfloat16).astype(dtype)


def _kernel_case(name, kernel_fn, reference_fn, args, atol, rtol) -> dict:
    """Compile ``kernel_fn`` with the real compiler (it must contain a
    Mosaic call), run it, and compare with ``reference_fn`` run at matmul
    precision "highest"."""
    import jax
    import numpy as np

    compiled = jax.jit(kernel_fn).lower(*args).compile()
    check(has_mosaic_call(compiled),
          f"{name}: no Mosaic custom call in the compiled program")
    got = jax.tree_util.tree_leaves(compiled(*args))
    with jax.default_matmul_precision("highest"):
        want = jax.tree_util.tree_leaves(jax.jit(reference_fn)(*args))
    worst = 0.0
    for g, w in zip(got, want):
        g = np.asarray(g, np.float32)
        w = np.asarray(w, np.float32)
        check(g.shape == w.shape, f"{name}: shape {g.shape} vs {w.shape}")
        check(bool(np.isfinite(g[np.isfinite(w)]).all()),
              f"{name}: non-finite output")
        # -inf running maxima (fully masked rows) must match exactly
        check(bool((np.isfinite(g) == np.isfinite(w)).all()),
              f"{name}: finiteness pattern differs from the reference")
        fin = np.isfinite(w)
        err = np.abs(g[fin] - w[fin])
        ok = err <= atol + rtol * np.abs(w[fin])
        check(bool(ok.all()),
              f"{name}: max abs error {float(err.max()):.4g} outside "
              f"atol={atol} rtol={rtol}")
        worst = max(worst, float(err.max()) if err.size else 0.0)
    say(f"kernel {name}: compiled by Mosaic, max abs err {worst:.3g}")
    return {"max_abs_err": round(worst, 6)}


def phase_kernels(devices) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.obs.metrics import registry
    from mmlspark_tpu.ops.group_norm import group_norm, group_norm_reference
    from mmlspark_tpu.ops.pallas import attention as fa
    from mmlspark_tpu.ops.pallas.budget import FALLBACK_COUNTER
    from mmlspark_tpu.ops.pallas.causal_conv import causal_conv
    from mmlspark_tpu.ops.pallas.selective_scan import selective_scan
    from mmlspark_tpu.ops.pallas.ssd_scan import ssd_scan

    rng = np.random.default_rng(4)
    bf16, f32 = jnp.bfloat16, jnp.float32
    facts: dict = {}

    # decode: 8 slots of mixed length, one of them empty (exact zeros)
    S, H, T, D = 8, 8, 1024, 64
    lengths = np.asarray([1, 17, 128, 129, 500, 1023, 1024, 0])
    mask = jnp.asarray(np.arange(T)[None, :] < lengths[:, None])
    args = (_bf16_exact(rng, (S, H, D), bf16),
            _bf16_exact(rng, (S, H, T, D), bf16),
            _bf16_exact(rng, (S, H, T, D), bf16), mask)
    facts["decode_attention[S8,H8,T1024,D64,bf16]"] = _kernel_case(
        "decode_attention",
        lambda q, k, v, m: fa.decode_attention(q, k, v, kv_mask=m,
                                               impl="pallas"),
        lambda q, k, v, m: fa.decode_attention(q, k, v, kv_mask=m,
                                               impl="xla"),
        args, KERNEL_ATOL, KERNEL_RTOL)

    # flash, the ViT-B/16 serving tile: T=197 is not tile-aligned
    B, H, T, D = 64, 12, 197, 64
    args = tuple(_bf16_exact(rng, (B, H, T, D), bf16) for _ in range(3))
    facts["flash_attention[B64,H12,T197,D64,bf16]"] = _kernel_case(
        "flash_attention/vit",
        lambda q, k, v: fa.flash_attention(q, k, v, impl="pallas"),
        lambda q, k, v: fa.flash_attention(q, k, v, impl="xla"),
        args, KERNEL_ATOL, KERNEL_RTOL)

    # flash, causal with a key-validity mask at T=1024
    B, H, T, D = 2, 8, 1024, 64
    mask = jnp.asarray(np.arange(T)[None, :] < np.asarray([[T], [700]]))
    args = tuple(_bf16_exact(rng, (B, H, T, D), bf16)
                 for _ in range(3)) + (mask,)
    facts["flash_attention[causal,B2,H8,T1024,D64,bf16]"] = _kernel_case(
        "flash_attention/causal",
        lambda q, k, v, m: fa.flash_attention(q, k, v, kv_mask=m,
                                              causal=True, impl="pallas"),
        lambda q, k, v, m: fa.flash_attention(q, k, v, kv_mask=m,
                                              causal=True, impl="xla"),
        args, KERNEL_ATOL, KERNEL_RTOL)

    # flash, tiled: the language model's causal window of whole tiles with
    # no key to mask (the blocks below the diagonal run with no mask),
    # equal to the bit to the same call with an all-true key row (every
    # block general); and a masked window of 2100, not a whole number of
    # tiles
    B, H, T, D = 2, 8, 4096, 128
    args = tuple(_bf16_exact(rng, (B, H, T, D), bf16) for _ in range(3))
    facts["flash_attention[tiled,causal,B2,H8,T4096,D128,bf16]"] = \
        _kernel_case(
            "flash_attention/tiled",
            lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                               impl="pallas"),
            lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                               impl="xla"),
            args, KERNEL_ATOL, KERNEL_RTOL)
    bare, rowed = (np.asarray(fa.flash_attention(
        *args, kv_mask=m, causal=True, impl="pallas"))
        for m in (None, jnp.ones((B, T), bool)))
    check(bool((bare.view(np.uint32) == rowed.view(np.uint32)).all()),
          "flash_attention/tiled: the mask-free tiles differ from the "
          "masked arithmetic on an all-true mask")
    T = 2100
    mask = jnp.asarray(np.arange(T)[None, :] < np.asarray([[T], [1300]]))
    args = tuple(_bf16_exact(rng, (B, H, T, D), bf16)
                 for _ in range(3)) + (mask,)
    facts["flash_attention[tiled,masked,B2,H8,T2100,D128,bf16]"] = \
        _kernel_case(
            "flash_attention/tiled_masked",
            lambda q, k, v, m: fa.flash_attention(
                q, k, v, kv_mask=m, causal=True, impl="pallas"),
            lambda q, k, v, m: fa.flash_attention(
                q, k, v, kv_mask=m, causal=True, impl="xla"),
            args, KERNEL_ATOL, KERNEL_RTOL)

    # the ring-hop block update: a second hop over a live carry, with a
    # causal-and-padding style mask (some rows fully masked)
    B, H, T, D = 2, 8, 512, 64
    keep = jnp.asarray(np.broadcast_to(
        (np.arange(T)[None, :] <= np.arange(T)[:, None])
        & (np.arange(T) < 400)[None, :], (B, T, T)))
    q, k, v = (_bf16_exact(rng, (B, H, T, D), f32) for _ in range(3))
    m0 = jnp.asarray(rng.standard_normal((B, H, T, 1), dtype="float32"))
    d0 = jnp.asarray(rng.random((B, H, T, 1), dtype="float32") + 0.5)
    a0 = _bf16_exact(rng, (B, H, T, D), f32)
    scale = fa._resolve_scale(None, D)

    def hop(impl):
        # the accumulator is unnormalized (row sums of up to 400 weights),
        # so it is compared the way the ring consumes it: divided by the
        # denominator. The running max and the denominator compare as is
        def run(*a):
            m, den, acc = fa.attention_block_update(*a, scale, impl=impl)
            return m, den, acc / jnp.maximum(den, 1e-30)
        return run

    facts["attention_block_update[B2,H8,T512,D64,f32]"] = _kernel_case(
        "attention_block_update", hop("pallas"), hop("xla"),
        (q, k, v, keep, m0, d0, a0), KERNEL_ATOL, KERNEL_RTOL)

    # GroupNorm(+ReLU) at the largest ResNet-50 mid-stage block
    N, HW, C, G = 8, 56, 256, 32
    x = _bf16_exact(rng, (N, HW, HW, C), bf16) * 3 + 1
    gamma = jnp.asarray(rng.standard_normal(C, dtype="float32"))
    beta = jnp.asarray(rng.standard_normal(C, dtype="float32"))
    facts["group_norm[N8,56x56x256,bf16,relu]"] = _kernel_case(
        "group_norm",
        lambda a, s, b: group_norm(a, s, b, G, relu=True),
        lambda a, s, b: group_norm_reference(a, s, b, G, relu=True),
        (x, gamma, beta), GN_ATOL, KERNEL_RTOL)

    # the selective scan at one row of the state-space cell: 16,384
    # positions in 64 chunks whose state is carried in VMEM, 5,120 channels,
    # 16 states; step sizes and decay rates as the mixer hands them
    L, C, NS = 16384, 5120, 16
    delta = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(1e-1),
                                           (1, L, C))), f32)
    a = -jnp.asarray(np.tile(np.arange(1, NS + 1, dtype=np.float32), (C, 1)))
    args = (_bf16_exact(rng, (1, L, C), bf16), delta, a,
            _bf16_exact(rng, (1, L, NS), f32), _bf16_exact(rng, (1, L, NS), f32),
            1 + 0.1 * _bf16_exact(rng, (C,), f32),
            _bf16_exact(rng, (1, L, C), bf16))
    facts["selective_scan[L16384,C5120,N16,bf16]"] = _kernel_case(
        "selective_scan",
        lambda *o: selective_scan(*o, impl="pallas").astype(f32),
        lambda *o: selective_scan(*o, impl="xla").astype(f32),
        args, SCAN_ATOL, KERNEL_RTOL)

    # the Mamba-2 scan at one row of its cell: 16,384 positions in 16 blocks
    # of 8 chunks whose state (a group's [128, 8 x 64]) is carried in VMEM,
    # 64 heads of 64 in 8 groups, [x | B | C] read where they lie
    sizes = dict(heads=64, head_dim=64, groups=8, state=128)
    args = (_bf16_exact(rng, (1, L, 6144), bf16),
            jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(1e-1),
                                           (1, L, 64))), f32),
            -jnp.asarray(rng.uniform(1, 16, 64), f32),
            1 + 0.1 * _bf16_exact(rng, (64,), f32))
    facts["ssd_scan[L16384,H64,P64,G8,N128,bf16]"] = _kernel_case(
        "ssd_scan",
        lambda *o: ssd_scan(*o, impl="pallas", **sizes).astype(f32),
        lambda *o: ssd_scan(*o, impl="xla", **sizes).astype(f32),
        args, SSD_ATOL, KERNEL_RTOL)

    # the short causal convolution at both families' cells, each reading
    # the wide float32 product where it lies: the Mamba mixer's (4 taps,
    # bias, SiLU, the gate half cast beside it) and the gated one (3 taps,
    # [B | C | u], two rows a step); one bfloat16 rounding apart at most
    def conv(impl, **parts):
        return lambda wide, taps, bias=None: causal_conv(
            wide, taps, bias=bias, dtype=bf16, impl=impl, **parts)

    mamba = dict(channels=5120, cast_at=5120, silu=True)
    args = (_bf16_exact(rng, (1, 16384, 10240), f32),
            _bf16_exact(rng, (4, 5120), bf16) / 2,
            _bf16_exact(rng, (5120,), f32) / 4)
    facts["causal_conv[L16384,C5120of10240,K4,bias,silu]"] = _kernel_case(
        "causal_conv", conv("pallas", **mamba), conv("xla", **mamba), args,
        KERNEL_ATOL, KERNEL_RTOL)
    gated = dict(channels=2048, at=4096, pre_at=0, post_at=2048)
    args = (_bf16_exact(rng, (2, 8192, 6144), f32),
            _bf16_exact(rng, (3, 2048), bf16) / 2)
    facts["causal_conv[B2,L8192,C2048of6144,K3,gated]"] = _kernel_case(
        "causal_conv", conv("pallas", **gated), conv("xla", **gated), args,
        KERNEL_ATOL, KERNEL_RTOL)
    del args

    # no wrapper, here or in any earlier phase, may have given way to its
    # reference over a VMEM estimate
    fallbacks = {k: v for k, v in
                 registry().snapshot()["counters"].items()
                 if k.startswith(FALLBACK_COUNTER) and v}
    check(not fallbacks, f"kernel wrappers fell back over VMEM: {fallbacks}")
    facts["vmem_fallbacks"] = 0
    return facts


# ---------------------------------------------------------------------------

def main() -> int:
    t_start = time.perf_counter()
    if not os.path.isdir(os.path.join(HERE, "mmlspark_tpu")):
        print("chip_smoke: FAIL the repo is not here — chip_smoke.py runs "
              "from the root of a checkout (no mmlspark_tpu/ next to it)",
              file=sys.stderr)
        return 2
    sys.path.insert(0, HERE)

    from mmlspark_tpu.utils.jit_cache import ENV_VAR, place_compilation_cache
    cache_dir = place_compilation_cache()

    import jax

    jax.monitoring.register_event_listener(_on_jax_event)
    devices = jax.devices()
    dev = devices[0]
    say(f"platform={dev.platform} device_kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        print(f"chip_smoke: FAIL no TPU — jax found platform="
              f"{dev.platform!r} (JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS')!r}); this script proves "
              "the system on the chip and does not fall back",
              file=sys.stderr)
        return 3
    say(f"jax {jax.__version__}; compile cache at {cache_dir} "
        f"({ENV_VAR} {'set' if os.environ.get(ENV_VAR) else 'unset'})")

    # what loads as the native image library must have been built from
    # native/src/imgops.cpp in THIS run: drop any stray (git-ignored) .so
    # before the lazy loader first looks for it
    stray = os.path.join(HERE, "mmlspark_tpu", "native", "libimgops.so")
    if os.path.exists(stray):
        os.remove(stray)
    from mmlspark_tpu.native import imgops
    say(f"native.imgops.available()={imgops.available()} "
        "(built from native/src/imgops.cpp in this run)")

    for name, fn in (("train", phase_train), ("serve", phase_serve),
                     ("generate", phase_generate),
                     ("kernels", phase_kernels)):
        run_phase(name, fn, devices)

    say(f"all phases ok in {time.perf_counter() - t_start:.0f} s; "
        f"persistent cache answered {cache_counts['hits']} of "
        f"{cache_counts['requests']} compile requests")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
