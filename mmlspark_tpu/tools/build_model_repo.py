"""Build a local pretrained-model repository (the zoo-publishing tool).

The reference serves pretrained CNTK models from an Azure CDN manifest
(reference: ModelDownloader.scala:184-186; Schema.scala:54-74 records each
model's dataset provenance). This environment has no egress, so the
equivalent is a reproducible local repository built from data available
in-image:

* image models (ConvNet / ResNet / ViT families) train on **real data** —
  scikit-learn's handwritten-digits set upscaled to 32×32 RGB — to
  genuinely good held-out accuracy, which is **measured and recorded in
  the manifest** (``eval_metric``/``eval_value``),
* the BiLSTM tagger trains on a deterministic synthetic tagging rule,
  with held-out token accuracy recorded the same way,
* the full-size ResNet50 / ViT_B16 entries are size stand-ins (real
  pretraining needs data egress); their manifests say so (dataset
  ``synthetic-standin``) rather than implying capability.

Usage:
    mmlspark-tpu-build-repo <repo_dir> [--scale small|full]
    (or: python -m mmlspark_tpu.tools.build_model_repo <repo_dir>)

``small`` (default) publishes CI-scale models in under two minutes;
``full`` also publishes ResNet50 / ResNet50_Infer (the folded frozen-BN
serving variant) / ViT_B16 at real parameter count.
"""

from __future__ import annotations

import argparse
import sys

import numpy as np


def digits_rgb32() -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Real image data without egress: sklearn digits (1797 8×8 grayscale)
    upscaled ×4 to 32×32 and tiled to RGB, pixel range 0-255. Deterministic
    80/20 split shared by the publisher, the examples, and the tests, so
    every recorded accuracy is honest held-out accuracy."""
    try:
        from sklearn.datasets import load_digits
    except ImportError as e:  # same convention as ml/learners._require_sklearn
        raise ImportError(
            "building the model repository trains on scikit-learn's digits "
            "dataset — pip install scikit-learn (or mmlspark-tpu[trees])"
        ) from e

    d = load_digits()
    x8 = d.images.astype(np.float32) * (255.0 / 16.0)       # [N, 8, 8]
    x32 = np.kron(x8, np.ones((1, 4, 4), np.float32))       # [N, 32, 32]
    x = np.repeat(x32[..., None], 3, axis=-1)               # [N, 32, 32, 3]
    y = d.target.astype(np.int64)
    order = np.random.default_rng(0).permutation(len(x))
    x, y = x[order], y[order]
    split = int(0.8 * len(x))
    return x[:split], y[:split], x[split:], y[split:]


def _train_eval(bundle, xtr, ytr, xte, yte, steps: int = 300,
                bs: int = 128, lr: float = 1e-3):
    """Train with Adam on (xtr, ytr), measure held-out accuracy on
    (xte, yte); returns (bundle, accuracy). Training runs through the same
    preprocessing the scoring path applies, so downloaded weights behave
    identically under ``JaxModel``."""
    import jax
    import jax.numpy as jnp
    import optax

    from mmlspark_tpu.models.bundle import PREPROCESSORS

    tx = optax.adam(lr)
    opt = tx.init(bundle.params)
    params = bundle.params
    pre = PREPROCESSORS.get(bundle.preprocess) if bundle.preprocess else None

    def logits_fn(p, xb):
        if pre is not None:
            xb = pre(xb)
        return bundle.module.apply({"params": p}, xb, output="logits")

    def loss_fn(p, xb, yb):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits_fn(p, xb), yb).mean()

    @jax.jit
    def step(p, o, xb, yb):
        l, g = jax.value_and_grad(loss_fn)(p, xb, yb)
        up, o = tx.update(g, o)
        return optax.apply_updates(p, up), o, l

    bs = min(bs, len(xtr))
    r = np.random.default_rng(0)
    first = last = None
    order = None
    per_epoch = max(1, len(xtr) // bs)
    for i in range(steps):
        if i % per_epoch == 0:
            order = r.permutation(len(xtr))
        s = (i % per_epoch) * bs
        idx = order[s:s + bs]
        params, opt, l = step(params, opt, xtr[idx], ytr[idx])
        # keep device scalars; resolve after the loop (a float() here
        # blocks the host on every step — JX105)
        if first is None:
            first = l
        last = l
    first, last = float(first), float(last)

    jeval = jax.jit(logits_fn)
    preds = []
    for s in range(0, len(xte), 256):
        preds.append(np.asarray(jeval(params, xte[s:s + 256])).argmax(-1))
    acc = float((np.concatenate(preds) == yte).mean())
    print(f"  {bundle.name}: loss {first:.3f} -> {last:.3f} "
          f"({steps} steps), held-out accuracy {acc:.3f}")
    bundle.params = params
    return bundle, acc


def _train_bn_and_fold(xtr, ytr, xte, yte, steps: int = 200, bs: int = 128,
                       lr: float = 1e-3):
    """The reference-parity zoo flow: train a *BatchNorm* ResNet (the
    reference zoo's ResNet-50 is a BN network, Schema.scala:54-74), then
    fold the frozen statistics into the conv weights at publish time
    (models/resnet.py:fold_batchnorm) and publish the norm-free inference
    bundle. The recorded accuracy is measured on the FOLDED net — the
    artifact users download."""
    import jax
    import jax.numpy as jnp
    import optax

    from mmlspark_tpu.models.bundle import PREPROCESSORS, ModelBundle
    from mmlspark_tpu.models.resnet import fold_batchnorm, resnet18_thin

    module = resnet18_thin(norm="batch")
    pre = PREPROCESSORS["imagenet_norm"]
    variables = module.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 32, 32, 3), jnp.float32))
    params, stats = variables["params"], variables["batch_stats"]
    tx = optax.adam(lr)
    opt = tx.init(params)

    def loss_fn(p, st, xb, yb):
        logits, new_state = module.apply(
            {"params": p, "batch_stats": st}, pre(xb), output="logits",
            train=True, mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, yb).mean()
        return loss, new_state["batch_stats"]

    @jax.jit
    def step(p, st, o, xb, yb):
        (l, st), g = jax.value_and_grad(loss_fn, has_aux=True)(p, st, xb, yb)
        up, o = tx.update(g, o)
        return optax.apply_updates(p, up), st, o, l

    bs = min(bs, len(xtr))
    r = np.random.default_rng(0)
    per_epoch = max(1, len(xtr) // bs)
    order = None
    first = last = None
    for i in range(steps):
        if i % per_epoch == 0:
            order = r.permutation(len(xtr))
        s = (i % per_epoch) * bs
        idx = order[s:s + bs]
        params, stats, opt, l = step(params, stats, opt, xtr[idx], ytr[idx])
        first = first if first is not None else l  # resolved after the loop
        last = l
    first, last = float(first), float(last)

    folded = fold_batchnorm({"params": params, "batch_stats": stats},
                            param_dtype=jnp.bfloat16)
    # publish with the MXU-shaped s2d stem — same param layout (parity
    # tested in tests/test_models.py::test_s2d_stem_matches_direct_stem)
    net = resnet18_thin(norm="none", stem="s2d")
    bundle = ModelBundle(module=net, params=folded, input_spec=(32, 32, 3),
                         output_names=type(net).OUTPUT_NAMES,
                         preprocess="imagenet_norm",
                         name="ResNet_Small_Infer")

    jeval = jax.jit(lambda p, xb: net.apply({"params": p}, pre(xb),
                                            output="logits"))
    preds = []
    for s in range(0, len(xte), 256):
        preds.append(np.asarray(jeval(folded, xte[s:s + 256])).argmax(-1))
    acc = float((np.concatenate(preds) == yte).mean())
    print(f"  ResNet_Small_Infer: loss {first:.3f} -> {last:.3f} "
          f"({steps} steps), folded held-out accuracy {acc:.3f}")
    return bundle, acc


def _class_blobs(n, shape, n_classes, seed=0):
    """Deterministic learnable image task (kept for the full-size
    stand-ins): class-dependent mean shift."""
    r = np.random.default_rng(seed)
    y = r.integers(0, n_classes, n)
    x = r.normal(size=(n,) + shape).astype(np.float32) * 20 + 128
    shift = (y[:, None].astype(np.float32) - n_classes / 2) * 8
    x = np.clip(x + shift[..., None, None], 0, 255)
    return x.astype(np.float32), y


def build(repo_dir: str, scale: str = "small") -> list:
    from mmlspark_tpu.data.downloader import ModelSchema, publish_model
    from mmlspark_tpu.models.zoo import get_model

    published = []

    def publish(bundle, dataset, model_type, layer_count,
                eval_metric="", eval_value=0.0):
        entry = publish_model(bundle, repo_dir, ModelSchema(
            name=bundle.name, dataset=dataset, model_type=model_type,
            input_node="input", num_layers=layer_count,
            eval_metric=eval_metric, eval_value=round(eval_value, 4)))
        published.append(entry)
        ev = (f", {eval_metric}={eval_value:.3f}" if eval_metric else "")
        print(f"  published {entry.name} ({entry.size} bytes, "
              f"sha256 {entry.hash[:12]}…{ev})")

    xtr, ytr, xte, yte = digits_rgb32()

    print("ConvNet_CIFAR10 (notebook-301 flagship) — digits-rgb32")
    # small scale keeps CI fast; full scale publishes the MXU-sized widths
    conv_kw = {} if scale == "full" else {
        "widths": (16, 32), "dense_width": 64}
    b = get_model("ConvNet_CIFAR10", **conv_kw)
    b, acc = _train_eval(b, xtr, ytr, xte, yte)
    publish(b, "digits-rgb32", "CNN", 8, "accuracy", acc)

    print("ResNet_Small (CI-scale ResNet family) — digits-rgb32")
    b = get_model("ResNet_Small", num_classes=10)
    b, acc = _train_eval(b, xtr, ytr, xte, yte)
    publish(b, "digits-rgb32", "ResNet", 18, "accuracy", acc)

    print("ResNet_Small_Infer (publish-time frozen-BN fold) — digits-rgb32")
    b, acc = _train_bn_and_fold(xtr, ytr, xte, yte)
    publish(b, "digits-rgb32", "ResNet-folded", 18, "accuracy", acc)

    print("ViT_Tiny (CI-scale ViT family) — digits-rgb32")
    b = get_model("ViT_Tiny", num_classes=10)
    b, acc = _train_eval(b, xtr, ytr, xte, yte)
    publish(b, "digits-rgb32", "ViT", 2, "accuracy", acc)

    print("BiLSTM_MedTag (notebook-304 tagger) — synthetic rule")
    import jax
    import optax

    vocab, tags, L = 512, 8, 64
    r = np.random.default_rng(2)
    toks = r.integers(1, vocab, size=(320, L)).astype(np.int32)
    # learnable rule: tag = token bucket
    tag = (toks % tags).astype(np.int32)
    tr_t, te_t = toks[:256], toks[256:]
    tr_y, te_y = tag[:256], tag[256:]
    b = get_model("BiLSTM_MedTag", vocab_size=vocab, num_tags=tags,
                  max_len=L, embed_dim=32, hidden=32)
    tx = optax.adam(3e-3)
    opt = tx.init(b.params)
    params = b.params

    def tag_loss(p, xb, yb):
        lg = b.module.apply({"params": p}, xb)
        return optax.softmax_cross_entropy_with_integer_labels(
            lg, yb).mean()

    @jax.jit
    def tstep(p, o, xb, yb):
        l, g = jax.value_and_grad(tag_loss)(p, xb, yb)
        up, o = tx.update(g, o)
        return optax.apply_updates(p, up), o, l

    first = last = None
    for i in range(120):
        s = (i * 64) % 192
        params, opt, l = tstep(params, opt, tr_t[s:s + 64], tr_y[s:s + 64])
        first = first if first is not None else l  # resolved after the loop
        last = l
    first, last = float(first), float(last)
    preds = np.asarray(jax.jit(
        lambda p, xb: b.module.apply({"params": p}, xb))(params, te_t)
    ).argmax(-1)
    tok_acc = float((preds == te_y).mean())
    print(f"  BiLSTM_MedTag: loss {first:.3f} -> {last:.3f}, "
          f"held-out token accuracy {tok_acc:.3f}")
    b.params = params
    publish(b, "MedEntity-synthetic", "BiLSTM", 2,
            "token_accuracy", tok_acc)

    if scale == "full":
        # full-size stand-ins: honest manifests (dataset says standin, no
        # eval claim) — real ImageNet-class pretraining needs data egress
        print("ResNet50 (full size, stand-in weights)")
        x64, y64 = _class_blobs(32, (64, 64, 3), 10, seed=3)
        b = get_model("ResNet50", num_classes=10, input_size=64)
        b, _ = _train_eval(b, x64, y64, x64, y64, steps=10, bs=32)
        publish(b, "synthetic-standin", "ResNet", 50)
        print("ResNet50_Infer (full size, folded inference variant)")
        # the featurization-serving form: frozen-BN folded + bf16 + s2d
        # stem (models/resnet.py)
        b = get_model("ResNet50_Infer", num_classes=10, input_size=224)
        publish(b, "synthetic-standin", "ResNet-folded", 50)
        print("ViT_B16 (full size, stand-in weights)")
        x224, y224 = _class_blobs(16, (224, 224, 3), 10, seed=4)
        b = get_model("ViT_B16", num_classes=10)
        b, _ = _train_eval(b, x224, y224, x224, y224, steps=5, bs=16)
        publish(b, "synthetic-standin", "ViT", 12)

    return published


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("repo_dir")
    ap.add_argument("--scale", choices=("small", "full"), default="small")
    args = ap.parse_args(argv if argv is not None else sys.argv[1:])
    entries = build(args.repo_dir, args.scale)
    print(f"published {len(entries)} models to {args.repo_dir}")


if __name__ == "__main__":
    main()
