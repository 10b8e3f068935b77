"""Plain reference for a ViT fine-tuning step: forward, loss, gradients and
the momentum update, in straightforward ``jax.numpy`` and float32.

It follows the published description (Dosovitskiy et al., arXiv:2010.11929,
section 3.1 and Appendix B.1.1) as google-research/vision_transformer
implements it: pre-LayerNorm encoder blocks, tanh-approximated GELU,
softmax cross-entropy, SGD with momentum. Its departures, each stated in
the configuration file: pooling is the global average over the patch tokens
(no class token), and the stored parameters and moments are rounded to the
storage type the configuration states after every step.

It imports nothing of the program and is given nothing the program made:
the weights come from :func:`make_params`, from the seed's key, and the
driver hands the same values to the program.

``quant`` puts a lower precision in the reference's place (the control of
"How correct is decided"): every operand of a matrix product is rounded to
that type, scaled per tensor, before the product.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference.rounding import operand_rounder, round_to

HIGHEST = jax.lax.Precision.HIGHEST


def param_shapes(cfg: dict) -> dict:
    """Flat ``path -> shape``; the paths are flax's, joined by '/'."""
    d, m = cfg["hidden_size"], cfg["intermediate_size"]
    h, dh = cfg["num_attention_heads"], cfg["head_dim"]
    p, c = cfg["patch_size"], cfg["num_channels"]
    tokens = (cfg["image_size"] // p) ** 2
    shapes = {"patch_embed/kernel": (p, p, c, d), "patch_embed/bias": (d,),
              "pos_embed": (tokens, d)}
    for i in range(cfg["num_hidden_layers"]):
        b = f"block{i}/"
        for ln in ("ln1", "ln2"):
            shapes[b + ln + "/scale"] = (d,)
            shapes[b + ln + "/bias"] = (d,)
        for name in ("query", "key", "value"):
            shapes[b + f"attn/{name}/kernel"] = (d, h, dh)
            shapes[b + f"attn/{name}/bias"] = (h, dh)
        shapes[b + "attn/out/kernel"] = (h, dh, d)
        shapes[b + "attn/out/bias"] = (d,)
        shapes[b + "mlp_in/kernel"] = (d, m)
        shapes[b + "mlp_in/bias"] = (m,)
        shapes[b + "mlp_out/kernel"] = (m, d)
        shapes[b + "mlp_out/bias"] = (d,)
    shapes["ln_f/scale"] = (d,)
    shapes["ln_f/bias"] = (d,)
    shapes["head/kernel"] = (d, cfg["num_classes"])
    shapes["head/bias"] = (cfg["num_classes"],)
    return shapes


def _fan_in(path: str, shape: tuple) -> int:
    if path.endswith("attn/out/kernel"):
        return shape[0] * shape[1]
    return math.prod(shape[:-1]) if "attn/" not in path else shape[0]


def make_params(cfg: dict, key) -> dict:
    """All weights from one key, as float32 values that the configuration's
    ``param_dtype`` holds exactly. Traceable: the driver jits it, so the
    weights are made on the device in one call."""
    store = jnp.dtype(cfg["param_dtype"])
    shapes = param_shapes(cfg)
    out = {}
    for (path, shape), k in zip(shapes.items(),
                                jax.random.split(key, len(shapes))):
        z = jax.random.normal(k, shape, jnp.float32)
        if path.endswith("/kernel"):
            v = z / math.sqrt(_fan_in(path, shape))
        elif path.endswith("/scale"):
            v = 1.0 + 0.1 * z
        else:                       # biases and the position embedding
            v = 0.02 * z
        out[path] = round_to(v, store)
    return out


def _layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def forward(params: dict, images, cfg: dict, quant: str | None = None):
    """Logits ``[B, classes]`` of uint8 (or float) images ``[B, S, S, C]``."""
    q = operand_rounder(quant)

    def mm(spec, a, b):
        return jnp.einsum(spec, q(a), q(b), precision=HIGHEST)

    p, d = cfg["patch_size"], cfg["hidden_size"]
    eps = cfg["layer_norm_eps"]
    x = images.astype(jnp.float32)
    if images.dtype == jnp.uint8:
        x = x * cfg["input_scale"]
    B, S, _, C = x.shape
    g = S // p
    x = x.reshape(B, g, p, g, p, C).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(B, g * g, p * p * C)
    x = mm("btk,kd->btd", x, params["patch_embed/kernel"].reshape(-1, d))
    x = x + params["patch_embed/bias"] + params["pos_embed"]
    scale = cfg["head_dim"] ** -0.5
    for i in range(cfg["num_hidden_layers"]):
        b = f"block{i}/"
        h = _layer_norm(x, params[b + "ln1/scale"], params[b + "ln1/bias"],
                        eps)
        qkv = [mm("btd,dhe->bhte", h, params[b + f"attn/{n}/kernel"])
               + params[b + f"attn/{n}/bias"][None, :, None, :]
               for n in ("query", "key", "value")]
        scores = mm("bhqe,bhke->bhqk", qkv[0] * scale, qkv[1])
        probs = jax.nn.softmax(scores, axis=-1)
        o = mm("bhqk,bhke->bhqe", probs, qkv[2])
        o = mm("bhte,hed->btd", o, params[b + "attn/out/kernel"])
        x = x + o + params[b + "attn/out/bias"]
        h = _layer_norm(x, params[b + "ln2/scale"], params[b + "ln2/bias"],
                        eps)
        h = mm("btd,dm->btm", h, params[b + "mlp_in/kernel"])
        h = _gelu_tanh(h + params[b + "mlp_in/bias"])
        h = mm("btm,md->btd", h, params[b + "mlp_out/kernel"])
        x = x + h + params[b + "mlp_out/bias"]
    x = _layer_norm(x, params["ln_f/scale"], params["ln_f/bias"], eps)
    x = jnp.mean(x, axis=1)
    return mm("bd,dc->bc", x, params["head/kernel"]) + params["head/bias"]


def loss_sum(params: dict, images, labels, cfg: dict,
             quant: str | None = None):
    """Softmax cross-entropy summed over the rows."""
    logits = forward(params, images, cfg, quant)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def make_block_grad(cfg: dict, quant: str | None = None):
    """The jitted ``(params, loss so far, gradient so far, images, labels)
    -> (loss sum, gradient sum)`` that adds one block of rows."""
    vg = jax.value_and_grad(lambda p, x, y: loss_sum(p, x, y, cfg, quant))

    def add_block(params, total, grads, images, labels):
        l, g = vg(params, images, labels)
        return total + l, jax.tree_util.tree_map(jnp.add, grads, g)
    return jax.jit(add_block, donate_argnums=(1, 2))


@jax.jit
def _scaled(tree, factor):
    return jax.tree_util.tree_map(lambda a: a * factor, tree)


def loss_and_grad(block_grad, params: dict, images, labels,
                  block_rows: int, rows: slice | None = None):
    """Mean loss and its gradient over a batch, in blocks of rows so that
    float32 activations fit beside nothing else. ``rows`` restricts the
    mean to a part of the batch (a planted fault)."""
    if rows is not None:
        images, labels = images[rows], labels[rows]
    n = len(images)
    total = jnp.zeros((), jnp.float32)
    grads = _scaled(params, 0.0)
    for s in range(0, n, block_rows):
        total, grads = block_grad(
            params, total, grads, jnp.asarray(images[s:s + block_rows]),
            jnp.asarray(labels[s:s + block_rows], jnp.int32))
    return total / n, _scaled(grads, 1.0 / n)


@functools.partial(jax.jit, static_argnames=("store",))
def _momentum_update(params, trace, grads, lr, decay, store):
    def rnd(a):
        return round_to(a, store)
    trace = jax.tree_util.tree_map(lambda g, t: rnd(g + decay * t),
                                   grads, trace)
    params = jax.tree_util.tree_map(lambda p, t: rnd(p - lr * t),
                                    params, trace)
    return params, trace


def momentum_step(params, trace, grads, cfg: dict):
    """``trace = g + momentum * trace; p = p - lr * trace``, in float32,
    the stored results rounded to the configuration's storage type."""
    return _momentum_update(params, trace, grads, cfg["learning_rate"],
                            cfg["momentum"], store=cfg["param_dtype"])


@jax.jit
def _leaf_norms(tree):
    return jax.tree_util.tree_map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), tree)


def leaf_norms(tree: dict) -> dict:
    """Euclidean norm of every leaf, as python floats."""
    return {k: float(v) for k, v in _leaf_norms(tree).items()}


def first_steps(cfg: dict, key, batches: list, block_rows: int,
                quant: str | None = None, rows: slice | None = None) -> dict:
    """Follow the first ``len(batches)`` steps from the seed's weights.

    Returns each step's loss, the leaf norms of the first gradient, and the
    leaf norms of the parameters' change after the last step."""
    block_grad = make_block_grad(cfg, quant)
    p0 = jax.jit(lambda k: make_params(cfg, k))(key)
    params = p0
    trace = jax.tree_util.tree_map(jnp.zeros_like, p0)
    losses, grad_norms = [], None
    for images, labels in batches:
        loss, grads = loss_and_grad(block_grad, params, images, labels,
                                    block_rows, rows)
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = leaf_norms(grads)
        params, trace = momentum_step(params, trace, grads, cfg)
    change = leaf_norms(jax.tree_util.tree_map(jnp.subtract, params, p0))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}
