"""The distributed training loop — mesh-sharded jit steps, no external process.

Where the reference writes the dataset to a text file and shells out to
``mpiexec -n <gpus> cntk ... parallelTrain=true`` for 1-bit-SGD MPI
all-reduce (reference: cntk-train/src/main/scala/CNTKLearner.scala:140-151,
CommandBuilders.scala:79-93), this trains in-process:

* a ``Mesh`` over the devices (``dp`` axis = the MPI-ring analog),
* batch arrays sharded ``P(('dp','fsdp'))``, params replicated (or sharded
  over ``fsdp``/``tp`` for large models),
* the loss is a mean over the *global* batch, so XLA inserts the gradient
  ``psum`` over ICI automatically — the collectives ride the compiled step,
* optimizer = any optax transformation; state is a pure pytree, so
  checkpoint/resume is just (de)serializing it.
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, Callable, Iterator

import numpy as np

from mmlspark_tpu.core import plan as plan_lib
from mmlspark_tpu.core.logging_utils import get_logger, timed
from mmlspark_tpu.obs import flight as _obs_flight
from mmlspark_tpu.obs import runtime as _obs_rt
from mmlspark_tpu.obs.anomaly import NonFiniteSentinel, StragglerDetector
from mmlspark_tpu.obs.metrics import registry as _obs_registry
from mmlspark_tpu.obs.spans import boundary_span as _obs_boundary
from mmlspark_tpu.obs.spans import span as _obs_span
from mmlspark_tpu.parallel import mesh as mesh_lib
from mmlspark_tpu.train import preprocess as preprocess_lib

_log = get_logger(__name__)


def _slow_step_detector(loop: str):
    """Lazy accessor for the per-fit slow-step detector
    (:class:`mmlspark_tpu.obs.slo.SlowStepDetector`): flags steps whose
    dispatch time exceeds 4× the rolling window median as
    ``train/slow_step`` events + a ``train.slow_steps`` counter — the
    per-step health signal of a training run (a preempted host, a
    straggling collective, a donation stall all surface here). Created
    on first use so a fit with the tracer off never touches the
    registry; call sites gate on ``obs.runtime._enabled``."""
    box: dict = {}

    def get():
        det = box.get("det")
        if det is None:
            from mmlspark_tpu.obs.slo import SlowStepDetector
            det = box["det"] = SlowStepDetector(loop=loop)
        return det

    return get


@dataclasses.dataclass
class TrainConfig:
    batch_size: int = 128
    epochs: int = 1
    learning_rate: float = 1e-3
    optimizer: str = "adam"          # adam | sgd | momentum | adamw
    weight_decay: float = 0.0
    momentum: float = 0.9
    loss: str = "softmax_xent"       # softmax_xent | sigmoid_xent | mse
    # master-free low-precision training: cast params (and hence the
    # optimizer moments, which inherit leaf dtypes) to this dtype at init.
    # "bfloat16" halves param/moment HBM traffic per step — standard for
    # fine-tuning with SGD/momentum; avoid with adam (its second-moment
    # statistics need f32). None = float32 params (default)
    param_dtype: str | None = None
    # weight on sown "moe_aux" load-balance losses (MoE models); modules
    # that sow nothing are unaffected
    moe_aux_weight: float = 0.01
    seed: int = 0
    mesh_spec: Any = None            # MeshSpec | dict | None (dp over all)
    donate_state: bool = True
    log_every: int = 50
    # asynchronous input pipeline (train/input.py): batch assembly runs on
    # a background thread and the device commit is issued up to this many
    # batches ahead of consumption, so steady-state step wall-clock is
    # max(H2D, compute) instead of the sum; HBM held by in-flight batches
    # is bounded by the depth. 0 = fully synchronous (assemble + commit
    # inline in the step loop — the pre-round-7 behavior). Numerics are
    # bit-identical at every depth: the same host batches commit to the
    # same shardings in the same order
    prefetch_depth: int = 2
    # on-device scale applied after the f32 cast of uint8 inputs: uint8
    # image batches ship thin (¼ the H2D bytes of f32 — the round-2
    # inference convention applied to training) and cast/normalize INSIDE
    # the jitted step. The default maps raw bytes to [0, 1]; float inputs
    # are never touched
    input_scale: float = 1.0 / 255.0
    # on-device train preprocessing (train/preprocess.py): a
    # DevicePreprocess spec (or its plain-dict form) whose geometry
    # (source crop + bilinear resize), normalization, and stochastic
    # augmentation (pad-crop/flips/brightness/contrast) fuse INTO the
    # jitted step — one program, zero extra dispatches, thin uint8 on
    # the wire. Stochastic draws fold from the CHECKPOINTED global step,
    # so prefetch depth, host count, and resume all replay the identical
    # augmentation stream. None = the plain uint8 cast convention above
    preprocess: Any = None
    # multi-host fit_stream: local batches buffered per cross-process
    # liveness exchange. 1 = a host-side barrier every step (the
    # conservative round-3 behavior); larger values amortize it over up to
    # N device steps at the cost of buffering N local batches host-side.
    # Short processes pad the block with zero-weight filler, so step
    # counts are identical for any value
    liveness_sync_every: int = 8
    # multi-host fit_arrays: unequal per-process shard lengths normally
    # pad shorter shards with zero-weight rows (exact training — padded
    # rows contribute nothing); True restores the loud error instead
    strict_shards: bool = False
    # non-finite loss sentinel (obs/anomaly.py), checked on the SAME
    # one-step-lagged loss fetches the history already pays for (no new
    # host sync): "raise" (default) dies AT the divergence with a typed
    # NonFiniteLossError — and a flight-recorder dump when that is
    # enabled — "event" records train/nonfinite + a counter and
    # continues, "off" disables the check entirely
    nonfinite_loss: str = "raise"
    # mid-training checkpoint/resume (beyond-reference capability; SURVEY §5)
    checkpoint_dir: str | None = None
    checkpoint_every: int = 0        # global steps between saves; 0 = end only
    max_to_keep: int = 3
    resume: bool = True              # restore latest checkpoint if present


def make_optimizer(cfg: TrainConfig):
    import optax
    if cfg.optimizer == "adam":
        return optax.adam(cfg.learning_rate)
    if cfg.optimizer == "adamw":
        return optax.adamw(cfg.learning_rate, weight_decay=cfg.weight_decay)
    if cfg.optimizer == "sgd":
        return optax.sgd(cfg.learning_rate)
    if cfg.optimizer == "momentum":
        return optax.sgd(cfg.learning_rate, momentum=cfg.momentum)
    raise ValueError(f"unknown optimizer {cfg.optimizer!r}")


def _row_reduce(per, token_mask, jnp):
    """[B, ...] per-position losses → [B] per-example.

    With a ``token_mask`` ([B, L]): masked mean — the mask must match the
    loss grid's leading dims exactly and broadcasts over any trailing
    (class) axes, so a per-token multi-label head ([B, L, K]) masks pad
    positions across all K classes. A mask that tiles neither way is a
    loud error, never a silent plain mean."""
    if token_mask is not None:
        if token_mask.shape == per.shape[:token_mask.ndim]:
            tm = token_mask.reshape(
                token_mask.shape + (1,) * (per.ndim - token_mask.ndim))
            tm = jnp.broadcast_to(tm, per.shape).astype(per.dtype)
        else:
            raise ValueError(
                f"token_mask shape {tuple(token_mask.shape)} does not "
                f"tile per-position loss shape {tuple(per.shape)}")
        per = (per * tm).reshape(per.shape[0], -1)
        tm = tm.reshape(per.shape)
        return per.sum(axis=1) / jnp.maximum(tm.sum(axis=1), 1.0)
    return per.reshape(per.shape[0], -1).mean(axis=1)


def make_loss(kind: str) -> Callable:
    """Per-example loss [B]; callers take a plain or mask-weighted mean
    (mask-weighting is how the padded tail batch trains without bias).

    ``token_mask`` ([B, L] 0/1, optional): per-token tasks reduce over L
    with a masked mean, so intra-row pad positions neither dilute the
    real-token loss nor push the model to predict tag 0 on padding
    (advisor round 4). The train step derives it from the module's
    ``pad_token_id`` when the input is a token matrix."""
    import jax.numpy as jnp
    import optax

    if kind == "softmax_xent":
        def loss(logits, labels, token_mask=None):
            per = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels.astype(jnp.int32))
            # per-token tasks (logits [B, L, K], labels [B, L]) reduce to
            # one loss per example, like the other loss kinds — the masked
            # step weights rows by a [B] vector, so [B, L] would broadcast
            # wrongly (or only by luck when L == B)
            if per.ndim > 1:
                return _row_reduce(per, token_mask, jnp)
            return per
    elif kind == "sigmoid_xent":
        def loss(logits, labels, token_mask=None):
            z = logits
            if z.ndim > labels.ndim and z.shape[-1] == 1:
                z = z.squeeze(-1)  # binary head [B,1] vs labels [B]
            per = optax.sigmoid_binary_cross_entropy(
                z, labels.astype(z.dtype))
            # multi-label [B,K] / per-token: one loss per example
            if per.ndim > 1:
                return _row_reduce(per, token_mask, jnp)
            return per
    elif kind == "mse":
        def loss(logits, labels, token_mask=None):
            pred = logits.squeeze(-1) if logits.ndim > labels.ndim else logits
            per = (pred - labels.astype(pred.dtype)) ** 2
            # multi-target regression / per-token: one loss per example
            if per.ndim > 1:
                return _row_reduce(per, token_mask, jnp)
            return per
    else:
        raise ValueError(f"unknown loss {kind!r}")
    return loss


# THE 1-device fast-path criterion, shared with the elastic reshard
# targets (parallel/mesh.state_shardings): make_train_step's plain-jit
# path, Trainer.data_target's commit target, and reshard placement must
# always agree, or batches committed with a NamedSharding would feed a
# plain-jit program (or vice versa)
single_device = mesh_lib.single_device


def resolve_mesh_hooks(module: Any, mesh: Any) -> dict:
    """Ask the module how it uses the mesh beyond dp/fsdp/tp.

    Model families implement ``mesh_hooks(mesh) -> dict`` with keys:

    * ``apply_kwargs`` — extra kwargs for ``module.apply`` that activate a
      parallel execution path with the SAME params (e.g. a ring-attention
      ``attention_fn`` for ``sp``, an expert-parallel ``moe_fn`` for
      ``ep``, a ``pipeline_mesh`` for ``pp``),
    * ``param_rules`` — ``callable(path, leaf) -> PartitionSpec | None``
      placing structurally special params
      (:func:`mmlspark_tpu.parallel.mesh.param_shardings`),
    * ``handled`` — the set of extra mesh axes those kwargs actually use.

    This is how ``Trainer(module, mesh_spec={'ep': 2})`` *just works* —
    the one-flag UX of the reference's ``parallelTrain=true``
    (reference: cntk-train/src/main/scala/CommandBuilders.scala:79-93),
    generalized to six mesh axes.
    """
    hooks = {"apply_kwargs": {}, "param_rules": None, "handled": set()}
    if hasattr(module, "mesh_hooks"):
        got = module.mesh_hooks(mesh) or {}
        hooks["apply_kwargs"] = dict(got.get("apply_kwargs", {}))
        hooks["param_rules"] = got.get("param_rules")
        hooks["handled"] = set(got.get("handled", ()))
    return hooks


_EXTRA_AXES = ("sp", "pp", "ep")  # beyond the always-used dp/fsdp/tp


def check_mesh_axes_used(module: Any, mesh: Any, handled: set) -> None:
    """Refuse meshes with axes the training step would silently waste
    (round-4 verdict: an unhandled ``pp=2`` replicated all work over half
    the devices with no warning)."""
    unused = [a for a in _EXTRA_AXES if mesh.shape.get(a, 1) > 1
              and a not in handled]
    if unused:
        raise ValueError(
            f"mesh axes {unused} have extent > 1 but "
            f"{type(module).__name__} does not use them — training would "
            "silently replicate all work over those devices. Use a module "
            "that implements mesh_hooks for these axes (TransformerTagger:"
            " sp via ring attention, ep via moe_experts>0; ViT: pp via "
            "pipelined encoder blocks), or drop the axes from mesh_spec.")


def make_train_step(module: Any, cfg: TrainConfig, mesh: Any):
    """Build (init_state, step, step_masked) for a flax module on a mesh.

    ``step(state, x, y) -> (state, metrics)`` is one jit-compiled program:
    forward (bf16 on MXU), backward, global-mean gradients (XLA psum over
    ``dp``/``fsdp`` ICI rings), optimizer update. ``step_masked`` takes an
    extra per-example weight vector ``w`` (0/1) and computes the weighted
    mean — how the zero-padded tail batch trains without bias.

    Extra mesh axes (``sp``/``pp``/``ep``) activate through the module's
    ``mesh_hooks`` (see :func:`resolve_mesh_hooks`); a mesh axis nothing
    uses raises instead of silently replicating work.
    """
    import jax
    import jax.numpy as jnp
    import optax

    tx = make_optimizer(cfg)
    loss_fn = make_loss(cfg.loss)
    pp = preprocess_lib.resolve(cfg.preprocess)
    hooks = resolve_mesh_hooks(module, mesh)
    check_mesh_axes_used(module, mesh, hooks["handled"])
    apply_kwargs = hooks["apply_kwargs"]
    # single-device path: plain placement + plain jit instead of a
    # one-shard NamedSharding — the same fork models/jax_model.py and
    # core/plan.py take for inference (whether it pays on the chip is
    # unmeasured: ROADMAP Design 3)
    dev0 = single_device(mesh)
    single = dev0 is not None
    repl = dev0 if single else mesh_lib.replicated(mesh)

    def init_state(input_spec: tuple) -> dict:
        from jax.sharding import NamedSharding

        rng = jax.random.PRNGKey(cfg.seed)
        shape = tuple(input_spec)
        if pp is not None and len(shape) == 3:
            # the module sees POST-preprocess geometry: a thin-wire
            # 40x40 source trains a 32x32 model when the spec resizes
            shape = pp.out_shape(shape)
        dummy = jnp.zeros((1,) + shape, jnp.float32)
        params = module.init(rng, dummy)["params"]
        if cfg.param_dtype:
            dt = jnp.dtype(cfg.param_dtype)
            params = jax.tree_util.tree_map(
                lambda a: a.astype(dt) if jnp.issubdtype(
                    a.dtype, jnp.floating) else a, params)
        # fsdp > 1 → zero-style parameter sharding; optimizer moments
        # inherit the leaf shardings through eager zeros_like propagation.
        # module param_rules place structurally special leaves first
        # (e.g. MoE expert stacks over ep)
        params = jax.device_put(
            params, dev0 if single
            else mesh_lib.param_shardings(mesh, params,
                                          rules=hooks["param_rules"]))
        opt_state = tx.init(params)

        # scalar leaves optax creates itself (e.g. adam's step count) land
        # uncommitted on the default device; commit them replicated so the
        # WHOLE state tree has explicit mesh shardings — required for a
        # checkpoint restore to rebuild arrays every process can address
        # (a single-local-device scalar restores fine on one process but
        # is not a global array, and the multi-host step rejects it)
        def commit_leaf(leaf):
            if single:
                return jax.device_put(leaf, dev0)
            sh = getattr(leaf, "sharding", None)
            if isinstance(sh, NamedSharding):
                return leaf  # inherited a mesh sharding already
            return jax.device_put(leaf, repl)

        opt_state = jax.tree_util.tree_map(commit_leaf, opt_state)
        return {"params": params, "opt_state": opt_state,
                "step": jax.device_put(jnp.zeros((), jnp.int32), repl)}

    def _update(state, loss, grads):
        updates, opt_state = tx.update(
            grads, state["opt_state"], state["params"])
        params = optax.apply_updates(state["params"], updates)
        new_state = {"params": params, "opt_state": opt_state,
                     "step": state["step"] + 1}
        return new_state, {"loss": loss}

    def _forward(params, x):
        """Apply with sown-intermediate capture: modules that sow auxiliary
        losses (e.g. the MoE load-balance term, models/sequence.py) train
        them through the standard Trainer instead of silently dropping
        them (flax discards sow() into an immutable collection)."""
        out, mut = module.apply({"params": params}, x, train=True,
                                mutable=["intermediates"], **apply_kwargs)
        from collections.abc import Mapping

        aux = jnp.zeros((), jnp.float32)
        inter = mut.get("intermediates", {})

        def walk(node):
            nonlocal aux
            if isinstance(node, Mapping):  # dict or flax FrozenDict
                for k, v in node.items():
                    if k == "moe_aux":
                        for leaf in jax.tree_util.tree_leaves(v):
                            aux = aux + jnp.mean(leaf)
                    else:
                        walk(v)

        walk(inter)
        return out, aux

    def _token_mask(x):
        """[B, L] 0/1 pad mask derived the same way the module derives its
        attention mask (pad_token_id) — per-token tasks then reduce over L
        with a masked mean instead of diluting real-token loss with
        padding (advisor round 4)."""
        pad_id = getattr(module, "pad_token_id", None)
        if (pad_id is not None and getattr(x, "ndim", 0) == 2
                and jnp.issubdtype(x.dtype, jnp.integer)):
            return (x.astype(jnp.int32) != pad_id).astype(jnp.float32)
        return None

    def _prep_x(x, step):
        # uint8 ships thin (¼ the H2D bytes) and casts/normalizes on
        # device — the round-2 inference convention, applied to training.
        # Token matrices are int32/int64 and pass through untouched.
        # With a DevicePreprocess spec, NHWC image batches additionally
        # replay geometry + stochastic augmentation in-step, keyed off
        # the (checkpointed) global step so every replay is bit-exact
        if pp is not None and getattr(x, "ndim", 0) == 4:
            key = jax.random.fold_in(jax.random.PRNGKey(cfg.seed), step)
            return preprocess_lib.apply(pp, key, x, cfg.input_scale)
        if x.dtype == jnp.uint8:
            return x.astype(jnp.float32) * cfg.input_scale
        return x

    def _step(state, x, y):
        def compute_loss(params):
            logits, aux = _forward(params, _prep_x(x, state["step"]))
            per = loss_fn(logits, y, token_mask=_token_mask(x))
            return per.mean() + cfg.moe_aux_weight * aux

        loss, grads = jax.value_and_grad(compute_loss)(state["params"])
        return _update(state, loss, grads)

    def _step_masked(state, x, y, w):
        # weighted global mean: zero-weight (padded) rows contribute nothing
        # to loss or gradients, so the tail batch trains exactly. The
        # clamped denominator makes an all-zero-weight batch (multi-host
        # filler between liveness syncs) an exact no-op instead of 0/0 NaN
        def compute_loss(params):
            logits, aux = _forward(params, _prep_x(x, state["step"]))
            per = loss_fn(logits, y, token_mask=_token_mask(x))
            # gate the aux term on the row weights too: an all-filler batch
            # must be an EXACT no-op, but routing statistics are computed
            # over the whole batch and would otherwise leak gate gradients
            # (advisor round 4)
            aux = aux * jnp.minimum(w.sum(), 1.0)
            return ((per * w).sum() / jnp.maximum(w.sum(), 1e-6)
                    + cfg.moe_aux_weight * aux)

        loss, grads = jax.value_and_grad(compute_loss)(state["params"])
        return _update(state, loss, grads)

    # state shardings are inferred from the committed arrays built by
    # init_state (replicated or fsdp-sharded per param_shardings); batch
    # shardings stay EXPLICIT so direct callers passing host numpy batches
    # still get dp-sharded data rather than silent replication. On a
    # 1-device mesh plain jit skips the sharding machinery entirely
    donate = (0,) if cfg.donate_state else ()
    if single:
        step = jax.jit(_step, donate_argnums=donate)
        step_masked = jax.jit(_step_masked, donate_argnums=donate)
    else:
        data = mesh_lib.batch_sharding(mesh)
        step = jax.jit(_step, in_shardings=(None, data, data),
                       donate_argnums=donate)
        step_masked = jax.jit(_step_masked,
                              in_shardings=(None, data, data, data),
                              donate_argnums=donate)
    return init_state, step, step_masked


def _batches(x: np.ndarray, y: np.ndarray, batch_size: int,
             seed: int, valid: np.ndarray | None = None) -> Iterator[tuple]:
    """Shuffled fixed-shape batches ``(bx, by, bw)``. The tail batch is
    zero-padded to ``batch_size`` with a 0/1 weight vector so no row is ever
    dropped (round-1/2 fix: ``drop_remainder`` silently lost up to
    ``batch_size-1`` rows per epoch) while XLA still sees one shape.

    ``valid`` (0/1 per row) marks rows that are themselves padding (the
    unequal-multi-host-shard case): they shuffle through the walk like any
    row but carry weight 0, so the batch count stays process-uniform while
    the padded rows train as exact no-ops."""
    n = len(x)
    order = np.random.default_rng(seed).permutation(n)
    weights = (np.ones(n, np.float32) if valid is None
               else np.asarray(valid, np.float32))
    for s in range(0, n, batch_size):
        idx = order[s:s + batch_size]
        if len(idx) == batch_size:
            yield x[idx], y[idx], weights[idx]
        else:
            pad = batch_size - len(idx)
            bx = np.concatenate([x[idx], np.zeros((pad,) + x.shape[1:],
                                                  x.dtype)])
            by = np.concatenate([y[idx], np.zeros((pad,) + y.shape[1:],
                                                  y.dtype)])
            bw = np.concatenate([weights[idx], np.zeros(pad, np.float32)])
            yield bx, by, bw


_SIG_BYTES = 256


def _sync_batch_signature(batch: Any) -> tuple | None:
    """All-gather this process's (x, y) tail shapes/dtypes; return the
    first non-empty peer signature. Keeps multi-host filler batches (and
    hence the compiled step program) identical on every process even when
    one process's stream is empty."""
    import json

    from jax.experimental import multihost_utils

    if batch is None:
        mine = np.zeros(_SIG_BYTES, np.uint8)
    else:
        bx, by, _ = batch
        enc = json.dumps({
            "xs": [int(d) for d in bx.shape[1:]], "xd": bx.dtype.str,
            "ys": [int(d) for d in by.shape[1:]], "yd": by.dtype.str,
        }).encode()
        if len(enc) > _SIG_BYTES:
            raise ValueError(f"batch signature too large: {enc!r}")
        mine = np.frombuffer(enc.ljust(_SIG_BYTES, b"\0"), np.uint8).copy()
    sigs = np.asarray(multihost_utils.process_allgather(mine))
    for row in sigs.reshape(-1, _SIG_BYTES):
        raw = bytes(row).rstrip(b"\0")
        if raw:
            d = json.loads(raw)
            return ((tuple(d["xs"]), np.dtype(d["xd"])),
                    (tuple(d["ys"]), np.dtype(d["yd"])))
    return None


def _rebatch(chunks: Any, batch_size: int) -> Iterator[tuple]:
    """Re-accumulate arbitrary-size (x, y) chunks into fixed-size batches
    ``(bx, by, bw)``; the final partial batch is zero-padded with a 0/1
    weight vector. Memory is bounded by one batch + one chunk."""
    buf_x: list[np.ndarray] = []
    buf_y: list[np.ndarray] = []
    have = 0
    for cx, cy in chunks:
        if len(cx) != len(cy):
            raise ValueError(f"chunk length mismatch: {len(cx)} vs {len(cy)}")
        buf_x.append(np.asarray(cx))
        buf_y.append(np.asarray(cy))
        have += len(cx)
        while have >= batch_size:
            x = np.concatenate(buf_x) if len(buf_x) > 1 else buf_x[0]
            y = np.concatenate(buf_y) if len(buf_y) > 1 else buf_y[0]
            yield (x[:batch_size], y[:batch_size],
                   np.ones(batch_size, np.float32))
            buf_x, buf_y = [x[batch_size:]], [y[batch_size:]]
            have -= batch_size
    if have:
        x = np.concatenate(buf_x) if len(buf_x) > 1 else buf_x[0]
        y = np.concatenate(buf_y) if len(buf_y) > 1 else buf_y[0]
        pad = batch_size - have
        bx = np.concatenate([x, np.zeros((pad,) + x.shape[1:], x.dtype)])
        by = np.concatenate([y, np.zeros((pad,) + y.shape[1:], y.dtype)])
        bw = np.concatenate([np.ones(have, np.float32),
                             np.zeros(pad, np.float32)])
        yield bx, by, bw


class Trainer:
    """Minimal array-in training driver used by the learners and bench.

    Handles mesh creation, state init, epoch loops, and loss tracking. The
    estimator-level one-call API (featurize → train → scored model) builds
    on this in the train package's classifier/regressor stages.
    """

    def __init__(self, module: Any, cfg: TrainConfig | None = None,
                 mesh: Any = None):
        self.module = module
        self.cfg = cfg or TrainConfig()
        self.mesh = mesh if mesh is not None else mesh_lib.make_mesh(
            self.cfg.mesh_spec)
        self.init_state, self.step, self.step_masked = make_train_step(
            module, self.cfg, self.mesh)
        self.state = None
        self.history: list[float] = []
        # per-step input-wait vs. step-time accounting for the last fit
        # (train/input.input_stats): input_bound_fraction, wait/step split,
        # committed_ahead_max — the honest answer to "was that run input-
        # bound or compute-bound?"
        self.input_stats: dict | None = None
        self._fingerprint: dict | None = None

    def data_target(self):
        """Where host batches commit: the bare device on a 1-device mesh
        (plain transfers — see make_train_step's fast path), else the
        dp-sharded NamedSharding. Shares the `single_device` predicate
        with make_train_step so the two can never disagree."""
        dev0 = single_device(self.mesh)
        return dev0 if dev0 is not None else mesh_lib.batch_sharding(
            self.mesh)

    def _checkpointer(self):
        if not self.cfg.checkpoint_dir:
            return None
        if getattr(self, "_ckpt", None) is None:
            from mmlspark_tpu.train.checkpoint import TrainCheckpointer
            self._ckpt = TrainCheckpointer(self.cfg.checkpoint_dir,
                                           self.cfg.max_to_keep)
        return self._ckpt

    def maybe_restore(self) -> int | None:
        """Resume from the latest checkpoint if configured; returns the
        restored global step or None."""
        ckpt = self._checkpointer()
        if ckpt is None or not self.cfg.resume:
            return None
        latest = ckpt.latest_step()
        if latest is None:
            return None
        # resume replays the first `resumed` batches as no-ops, which is only
        # correct if the schedule (dataset length, batch size, seed, epochs)
        # is identical to the run that wrote the checkpoint — validate it
        saved = ckpt.fingerprint()
        if (saved is not None and self._fingerprint is not None
                and saved != self._fingerprint):
            raise ValueError(
                "checkpoint schedule fingerprint mismatch: saved "
                f"{saved} vs current {self._fingerprint}; resuming would "
                "silently skip the wrong batches. Start a fresh "
                "checkpoint_dir (or set resume=False) to train with a "
                "changed dataset/batch_size/seed/epochs")
        # restores directly to each target leaf's sharding — the target
        # was built by init_state on THIS trainer's mesh, so a checkpoint
        # written on a different topology reshards on read (elastic
        # recovery). step=None takes the integrity-validated path: a torn
        # latest step falls back to the previous manifest step instead of
        # crashing the recovery (train/checkpoint_corrupt event)
        self.state = ckpt.restore(target=self.state)
        restored = int(np.asarray(self.state["step"]))
        _log.info(f"resumed from checkpoint step {restored} "
                  f"({self.cfg.checkpoint_dir})")
        return restored

    def save_checkpoint(self) -> int | None:
        ckpt = self._checkpointer()
        if ckpt is None:
            return None
        return ckpt.save(self.state, fingerprint=self._fingerprint)

    def rescale(self, mesh: Any = None, mesh_spec: Any = None) -> "Trainer":
        """Re-form the training step on a new mesh and reshard live state
        onto it — the in-process elastic path (surviving devices
        re-forming after a topology change; the cross-process path
        restores a checkpoint on the new topology instead).

        The step/step_masked programs are rebuilt for the new mesh and
        every state leaf is bit-preserved through
        :func:`mmlspark_tpu.train.checkpoint.reshard_state`, so the next
        ``fit_*`` call continues the schedule exactly where the old
        topology left it. The schedule fingerprint is unchanged — which
        also means the new data-parallel extent must keep the effective
        batch size identical (it must still divide the configured batch),
        or the resume-replay validation refuses loudly.
        """
        old_mesh = self.mesh
        new_mesh = mesh if mesh is not None else mesh_lib.make_mesh(
            mesh_spec if mesh_spec is not None else self.cfg.mesh_spec)
        self.init_state, self.step, self.step_masked = make_train_step(
            self.module, self.cfg, new_mesh)
        self.mesh = new_mesh
        if self.state is not None:
            from mmlspark_tpu.train.checkpoint import reshard_state
            hooks = resolve_mesh_hooks(self.module, new_mesh)
            self.state = reshard_state(self.state, old_mesh, new_mesh,
                                       rules=hooks["param_rules"])
        if _obs_rt._enabled:
            _obs_registry().counter("train.rescales").add()
        _log.info("rescaled trainer mesh %s -> %s",
                  dict(zip(old_mesh.axis_names, old_mesh.devices.shape)),
                  dict(zip(new_mesh.axis_names, new_mesh.devices.shape)))
        return self

    def _note_loss(self, value: float) -> None:
        """Record one logged loss: appended to ``self.history`` AND
        published to the windowed ``train.loss`` histogram
        (tracer-gated) — the eval series the service beacon exports to
        the supervisor, where the lifecycle ``EvalGate`` judges it
        (docs/lifecycle.md)."""
        self.history.append(value)
        if _obs_rt._enabled:
            _obs_registry().histogram("train.loss").observe(float(value))

    def _fetch_loss(self, sentinel: NonFiniteSentinel,
                    pending: tuple) -> None:
        """Resolve one lagged ``(step, device loss scalar)`` log point.
        The ``float()`` is the fit loops' only explicit host sync, hence
        a boundary span. It rarely waits: the scalar is a log interval
        old, and the runtime already holds the host back inside the
        step's dispatch once its limit of computations is in flight
        (about 34 steps on a v5e, PERF.md). Only the fetch that closes a
        fit waits long: the device is that far behind the last step."""
        step, loss = pending
        with _obs_boundary("train/loss_fetch", "train"):
            value = float(loss)
        self._note_loss(sentinel.check(step, value))

    def fit_arrays(self, x: np.ndarray, y: np.ndarray) -> "Trainer":
        """Train on host arrays.

        Multi-host: each process passes only its own equal-length shard of
        the dataset (the per-host sharded input pipeline, SURVEY §5 — no
        shuffle engine; file-shard → host → HBM). Global batches are
        assembled from every process's local slice via
        ``jax.make_array_from_process_local_data``; ``cfg.batch_size`` is
        the GLOBAL batch size.
        """
        import jax

        cfg = self.cfg
        nproc = jax.process_count()
        valid: np.ndarray | None = None
        if nproc > 1:
            # every process must walk the same number of steps or the
            # gradient all-reduce deadlocks. Unequal shards pad up to the
            # longest with zero-weight rows (exact: padded rows shuffle
            # through the walk contributing nothing); strict_shards=True
            # restores the loud error for jobs where unequal shards can
            # only mean an upstream partitioning bug
            from jax.experimental import multihost_utils
            lens = np.asarray(multihost_utils.process_allgather(
                np.asarray(len(x), np.int64)))
            if len(set(lens.tolist())) != 1:
                if cfg.strict_shards:
                    raise ValueError(
                        "fit_arrays multi-host requires equal per-process "
                        f"shard lengths, got {lens.tolist()} (strict_shards"
                        "=True) — pad or trim the shards, or use fit_stream "
                        "(which reconciles unequal streams with filler "
                        "batches)")
                longest = int(lens.max())
                _log.warning(
                    "fit_arrays: unequal per-process shards %s — padding "
                    "to %d rows with zero-weight filler",
                    lens.tolist(), longest)
                pad = longest - len(x)
                valid = np.concatenate([np.ones(len(x), np.float32),
                                        np.zeros(pad, np.float32)])
                if pad:
                    x = np.concatenate(
                        [x, np.zeros((pad,) + x.shape[1:], x.dtype)])
                    y = np.concatenate(
                        [y, np.zeros((pad,) + y.shape[1:], y.dtype)])
        # the batch must divide over the data axes AND split evenly across
        # processes (each contributes bs/nproc rows), so round down to a
        # multiple of lcm(dp, nproc)
        dp = self.mesh.shape["dp"] * self.mesh.shape["fsdp"]
        import math as _math
        q = _math.lcm(dp, nproc)
        n_global = len(x) * nproc
        bs = (min(cfg.batch_size, n_global) // q) * q
        if bs == 0:
            raise ValueError(
                f"dataset of {n_global} rows (or batch_size "
                f"{cfg.batch_size}) is smaller than "
                f"lcm(data-parallel extent {dp}, processes {nproc}) = {q}")
        # each process walks its local shard with the same seed; the global
        # batch is the process-order concatenation of the local slices
        bs_local = bs // nproc
        # fingerprint the EFFECTIVE batch size: resuming on a mesh with a
        # different dp extent changes the rounded bs (and hence the batch
        # walk) even when cfg.batch_size is unchanged. sched=2 marks the
        # padded-tail batch walk (one more step per epoch than sched-1 runs)
        # param_dtype is part of the fingerprint: restoring an f32
        # checkpoint into bf16 targets (or vice versa) would silently
        # change precision mid-run instead of erroring loudly
        self._fingerprint = {"n_rows": int(n_global),
                             "batch_size": int(bs),
                             "seed": int(cfg.seed),
                             "epochs": int(cfg.epochs),
                             "param_dtype": cfg.param_dtype or "float32",
                             "sched": 2}
        if cfg.preprocess is not None:
            # resuming under a CHANGED preprocess spec would silently
            # replay different pixels into the remaining steps
            self._fingerprint["preprocess"] = preprocess_lib.resolve(
                cfg.preprocess).fingerprint()
        resumed = 0
        if self.state is None:
            self.state = self.init_state(x.shape[1:])
            resumed = self.maybe_restore() or 0
        data = self.data_target()
        ckpt = self._checkpointer()
        # resume completes the REMAINDER of the configured schedule: the
        # first `resumed` (already-trained) steps of the epoch/batch walk are
        # replayed as no-ops so batch order stays deterministic. The resumed
        # prefix is skipped in the PRODUCER, before assembly/commit — a
        # replayed batch never crosses the link
        from mmlspark_tpu.train.input import DeviceLoader, input_stats

        if nproc > 1:
            def commit(arr):
                # local slice → its block of the globally-sharded array
                # (multi-host assembly has no single-transfer seam to
                # route through — bytes are accounted by the loader)
                return jax.make_array_from_process_local_data(data, arr)
        else:
            def commit(arr):
                # through the planner's upload seam: train-path H2D
                # transfers share the crossing/byte counters (and
                # count_crossings patches) with the pipeline executor
                return plan_lib.train_commit(arr, data)

        total_steps = cfg.epochs * (-(-len(x) // bs_local))

        def host_batches():
            gs = 0
            for epoch in range(cfg.epochs):
                for i, batch in enumerate(
                        _batches(x, y, bs_local, cfg.seed + epoch, valid)):
                    gs += 1
                    if gs <= resumed:
                        continue
                    yield gs, i, batch

        def commit_batch(item):
            gs, i, (bx, by, bw) = item
            return gs, i, (commit(bx), commit(by), commit(bw))

        # one-step-lagged loss fetch: resolving the PREVIOUS log point's
        # device scalar never stalls the in-flight prefetch window (the
        # inline float() was a host sync mid-pipeline every log_every
        # steps). The non-finite sentinel rides these exact fetches
        pending = None  # (global step, device loss scalar)
        sentinel = NonFiniteSentinel("fit_arrays", cfg.nonfinite_loss)
        loader = DeviceLoader(host_batches(), commit_batch,
                              depth=cfg.prefetch_depth, name="fit_arrays")
        slow_steps = _slow_step_detector("fit_arrays")
        hb = "train/fit_arrays"  # flight-recorder heartbeat: a step loop
        #                          that stops stepping is a hang
        if _obs_flight._rec is not None:
            _obs_flight._rec.arm(hb)
        t_loop = time.perf_counter()
        try:
            with timed(f"Trainer[{type(self.module).__name__}]", _log,
                       len(x)):
                for gs, i, (dx, dy, dw) in loader:
                    # the span times step DISPATCH (async issue), not
                    # device compute; on a device-bound job the dispatch
                    # itself blocks once the runtime's limit of
                    # computations is in flight, so back-pressure shows
                    # here (input starvation in the loader's wait span)
                    t_step = time.perf_counter() if _obs_rt._enabled \
                        else None
                    with _obs_boundary("train/step", "train"):
                        self.state, metrics = self.step_masked(
                            self.state, dx, dy, dw)
                    if _obs_flight._rec is not None:
                        _obs_flight._rec.beat(hb)
                    if _obs_rt._enabled:
                        _obs_registry().counter("train.steps").add()
                        if t_step is not None:
                            slow_steps().observe(
                                (time.perf_counter() - t_step) * 1e3)
                    if i % cfg.log_every == 0:
                        if pending is not None:
                            self._fetch_loss(sentinel, pending)
                        pending = (gs, metrics["loss"])
                    if (ckpt is not None and cfg.checkpoint_every > 0
                            and gs % cfg.checkpoint_every == 0):
                        self.save_checkpoint()
            if pending is not None:
                self._fetch_loss(sentinel, pending)
                pending = None
        except BaseException as e:
            # the post-mortem happens AT the failure point, before any
            # caller can swallow the exception (obs/flight.py)
            _obs_flight.on_crash(e, context="Trainer.fit_arrays")
            raise
        finally:
            loader.close()
            if _obs_flight._rec is not None:
                _obs_flight._rec.disarm(hb)
        self.input_stats = input_stats(loader, time.perf_counter() - t_loop)
        if ckpt is not None and total_steps > resumed:
            self.save_checkpoint()
        return self

    def fit_stream(self, source: Any, input_spec: tuple | None = None
                   ) -> "Trainer":
        """Train from a stream of ``(x_chunk, y_chunk)`` host arrays without
        ever materializing the dataset (bounded-memory ingest; reference
        streaming reader: readers/src/main/scala/ImageReader.scala:85-98).

        ``source`` is an iterable of chunks, or a zero-arg callable
        returning a fresh iterator (required when ``cfg.epochs > 1``).
        Chunks may be any size: rows are re-accumulated into fixed
        ``cfg.batch_size`` global batches (one XLA program), with the final
        partial batch padded + masked. Multi-host: each process streams its
        own shard, exactly as in :meth:`fit_arrays`.
        """
        import jax

        cfg = self.cfg
        nproc = jax.process_count()
        dp = self.mesh.shape["dp"] * self.mesh.shape["fsdp"]
        import math as _math
        q = _math.lcm(dp, nproc)
        bs = (cfg.batch_size // q) * q
        if bs == 0:
            raise ValueError(
                f"batch_size {cfg.batch_size} smaller than lcm("
                f"data-parallel extent {dp}, processes {nproc}) = {q}")
        bs_local = bs // nproc

        def epoch_iter():
            it = source() if callable(source) else source
            return _rebatch(it, bs_local)

        if cfg.epochs > 1 and not callable(source):
            raise ValueError(
                "epochs > 1 needs a callable source (a fresh iterator per "
                "epoch); a plain iterator is exhausted after one pass")

        data = self.data_target()
        if nproc > 1:
            def commit(arr):
                return jax.make_array_from_process_local_data(data, arr)
        else:
            def commit(arr):
                return plan_lib.train_commit(arr, data)  # counted seam

        # streams have no stable row count; fingerprint only the schedule
        # shape that must match for a resume to replay correctly
        self._fingerprint = {"stream": True, "batch_size": int(bs),
                             "seed": int(cfg.seed),
                             "epochs": int(cfg.epochs),
                             "param_dtype": cfg.param_dtype or "float32",
                             "sched": 2}
        if cfg.preprocess is not None:
            self._fingerprint["preprocess"] = preprocess_lib.resolve(
                cfg.preprocess).fingerprint()
        ckpt = self._checkpointer()
        # producer-side progress, read by the consumer once the loader is
        # drained (the worker has exited by then): walked steps include the
        # resumed prefix, rows count only real (non-filler) examples
        prog = {"steps": 0, "rows": 0, "resumed": 0}
        box: dict = {"loader": None}

        from mmlspark_tpu.train.input import DeviceLoader, input_stats

        def ensure_state(bx) -> None:
            # runs on the producer thread BEFORE the first batch is
            # yielded — the consumer is still blocked on the queue, so
            # state init / checkpoint restore never overlaps step dispatch
            if self.state is None:
                spec = tuple(input_spec or bx.shape[1:])
                self.state = self.init_state(spec)
                prog["resumed"] = self.maybe_restore() or 0

        def fence() -> None:
            # multi-host: every cross-process exchange must interleave
            # with step dispatch in the same order on every process —
            # drain the in-flight window before issuing the collective
            # (docs/training_input.md, "lockstep rules")
            if box["loader"] is not None:
                box["loader"].drain_barrier()

        def dummy_batch(shapes: tuple | None) -> tuple:
            # zero-weight filler keeping cross-process collectives aligned
            # when this process's shard ran dry before its peers'
            if shapes is not None:
                (xs, xd), (ys, yd) = shapes
            elif input_spec is not None:
                (xs, xd), (ys, yd) = ((tuple(input_spec), np.float32),
                                      ((), np.int64))
            else:
                raise ValueError(
                    "this process's stream yielded no data and no "
                    "input_spec was given; cannot synthesize filler "
                    "batches for the multi-host schedule")
            return (np.zeros((bs_local,) + xs, xd),
                    np.zeros((bs_local,) + ys, yd),
                    np.zeros(bs_local, np.float32))

        import itertools as _itertools
        sync_n = max(int(cfg.liveness_sync_every), 1)

        def host_batches():
            # chunk pull (→ image decode in streaming sources) + rebatch +
            # filler/liveness reconciliation, all on the producer thread —
            # with prefetch_depth > 0 the whole input side overlaps step
            # compute. Filler batches and the signature sync flow through
            # unchanged, so the multi-host step walk is identical to the
            # synchronous path
            shapes: tuple | None = None  # (x tail shape/dtype, y tail/dt)
            sig_synced = False
            gs = 0
            for epoch in range(cfg.epochs):
                it = iter(epoch_iter())
                if nproc > 1 and not sig_synced:
                    # exchange batch signatures once (symmetric across
                    # processes): a process whose shard is empty adopts its
                    # peers' shapes/dtypes for filler batches, so every
                    # process compiles the identical step program
                    fence()
                    first = next(it, None)
                    shapes = _sync_batch_signature(first) or shapes
                    sig_synced = True
                    if first is not None:
                        it = _itertools.chain([first], it)
                while True:
                    if nproc > 1:
                        # streams rarely shard into equal batch counts per
                        # process, and a process that runs dry would leave
                        # its peers deadlocked inside the step's
                        # collectives. Buffer up to sync_n local batches,
                        # exchange counts ONCE per block (the host-side
                        # barrier amortizes over the whole block instead
                        # of serializing every step — advisor round 3),
                        # and let short processes pad with zero-weight
                        # filler up to the block's max count. Step counts
                        # are exact: the longest stream sets the walk.
                        # The liveness payload carries (count, mean step
                        # ms): the straggler exchange RIDES the same
                        # fenced collective — no new exchange site, and
                        # the schedule is identical on every process
                        # whether or not its tracer is enabled
                        block = list(_itertools.islice(it, sync_n))
                        # the fence + allgather is the one seam every
                        # process crosses at the same real instant — the
                        # span is the fleet plane's skew-correction and
                        # flow-stitch anchor (obs/fleet.FENCE_SPAN_NAMES)
                        with _obs_span("train/liveness_sync", "train"):
                            fence()
                            from jax.experimental import multihost_utils
                            payload = np.asarray(
                                [float(len(block)),
                                 straggler.local_mean_ms()], np.float64)
                            gathered = np.asarray(
                                multihost_utils.process_allgather(
                                    payload)).reshape(-1, 2)
                        block_steps = int(gathered[:, 0].max())
                        if _obs_rt._enabled:
                            straggler.ingest(gathered[:, 1],
                                             jax.process_index())
                        if block_steps == 0:
                            break
                        block += [None] * (block_steps - len(block))
                    else:
                        nxt = next(it, None)
                        if nxt is None:
                            break
                        block = [nxt]
                    for batch in block:
                        if batch is None:
                            batch = dummy_batch(shapes)
                        bx, by, bw = batch
                        shapes = ((bx.shape[1:], bx.dtype),
                                  (by.shape[1:], by.dtype))
                        ensure_state(bx)
                        gs += 1
                        prog["steps"] = gs
                        if gs <= prog["resumed"]:
                            continue
                        prog["rows"] += int(bw.sum())
                        yield gs, batch

        def commit_batch(item):
            gs, (bx, by, bw) = item
            return gs, (commit(bx), commit(by), commit(bw))

        pending = None  # (step, loss) one-step-lagged fetch (fit_arrays)
        sentinel = NonFiniteSentinel("fit_stream", cfg.nonfinite_loss)
        # created BEFORE the loader: its worker starts pulling
        # host_batches immediately, and that closure reads `straggler`
        straggler = StragglerDetector("fit_stream")
        loader = DeviceLoader(host_batches(), commit_batch,
                              depth=cfg.prefetch_depth, name="fit_stream")
        box["loader"] = loader
        slow_steps = _slow_step_detector("fit_stream")
        hb = "train/fit_stream"
        if _obs_flight._rec is not None:
            _obs_flight._rec.arm(hb)
        t_loop = time.perf_counter()
        try:
            with timed(f"Trainer[{type(self.module).__name__}:stream]",
                       _log):
                for gs, (dx, dy, dw) in loader:
                    t_step = time.perf_counter() if _obs_rt._enabled \
                        else None
                    with _obs_boundary("train/step", "train"):
                        self.state, metrics = self.step_masked(
                            self.state, dx, dy, dw)
                    if _obs_flight._rec is not None:
                        _obs_flight._rec.beat(hb)
                    if _obs_rt._enabled:
                        _obs_registry().counter("train.steps").add()
                        if t_step is not None:
                            dur_ms = (time.perf_counter() - t_step) * 1e3
                            slow_steps().observe(dur_ms)
                            straggler.observe(dur_ms)
                    if (gs - 1) % cfg.log_every == 0:
                        if pending is not None:
                            self._fetch_loss(sentinel, pending)
                        pending = (gs, metrics["loss"])
                    if (ckpt is not None and cfg.checkpoint_every > 0
                            and gs % cfg.checkpoint_every == 0):
                        self.save_checkpoint()
                    # AFTER the checkpoint: save_checkpoint's
                    # sync_global_devices is itself a cross-process
                    # collective, so the producer's drain_barrier must
                    # hold until it completes — releasing it at step
                    # dispatch would let the liveness allgather race the
                    # checkpoint barrier across processes
                    loader.note_dispatched()
            if pending is not None:
                self._fetch_loss(sentinel, pending)
                pending = None
        except BaseException as e:
            _obs_flight.on_crash(e, context="Trainer.fit_stream")
            raise
        finally:
            loader.close()
            if _obs_flight._rec is not None:
                _obs_flight._rec.disarm(hb)
        self.input_stats = input_stats(loader, time.perf_counter() - t_loop)
        if prog["steps"] == 0:
            raise ValueError(
                "fit_stream: the stream yielded no data (empty source or "
                "mistyped path?)")
        _log.info("fit_stream: %d rows in %d steps", prog["rows"],
                  prog["steps"])
        if ckpt is not None and prog["steps"] > prog["resumed"]:
            self.save_checkpoint()
        return self

    @property
    def params(self):
        return self.state["params"]
