"""Traffic kind ``train_stream``: a fine-tuning job of optimizer steps.

``Trainer.fit_stream`` is fed by a generator that cycles a seeded pool of
distinct uint8 batches and stops yielding when the window's seconds are up;
the window ends in ``block_until_ready`` on the last step's state. The
end-to-end metric is ``train_step_ms``: the whole window's wall time over
all the optimizer steps completed in it.

Set-up builds ONE ``Trainer``, loads the seed's weights into it (made on
the device in one jitted call by the reference's generator: the stand-in
for a pretrained checkpoint), and drives it through its first steps, one
``fit_stream`` call a step, on the pool's first batches. The same object
then runs the window. After the window the plain reference follows those
first steps from the same weights and batches, and ``check`` compares each
step's loss, the first gradient's norm (read from the momentum after one
step) and the parameters' change after the last, leaf by leaf.

Workload file keys read here: ``batch_size``, ``pool_batches``,
``first_steps``, ``reference_block_rows``, ``limits`` and, optionally,
``train_config`` (further ``TrainConfig`` fields).
"""

from __future__ import annotations

import statistics
import time

import numpy as np

# leaves whose reference gradient is under this share of the median leaf's
# are nought to rounding (a key's bias under softmax): their change under
# momentum is round-off alone and is left out of the change comparison
NOUGHT_GRADIENT = 1e-3


def build_module(cfg: dict):
    """The program's module for the configuration's family and sizes."""
    import jax.numpy as jnp

    if cfg["family"] != "vit":
        raise ValueError(f"train_stream has no module for family "
                         f"{cfg['family']!r}")
    from mmlspark_tpu.models.vit import ViT

    return ViT(num_classes=cfg["num_classes"], patch=cfg["patch_size"],
               dim=cfg["hidden_size"], depth=cfg["num_hidden_layers"],
               heads=cfg["num_attention_heads"],
               mlp_dim=cfg["intermediate_size"],
               dtype=jnp.dtype(cfg["compute_dtype"]))


def make_pool(ctx) -> list:
    """``pool_batches`` distinct ``(uint8 images, int labels)`` batches
    from the seed; every seed gives the same sizes."""
    cfg, wl = ctx.config, ctx.workload
    rng = np.random.default_rng(ctx.seed)
    shape = (wl["batch_size"], cfg["image_size"], cfg["image_size"],
             cfg["num_channels"])
    pool = []
    for _ in range(wl["pool_batches"]):
        x = rng.integers(0, 256, size=shape, dtype=np.uint8)
        y = rng.integers(0, cfg["num_classes"], size=shape[0])
        pool.append((x, y))
    return pool


def load_state(trainer, flat_params: dict, input_spec: tuple) -> dict:
    """A train state of the trainer's own structure (read off
    ``init_state`` without running it) holding ``flat_params``, every
    other leaf zero, placed where ``init_state`` would place it."""
    import jax
    import jax.numpy as jnp
    from flax.traverse_util import flatten_dict, unflatten_dict

    from mmlspark_tpu.parallel import mesh as mesh_lib
    from mmlspark_tpu.parallel.mesh import single_device

    struct = jax.eval_shape(lambda: trainer.init_state(input_spec))
    want = flatten_dict(struct["params"], sep="/")
    if ({k: v.shape for k, v in want.items()}
            != {k: v.shape for k, v in flat_params.items()}):
        odd = sorted(set(want) ^ set(flat_params))[:6]
        raise ValueError("the program's parameter tree is not the "
                         f"reference's; differing paths: {odd}")

    def build(flat):
        state = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), struct)
        state["params"] = unflatten_dict(
            {k: flat[k].astype(want[k].dtype) for k in want}, sep="/")
        return state

    dev0 = single_device(trainer.mesh)
    target = dev0 if dev0 is not None else mesh_lib.replicated(trainer.mesh)
    return jax.device_put(jax.jit(build)(flat_params), target)


def momentum_of(state: dict):
    """The momentum tree inside an optax ``sgd(momentum=...)`` state: the
    one part of ``opt_state`` shaped like the parameters."""
    import jax

    leaves = jax.tree_util.tree_leaves(state["opt_state"])
    params_def = jax.tree_util.tree_structure(state["params"])
    if len(leaves) != params_def.num_leaves:
        raise ValueError("opt_state is not one momentum per parameter; "
                         "this driver reads the first gradient from it")
    return jax.tree_util.tree_unflatten(params_def, leaves)


def flat_norms(tree, minus=None) -> dict:
    """``path -> norm`` of every leaf of ``tree`` (less ``minus``), taken
    on the device in float32."""
    import jax
    import jax.numpy as jnp
    from flax.traverse_util import flatten_dict

    def norms(t, m):
        def one(a, b=None):
            a = a.astype(jnp.float32)
            if b is not None:
                a = a - b.astype(jnp.float32)
            return jnp.sqrt(jnp.sum(jnp.square(a)))
        return (jax.tree_util.tree_map(one, t) if m is None
                else jax.tree_util.tree_map(one, t, m))

    out = jax.jit(norms)(tree, minus)
    return {k: float(v) for k, v in flatten_dict(out, sep="/").items()}


def setup(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.train.loop import TrainConfig, Trainer

    cfg, wl = ctx.config, ctx.workload
    t0 = time.perf_counter()
    param_dtype = cfg["param_dtype"]
    tcfg = TrainConfig(
        batch_size=wl["batch_size"], epochs=1, optimizer=cfg["optimizer"],
        learning_rate=cfg["learning_rate"], momentum=cfg["momentum"],
        param_dtype=None if param_dtype == "float32" else param_dtype,
        input_scale=cfg["input_scale"], **wl.get("train_config", {}))
    trainer = Trainer(build_module(cfg), tcfg)
    if trainer.mesh.devices.size != ctx.cell["chips"]:
        raise ValueError(f"the trainer's mesh has "
                         f"{trainer.mesh.devices.size} device(s), the cell "
                         f"states {ctx.cell['chips']}")
    ref = ctx.reference()
    flat = jax.jit(lambda k: ref.make_params(cfg, k))(ctx.key())
    spec = (cfg["image_size"], cfg["image_size"], cfg["num_channels"])
    trainer.state = load_state(trainer, flat, spec)
    del flat
    start = jax.tree_util.tree_map(jnp.copy, trainer.state["params"])
    pool = make_pool(ctx)
    t1 = time.perf_counter()

    # the first steps, through the window's own call and feed; the first
    # of them compiles the step (or loads it from the persistent cache)
    first = wl["first_steps"]
    grad_norms = None
    for x, y in pool[:first]:
        trainer.fit_stream(iter([(x, y)]))
        if grad_norms is None:
            grad_norms = flat_norms(momentum_of(trainer.state))
    change_norms = flat_norms(trainer.state["params"], start)
    del start
    losses = [float(v) for v in trainer.history[:first]]
    ctx.say(f"train_stream set-up: trainer, weights and pool "
            f"{t1 - t0:.2f} s, first {first} steps "
            f"{time.perf_counter() - t1:.2f} s, losses {losses}")
    return {"trainer": trainer, "pool": pool,
            "program": {"losses": losses, "grad_norms": grad_norms,
                        "change_norms": change_norms}}


def measure(ctx, state: dict) -> dict:
    import jax

    trainer, pool = state["trainer"], state["pool"]
    first = ctx.workload["first_steps"]
    t0 = time.perf_counter()

    def feed():
        i = first
        while time.perf_counter() - t0 < ctx.seconds:
            yield pool[i % len(pool)]
            i += 1

    trainer.fit_stream(feed())
    jax.block_until_ready(trainer.state)
    window_s = time.perf_counter() - t0
    stats = trainer.input_stats
    steps = int(stats["batches"])
    state["window_losses"] = [float(v) for v in trainer.history[first:]]
    return {"window_s": window_s, "attempted": steps, "failed": 0,
            "steps": steps, "rows": steps * ctx.workload["batch_size"],
            "input_stats": stats,
            "metrics": {"train_step_ms": 1e3 * window_s / max(steps, 1)}}


def release(state: dict) -> None:
    """Free the program's device state before the reference runs."""
    trainer = state.pop("trainer")
    trainer.state = None
    del trainer


def leaf_gaps(program: dict, reference: dict,
              leave_out: set = frozenset()) -> dict:
    """For every leaf the gap between the program's norm and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    floor = statistics.median(reference.values())
    return {k: abs(program[k] - reference[k]) / max(reference[k], floor)
            for k in reference if k not in leave_out}


def worst_leaf_gap(program: dict, reference: dict,
                   leave_out: set = frozenset()) -> float:
    """The widest of :func:`leaf_gaps`."""
    return max(leaf_gaps(program, reference, leave_out).values())


def compare(program: dict, reference: dict) -> dict:
    """The numbers ``check`` holds to limits, from both sides' readings."""
    out = {}
    for i, (lp, lr) in enumerate(zip(program["losses"],
                                     reference["losses"]), 1):
        out[f"loss_gap_step{i}"] = abs(lp - lr) / abs(lr)
    out["grad_norm_gap"] = worst_leaf_gap(program["grad_norms"],
                                          reference["grad_norms"])
    median = statistics.median(reference["grad_norms"].values())
    nought = {k for k, v in reference["grad_norms"].items()
              if v < NOUGHT_GRADIENT * median}
    out["change_norm_gap"] = worst_leaf_gap(program["change_norms"],
                                            reference["change_norms"],
                                            leave_out=nought)
    return out


# faults that ``calibrate.py`` plants in the reference put in the program's
# place: half of the batch left out, the mean taken over the rest
FAULTS = ("half_batch",)


def program_readings(state: dict) -> dict:
    """What the timed object produced in its first steps."""
    return state["program"]


def reference_readings(ctx, state: dict, quant: str | None = None,
                       fault: str | None = None) -> dict:
    """The plain reference over the same first steps; ``quant`` computes
    it in a lower precision (the control), ``fault`` plants a fault in it
    (both stand in the program's place, for ``calibrate.py`` and the
    tests)."""
    ref = ctx.reference()
    wl = ctx.workload
    return ref.first_steps(
        ctx.config, ctx.key(), state["pool"][:wl["first_steps"]],
        wl["reference_block_rows"], quant=quant,
        rows=slice(0, wl["batch_size"] // 2) if fault == "half_batch"
        else None)


def check(ctx, state: dict) -> dict:
    """``name -> (value, limit)`` once the window has closed."""
    t0 = time.perf_counter()
    reference = reference_readings(ctx, state)
    limits = ctx.workload["limits"]
    program = program_readings(state)
    numbers = compare(program, reference)
    finite = all(np.isfinite(state["window_losses"]))
    numbers["window_losses_not_finite"] = 0.0 if finite else 1.0
    for what in ("grad_norms", "change_norms"):
        gaps = leaf_gaps(program[what], reference[what])
        worst = max(gaps, key=gaps.get)
        ctx.say(f"widest {what} gap {gaps[worst]:.4f} at {worst}: program "
                f"{program[what][worst]:.4g}, reference "
                f"{reference[what][worst]:.4g}, median leaf "
                f"{statistics.median(reference[what].values()):.4g}")
    ctx.say(f"reference {time.perf_counter() - t0:.2f} s, losses "
            f"{reference['losses']}")
    return {k: (v, limits[k.split("_step")[0]]) for k, v in numbers.items()}
