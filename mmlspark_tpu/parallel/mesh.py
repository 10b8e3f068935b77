"""Device-mesh construction and axis conventions.

Canonical mesh axes, in order:

* ``dp``  — data parallelism (gradient all-reduce, batch sharding)
* ``fsdp``— parameter/optimizer sharding across the data axis (zero-style)
* ``tp``  — tensor parallelism (matmul column/row sharding)
* ``sp``  — sequence/context parallelism (ring attention)
* ``pp``  — pipeline stages
* ``ep``  — expert parallelism (MoE)

The reference's only strategy is single-node MPI data parallelism with GPU
count discovered via ``nvidia-smi`` (reference:
cntk-train/src/main/scala/CommandBuilders.scala:79-93,
core/env/src/main/scala/EnvironmentUtils.scala:20-50); here every strategy
is a mesh axis and XLA inserts the collectives. Multi-host: the same mesh
spans all processes' devices (``jax.devices()`` is global after
``jax.distributed.initialize``), with DCN-friendly axis ordering (dp
outermost so cross-slice traffic is gradient-only).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Mapping, Sequence

import numpy as np

# the canonical axis vocabulary. Collectives must name these axes
# literally where possible: the JX202 lint (tools/lint_jax.py keeps a
# jax-free mirror of this tuple) rejects any other literal, and the
# SPMD verifier (analysis/spmd.py) checks traced axis names against the
# concrete mesh
AXES = ("dp", "fsdp", "tp", "sp", "pp", "ep")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Logical parallelism layout; -1 on ``dp`` means "all remaining"."""

    dp: int = -1
    fsdp: int = 1
    tp: int = 1
    sp: int = 1
    pp: int = 1
    ep: int = 1

    def resolve(self, n_devices: int) -> dict[str, int]:
        sizes = dataclasses.asdict(self)
        fixed = math.prod(v for v in sizes.values() if v != -1)
        free = [k for k, v in sizes.items() if v == -1]
        if len(free) > 1:
            raise ValueError(f"at most one -1 axis allowed, got {free}")
        if free:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes "
                    f"product {fixed}")
            sizes[free[0]] = n_devices // fixed
        total = math.prod(sizes.values())
        if total != n_devices:
            raise ValueError(
                f"mesh {sizes} covers {total} devices, have {n_devices}")
        return sizes


def make_mesh(spec: MeshSpec | Mapping[str, int] | None = None,
              devices: Sequence[Any] | None = None):
    """Build a ``jax.sharding.Mesh`` over all (or given) devices."""
    import jax
    from jax.sharding import Mesh

    devices = list(devices if devices is not None else jax.devices())
    if spec is None:
        spec = MeshSpec()
    if isinstance(spec, Mapping):
        spec = MeshSpec(**dict(spec))
    explicit = dataclasses.asdict(spec)
    if -1 not in explicit.values() and jax.process_count() == 1:
        # a fully-explicit spec smaller than the host's device count means
        # "use this many devices" — take a prefix instead of raising.
        # Single-process only: in a multi-host run a prefix would be
        # host-0's devices, leaving other processes nothing addressable —
        # there the loud size-mismatch ValueError below is correct
        total = math.prod(explicit.values())
        if total < len(devices):
            import logging
            logging.getLogger(__name__).info(
                "make_mesh: explicit spec uses %d of %d local devices",
                total, len(devices))
            devices = devices[:total]
    sizes = spec.resolve(len(devices))
    shape = tuple(sizes[a] for a in AXES)
    arr = np.array(devices).reshape(shape)
    return Mesh(arr, AXES)


def default_mesh_spec(n_devices: int | None = None) -> MeshSpec:
    """Pure data parallelism over every device — the reference-parity
    strategy (MPI DP ring analog)."""
    return MeshSpec(dp=-1)


def single_device(mesh) -> Any | None:
    """The 1-device fast-path criterion: the bare device when the mesh has
    exactly one, else None. THE single source of truth — the train step's
    plain-jit path, the Trainer's commit target, and the elastic reshard
    targets (:func:`state_shardings`) must always agree, or batches
    committed with a NamedSharding would feed a plain-jit program (or
    vice versa)."""
    if int(mesh.devices.size) == 1:
        return mesh.devices.reshape(-1)[0]
    return None


def batch_sharding(mesh) -> Any:
    """Sharding for a [batch, ...] array: batch split over dp (and fsdp)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P(("dp", "fsdp")))


def replicated(mesh) -> Any:
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P())


def param_shardings(mesh, params, rules: Any = None) -> Any:
    """Pytree of shardings for the params.

    * ``rules`` (optional): ``callable(path: str, leaf) -> PartitionSpec |
      None`` consulted FIRST — how model families place structurally
      special params (e.g. a TransformerTagger's stacked MoE expert
      weights over ``ep``; see ``Module.mesh_hooks`` in
      :mod:`mmlspark_tpu.train.loop`). ``path`` is the ``/``-joined key
      path of the leaf. Returning None falls through to the generic
      rules below.
    * ``tp > 1``: every ≥2-D leaf's LAST (output-feature) dim shards over
      the tensor-parallel axis when divisible — column-parallel matmuls;
      GSPMD propagates the activation shardings and inserts the
      all-reduces/all-gathers (the annotate-and-let-XLA recipe; no manual
      collectives).
    * ``fsdp > 1``: the largest remaining divisible dim shards over fsdp
      (zero-style parameter sharding; XLA all-gathers for the forward and
      reduce-scatters the grads).
    * Leaves with no divisible dim — and everything on a pure-dp mesh —
      replicate. ``pp`` layouts are structural, not per-leaf: pipeline
      stages shard stacked layer params via
      :func:`mmlspark_tpu.parallel.pipeline.pipeline_spec` (the Trainer
      re-stacks per-block params at trace time instead).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    fsdp = mesh.shape["fsdp"]
    tp = mesh.shape["tp"]

    def one(path, leaf):
        if rules is not None:
            spec = rules("/".join(str(getattr(k, "key", k)) for k in path),
                         leaf)
            if spec is not None:
                return NamedSharding(mesh, spec)
        shape = getattr(leaf, "shape", ())
        spec: list = [None] * len(shape)
        if tp > 1 and len(shape) >= 2 and shape[-1] % tp == 0:
            spec[-1] = "tp"
        if fsdp > 1 and len(shape) > 0:
            divisible = [(d, s) for d, s in enumerate(shape)
                         if spec[d] is None and s % fsdp == 0]
            if divisible:
                d = max(divisible, key=lambda t: t[1])[0]
                spec[d] = "fsdp"
        if all(s is None for s in spec):
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(one, params)


def state_shardings(mesh, state: Mapping[str, Any],
                    rules: Any = None) -> Any:
    """Placement targets for a FULL train-state pytree
    (``{params, opt_state, step}``) on ``mesh`` — the elastic-rescale
    counterpart of the placement ``Trainer.init_state`` performs:

    * ``params`` leaves place via :func:`param_shardings` (module
      ``rules`` first, then the generic tp/fsdp rules),
    * optimizer moments mirror the params: any subtree of a non-param
      entry whose tree STRUCTURE equals the params tree (optax moments
      — adam's ``mu``/``nu``, momentum's ``trace`` — are built by
      ``tree_map`` over the params) takes the params shardings leaf for
      leaf, exactly where eager ``zeros_like`` propagation put them at
      init — including ``rules``-placed leaves (MoE expert stacks over
      ``ep``),
    * remaining leaves place by the generic per-leaf rule on their own
      shape; scalar leaves (optax step counts) replicate,
    * a 1-device mesh returns the bare device for every leaf (the
      plain-placement fast path ``single_device`` defines).

    This is what makes ``reshard_state`` (train/checkpoint.py) exact: a
    state restored or re-placed through these targets is
    indistinguishable from one built by ``init_state`` on the same mesh.
    """
    import jax

    dev0 = single_device(mesh)
    if dev0 is not None:
        return jax.tree_util.tree_map(lambda leaf: dev0, state)
    placed = dict(state)
    params_sh = param_shardings(mesh, state["params"], rules=rules)
    placed["params"] = params_sh
    p_treedef = jax.tree_util.tree_structure(state["params"])
    repl = replicated(mesh)

    # bare-leaf params would make every leaf "mirror" them (a scalar
    # optax count included) — mirroring only means anything for a real
    # params CONTAINER
    leaf_def = jax.tree_util.tree_structure(0)

    def mirrors_params(node) -> bool:
        return (p_treedef != leaf_def
                and jax.tree_util.tree_structure(node) == p_treedef)

    def one(node):
        if mirrors_params(node):  # a params-shaped moment subtree
            return params_sh
        if getattr(node, "shape", ()):
            return param_shardings(mesh, {"leaf": node})["leaf"]
        return repl

    for key in state:
        if key != "params":
            placed[key] = jax.tree_util.tree_map(
                one, state[key], is_leaf=mirrors_params)
    return placed
