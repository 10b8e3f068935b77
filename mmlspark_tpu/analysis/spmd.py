"""Symbolic SPMD verifier — static sharding propagation and collective
checking for the parallel layer and multi-chip plans.

PR 2's analyzer proves a *pipeline* well-formed before data moves; this
module does the same for the *parallel* execution paths, where the
failure modes are silent numerics corruption and cross-host deadlock
rather than a schema error. Every parallel module here runs inside
``shard_map`` with the replication check off (``check_vma=False`` — the
per-shard code needs ``axis_index``), which means jax no longer verifies
the replication claims ``out_specs`` make. The verifier re-checks them
statically:

* **Sharding-state lattice** (:class:`ShardState`): each array dim is
  replicated or sharded over a tuple of mesh axes, and a value as a
  whole may additionally be *varying* (an unreduced partial state) over
  axes — the three-level lattice ``replicated ⊑ sharded ⊑ partial``.
  :func:`varying_axes` runs a VMA-style dataflow over a shard_map body
  jaxpr: inputs vary over the axes their ``in_specs`` shard,
  ``axis_index`` introduces variance, ``psum``/``all_gather`` over an
  axis removes it, ``psum_scatter``/``all_to_all`` introduce it, and
  everything else unions. An output claimed replicated over an axis it
  still varies over is an **unreduced partial sum escaping** (SPMD103)
  — exactly the class of bug ``check_vma=False`` stops jax from seeing.
* **Call-site provenance** (SPMD103/SPMD102): a shard_map operand built
  by trace-time structure ops (``jnp.stack``/``concatenate`` — the
  re-stacked pipeline layer params) without an explicit replication pin
  hits the jax ≤ 0.4.37 GSPMD full-to-shard sharp edge: mesh axes the
  ``in_spec`` leaves unmentioned consume the operand as an unreduced
  partial sum (dp-extent × the true value — the dp×pp loss-parity seed
  bug). The verifier requires such operands to pass through
  ``with_sharding_constraint``/``device_put`` pinned replicated over the
  unmentioned axes (:func:`~mmlspark_tpu.parallel.pipeline.commit_replicated`).
* **Divisibility / capacity hazards** (SPMD104): dims that do not divide
  by their sharding axes' extents, and — for capacity-dispatch contracts
  (MoE) — dispatch collectives issued with no cross-shard count exchange
  first, the pad-capacity bug class: slot budgets split per source shard
  make a token's survival depend on where its padding landed.
* **Collective schedules** (:mod:`~mmlspark_tpu.analysis.collectives`):
  ordered psum/all_gather/ppermute/all_to_all/psum_scatter extraction
  with conditional-collective (SPMD201), cross-host agreement (SPMD202)
  and drain-fence (SPMD203) checks.

Entry points: :func:`verify_function` for any traceable callable,
:data:`ENTRY_POINTS`/:func:`verify_parallel_layer` for the declared
contracts of ``parallel/{moe,pipeline,ring_attention}``, and
:func:`audit_plan_spmd` — the device-plan audit's multi-chip mode: a
fused inference segment must contain **zero** manual collectives (XLA
inserts the dp resharding; a hand-rolled collective in an inference
composite is a bug) and its minibatch sizing must divide the mesh's
data extent. ``tools/analyze.py spmd`` is the CLI; the repo-wide gate
(:func:`verify_repo`) runs in tier-1 via ``tools/perf_smoke.py``.

Verification work registers through the one telemetry substrate
(``mmlspark_tpu/obs``): ``analysis.spmd.*`` counters and a
``spmd/verify`` span per verified function.
"""

from __future__ import annotations

import dataclasses
import math
import os
from typing import Any, Callable, Iterable

from mmlspark_tpu.analysis.collectives import (
    COLLECTIVE_PRIMS, CollectiveSchedule, SpmdFinding, check_fence_discipline,
    check_schedule, compare_schedules, extract_schedule,
)
from mmlspark_tpu.obs import runtime as _obs_rt
from mmlspark_tpu.obs.metrics import registry as _obs_registry
from mmlspark_tpu.obs.spans import span as _obs_span

# ---- the sharding-state lattice ----


@dataclasses.dataclass(frozen=True)
class ShardState:
    """Abstract placement of one array on a mesh.

    ``dims[i]`` is the tuple of mesh axes dim ``i`` is sharded over
    (empty = replicated along that dim); ``partial`` is the set of axes
    over which the VALUE is an unreduced partial state (each shard holds
    a different contribution that has not been reduced). The lattice:
    ``replicated ⊑ sharded(dims) ⊑ partial(axes)`` — a partial value
    must meet a reducing collective before it may escape as replicated.
    """

    dims: tuple[tuple[str, ...], ...]
    partial: frozenset = frozenset()

    @classmethod
    def from_spec(cls, spec: Any, ndim: int) -> "ShardState":
        """From one entry of a shard_map eqn's ``in_specs``/``out_specs``
        (a ``PartitionSpec``: per dim None, one axis name, or a tuple of
        them; trailing dims it does not mention are replicated)."""
        dims = [() if e is None else (e,) if isinstance(e, str) else tuple(e)
                for e in spec]
        return cls(tuple(dims + [()] * (ndim - len(dims))))

    def axes_used(self) -> set[str]:
        return {a for axes in self.dims for a in axes} | set(self.partial)

    @property
    def is_replicated(self) -> bool:
        return not self.axes_used()

    def describe(self) -> str:
        spec = ", ".join("×".join(axes) if axes else "·"
                         for axes in self.dims)
        s = f"[{spec}]"
        if self.partial:
            s += f" partial({','.join(sorted(self.partial))})"
        return s


def check_divisibility(state: ShardState, shape: tuple[int, ...],
                       mesh_shape: dict, where: str) -> list[SpmdFinding]:
    """SPMD104: a sharded dim must divide by its axes' total extent, or
    the per-shard padding silently skews whatever is computed from it."""
    findings = []
    for d, axes in enumerate(state.dims):
        ext = math.prod(mesh_shape.get(a, 1) for a in axes)
        if ext > 1 and shape[d] % ext:
            findings.append(SpmdFinding(
                "SPMD104", where,
                f"dim {d} of size {shape[d]} does not divide by the "
                f"{'×'.join(axes)} extent {ext}: implicit per-shard "
                "padding — make the padding (and who owns the pad rows) "
                "explicit"))
    return findings


# ---- varying-axes dataflow over a shard_map body ----

_REMOVES_VARIANCE = {"psum", "pmax", "pmin", "all_gather"}
_ADDS_VARIANCE = {"reduce_scatter", "all_to_all"}


def _eqn_axes(eqn: Any) -> set[str]:
    params = eqn.params
    axes = params.get("axes", params.get("axis_name"))
    if axes is None:
        return set()
    if isinstance(axes, str):
        return {axes}
    return {str(a) for a in axes}


def _propagate(jaxpr: Any, in_sets: list) -> list:
    """Map invar varying-axes sets to outvar sets through one jaxpr."""
    env: dict[Any, frozenset] = {}

    def read(v: Any) -> frozenset:
        if not hasattr(v, "count"):  # Literal
            return frozenset()
        return env.get(v, frozenset())

    def write(v: Any, s: frozenset) -> None:
        if hasattr(v, "count"):
            env[v] = s

    for v, s in zip(jaxpr.invars, in_sets):
        write(v, frozenset(s))
    for v in jaxpr.constvars:
        write(v, frozenset())

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        joined = frozenset().union(*[read(v) for v in eqn.invars]) \
            if eqn.invars else frozenset()
        if name == "axis_index":
            out = joined | _eqn_axes(eqn)
        elif name in _REMOVES_VARIANCE:
            out = joined - _eqn_axes(eqn)
        elif name in _ADDS_VARIANCE:
            out = joined | _eqn_axes(eqn)
        elif name == "ppermute":
            out = joined  # permuting identical values stays identical
        elif name == "scan":
            outs = _fixpoint_scan(eqn, [read(v) for v in eqn.invars])
            for v, s in zip(eqn.outvars, outs):
                write(v, s)
            continue
        elif name == "while":
            outs = _fixpoint_while(eqn, [read(v) for v in eqn.invars])
            for v, s in zip(eqn.outvars, outs):
                write(v, s)
            continue
        elif name == "cond":
            pred = read(eqn.invars[0])
            ops = [read(v) for v in eqn.invars[1:]]
            branch_outs = None
            for br in eqn.params["branches"]:
                bo = _propagate(br.jaxpr if hasattr(br, "jaxpr") else br,
                                ops)
                branch_outs = bo if branch_outs is None else [
                    a | b for a, b in zip(branch_outs, bo)]
            for v, s in zip(eqn.outvars, branch_outs or []):
                write(v, s | pred)
            continue
        elif "jaxpr" in eqn.params or "call_jaxpr" in eqn.params:
            sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
            sub = sub.jaxpr if hasattr(sub, "jaxpr") else sub
            if len(sub.invars) == len(eqn.invars):
                outs = _propagate(sub, [read(v) for v in eqn.invars])
                for v, s in zip(eqn.outvars, outs):
                    write(v, s)
                continue
            out = joined
        else:
            out = joined
        for v in eqn.outvars:
            write(v, out)
    return [read(v) for v in jaxpr.outvars]


def _fixpoint_scan(eqn: Any, in_sets: list) -> list:
    sub = eqn.params["jaxpr"]
    sub = sub.jaxpr if hasattr(sub, "jaxpr") else sub
    nc, ncarry = eqn.params["num_consts"], eqn.params["num_carry"]
    consts, carry, xs = in_sets[:nc], in_sets[nc:nc + ncarry], \
        in_sets[nc + ncarry:]
    for _ in range(8):  # axes sets only grow; tiny fixpoint
        outs = _propagate(sub, consts + carry + xs)
        new_carry = [a | b for a, b in zip(carry, outs[:ncarry])]
        if new_carry == carry:
            break
        carry = new_carry
    outs = _propagate(sub, consts + carry + xs)
    return [a | b for a, b in zip(carry, outs[:ncarry])] + outs[ncarry:]


def _fixpoint_while(eqn: Any, in_sets: list) -> list:
    body = eqn.params["body_jaxpr"]
    body = body.jaxpr if hasattr(body, "jaxpr") else body
    cn, bn = eqn.params["cond_nconsts"], eqn.params["body_nconsts"]
    bconsts = in_sets[cn:cn + bn]
    carry = in_sets[cn + bn:]
    for _ in range(8):
        outs = _propagate(body, bconsts + carry)
        new_carry = [a | b for a, b in zip(carry, outs)]
        if new_carry == carry:
            break
        carry = new_carry
    return carry


def varying_axes(body_jaxpr: Any, in_states: list[ShardState]) -> list:
    """Axes each body output may still vary over, given input states:
    an input varies over every axis its spec shards (each shard holds a
    different slice) plus its declared partial axes."""
    in_sets = [frozenset(st.axes_used()) for st in in_states]
    return _propagate(body_jaxpr, in_sets)


# ---- shard_map call-site verification ----

# producer primitives that pin an operand's sharding before shard_map
# entry (the legal way to feed a trace-computed value in)
_PIN_PRIMS = {"sharding_constraint", "device_put"}
# trace-time structure builders — the stack_layer_params class that hits
# the GSPMD full-to-shard partial-sum edge when fed in unpinned
_STRUCTURE_PRIMS = {"concatenate"}
# value-preserving views walked through when resolving provenance
_VIEW_PRIMS = {"reshape", "squeeze", "expand_dims", "transpose",
               "convert_element_type", "broadcast_in_dim", "rev"}


def _pin_replicates(eqn: Any, axes: set[str]) -> bool:
    """Does this sharding_constraint/device_put pin leave ``axes``
    unsharded (replicated)? Unparseable shardings fail safe (False)."""
    sh = eqn.params.get("sharding") or eqn.params.get("device")
    spec = getattr(sh, "spec", None)
    if spec is None:
        # device_put carries a list in some versions
        devices = eqn.params.get("devices")
        if devices:
            spec = getattr(devices[0], "spec", None)
    if spec is None:
        return False
    used: set[str] = set()
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, str):
            used.add(entry)
        else:
            used.update(entry)
    return not (used & axes)


def _operand_provenance(var: Any, producers: dict, unmentioned: set[str],
                        depth: int = 12) -> str:
    """'boundary' (jit invar/const — committed), 'pinned' (explicit
    replication constraint), 'structure' (trace-built stack/concat —
    the hazard), or 'traced' (other in-trace computation)."""
    seen = 0
    while seen < depth:
        eqn = producers.get(var)
        if eqn is None:
            return "boundary"
        name = eqn.primitive.name
        if name in _PIN_PRIMS:
            return "pinned" if _pin_replicates(eqn, unmentioned) \
                else "mis-pinned"
        if name in _STRUCTURE_PRIMS:
            return "structure"
        if name in _VIEW_PRIMS and eqn.invars:
            var = eqn.invars[0]
            seen += 1
            continue
        return "traced"
    return "traced"


@dataclasses.dataclass
class ShardMapSite:
    """One verified shard_map call: declared contract + body analysis."""

    where: str
    mesh_shape: dict
    in_states: list[ShardState]
    out_states: list[ShardState]
    schedule: CollectiveSchedule
    findings: list[SpmdFinding]

    def describe(self) -> str:
        ins = ", ".join(s.describe() for s in self.in_states)
        outs = ", ".join(s.describe() for s in self.out_states)
        return f"{self.where}: in ({ins}) → out ({outs})"


def _verify_shard_map_eqn(eqn: Any, producers: dict,
                          where: str) -> ShardMapSite:
    mesh = eqn.params["mesh"]
    mesh_shape = dict(mesh.shape)
    big_axes = {a for a, n in mesh_shape.items() if n > 1}
    body = eqn.params["jaxpr"]
    body = body.jaxpr if hasattr(body, "jaxpr") else body
    findings: list[SpmdFinding] = []

    in_states = []
    for k, (spec, var) in enumerate(zip(eqn.params["in_specs"],
                                        eqn.invars)):
        ndim = len(getattr(var.aval, "shape", ()))
        st = ShardState.from_spec(spec, ndim)
        in_states.append(st)
        # SPMD101: axis names the mesh does not carry
        bad = [a for a in st.axes_used() if a not in mesh_shape]
        if bad:
            findings.append(SpmdFinding(
                "SPMD101", where,
                f"operand {k} in_spec names axes {bad} the mesh does not "
                f"carry (mesh axes: {sorted(mesh_shape)})"))
        # SPMD104: divisibility of sharded dims
        shape = tuple(getattr(var.aval, "shape", ()))
        findings.extend(check_divisibility(
            st, shape, mesh_shape, f"{where} operand {k}"))
        # SPMD103 (call-site): trace-built operands with unmentioned
        # axes hit the full-to-shard partial-sum edge unless pinned
        unmentioned = big_axes - st.axes_used()
        if unmentioned:
            prov = _operand_provenance(var, producers, unmentioned)
            if prov == "structure":
                findings.append(SpmdFinding(
                    "SPMD103", where,
                    f"operand {k} is built by trace-time stack/concat "
                    f"and enters with mesh axes {sorted(unmentioned)} "
                    "unmentioned in its in_spec: the full-to-shard "
                    "conversion consumes it as an UNREDUCED PARTIAL SUM "
                    "(axis-extent × the true value) under "
                    "check_vma=False. Pin it replicated first "
                    "(parallel.pipeline.commit_replicated)"))
            elif prov == "mis-pinned":
                findings.append(SpmdFinding(
                    "SPMD102", where,
                    f"operand {k} is pinned to a sharding that shards "
                    f"axes {sorted(unmentioned)} its in_spec replicates: "
                    "entry forces an implicit reshard (hidden "
                    "all-gather) — align the pin with the in_spec or "
                    "replicate"))

    # body dataflow: outputs must not vary over axes their out_spec
    # claims replicated (SPMD103 — the check check_vma=False disables)
    out_vary = varying_axes(body, in_states)
    out_states = []
    for k, (spec, var, vary) in enumerate(zip(eqn.params["out_specs"],
                                              eqn.outvars, out_vary)):
        ndim = len(getattr(var.aval, "shape", ()))
        st = ShardState.from_spec(spec, ndim)
        claimed_replicated = big_axes - st.axes_used()
        escape = set(vary) & claimed_replicated
        if escape:
            st = dataclasses.replace(st, partial=frozenset(escape))
            findings.append(SpmdFinding(
                "SPMD103", where,
                f"output {k} still varies over {sorted(escape)} but its "
                "out_spec claims replication there: an unreduced "
                "partial-sum value escapes the shard_map — reduce it "
                "(psum/all_gather) before returning"))
        out_states.append(st)

    schedule = extract_schedule(body)
    findings.extend(check_schedule(schedule, mesh_shape))
    return ShardMapSite(where, mesh_shape, in_states, out_states,
                        schedule, findings)


def _shard_map_sites(jaxpr: Any, prefix: str):
    """Yield ``(shard_map eqn, producer map, where)`` at every nesting
    level — a jitted train step wraps its shard_maps in a pjit (and the
    pipeline's in a scan), so site discovery must recurse. The producer
    map is per-level: operands that are that level's invars count as
    boundary values."""
    producers = {v: e for e in jaxpr.eqns for v in e.outvars}
    for i, eqn in enumerate(jaxpr.eqns):
        name = eqn.primitive.name
        if name == "shard_map":
            yield eqn, producers, f"{prefix}:shard_map[{i}]"
            continue
        subs = []
        if name == "cond":
            subs = [(f"cond[{b}]", br)
                    for b, br in enumerate(eqn.params["branches"])]
        elif name == "while":
            subs = [("while.cond", eqn.params["cond_jaxpr"]),
                    ("while.body", eqn.params["body_jaxpr"])]
        else:
            sub = (eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
                   or eqn.params.get("fun_jaxpr")) \
                if isinstance(eqn.params, dict) else None
            if sub is not None:
                subs = [(name if name not in ("jit", "closed_call")
                         else "", sub)]
        for label, sub in subs:
            sub = sub.jaxpr if hasattr(sub, "jaxpr") else sub
            inner_prefix = f"{prefix}/{label}" if label else prefix
            yield from _shard_map_sites(sub, inner_prefix)


# ---- whole-function verification ----


@dataclasses.dataclass
class SpmdReport:
    """Verification result for one traced function."""

    name: str
    schedule: CollectiveSchedule
    sites: list[ShardMapSite]
    findings: list[SpmdFinding]

    @property
    def ok(self) -> bool:
        return not self.findings

    def format(self) -> str:
        lines = [f"spmd: {self.name} — {len(self.sites)} shard_map "
                 f"site(s), {len(self.schedule.ops)} collective(s)"]
        for site in self.sites:
            lines.append(f"  {site.describe()}")
        lines.append("schedule:")
        lines.append(self.schedule.format())
        if self.findings:
            lines.append(f"{len(self.findings)} finding(s):")
            lines.extend(f"  {f}" for f in self.findings)
        else:
            lines.append("no findings")
        return "\n".join(lines)


def _capacity_findings(schedule: CollectiveSchedule,
                       where: str) -> list[SpmdFinding]:
    """SPMD104 (capacity contract): a dispatch collective must be
    preceded by a cross-shard count exchange over the same axis, or the
    slot budget is split per source shard — a token's survival then
    depends on where the batch (and its padding) landed, not on the
    expert's global load (the MoE pad-capacity bug class)."""
    seen_exchange: set[str] = set()
    for op in schedule.ops:
        if op.kind in ("all_gather", "psum"):
            seen_exchange.update(op.axes)
        elif op.kind in ("psum_scatter", "all_to_all"):
            missing = [a for a in op.axes if a not in seen_exchange]
            if missing:
                return [SpmdFinding(
                    "SPMD104", where,
                    f"capacity dispatch ({op.kind} over {missing}) with "
                    "no preceding cross-shard count exchange "
                    "(all_gather/psum of the routed counts): capacity "
                    "slots are assigned per source shard, so padded/"
                    "masked tokens shift which REAL tokens survive — "
                    "assign slot positions globally")]
            return []
    return []


def verify_function(fn: Callable, *args: Any, name: str = "<fn>",
                    capacity_dispatch: bool = False,
                    expect_axes: Iterable[str] | None = None,
                    expect_no_collectives: bool = False) -> SpmdReport:
    """Trace ``fn`` over ``args`` (ShapeDtypeStructs are fine — nothing
    executes) and statically verify every shard_map site, the collective
    schedule, and the declared contract."""
    import jax

    with _obs_span("spmd/verify", "analysis", {"fn": name}):
        closed = jax.make_jaxpr(fn)(*args)
        sites: list[ShardMapSite] = []
        findings: list[SpmdFinding] = []
        for eqn, producers, where in _shard_map_sites(closed.jaxpr, name):
            site = _verify_shard_map_eqn(eqn, producers, where)
            sites.append(site)
            findings.extend(site.findings)
        schedule = extract_schedule(closed)
        if capacity_dispatch:
            findings.extend(_capacity_findings(schedule, name))
        if expect_axes is not None:
            extra = schedule.axes_used() - set(expect_axes)
            if extra:
                findings.append(SpmdFinding(
                    "SPMD101", name,
                    f"communicates over axes {sorted(extra)} outside its "
                    f"declared contract {sorted(set(expect_axes))}"))
        if expect_no_collectives and schedule.ops:
            findings.append(SpmdFinding(
                "SPMD105", name,
                f"{len(schedule.ops)} manual collective(s) in a program "
                "declared collective-free (fused inference segments rely "
                "on XLA-inserted resharding only): "
                f"{[op.describe() for op in schedule.ops]}"))
    if _obs_rt._enabled:
        reg = _obs_registry()
        reg.counter("analysis.spmd.functions_verified").add()
        reg.counter("analysis.spmd.findings").add(len(findings))
    return SpmdReport(name, schedule, sites, findings)


# ---- declared contracts for the parallel layer ----


@dataclasses.dataclass(frozen=True)
class EntryPoint:
    """A parallel module's declared sharding contract: the mesh it
    expects, the axes it may communicate over, whether it performs
    capacity dispatch (enabling the count-exchange rule), and whether it
    must be manual-collective-free (the serve dp-replica / GSPMD-tp
    segment contract — XLA-inserted resharding only)."""

    name: str
    mesh_spec: dict
    expect_axes: tuple[str, ...]
    build: Callable                  # (mesh) -> (fn, example_args)
    capacity_dispatch: bool = False
    expect_no_collectives: bool = False


def _build_moe(mesh):
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.parallel.moe import moe_apply
    E, D, DH, N = 8, 16, 32, 64
    params = {
        "gate": jax.ShapeDtypeStruct((D, E), jnp.float32),
        "w_in": jax.ShapeDtypeStruct((E, D, DH), jnp.float32),
        "b_in": jax.ShapeDtypeStruct((E, DH), jnp.float32),
        "w_out": jax.ShapeDtypeStruct((E, DH, D), jnp.float32),
        "b_out": jax.ShapeDtypeStruct((E, D), jnp.float32),
    }
    x = jax.ShapeDtypeStruct((N, D), jnp.float32)
    m = jax.ShapeDtypeStruct((N,), jnp.float32)

    def fn(p, xs, mask):
        return moe_apply(p, xs, mesh, capacity_factor=2.0, token_mask=mask)

    return fn, (params, x, m)


def _build_pipeline(mesh):
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.parallel.pipeline import (
        pipeline_apply, stack_layer_params,
    )
    L, D = 8, 16
    layers = [{"w": jax.ShapeDtypeStruct((D, D), jnp.float32),
               "b": jax.ShapeDtypeStruct((D,), jnp.float32)}
              for _ in range(L)]
    x = jax.ShapeDtypeStruct((16, D), jnp.float32)

    def block_fn(layer, h):
        return h + jnp.tanh(h @ layer["w"] + layer["b"])

    def fn(per_layer, xs):
        # stacked at trace time — the Trainer's calling convention, so
        # the verifier sees the commit_replicated pin (or its absence)
        return pipeline_apply(block_fn, stack_layer_params(per_layer),
                              xs, mesh, num_microbatches=4)

    return fn, (layers, x)


def _build_ring(mesh):
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.parallel.ring_attention import ring_attention
    q = jax.ShapeDtypeStruct((4, 16, 4, 8), jnp.float32)

    def fn(qq, kk, vv):
        return ring_attention(qq, kk, vv, mesh, causal=True)

    return fn, (q, q, q)


def _build_ulysses(mesh):
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.parallel.ring_attention import ulysses_attention
    q = jax.ShapeDtypeStruct((4, 16, 4, 8), jnp.float32)

    def fn(qq, kk, vv):
        return ulysses_attention(qq, kk, vv, mesh)

    return fn, (q, q, q)


def _build_serve_segment(mesh):
    """The sharded serve dispatch entry: the composite
    ``core.plan.dispatch_segment`` jits for a lone-JaxModel segment on
    ``mesh`` — a DP replica's sub-mesh or a GSPMD-tp model-parallel
    layout. The contract either way: ZERO manual collectives (replicas
    are independent; tp resharding is XLA-inserted from the param
    shardings, never hand-rolled)."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.core import plan
    from mmlspark_tpu.core.stage import ArrayMeta
    from mmlspark_tpu.models.bundle import ModelBundle
    from mmlspark_tpu.models.jax_model import JaxModel
    from mmlspark_tpu.models.zoo import MLP

    d_in, width, n_out = 16, 32, 8
    module = MLP(features=(width,), num_outputs=n_out)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, d_in), jnp.float32))["params"]
    bundle = ModelBundle(module=module, params=params, input_spec=(d_in,),
                         output_names=("features", "logits"))
    jm = JaxModel(model=bundle, input_col="x", output_col="scores")
    seg = plan.collect_segment([jm], 0,
                               lambda c: ArrayMeta((d_in,), "float32"),
                               min_stages=1, mesh=mesh)
    composite, params_tuple = plan_segment_composite(seg)
    rows = plan.dp_rounded_minibatch(8, plan.mesh_dp(mesh), 8)
    entry = jax.ShapeDtypeStruct((rows, d_in), jnp.float32)
    return composite, (params_tuple, entry)


def _build_serve_pp(mesh):
    """The pp-sharded serve segment: what a pipelined stage's
    ``device_fn`` wraps — L stacked blocks through
    :func:`~mmlspark_tpu.parallel.pipeline.pipeline_apply` under the
    bucket ladder. Manual collectives allowed, over ``pp`` only."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.parallel.pipeline import pipeline_apply
    L, D = 8, 16
    stacked = {"w": jax.ShapeDtypeStruct((L, D, D), jnp.float32),
               "b": jax.ShapeDtypeStruct((L, D), jnp.float32)}
    x = jax.ShapeDtypeStruct((8, D), jnp.float32)

    def block_fn(layer, h):
        return jnp.tanh(h @ layer["w"] + layer["b"])

    def fn(p, xs):
        return pipeline_apply(block_fn, p, xs, mesh, num_microbatches=2)

    return fn, (stacked, x)


def _build_serve_lowprec(mesh):
    """The low-precision serve segment (docs/quantization.md): the same
    lone-JaxModel composite, int8w-quantized by the plan-level precision
    pass (``core/precision`` — bf16 activations, int8 per-channel
    weights dequantized inside the trace). Built through the SAME
    ``segment_composite`` builder the executor jits, with REAL init
    params (weight quantization needs concrete values for its max-abs
    scales). The contract is unchanged by the pass: ZERO manual
    collectives — dequant is pure elementwise math, and any tp
    resharding of the int8 weights stays GSPMD-inserted."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.core import plan
    from mmlspark_tpu.core.precision import PrecisionPolicy
    from mmlspark_tpu.core.stage import ArrayMeta
    from mmlspark_tpu.models.bundle import ModelBundle
    from mmlspark_tpu.models.jax_model import JaxModel
    from mmlspark_tpu.models.zoo import MLP

    d_in, width, n_out = 16, 32, 8
    module = MLP(features=(width,), num_outputs=n_out)
    params = module.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, d_in), jnp.float32))["params"]
    bundle = ModelBundle(module=module, params=params, input_spec=(d_in,),
                         output_names=("features", "logits"))
    jm = JaxModel(model=bundle, input_col="x", output_col="scores")
    seg = plan.collect_segment([jm], 0,
                               lambda c: ArrayMeta((d_in,), "float32"),
                               min_stages=1, mesh=mesh,
                               precision=PrecisionPolicy(mode="int8w"))
    composite, params_tuple = plan_segment_composite(seg)
    rows = plan.dp_rounded_minibatch(8, plan.mesh_dp(mesh), 8)
    entry = jax.ShapeDtypeStruct((rows, d_in), jnp.float32)
    return composite, (params_tuple, entry)


def _build_serve_decode(mesh):
    """The continuous-batching decode program (serve/generate.py): ONE
    fixed-shape ``[slots]`` token step over the slot-major KV cache,
    requests joining/leaving through the active mask. A DP replica owns
    its own slot table and cache, so the contract is ZERO manual
    collectives — a collective here would lockstep independent replicas'
    decode loops. Donation safety (the cache buffers return
    shape/dtype-identical, so ``donate_argnums=(0,)`` updates in place)
    is the other half of the contract; :func:`audit_stateful_spmd` and
    tests/test_spmd.py pin it on this same build."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.models.sequence import TransformerTagger
    from mmlspark_tpu.serve.generate import build_decode_step

    S, L, H, T, hd = 4, 2, 2, 16, 8
    model = TransformerTagger(vocab_size=32, embed_dim=H * hd,
                              num_heads=H, num_layers=L, mlp_dim=32,
                              num_tags=32, max_len=T, causal=True)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    step = build_decode_step(model)
    bufs = {"k": jax.ShapeDtypeStruct((S, L, H, T, hd), jnp.float32),
            "v": jax.ShapeDtypeStruct((S, L, H, T, hd), jnp.float32)}
    iv = jax.ShapeDtypeStruct((S,), jnp.int32)
    bv = jax.ShapeDtypeStruct((S,), jnp.bool_)
    return step, (bufs, params, iv, iv, bv, iv, bv)


def serve_decode_build(mesh: Any = None):
    """Public handle on the decode entry's build (what
    ``tests/test_spmd.py`` and the stateful audit reuse)."""
    return _build_serve_decode(mesh)


ENTRY_POINTS: tuple[EntryPoint, ...] = (
    EntryPoint("moe_apply", {"dp": 2, "ep": 4},
               ("dp", "fsdp", "ep"), _build_moe, capacity_dispatch=True),
    EntryPoint("pipeline_apply", {"dp": 2, "pp": 4},
               ("pp",), _build_pipeline),
    EntryPoint("ring_attention", {"dp": 2, "sp": 4},
               ("sp",), _build_ring),
    EntryPoint("ulysses_attention", {"dp": 2, "sp": 4},
               ("sp",), _build_ulysses),
    # the sharded serving entries (docs/serving.md): a DP replica's
    # single-chip segment, the same segment GSPMD-tp-sharded, and the
    # pipelined pp serve segment — the contracts ModelServer.add_model
    # audits a sharded load against
    EntryPoint("serve_dp_replica", {"dp": 1}, (), _build_serve_segment,
               expect_no_collectives=True),
    EntryPoint("serve_tp_segment", {"dp": 2, "tp": 4}, (),
               _build_serve_segment, expect_no_collectives=True),
    EntryPoint("serve_pp_segment", {"dp": 2, "pp": 4}, ("pp",),
               _build_serve_pp),
    # the int8w+bf16 quantized serve segments (docs/quantization.md):
    # the precision pass must not introduce collectives on a dp replica
    # nor communicate off-contract when the int8 weights tp-shard
    EntryPoint("serve_int8w_replica", {"dp": 1}, (),
               _build_serve_lowprec, expect_no_collectives=True),
    EntryPoint("serve_int8w_tp", {"dp": 2, "tp": 4}, (),
               _build_serve_lowprec, expect_no_collectives=True),
    # the continuous-batching token-serving decode step (PR 18,
    # serve/generate.py): one fixed-shape [slots] program over the
    # donated KV cache — a DP replica's decode loop must stay
    # manual-collective-free, like every other replica segment
    EntryPoint("serve_decode_replica", {"dp": 1}, (),
               _build_serve_decode, expect_no_collectives=True),
)


def verify_entry_point(ep: EntryPoint, devices: Any = None) -> SpmdReport:
    from mmlspark_tpu.parallel.mesh import make_mesh
    mesh = make_mesh(ep.mesh_spec, devices)
    fn, args = ep.build(mesh)
    return verify_function(fn, *args, name=ep.name,
                           capacity_dispatch=ep.capacity_dispatch,
                           expect_axes=ep.expect_axes,
                           expect_no_collectives=ep.expect_no_collectives)


def verify_parallel_layer(devices: Any = None) -> dict[str, SpmdReport]:
    """Verify every declared parallel entry point; the repo gate expects
    every report clean. Needs ≥ 8 devices (the tier-1 CPU mesh)."""
    return {ep.name: verify_entry_point(ep, devices)
            for ep in ENTRY_POINTS}


# ---- the device-plan audit's multi-chip mode ----


@dataclasses.dataclass
class SegmentSpmdReport:
    """SPMD view of one fused device segment."""

    stages: list[str]
    entry_col: str
    entry_state: ShardState
    dp_extent: int
    minibatches: int | None
    schedule: CollectiveSchedule
    findings: list[SpmdFinding]

    def describe(self) -> str:
        names = "→".join(self.stages)
        mb = ("?" if self.minibatches is None else self.minibatches)
        return (f"device[{names}] entry {self.entry_col!r} "
                f"{self.entry_state.describe()} dp={self.dp_extent} "
                f"{mb} minibatch round(s), "
                f"{len(self.schedule.ops)} manual collective(s)")


@dataclasses.dataclass
class PlanSpmdAudit:
    """Multi-chip audit of a transform plan: per-segment shardings,
    dp-divisibility of the minibatch walk, and the (required-empty)
    manual collective schedule of each fused inference program."""

    segments: list[SegmentSpmdReport] = dataclasses.field(
        default_factory=list)
    findings: list[SpmdFinding] = dataclasses.field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.findings

    def format(self) -> str:
        lines = [s.describe() for s in self.segments]
        if self.findings:
            lines.append(f"{len(self.findings)} finding(s):")
            lines.extend(f"  {f}" for f in self.findings)
        else:
            lines.append("no findings")
        return "\n".join(lines)


def plan_segment_composite(seg: Any) -> tuple[Callable, tuple]:
    """(composite fn, params tuple) for a fused plan segment — built by
    ``core.plan.segment_composite``, the SAME builder the executor jits.
    Shared by the multi-chip plan audit and the serve entry-point
    contracts so the verified program cannot drift from the dispatched
    one."""
    from mmlspark_tpu.core import plan

    return plan.segment_composite(seg, plan._segment_mesh(seg))


def audit_plan_spmd(stages: list, meta_of: Callable,
                    n_rows: int | None = None, mesh: Any = None,
                    expect_axes: Iterable[str] | None = None,
                    precision: Any = None) -> PlanSpmdAudit:
    """Replay the planner's segmentation (``core/plan.collect_segment``
    with the abstract ``meta_of`` probe — same contract as the PR 2 plan
    audit) and verify each fused segment's SPMD behavior on its
    inference mesh: batch sharded over the data axes, minibatch sizing
    divisible by the dp extent, and the collective contract.

    ``mesh`` pins the segments to an explicit mesh — the sharded-serving
    audit passes a replica's sub-mesh here (the same override
    ``serve``'s dispatch lanes pass to ``core.plan.transform_async``).
    ``expect_axes=None`` (the default, and the dp-replica contract)
    requires ZERO manual collectives in the composite; a tp/pp
    model-parallel serve segment instead passes its declared
    model-parallel axes, and any collective outside them (in particular
    over ``dp``) is a finding.

    ``precision`` pins the segments' low-precision policy
    (:mod:`mmlspark_tpu.core.precision`): the audit then traces the
    QUANTIZED composite — the same ``segment_composite`` builder the
    executor jits applies the pass, so a quantized serve load is
    verified against exactly the program it will dispatch."""
    import jax

    from mmlspark_tpu.core import plan
    from mmlspark_tpu.core.precision import PrecisionPolicy

    precision = PrecisionPolicy.parse(precision)
    audit = PlanSpmdAudit()
    i = 0
    while i < len(stages):
        # min_stages=1: serving dispatches even a LONE model stage
        # through the fused path (core/plan.transform_async), so the
        # audit must cover single-stage plans too — a lone JaxModel
        # with a manual collective must not audit as "no segments"
        seg = plan.collect_segment(stages, i, meta_of, min_stages=1,
                                   mesh=mesh, precision=precision)
        if seg is None:
            i += 1
            continue
        seg_mesh = plan._segment_mesh(seg)
        dp = plan.mesh_dp(seg_mesh)
        composite, params_tuple = plan_segment_composite(seg)
        size, _ = plan._segment_minibatch(seg)
        mb_rows = plan.segment_entry_rows(seg, n_rows or size)
        entry = jax.ShapeDtypeStruct(
            (mb_rows,) + tuple(seg.entry_meta.shape),
            seg.entry_meta.dtype)
        name = "→".join(type(s).__name__ for s in seg.stages)
        report = verify_function(
            composite, params_tuple, entry, name=f"segment[{name}]",
            expect_axes=expect_axes,
            expect_no_collectives=expect_axes is None)
        # the executor shards minibatches P(('dp','fsdp')) on dim 0
        entry_state = ShardState((("dp", "fsdp"),) + ((),) * len(
            seg.entry_meta.shape))
        findings = list(report.findings)
        findings.extend(check_divisibility(
            entry_state, (mb_rows,) + tuple(seg.entry_meta.shape),
            dict(seg_mesh.shape), f"segment[{name}] minibatch"))
        minibatches = (plan.predict_segment_minibatches(seg, n_rows)
                       if n_rows else None)
        audit.segments.append(SegmentSpmdReport(
            [type(s).__name__ for s in seg.stages], seg.entry_col,
            entry_state, dp, minibatches, report.schedule, findings))
        audit.findings.extend(findings)
        i = seg.end
    return audit


def audit_stateful_spmd(step_fn: Callable, state_structs: Any,
                        args: tuple, name: str = "<stateful>",
                        expect_axes: Iterable[str] | None = None
                        ) -> SpmdReport:
    """SPMD audit of one stateful plan segment
    (:class:`~mmlspark_tpu.core.plan.StatefulSegment`): the multi-chip
    audit's coverage of programs that OWN device state across
    dispatches, which ``audit_plan_spmd``'s stateless segment replay
    cannot see.

    Two contracts, both static:

    * the usual collective contract — ``expect_axes=None`` (the
      dp-replica default) requires ZERO manual collectives
      (SPMD105), any declared axes bound communication (SPMD101);
    * **donation safety** (SPMD106): the step's returned state subtree
      must match the input state leaf-for-leaf in shape AND dtype, or
      ``donate_argnums=(0,)`` cannot alias the buffers in place — XLA
      silently falls back to a copy on CPU and refuses the donation on
      TPU, turning every token step into a full cache copy.
    """
    import jax

    report = verify_function(step_fn, state_structs, *args, name=name,
                             expect_axes=expect_axes,
                             expect_no_collectives=expect_axes is None)
    out = jax.eval_shape(step_fn, state_structs, *args)
    new_state = out[0] if isinstance(out, tuple) else out
    in_leaves, in_tree = jax.tree_util.tree_flatten(state_structs)
    out_leaves, out_tree = jax.tree_util.tree_flatten(new_state)
    mismatched = in_tree != out_tree or any(
        a.shape != b.shape or a.dtype != b.dtype
        for a, b in zip(in_leaves, out_leaves))
    if mismatched:
        report.findings.append(SpmdFinding(
            "SPMD106", name,
            "stateful step returns a state subtree that does not match "
            "the input state leaf-for-leaf (shape/dtype/structure): the "
            "donated buffers cannot be updated in place — every "
            "dispatch would copy the whole device state"))
    return report


# ---- the repo-wide gate ----

_FENCED_SOURCES = ("train/loop.py", "train/input.py", "serve/batcher.py",
                   "serve/mesh.py", "serve/generate.py")


def verify_repo(repo_root: str | None = None,
                devices: Any = None) -> dict:
    """The tier-1 gate: every parallel entry point verifies clean, and
    the multi-host train/serve sources keep the drain-fence discipline.
    Returns ``{"findings": [...], "reports": {...}, "fence_files": N}``.
    """
    if repo_root is None:
        repo_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
    findings: list[SpmdFinding] = []
    reports = verify_parallel_layer(devices)
    for rep in reports.values():
        findings.extend(rep.findings)
    n_fence = 0
    for rel in _FENCED_SOURCES:
        path = os.path.join(repo_root, "mmlspark_tpu",
                            rel.replace("/", os.sep))
        if not os.path.exists(path):
            continue
        with open(path, "r", encoding="utf-8") as fh:
            findings.extend(check_fence_discipline(fh.read(), rel))
        n_fence += 1
    return {"findings": findings, "reports": reports,
            "fence_files": n_fence}
