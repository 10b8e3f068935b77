"""Collective-schedule extraction — the static model of what a compiled
program will do on the wire.

Every cross-chip interaction in this codebase is a jax collective
(``psum``/``all_gather``/``ppermute``/``all_to_all``/``psum_scatter``)
issued inside a ``shard_map`` body; on a multi-host mesh every process
compiles and runs the SAME program, so the one way to deadlock is for
the *schedule* — the ordered sequence of collectives — to diverge across
processes. That can only happen through data-dependent control flow
(a collective under ``lax.cond``/``lax.while_loop``, whose predicate can
differ per host) or through host-side exchanges racing device dispatch
(the ``drain_barrier`` fence discipline of ``train/input.py``). Both are
statically visible, so this module checks them before anything runs:

* :func:`extract_schedule` walks a jaxpr (recursing through ``pjit``,
  ``scan``, ``while``, ``cond``, ``shard_map`` and custom-derivative
  wrappers) and returns the ordered :class:`CollectiveSchedule`. Each op
  records its mesh axes, its structural context (e.g. a ``ppermute``
  inside the pipeline's scan), the static trip count when one exists,
  and whether it sits under data-dependent control flow.
* :func:`check_schedule` reports deadlocks-in-waiting: collectives under
  data-dependent conditionals (SPMD201) and axis names the mesh does not
  carry (SPMD101).
* :func:`compare_schedules` pins cross-host agreement: two traces of the
  step program (or the same program on two hosts) must produce identical
  fingerprints.
* :func:`check_fence_discipline` is the host-side half: an AST check
  that cross-process exchanges (``process_allgather``,
  ``sync_global_devices``) inside a dispatch loop are preceded by a
  drain fence, so the liveness exchange can never race the in-flight
  step window (docs/training_input.md, "lockstep rules").

The schedule is *predictive*: each jaxpr collective lowers to exactly
one StableHLO collective op (``psum`` → ``all_reduce``, ``ppermute`` →
``collective_permute``, ``psum_scatter`` → ``reduce_scatter``; loops
keep their body ops, so counts are invariant to trip count).
``tests/test_spmd.py`` holds predicted counts equal to the lowered text
of every parallel entry point on the 8-device CPU mesh.
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Any, Callable, Iterable

# jaxpr primitive name → schedule kind (the public jax.lax spelling)
COLLECTIVE_PRIMS: dict[str, str] = {
    "psum": "psum",
    "pmax": "pmax",
    "pmin": "pmin",
    "ppermute": "ppermute",
    "all_gather": "all_gather",
    "all_to_all": "all_to_all",
    "reduce_scatter": "psum_scatter",   # jax.lax.psum_scatter's primitive
}

# schedule kind → the StableHLO op it lowers to (the observable side of
# the prediction; reductions share all_reduce)
STABLEHLO_OP: dict[str, str] = {
    "psum": "all_reduce",
    "pmax": "all_reduce",
    "pmin": "all_reduce",
    "ppermute": "collective_permute",
    "all_gather": "all_gather",
    "all_to_all": "all_to_all",
    "psum_scatter": "reduce_scatter",
}

# sub-jaxpr-carrying primitives that are structurally transparent (no
# control-flow semantics of their own)
_TRANSPARENT = ("jit", "closed_call", "call", "custom_jvp_call",
                "custom_vjp_call", "remat2", "custom_lin")


def _axes_of(eqn: Any) -> tuple[str, ...]:
    """Mesh axis names a collective eqn operates over."""
    params = eqn.params
    axes = params.get("axes", params.get("axis_name"))
    if axes is None:
        return ()
    if isinstance(axes, str):
        return (axes,)
    return tuple(str(a) for a in axes)


@dataclasses.dataclass(frozen=True)
class CollectiveOp:
    """One collective in program order."""

    kind: str                       # psum | all_gather | ppermute | ...
    axes: tuple[str, ...]           # mesh axes it communicates over
    context: tuple[str, ...]        # structural path, e.g. (shard_map, scan)
    conditional: bool = False       # under data-dependent control flow
    trips: int | None = None        # static trip count (innermost scan)

    def describe(self) -> str:
        where = "/".join(self.context) or "top"
        s = f"{self.kind}({','.join(self.axes)}) @ {where}"
        if self.trips is not None:
            s += f" ×{self.trips}"
        if self.conditional:
            s += " [data-dependent!]"
        return s


@dataclasses.dataclass
class CollectiveSchedule:
    """The ordered collective sequence of one traced program."""

    ops: list[CollectiveOp] = dataclasses.field(default_factory=list)

    def counts(self) -> dict[str, int]:
        """Static op counts by kind — one per program site, matching how
        each site appears exactly once in the lowered StableHLO text
        (loop bodies lower once, whatever the trip count)."""
        out: dict[str, int] = {}
        for op in self.ops:
            out[op.kind] = out.get(op.kind, 0) + 1
        return out

    def stablehlo_counts(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for op in self.ops:
            hlo = STABLEHLO_OP[op.kind]
            out[hlo] = out.get(hlo, 0) + 1
        return out

    def axes_used(self) -> set[str]:
        return {a for op in self.ops for a in op.axes}

    def fingerprint(self) -> tuple:
        """Order-sensitive identity for cross-host agreement checks."""
        return tuple((op.kind, op.axes, op.context, op.conditional,
                      op.trips) for op in self.ops)

    def conditional_ops(self) -> list[CollectiveOp]:
        return [op for op in self.ops if op.conditional]

    def format(self) -> str:
        if not self.ops:
            return "(no collectives)"
        return "\n".join(f"  {i}. {op.describe()}"
                         for i, op in enumerate(self.ops))


def _sub_jaxpr(obj: Any) -> Any:
    """Unwrap ClosedJaxpr → Jaxpr."""
    return obj.jaxpr if hasattr(obj, "jaxpr") else obj


def _walk(jaxpr: Any, context: tuple[str, ...], conditional: bool,
          trips: int | None, out: list[CollectiveOp]) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            axes = _axes_of(eqn)
            if not axes:
                continue  # psum over no axes: an identity the grad
                # transpose machinery emits; nothing crosses the wire
                # (and nothing appears in the lowered program)
            out.append(CollectiveOp(COLLECTIVE_PRIMS[name], axes,
                                    context, conditional, trips))
        elif name == "shard_map":
            _walk(_sub_jaxpr(eqn.params["jaxpr"]),
                  context + ("shard_map",), conditional, trips, out)
        elif name == "scan":
            _walk(_sub_jaxpr(eqn.params["jaxpr"]), context + ("scan",),
                  conditional, int(eqn.params.get("length") or 0) or None,
                  out)
        elif name == "while":
            # trip count is data-dependent: any collective inside is a
            # cross-host divergence hazard
            _walk(_sub_jaxpr(eqn.params["cond_jaxpr"]),
                  context + ("while.cond",), True, None, out)
            _walk(_sub_jaxpr(eqn.params["body_jaxpr"]),
                  context + ("while.body",), True, None, out)
        elif name == "cond":
            for b, branch in enumerate(eqn.params["branches"]):
                _walk(_sub_jaxpr(branch), context + (f"cond.branch{b}",),
                      True, trips, out)
        elif name in _TRANSPARENT:
            sub = (eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
                   or eqn.params.get("fun_jaxpr"))
            if sub is not None:
                _walk(_sub_jaxpr(sub), context, conditional, trips, out)


def extract_schedule(traced: Any, *example_args: Any) -> CollectiveSchedule:
    """Collective schedule of ``traced`` — a ClosedJaxpr/Jaxpr, or a
    callable traced with ``jax.make_jaxpr`` over ``example_args`` (shape
    structs are fine; nothing executes)."""
    if callable(traced) and not hasattr(traced, "eqns") \
            and not hasattr(traced, "jaxpr"):
        import jax
        traced = jax.make_jaxpr(traced)(*example_args)
    ops: list[CollectiveOp] = []
    _walk(_sub_jaxpr(traced), (), False, None, ops)
    return CollectiveSchedule(ops)


def lowered_collective_counts(text: str) -> dict[str, int]:
    """Count StableHLO collective ops in ``jax.jit(f).lower(...).as_text()``
    — the observed side of the schedule prediction. Matches both the
    pretty (``stablehlo.all_reduce(...)``) and generic
    (``"stablehlo.all_reduce"(...)``) MLIR spellings."""
    import re

    out: dict[str, int] = {}
    for op in set(STABLEHLO_OP.values()):
        n = len(re.findall(rf'stablehlo\.{op}"?[ (]', text))
        if n:
            out[op] = n
    return out


# ---- checks ----


@dataclasses.dataclass(frozen=True)
class SpmdFinding:
    """One verifier finding; codes are catalogued in
    docs/spmd_analysis.md (SPMD1xx sharding, SPMD2xx schedule)."""

    code: str
    where: str
    message: str

    def __str__(self) -> str:
        return f"{self.where}: {self.code} {self.message}"


def check_schedule(schedule: CollectiveSchedule,
                   mesh_axes: Iterable[str]) -> list[SpmdFinding]:
    """Schedule-level hazards: data-dependent collectives and unknown
    axis names."""
    known = set(mesh_axes)
    findings: list[SpmdFinding] = []
    for op in schedule.ops:
        bad = [a for a in op.axes if a not in known]
        if bad:
            findings.append(SpmdFinding(
                "SPMD101", "/".join(op.context) or "top",
                f"collective {op.kind} names axes {bad} the mesh does not "
                f"carry (mesh axes: {sorted(known)})"))
        if op.conditional:
            findings.append(SpmdFinding(
                "SPMD201", "/".join(op.context),
                f"collective {op.kind}({','.join(op.axes)}) under "
                "data-dependent control flow: hosts whose predicate "
                "differs will disagree on the collective schedule — a "
                "deadlock-in-waiting. Hoist the collective out of the "
                "cond/while (compute both sides, select after)"))
    return findings


def compare_schedules(a: CollectiveSchedule, b: CollectiveSchedule,
                      where: str = "schedule") -> list[SpmdFinding]:
    """Cross-host agreement: two traces of the same logical program must
    produce the identical ordered schedule."""
    fa, fb = a.fingerprint(), b.fingerprint()
    if fa == fb:
        return []
    n = min(len(fa), len(fb))
    for i in range(n):
        if fa[i] != fb[i]:
            return [SpmdFinding(
                "SPMD202", where,
                f"collective schedules diverge at position {i}: "
                f"{a.ops[i].describe()} vs {b.ops[i].describe()} — "
                "processes running these programs will deadlock")]
    return [SpmdFinding(
        "SPMD202", where,
        f"collective schedules diverge in length: {len(fa)} vs {len(fb)} "
        "ops — processes running these programs will deadlock")]


# ---- host-side fence discipline (AST) ----

_EXCHANGE_CALLS = {"process_allgather", "sync_global_devices"}
_FENCE_CALLS = {"drain_barrier", "fence"}


def _callee(node: ast.AST) -> str | None:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    return None


def check_fence_discipline(source: str,
                           path: str = "<string>") -> list[SpmdFinding]:
    """Cross-process host exchanges inside a loop must follow a drain
    fence (``drain_barrier()``/``fence()``) *earlier in the same loop
    body*: an allgather issued while device steps are still in flight
    interleaves differently per process, deadlocking the step
    collectives (the PR 3 lockstep rule, now statically checked)."""
    findings: list[SpmdFinding] = []
    tree = ast.parse(source, filename=path)
    for loop in ast.walk(tree):
        if not isinstance(loop, (ast.For, ast.While)):
            continue
        fence_lines = [n.lineno for n in ast.walk(loop)
                       if isinstance(n, ast.Call)
                       and _callee(n.func) in _FENCE_CALLS]
        for node in ast.walk(loop):
            if isinstance(node, ast.Call) \
                    and _callee(node.func) in _EXCHANGE_CALLS:
                if not any(ln <= node.lineno for ln in fence_lines):
                    findings.append(SpmdFinding(
                        "SPMD203", f"{path}:{node.lineno}",
                        f"{_callee(node.func)} inside a loop with no "
                        "preceding drain fence: the exchange can race "
                        "in-flight step dispatch and deadlock the step "
                        "collectives — call drain_barrier() first"))
    return findings
