"""lint_jax — AST lint for JAX anti-patterns in mmlspark_tpu.

A pyflakes-style single-pass visitor (no imports of the linted code, no
jax initialization) catching the mistakes that cost the most on TPU:

* **JX101 host sync in jit** — ``np.asarray``/``np.array``, ``float()``/
  ``int()``/``bool()`` on non-constants, ``.item()``/``.tolist()`` inside
  a jit-compiled function. Each forces a device→host transfer + blocking
  sync in the middle of a traced computation (or a tracer error).
* **JX102 jit in loop** — ``jax.jit(...)`` constructed inside a for/while
  body: every iteration builds a fresh callable with an empty compile
  cache (the classic accidental-recompile).
* **JX103 legacy shard_map** — importing ``jax.experimental.shard_map``
  (the pre-``jax.shard_map`` spelling, with the replication check named
  ``check_rep``) or probing for the entry point with
  ``getattr(jax, "shard_map")``. The tree is written for the installed
  jax (``pyproject.toml`` pins it): ``jax.shard_map(..., check_vma=...)``
  directly, no version shim.
* **JX104 mutable Param default** — ``Param(default=[])`` / ``{}`` /
  ``set()``: the default is shared across every stage instance.
* **JX105 blocking scalar fetch in a step loop** — ``float()``/``int()``/
  ``.item()`` on the output of a ``*step*`` call inside the training loop
  that issued it: the coercion blocks the host on that step's device
  completion mid-pipeline, stalling the prefetch window every time it
  runs. Record the device scalar and resolve it one step later (the
  lagged-fetch sites in ``train/loop.py`` carry the pragma).
* **JX106 blocking device fetch in a serve dispatch loop** —
  ``np.asarray``/``float()``/``int()``/``.item()``/``.tolist()`` on the
  output of a ``*dispatch*``/``*_async`` call inside the loop that issued
  it: the fetch blocks the dispatch loop on that batch's device
  completion, serializing host packing with device compute and forfeiting
  the overlap the serving batcher exists for. Push the dispatched handle
  through the bounded in-flight window and drain the *oldest* entry (or
  fetch after the loop) — the discipline of
  ``mmlspark_tpu/serve/batcher.py``.
* **JX109 blocking fetch in a decode/generate loop** — ``np.asarray``/
  ``float()``/``int()``/``.item()``/``.tolist()`` on the output of a
  ``*decode*``/``*generate*`` call (the full dotted spelling counts:
  ``self._decode.jitted(...)`` qualifies) inside the loop that issued
  it: autoregressive decode is a chain of tiny dispatches, so a
  same-step host fetch serializes every token on its device round-trip
  — the worst case of the JX105/JX106 stall, paid per token. Carry the
  token on device (the decode program's own output feeds the next
  step's input) and consume the *previous* step's output instead — the
  one-step-lagged discipline of ``mmlspark_tpu/serve/generate.py``.
* **JX108 implicit f64 promotion in device code** — ``np.float64(...)``/
  ``np.double(...)`` scalar constructors or ``dtype=np.float64`` /
  ``dtype="float64"`` arguments inside a jit-traced body, a device-stage
  body (a function defined inside ``device_fn``/``device_fn_mesh``), or
  a step/serve dispatch loop. numpy float64 scalars are STRONGLY typed
  under jax promotion, so one leaking into jitted math silently widens
  a bf16/f32 activation chain (the exact degradation a bf16 serving
  policy exists to avoid — docs/quantization.md); python float literals
  are weak-typed and fine, which is why the rule targets the np scalar
  forms specifically.
* **JX107 host-side image work under a device-preprocess spec** —
  ``imgops.resize``/any ``cv2.*`` call/PIL decode (``Image.open``,
  ``decode_image``) inside a train step loop or inside a function fed to
  a ``DeviceLoader`` as its source, in a module that uses
  ``DevicePreprocess`` (the static stand-in for "a device-preprocess
  spec is active"): the spec already replays geometry inside the jitted
  step, so host image work in the input path burns producer-thread time
  AND fattens the wire (f32/resized pixels instead of thin uint8).
  Ship source-resolution uint8 and let ``train/preprocess.py`` do the
  geometry on device.

The JX2xx family is the AST face of the SPMD verifier
(``mmlspark_tpu/analysis/spmd.py`` — which checks the same hazards
semantically on the traced jaxpr; see docs/spmd_analysis.md):

* **JX201 collective under data-dependent control flow** — a
  ``psum``/``ppermute``/``all_gather``/``all_to_all``/``psum_scatter``
  inside a ``lax.cond``/``lax.switch``/``lax.while_loop`` branch or
  body: hosts whose predicate (or trip count) differs disagree on the
  collective schedule — a cross-host deadlock-in-waiting. Hoist the
  collective out (compute both sides, select after).
* **JX202 unknown mesh axis name** — a collective (or ``axis_index``)
  whose literal axis name is not one of the canonical mesh axes
  (``parallel/mesh.py`` ``AXES``): a typo'd axis traces fine inside a
  matching-named shard_map but can never bind to the production meshes.
* **JX203 unreduced axis escapes a shard_map** — an axis named in
  ``in_specs`` but absent from every ``out_specs`` entry, with no
  reducing collective (``psum``/``all_gather``/...) over it in the
  body: the out_spec claims replication over an axis the inputs vary
  over, and ``check_vma=False`` (which every body here needs) stops jax
  from checking the claim — values escape as unreduced partial sums.
* **JX204 per-shard capacity arithmetic** — a shard_map body that
  assigns capacity slots from a local ``cumsum`` and dispatches with
  ``all_to_all``/``psum_scatter`` but never exchanges the routed counts
  (``all_gather``): the slot budget is split per source shard, so
  which tokens survive depends on where the batch (and its padding)
  landed — the MoE pad-capacity bug class. Assign slot positions
  globally (gather counts, offset the local ranks).

The JX3xx family is the AST face of the whole-repo concurrency verifier
(``mmlspark_tpu/analysis/concurrency.py`` — which derives the same
hazards interprocedurally, with lock identity and call-graph context;
see docs/concurrency.md). These are the single-file checks cheap enough
to run on every save:

* **JX301 blocking call under a held lock** — ``time.sleep`` or a
  ``subprocess.*`` call lexically inside a ``with <lock>:`` block (the
  receiver *looks* like a lock: ``_lock``/``_cv``/``mutex``/...). The
  deep pass (CC102) also follows callees and thread joins.
* **JX302 manual acquire without try/finally** — a bare
  ``lock.acquire()`` statement not immediately followed by a
  ``try/finally`` that releases it: an exception between the two leaks
  the lock forever (CC103's single-file face). Use ``with``.
* **JX303 Thread() without an explicit daemon flag** — every spawn site
  must declare its lifecycle; the deep pass (CC104) audits that
  non-daemon threads have a reachable ``join()`` owner.

Intentional exceptions are suppressed two ways, both documented in
docs/static_analysis.md:

* an inline pragma on the offending line: ``# lint-jax: allow(JX101)``.
  JX3xx pragmas **require a justification** after a colon
  (``# lint-jax: allow(JX301): why this wait is the contract``) — an
  unjustified one is itself a finding (**JX300**);
* the curated :data:`DEFAULT_ALLOWLIST` below (file-suffix → rules,
  with a per-entry justification), for files whose whole purpose is the
  exception (currently none).

Usage::

    python tools/lint_jax.py [path ...] [--json]   # default: mmlspark_tpu/

Prints one line per finding and exits 1 if any survive the allowlist
(0 clean, 2 on a nonexistent path). ``--json`` emits the machine
report — findings and suppressions with rule id, path, line, message,
and pragma status — the same schema ``analyze.py concurrency --json``
uses. ``tests/test_lint.py`` runs this over the codebase in tier-1
(zero-findings gate) and over a seeded fixture (exact-findings gate).
"""

from __future__ import annotations

import ast
import dataclasses
import json
import os
import re
import sys

# files whose entire purpose is the exception; suffix-matched against the
# normalized path, each rule carrying its justification so the gate
# stays reviewable in one place.
DEFAULT_ALLOWLIST: dict[str, dict] = {}

RULES = {
    "JX101": "host sync inside a jit-compiled function",
    "JX102": "jax.jit constructed inside a loop body",
    "JX103": "legacy jax.experimental.shard_map / version-probing "
             "getattr; call jax.shard_map(..., check_vma=...) directly",
    "JX104": "mutable default value in a Param declaration",
    "JX105": "blocking scalar fetch on a step output inside the step loop; "
             "record the device scalar and resolve it one step later",
    "JX106": "blocking device fetch on a dispatched batch inside a serve "
             "dispatch loop; drain through the bounded in-flight window "
             "(or after the loop)",
    "JX107": "host-side image work in a train step loop or DeviceLoader "
             "producer while a device-preprocess spec is active; ship "
             "thin uint8 and replay the geometry on device "
             "(train/preprocess.py)",
    "JX108": "np.float64/np.double scalar (or dtype=float64) inside "
             "device-stage bodies or step/serve loops; numpy f64 scalars "
             "are strongly typed and silently widen bf16/f32 activation "
             "chains — use np.float32 or a python literal",
    "JX109": "blocking fetch on the current decode step's output inside "
             "the decode/generate loop; carry the token on device and "
             "consume the previous step's output one step lagged "
             "(serve/generate.py's discipline)",
    "JX201": "collective under data-dependent control flow (lax.cond/"
             "switch/while_loop); hoist it out — hosts that disagree on "
             "the predicate deadlock",
    "JX202": "collective names a mesh axis outside the canonical AXES "
             "(parallel/mesh.py); typo'd axes can never bind to the "
             "production meshes",
    "JX203": "axis sharded by in_specs but absent from out_specs with no "
             "reducing collective over it in the body; the output escapes "
             "as an unreduced partial sum (check_vma=False hides it)",
    "JX204": "capacity slots assigned from a local cumsum with no "
             "cross-shard count exchange (all_gather) before the "
             "dispatch; assign slot positions globally",
    "JX300": "pragma suppressing a JX3xx rule has no justification; add "
             "one after a colon: # lint-jax: allow(JX30n): why",
    "JX301": "blocking call (time.sleep / subprocess.*) inside a "
             "with-lock block; move the wait outside the critical "
             "section (deep face: analysis/concurrency.py CC102)",
    "JX302": "bare lock.acquire() not followed by try/finally release; "
             "an exception in between leaks the lock — use `with` "
             "(deep face: CC103)",
    "JX303": "threading.Thread(...) without an explicit daemon= flag; "
             "declare the lifecycle at the spawn site (deep face: "
             "CC104 audits join ownership)",
}

# JX301's "looks like a lock" heuristic: the terminal name of a with-item
# context expression. The deep pass resolves real lock identities; the
# lint only needs the conventional spellings used in this codebase.
_LOCKISH_RE = re.compile(
    r"(?:^|_)(lock|locks|cv|cond|condition|mutex|sem|semaphore)$")

# JX301's needles: module-level blocking calls that never belong inside
# a critical section (thread joins / queue ops need type context — the
# deep pass covers those)
_BLOCKING_UNDER_LOCK = {("time", "sleep"), ("subprocess", "run"),
                        ("subprocess", "call"), ("subprocess", "check_call"),
                        ("subprocess", "check_output")}

_PRAGMA_RE = re.compile(
    r"lint-jax:\s*allow\(([A-Z0-9,\s]+)\)(?::\s*(.*))?")

# mirror of parallel/mesh.py AXES — the lint must not import jax code
_MESH_AXES = ("dp", "fsdp", "tp", "sp", "pp", "ep")
_COLLECTIVE_CALLS = {"psum", "pmean", "pmax", "pmin", "ppermute",
                     "pshuffle", "all_gather", "all_to_all",
                     "psum_scatter"}
# collectives that make a value invariant over their axis (JX203)
_REDUCING_CALLS = {"psum", "pmean", "pmax", "pmin", "all_gather",
                   "all_to_all", "psum_scatter"}
_COND_CALLS = {"cond", "switch", "while_loop"}

# the callee-name hint marking a train-step call whose outputs JX105 tracks
_STEP_HINT = "step"

# JX108: the strongly-typed f64 spellings (namespace attr names) and the
# namespaces they ride on. jnp.float64 is included — with x64 disabled it
# canonicalizes, but code written against it flips behavior the moment a
# library enables x64
_F64_ATTRS = {"float64", "double"}
_F64_NAMESPACES = {"np", "numpy", "onp", "jnp"}

# PIL-style decode roots for JX107 (cv2 is matched as a whole namespace)
_PIL_ROOTS = {"Image", "PIL"}


def _is_step_call(name: str) -> bool:
    # "decode" spellings route to JX109 (the per-token face of the same
    # stall), so a `decode_step` call must not double-fire as JX105
    low = name.lower()
    return _STEP_HINT in low.rsplit(".", 1)[-1] \
        and not _is_decode_call(low)


def _is_decode_call(name: str) -> bool:
    """JX109's taint source: an autoregressive decode/generate call —
    matched over the FULL dotted spelling (``self._decode.jitted``,
    ``engine.advance_decode``, ``decode_step``), because the decode
    handle is usually the receiver, not the terminal attribute."""
    low = name.lower()
    return "decode" in low or "generate" in low


def _host_image_call(node: ast.Call) -> str | None:
    """JX107's needle: a host-side image decode/geometry call —
    ``imgops.resize``, any ``cv2.*``, PIL ``Image.open``, or the
    readers' ``decode_image`` helper. Returns the spelled call or
    None."""
    func = node.func
    if isinstance(func, ast.Attribute):
        root = func.value
        while isinstance(root, ast.Attribute):
            root = root.value
        root_name = root.id if isinstance(root, ast.Name) else None
        if root_name == "cv2":
            return f"cv2.{func.attr}"
        if func.attr == "resize" and root_name == "imgops":
            return "imgops.resize"
        if func.attr in ("open", "imdecode") and root_name in _PIL_ROOTS:
            return f"{root_name}.{func.attr}"
    if isinstance(func, ast.Name) and func.id == "decode_image":
        return "decode_image"
    return None


def _is_dispatch_call(name: str) -> bool:
    """JX106's taint source: an async batch dispatch — ``*dispatch*`` or
    the ``*_async`` naming convention (``transform_async`` & co). A
    decode-flavored dispatch (``self._decode.dispatch``) routes to
    JX109 instead — one site, one rule."""
    low = name.lower()
    leaf = low.rsplit(".", 1)[-1]
    return ("dispatch" in leaf or leaf.endswith("_async")) \
        and not _is_decode_call(low)

_JIT_NAMES = {"jit", "pjit"}
_NUMPY_ALIASES = {"np", "numpy", "onp"}
_HOST_NP_CALLS = {"asarray", "array", "copy"}
_HOST_BUILTINS = {"float", "int", "bool"}
_HOST_METHODS = {"item", "tolist"}


@dataclasses.dataclass(frozen=True)
class Finding:
    path: str
    line: int
    rule: str
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} {self.message}"

    def as_dict(self) -> dict:  # same schema as analysis/concurrency.py
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "message": self.message}


def _callee_name(node: ast.AST) -> str | None:
    """Terminal name of a call target: ``step`` / ``self.step_masked``."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    return None


def _call_spelling(node: ast.AST) -> str | None:
    """Full dotted spelling of a call target, lowercased:
    ``self._decode.jitted`` → ``"self._decode.jitted"``. The fetch-loop
    rules match sources over this (JX109 needs the qualifying path —
    the decode handle is the receiver, the terminal attr is just
    ``dispatch``/``jitted``); predicates that only care about the
    terminal name split the last segment off themselves."""
    parts = []
    cur = node
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if isinstance(cur, ast.Name):
        parts.append(cur.id)
    elif not parts:
        return None
    return ".".join(reversed(parts)).lower()


def _literal_axis_names(expr: ast.AST | None) -> set:
    """String literals in an axis argument: ``"pp"`` or ``("dp", "ep")``.
    Non-literal axis expressions yield nothing (the lint never guesses)."""
    if expr is None:
        return set()
    if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
        return {expr.value}
    if isinstance(expr, (ast.Tuple, ast.List)):
        out = set()
        for elt in expr.elts:
            if isinstance(elt, ast.Constant) and isinstance(elt.value, str):
                out.add(elt.value)
        return out
    return set()


def _spec_axis_names(expr: ast.AST | None) -> set:
    """Canonical axis names appearing literally anywhere in an
    in_specs/out_specs expression (inside ``P(...)`` calls and tuples)."""
    if expr is None:
        return set()
    return {n.value for n in ast.walk(expr)
            if isinstance(n, ast.Constant) and isinstance(n.value, str)
            and n.value in _MESH_AXES}


def _is_jit_func(node: ast.AST) -> bool:
    """Is this expression a reference to jax.jit / jit / pjit?"""
    if isinstance(node, ast.Name):
        return node.id in _JIT_NAMES
    if isinstance(node, ast.Attribute):
        return node.attr in _JIT_NAMES
    return False


def _has_jit_decorator(fn: ast.AST) -> bool:
    for dec in getattr(fn, "decorator_list", []):
        if _is_jit_func(dec):
            return True
        if isinstance(dec, ast.Call):
            # @partial(jax.jit, ...) / @functools.partial(jit, ...)
            fname = dec.func
            is_partial = (isinstance(fname, ast.Name)
                          and fname.id == "partial") or (
                isinstance(fname, ast.Attribute) and fname.attr == "partial")
            if is_partial and dec.args and _is_jit_func(dec.args[0]):
                return True
            if _is_jit_func(fname):  # @jax.jit(static_argnums=...)
                return True
    return False


class _Linter(ast.NodeVisitor):
    def __init__(self, path: str, source: str):
        self.path = path
        self.lines = source.splitlines()
        self.findings: list[Finding] = []
        self.suppressed: list[tuple[Finding, str]] = []  # (finding, why)
        self.loop_depth = 0
        self.jitted_names: set[str] = set()
        self.jitted_lambdas: list[ast.Lambda] = []
        self.func_defs: dict[str, ast.AST] = {}
        self.uses_device_preprocess = False

    # -- pass 1 collects jit targets + local defs; pass 2 walks bodies --

    def collect(self, tree: ast.AST) -> None:
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and _is_jit_func(node.func):
                if node.args:
                    target = node.args[0]
                    if isinstance(target, ast.Name):
                        self.jitted_names.add(target.id)
                    elif isinstance(target, ast.Lambda):
                        self.jitted_lambdas.append(target)
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # JX201/JX203/JX204 resolve branch/body callables by name;
                # later definitions shadow earlier ones, as at runtime
                self.func_defs[node.name] = node
            # JX107 fires only when the module actually engages the
            # device-preprocess layer — the static stand-in for "a spec
            # is active" (an import or any mention of DevicePreprocess)
            if (isinstance(node, ast.Name)
                    and node.id == "DevicePreprocess") or (
                    isinstance(node, ast.Attribute)
                    and node.attr == "DevicePreprocess") or (
                    isinstance(node, ast.ImportFrom)
                    and any(a.name == "DevicePreprocess"
                            for a in node.names)):
                self.uses_device_preprocess = True

    def _emit(self, node: ast.AST, rule: str, message: str) -> None:
        line = getattr(node, "lineno", 0)
        text = self.lines[line - 1] if 0 < line <= len(self.lines) else ""
        finding = Finding(self.path, line, rule, message)
        m = _PRAGMA_RE.search(text)
        if m and rule in {r.strip() for r in m.group(1).split(",")}:
            why = (m.group(2) or "").strip()
            if rule.startswith("JX3") and not why:
                # concurrency-family suppressions must say why — an
                # unjustified pragma is itself a finding (mirrors CC100)
                finding = Finding(self.path, line, "JX300", RULES["JX300"])
                if finding not in self.findings:
                    self.findings.append(finding)
                return
            if finding not in (f for f, _ in self.suppressed):
                self.suppressed.append((finding, why))
            return
        # nested loops run the JX105 subtree analysis once per level —
        # report each site once
        if finding not in self.findings:
            self.findings.append(finding)

    # -- JX301 / JX302 / JX303: single-file concurrency face --

    @staticmethod
    def _lockish(expr: ast.AST) -> bool:
        name = None
        if isinstance(expr, ast.Attribute):
            name = expr.attr
        elif isinstance(expr, ast.Name):
            name = expr.id
        return bool(name and _LOCKISH_RE.search(name.lower()))

    def visit_With(self, node: ast.With) -> None:
        if any(self._lockish(item.context_expr) for item in node.items):
            for sub in ast.walk(node):
                if not isinstance(sub, ast.Call):
                    continue
                f = sub.func
                if (isinstance(f, ast.Attribute)
                        and isinstance(f.value, ast.Name)
                        and (f.value.id, f.attr) in _BLOCKING_UNDER_LOCK):
                    self._emit(sub, "JX301",
                               f"{f.value.id}.{f.attr}(...) blocks inside "
                               "a with-lock block; move the wait outside "
                               "the critical section")
        self.generic_visit(node)

    def lint_acquire_blocks(self, tree: ast.AST) -> None:
        """JX302: a bare ``lock.acquire()`` statement must be chained to
        a ``try/finally`` releasing it as its immediate next sibling."""
        for node in ast.walk(tree):
            for field in ("body", "orelse", "finalbody"):
                stmts = getattr(node, field, None)
                if not isinstance(stmts, list):
                    continue
                for i, stmt in enumerate(stmts):
                    if not (isinstance(stmt, ast.Expr)
                            and isinstance(stmt.value, ast.Call)
                            and isinstance(stmt.value.func, ast.Attribute)
                            and stmt.value.func.attr == "acquire"
                            and self._lockish(stmt.value.func.value)):
                        continue
                    nxt = stmts[i + 1] if i + 1 < len(stmts) else None
                    if isinstance(nxt, ast.Try) and any(
                            isinstance(sub, ast.Call)
                            and isinstance(sub.func, ast.Attribute)
                            and sub.func.attr == "release"
                            for s in nxt.finalbody
                            for sub in ast.walk(s)):
                        continue
                    self._emit(stmt.value, "JX302", RULES["JX302"])

    # -- JX102 / JX103 / JX104 / JX105: module-wide --

    def visit_For(self, node: ast.For) -> None:
        self._loop_body(node)

    def visit_While(self, node: ast.While) -> None:
        self._loop_body(node)

    def _loop_body(self, node: ast.AST) -> None:
        # JX105: blocking scalar coercion on train-step outputs
        self._lint_fetch_loop(node, _is_step_call, "JX105",
                              "a step output", "mid-pipeline",
                              flag_np=False)
        # JX106: blocking device fetch on serve-dispatch outputs (also
        # catches np.asarray — a full-batch fetch, not just a scalar)
        self._lint_fetch_loop(node, _is_dispatch_call, "JX106",
                              "a dispatched batch",
                              "inside the serve dispatch loop",
                              flag_np=True)
        # JX109: same stall, paid PER TOKEN — a fetch on the current
        # decode step's output inside the decode/generate loop
        self._lint_fetch_loop(node, _is_decode_call, "JX109",
                              "a decode-step output",
                              "inside the decode loop", flag_np=True)
        has_step = any(
            isinstance(sub, ast.Call)
            and (name := _callee_name(sub.func)) is not None
            and _is_step_call(name)
            for sub in ast.walk(node))
        # JX107: host image work in a loop that dispatches train steps,
        # in a module where a device-preprocess spec is active
        if self.uses_device_preprocess and has_step:
            self._lint_host_image_calls(node, "the train step loop")
        # JX108: f64 scalars built in a step or serve dispatch loop —
        # they feed the loop's device calls as strong float64
        has_dispatch = any(
            isinstance(sub, ast.Call)
            and (name := _callee_name(sub.func)) is not None
            and _is_dispatch_call(name)
            for sub in ast.walk(node))
        if has_step or has_dispatch:
            self._lint_f64_sites(node)
        self.loop_depth += 1
        self.generic_visit(node)
        self.loop_depth -= 1

    # -- JX108: strongly-typed f64 leaking into device code --

    def _is_f64_expr(self, expr: ast.AST) -> bool:
        if isinstance(expr, ast.Constant) and expr.value in ("float64",
                                                             "double"):
            return True
        if isinstance(expr, ast.Attribute) and expr.attr in _F64_ATTRS:
            root = expr.value
            while isinstance(root, ast.Attribute):
                root = root.value
            return isinstance(root, ast.Name) \
                and root.id in _F64_NAMESPACES
        return False

    def _lint_f64_sites(self, scope: ast.AST) -> None:
        """Flag f64-spelling sites anywhere in ``scope`` (a traced body,
        a device-stage body, or a step/serve loop). The message is
        context-free so a site reachable through two scopes (a jitted
        fn inside a step loop) reports once — ``_emit`` dedups."""
        for sub in ast.walk(scope):
            if not isinstance(sub, ast.Call):
                continue
            if self._is_f64_expr(sub.func):
                self._emit(sub, "JX108",
                           f"{ast.unparse(sub.func)}(...) builds a "
                           "strongly-typed float64 scalar in device "
                           "code — it silently widens bf16/f32 "
                           "activation chains; use np.float32 or a "
                           "python literal")
                continue
            for kw in sub.keywords:
                if kw.arg == "dtype" and self._is_f64_expr(kw.value):
                    self._emit(sub, "JX108",
                               f"dtype={ast.unparse(kw.value)} in device "
                               "code — it silently widens bf16/f32 "
                               "activation chains; use np.float32 or a "
                               "python literal")

    def _lint_host_image_calls(self, scope: ast.AST, where: str) -> None:
        for sub in ast.walk(scope):
            if isinstance(sub, ast.Call):
                spelled = _host_image_call(sub)
                if spelled is not None:
                    self._emit(sub, "JX107",
                               f"{spelled}() runs host-side image work "
                               f"in {where} while a device-preprocess "
                               "spec is active; ship thin uint8 and "
                               "replay the geometry on device "
                               "(train/preprocess.py)")

    # -- JX105 / JX106: blocking fetches on pipelined outputs in a loop --

    def _lint_fetch_loop(self, loop: ast.AST, is_source, rule: str,
                         noun: str, where: str, flag_np: bool) -> None:
        """Taint names bound from source calls (``is_source`` over the
        callee name) anywhere in this loop's subtree (``state, metrics =
        self.step_masked(...)``), propagate through plain/subscript
        aliasing (``pending = metrics["loss"]``), and flag blocking
        coercions on tainted values inside the loop. Host fetches after
        the loop drains are fine — only the in-loop sync stalls the
        pipeline."""
        tainted: set[str] = set()
        for node in ast.walk(loop):
            if not (isinstance(node, ast.Assign)
                    and isinstance(node.value, ast.Call)):
                continue
            fname = _call_spelling(node.value.func)
            if fname and is_source(fname):
                for target in node.targets:
                    elts = (target.elts if isinstance(target, ast.Tuple)
                            else [target])
                    tainted.update(n.id for n in elts
                                   if isinstance(n, ast.Name))
        if not tainted:
            return
        changed = True
        while changed:  # alias fixpoint: pending = metrics["loss"]
            changed = False
            for node in ast.walk(loop):
                if not isinstance(node, ast.Assign):
                    continue
                src = node.value
                if isinstance(src, ast.Subscript):
                    src = src.value
                if isinstance(src, ast.Name) and src.id in tainted:
                    for target in node.targets:
                        if (isinstance(target, ast.Name)
                                and target.id not in tainted):
                            tainted.add(target.id)
                            changed = True

        def tainted_value(expr: ast.AST) -> bool:
            if isinstance(expr, ast.Subscript):
                expr = expr.value
            return isinstance(expr, ast.Name) and expr.id in tainted

        fix = RULES[rule].split("; ")[1]
        for node in ast.walk(loop):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            if (isinstance(func, ast.Name) and func.id in ("float", "int")
                    and node.args and tainted_value(node.args[0])):
                self._emit(node, rule,
                           f"{func.id}() on {noun} blocks the host "
                           f"{where}; {fix}")
            elif (isinstance(func, ast.Attribute)
                    and func.attr in ("item", "tolist")
                    and tainted_value(func.value)):
                self._emit(node, rule,
                           f".{func.attr}() on {noun} blocks the "
                           f"host {where}; {fix}")
            elif (flag_np and isinstance(func, ast.Attribute)
                    and func.attr in _HOST_NP_CALLS
                    and isinstance(func.value, ast.Name)
                    and func.value.id in _NUMPY_ALIASES
                    and node.args and tainted_value(node.args[0])):
                self._emit(node, rule,
                           f"np.{func.attr}() on {noun} blocks the "
                           f"host {where}; {fix}")

    def visit_Call(self, node: ast.Call) -> None:
        if _is_jit_func(node.func) and self.loop_depth > 0:
            self._emit(node, "JX102",
                       "jax.jit called inside a loop builds a fresh "
                       "callable (and compile cache) every iteration; "
                       "hoist it out of the loop")
        func = node.func
        # jax.experimental.shard_map.shard_map(...) — the legacy dotted
        # spelling; jax.shard_map(...) itself is the call to make
        if (isinstance(func, ast.Attribute) and func.attr == "shard_map"
                and isinstance(func.value, ast.Attribute)
                and func.value.attr == "shard_map"):
            self._emit(node, "JX103", RULES["JX103"])
        # getattr(jax, "shard_map")
        if (isinstance(func, ast.Name) and func.id == "getattr"
                and len(node.args) >= 2
                and isinstance(node.args[1], ast.Constant)
                and node.args[1].value == "shard_map"):
            self._emit(node, "JX103", RULES["JX103"])
        # JX303: Thread spawned without declaring its lifecycle
        if ((isinstance(func, ast.Attribute) and func.attr == "Thread"
                and isinstance(func.value, ast.Name)
                and func.value.id == "threading")
                or (isinstance(func, ast.Name) and func.id == "Thread")):
            if not any(kw.arg == "daemon" for kw in node.keywords):
                self._emit(node, "JX303", RULES["JX303"])
        # Param(default=<mutable>)
        if (isinstance(func, ast.Name) and func.id == "Param") or (
                isinstance(func, ast.Attribute) and func.attr == "Param"):
            for kw in node.keywords:
                if kw.arg == "default" and isinstance(
                        kw.value, (ast.List, ast.Dict, ast.Set)):
                    self._emit(node, "JX104",
                               "Param(default=<mutable literal>) is shared "
                               "across every stage instance; use None or a "
                               "tuple")
        callee = _callee_name(func)
        # JX201: collective inside a lax.cond/switch/while_loop callable
        if callee in _COND_CALLS:
            for arg in node.args:
                body = self._resolve_callable(arg)
                if body is None:
                    continue
                for sub in ast.walk(body):
                    if (isinstance(sub, ast.Call) and _callee_name(sub.func)
                            in _COLLECTIVE_CALLS):
                        self._emit(sub, "JX201", RULES["JX201"])
        # JX202: collective with a literal axis name outside the canon
        if callee in _COLLECTIVE_CALLS or callee == "axis_index":
            pos = 0 if callee == "axis_index" else 1
            axis_arg = None
            for kw in node.keywords:
                if kw.arg in ("axis_name", "axes"):
                    axis_arg = kw.value
            if axis_arg is None and len(node.args) > pos:
                axis_arg = node.args[pos]
            for name in _literal_axis_names(axis_arg):
                if name not in _MESH_AXES:
                    self._emit(node, "JX202",
                               f"axis {name!r} is not a canonical mesh "
                               f"axis {_MESH_AXES}; see parallel/mesh.py")
        # JX203/JX204: shard_map contract checks at the shim call site
        if callee == "shard_map":
            self._lint_shard_map_site(node)
        # JX107 (producer face): host image work inside the function fed
        # to a DeviceLoader as its batch source — that function IS the
        # train input path, loop or not
        if callee == "DeviceLoader" and self.uses_device_preprocess \
                and node.args:
            src = node.args[0]
            if isinstance(src, ast.Call):  # DeviceLoader(batches(), ...)
                src = src.func
            body = self._resolve_callable(src)
            if body is not None:
                self._lint_host_image_calls(
                    body, "a DeviceLoader producer")
        self.generic_visit(node)

    # -- JX201/JX203/JX204 helpers --

    def _resolve_callable(self, expr: ast.AST) -> ast.AST | None:
        """A Lambda inline, or a Name bound to a module-local def."""
        if isinstance(expr, ast.Lambda):
            return expr
        if isinstance(expr, ast.Name):
            return self.func_defs.get(expr.id)
        return None

    def _lint_shard_map_site(self, node: ast.Call) -> None:
        kw = {k.arg: k.value for k in node.keywords}
        in_specs = kw.get("in_specs") if "in_specs" in kw else (
            node.args[2] if len(node.args) > 2 else None)
        out_specs = kw.get("out_specs") if "out_specs" in kw else (
            node.args[3] if len(node.args) > 3 else None)
        body = self._resolve_callable(node.args[0]) if node.args else None
        in_axes = _spec_axis_names(in_specs)
        out_axes = _spec_axis_names(out_specs)
        # JX203: in_spec axes that never reach an out_spec need a
        # reducing collective in the body (literal-resolvable sites only;
        # a variable axis arg in the body gets the benefit of the doubt)
        missing = in_axes - out_axes
        if missing and body is not None:
            covered: set[str] = set()
            for sub in ast.walk(body):
                if not (isinstance(sub, ast.Call) and _callee_name(sub.func)
                        in _REDUCING_CALLS):
                    continue
                axis_arg = None
                for k in sub.keywords:
                    if k.arg in ("axis_name", "axes"):
                        axis_arg = k.value
                if axis_arg is None and len(sub.args) > 1:
                    axis_arg = sub.args[1]
                lits = _literal_axis_names(axis_arg)
                if lits:
                    covered |= lits
                elif axis_arg is not None:
                    covered |= missing  # unresolvable axis: assume covers
            for axis in sorted(missing - covered):
                self._emit(node, "JX203",
                           f"axis {axis!r} is sharded by in_specs, absent "
                           "from out_specs, and never reduced in the body "
                           "— the output escapes as an unreduced partial "
                           "sum over it (check_vma=False hides this)")
        # JX204: local-cumsum capacity slots + dispatch, no count exchange
        if body is not None:
            calls = {_callee_name(sub.func) for sub in ast.walk(body)
                     if isinstance(sub, ast.Call)}
            if ("cumsum" in calls
                    and calls & {"all_to_all", "psum_scatter"}
                    and "all_gather" not in calls):
                for sub in ast.walk(body):
                    if (isinstance(sub, ast.Call)
                            and _callee_name(sub.func) == "cumsum"):
                        self._emit(sub, "JX204", RULES["JX204"])
                        break

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if node.module and node.module.startswith("jax.experimental.shard_map"):
            self._emit(node, "JX103", RULES["JX103"])
        self.generic_visit(node)

    # -- JX101: walk jitted function bodies --

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._maybe_lint_jit_body(node)
        self.generic_visit(node)

    def visit_AsyncFunctionDef(self, node: ast.AsyncFunctionDef) -> None:
        self._maybe_lint_jit_body(node)
        self.generic_visit(node)

    def _maybe_lint_jit_body(self, node: ast.AST) -> None:
        name = getattr(node, "name", None)
        if _has_jit_decorator(node) or (name and name in self.jitted_names):
            self._lint_traced_body(node)
        if name in ("device_fn", "device_fn_mesh"):
            # a device-stage body: everything built here (closure
            # constants included) flows into the planner's jitted
            # composite — JX108 guards the f64 spellings
            self._lint_f64_sites(node)

    def lint_lambdas(self) -> None:
        for lam in self.jitted_lambdas:
            self._lint_traced_body(lam)

    def _lint_traced_body(self, fn: ast.AST) -> None:
        """Flag host syncs anywhere inside a traced function (nested defs
        included — they trace too)."""
        self._lint_f64_sites(fn)  # JX108 rides every traced body
        body = fn.body if isinstance(fn.body, list) else [fn.body]
        for stmt in body:
            for node in ast.walk(stmt):
                if not isinstance(node, ast.Call):
                    continue
                func = node.func
                if isinstance(func, ast.Attribute):
                    if func.attr in _HOST_METHODS:
                        self._emit(node, "JX101",
                                   f".{func.attr}() blocks on a device→"
                                   "host sync inside a traced function")
                    elif (func.attr in _HOST_NP_CALLS
                          and isinstance(func.value, ast.Name)
                          and func.value.id in _NUMPY_ALIASES):
                        self._emit(node, "JX101",
                                   f"np.{func.attr} materializes a traced "
                                   "value on host; use jnp inside jitted "
                                   "code")
                elif isinstance(func, ast.Name) \
                        and func.id in _HOST_BUILTINS:
                    if node.args and not isinstance(node.args[0],
                                                    ast.Constant):
                        self._emit(node, "JX101",
                                   f"{func.id}() on a traced value forces "
                                   "a host sync (or a tracer error); keep "
                                   "the computation in jax")


def lint_source_full(source: str, path: str = "<string>",
                     ) -> tuple[list[Finding], list[tuple[Finding, str]]]:
    """(active findings, pragma-suppressed (finding, justification))."""
    tree = ast.parse(source, filename=path)
    linter = _Linter(path, source)
    linter.collect(tree)
    linter.visit(tree)
    linter.lint_lambdas()
    linter.lint_acquire_blocks(tree)
    return linter.findings, linter.suppressed


def lint_source(source: str, path: str = "<string>") -> list[Finding]:
    return lint_source_full(source, path)[0]


def _allowed(path: str, rule: str, allowlist: dict) -> str | None:
    """The allowlist justification suppressing (path, rule), or None.
    Legacy frozenset entries justify as the empty string."""
    norm = path.replace(os.sep, "/")
    for suffix, rules in allowlist.items():
        if norm.endswith(suffix) and rule in rules:
            return rules[rule] if isinstance(rules, dict) else ""
    return None


def lint_paths_full(paths: list[str], allowlist: dict | None = None,
                    ) -> tuple[list[Finding], list[dict]]:
    """(active findings, suppressed entries with pragma status) over
    files/trees — the ``--json`` payload halves."""
    allowlist = DEFAULT_ALLOWLIST if allowlist is None else allowlist
    findings: list[Finding] = []
    suppressed: list[dict] = []
    for root in paths:
        files = []
        if os.path.isdir(root):
            for dirpath, _dirs, names in os.walk(root):
                files.extend(os.path.join(dirpath, n) for n in names
                             if n.endswith(".py"))
        else:
            files.append(root)
        for f in sorted(files):
            with open(f, "r", encoding="utf-8") as fh:
                src = fh.read()
            active, pragmaed = lint_source_full(src, f)
            for x in active:
                why = _allowed(f, x.rule, allowlist)
                if why is None:
                    findings.append(x)
                else:
                    suppressed.append({**x.as_dict(), "pragma": "allowed",
                                       "justification": why})
            suppressed.extend({**x.as_dict(), "pragma": "allowed",
                               "justification": why}
                              for x, why in pragmaed)
    return findings, suppressed


def lint_paths(paths: list[str],
               allowlist: dict | None = None) -> list[Finding]:
    return lint_paths_full(paths, allowlist)[0]


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    json_out = "--json" in argv
    argv = [a for a in argv if a != "--json"]
    bad = [p for p in argv if not os.path.exists(p)]
    if bad:
        print(f"no such path(s): {', '.join(bad)}", file=sys.stderr)
        return 2
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    paths = argv or [os.path.join(repo, "mmlspark_tpu")]
    findings, suppressed = lint_paths_full(paths)
    if json_out:
        print(json.dumps(
            {"findings": [{**f.as_dict(), "pragma": "none"}
                          for f in findings],
             "suppressed": suppressed},
            indent=2, sort_keys=True))
        return 1 if findings else 0
    for f in findings:
        print(f)
    print(f"lint_jax: {len(findings)} finding(s) over {paths} "
          f"({len(suppressed)} suppressed)")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
