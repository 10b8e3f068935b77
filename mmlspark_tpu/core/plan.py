"""Pipeline execution planner — fuse adjacent device stages into one program.

The host pipeline walks stages one at a time, so a device-heavy chain
(image transform → featurize → score) pays a host↔device round-trip
(an upload, a dispatch, a blocking fetch) per stage. The planner
partitions a stage list into
maximal runs of :class:`~mmlspark_tpu.core.stage.DeviceStage`-capable
stages and compiles each run into ONE jitted composite: a single H2D
upload per minibatch (per piece of one whose upload is long:
:func:`piece_rows`), one fused XLA program, and one async-windowed D2H
fetch round (the ``copy_to_host_async``/``max_inflight`` software pipeline
of :func:`pipeline_minibatches`).

Fallback rules (also documented in docs/device_stages.md):

* a stage that is not a ``DeviceStage``, or whose ``device_fn`` declines
  the incoming :class:`~mmlspark_tpu.core.stage.ArrayMeta`, runs on host;
* in a stage list a segment needs ≥ 2 consecutive device-capable stages; a
  lone device stage is handed to its own ``transform``: for a model
  (``JaxModel``) this executor again, on a segment of one
  (:func:`run_entered_segment`); for any other stage the host;
* entry coercion is strict: rows must be non-missing and share one
  shape/dtype, else the whole segment falls back to the host path;
* every column a fused run writes is materialized from the same composite
  program (tuple outputs, fetched in the same async window), so the fused
  table is column-for-column identical to the stage-by-stage result.
"""

from __future__ import annotations

import contextlib
import threading
import weakref
from collections import deque
from typing import Any, Callable, Iterator

import numpy as np

from mmlspark_tpu.core import config
from mmlspark_tpu.core.logging_utils import get_logger, timed
from mmlspark_tpu.core.schema import is_image_column
from mmlspark_tpu.core.stage import ArrayMeta, DeviceOp, DeviceStage
from mmlspark_tpu.data.table import DataTable, copied_nbytes
from mmlspark_tpu.obs import device as _obs_dev
from mmlspark_tpu.obs import runtime as _obs_rt
from mmlspark_tpu.obs.metrics import registry as _obs_registry
from mmlspark_tpu.obs.spans import boundary_span as _obs_boundary
from mmlspark_tpu.obs.spans import span as _obs_span

_log = get_logger(__name__)


# ---- fixed-shape minibatching (moved here from models.jax_model so the
#      bridge, JaxModel, and fused segments share one definition) ----

def minibatches(batch: np.ndarray, size: int
                ) -> Iterator[tuple[np.ndarray, int]]:
    """Yield fixed-shape minibatches; the tail is zero-padded to ``size``.

    Fixed shapes mean XLA compiles one program total — the analog of the
    reference's re-batching iterator (CNTKModel.scala:51-88) designed for
    the compilation model instead of JNI marshalling.
    """
    n = len(batch)
    for start in range(0, n, size):
        chunk = batch[start:start + size]
        valid = len(chunk)
        if valid < size:
            pad = np.zeros((size - valid,) + chunk.shape[1:], chunk.dtype)
            chunk = np.concatenate([chunk, pad])
        yield chunk, valid


# ---- the H2D / D2H crossing points. Every device entry and exit of the
#      minibatch pipeline goes through these two functions, so crossing
#      counts are observable: tools/perf_smoke.py monkeypatches them, and
#      the obs registry counts them — plan.h2d_uploads / plan.h2d_bytes /
#      plan.d2h_bytes always (the boundary tier's counters, with
#      plan.split_minibatches beside them in pipeline_minibatches), and with
#      tracing on plan.d2h_fetches plus one plan.h2d_shapes series per
#      distinct upload shape (the recompile observable) ----

def _upload(chunk: np.ndarray, target: Any) -> Any:
    """ONE host→device transfer of one minibatch, or one piece of it."""
    import jax
    nbytes = int(getattr(chunk, "nbytes", 0))
    reg = _obs_registry()
    reg.counter("plan.h2d_uploads").add()
    reg.counter("plan.h2d_bytes").add(nbytes)
    labels = None
    if _obs_rt._enabled:
        shape = getattr(chunk, "shape", None)
        if shape is not None:
            reg.counter("plan.h2d_shapes",
                        shape=str(tuple(shape))).add()
        labels = {"bytes": nbytes}
    with _obs_boundary("plan/h2d", "plan", labels, nbytes=nbytes):
        return jax.device_put(chunk, target)


def _issue_fetch(outs: tuple) -> None:
    """ONE async device→host fetch round for one minibatch's outputs."""
    if _obs_rt._enabled:
        _obs_registry().counter("plan.d2h_fetches").add()
    for o in outs:
        o.copy_to_host_async()


def train_commit(chunk: np.ndarray, target: Any) -> Any:
    """ONE train-batch H2D commit, through the planner's upload seam.

    The train input pipeline (``train/loop.py`` commit closures, running
    on the ``DeviceLoader`` worker) routes its transfers here so the
    train path's crossings and bytes land in the SAME observable —
    ``count_crossings`` patches and the obs registry counters — as the
    pipeline executor's. The thin-wire preprocessing gate
    (``tools/perf_smoke.py check_train_device_preprocess``) reads its
    ≥4× byte reduction off exactly this seam."""
    return _upload(chunk, target)


class CrossingCounter:
    """Tally of device crossings observed by :func:`count_crossings`."""

    def __init__(self) -> None:
        self.uploads = 0        # H2D transfers (one per minibatch)
        self.fetches = 0        # D2H fetch rounds (one per minibatch)
        self.upload_bytes = 0   # total H2D payload — fusion ships the
        #                         thinnest (entry) form, e.g. uint8 pixels
        #                         instead of f32 features
        self.upload_shapes: set = set()  # distinct batch shapes entering the
        #                         device — for a fixed program each new shape
        #                         is one XLA compile, so this set is the
        #                         recompile observable (serve's bucket gate)


@contextlib.contextmanager
def count_crossings():
    """Count H2D uploads and D2H fetch rounds issued by the minibatch
    pipeline — the observability hook behind tools/perf_smoke.py and the
    bench's crossing metrics. Patches this module's ``_upload`` /
    ``_issue_fetch`` seams, which every segment, a lone model's included,
    crosses. Not thread-safe; use from single-threaded callers."""
    global _upload, _issue_fetch
    counter = CrossingCounter()
    orig_upload, orig_fetch = _upload, _issue_fetch

    def counting_upload(chunk, target):
        counter.uploads += 1
        counter.upload_bytes += int(getattr(chunk, "nbytes", 0))
        shape = getattr(chunk, "shape", None)
        if shape is not None:
            counter.upload_shapes.add(tuple(shape))
        return orig_upload(chunk, target)

    def counting_fetch(outs):
        counter.fetches += 1
        return orig_fetch(outs)

    _upload, _issue_fetch = counting_upload, counting_fetch
    try:
        yield counter
    finally:
        _upload, _issue_fetch = orig_upload, orig_fetch


def _windowed_dispatch(fn: Callable, dev_params: Any, batch: np.ndarray,
                       size: int, target: Any, max_inflight: int,
                       label: str | None = None
                       ) -> tuple[list, list, Callable[[], None]]:
    """The ONE definition of the upload → call → async-fetch → bounded-
    window discipline, shared by batch execution
    (:func:`pipeline_minibatches`) and the serving dispatch entry
    (:func:`dispatch_segment`). Dispatches every minibatch, draining
    device-resident outputs to ``max_inflight`` as it goes; returns
    ``(pieces, shapes, drain_rest)`` where ``pieces`` accumulates one
    ``[trimmed host array per output]`` list per drained chunk (in chunk
    order), ``shapes`` is the observed upload shapes, and ``drain_rest()``
    blocks until the window is empty — callers choose when to pay it.
    ``label`` names the segment for device attribution
    (:mod:`mmlspark_tpu.obs.device`) when that pillar is enabled."""
    window: deque = deque()
    pieces: list[list[np.ndarray]] = []
    shapes: list[tuple] = []
    inflight = max(2, int(max_inflight))

    def drain_one() -> None:
        outs, valid = window.popleft()
        # the host blocks here until the device has produced this
        # minibatch: the span is the wait for the device
        with _obs_boundary("plan/d2h", "plan"):
            host = [np.asarray(o)[:valid] for o in outs]
        _obs_registry().counter("plan.d2h_bytes").add(
            sum(int(h.nbytes) for h in host))
        pieces.append(host)

    for chunk, valid in minibatches(batch, size):
        shapes.append(tuple(chunk.shape))
        # labels built only when tracing: the disabled path allocates
        # nothing beyond the span() call itself (perf_smoke's < 2% gate)
        attrib = _obs_rt._enabled and _obs_dev._enabled
        labels = ({"shape": str(tuple(chunk.shape))}
                  if _obs_rt._enabled else None)
        with _obs_boundary("plan/dispatch", "plan", labels):
            committed = _upload(chunk, target)
            if attrib:
                # device attribution: what the compile tier writes for
                # this thread inside the call is the call's compiles;
                # their time + cost/memory analyses (obs/device.py)
                compiled_before = _obs_dev.thread_compiles()
            outs = fn(dev_params, committed)
            if not isinstance(outs, tuple):
                outs = (outs,)
            _issue_fetch(outs)
        if attrib:
            # outside the dispatch span: cost capture AOT-recompiles the
            # program once per entry shape, and that second compile must
            # not count as dispatch time in host_phase_split()
            _obs_dev.note_dispatch(fn, dev_params, chunk, label,
                                   compiled_before)
        window.append((outs, valid))
        # drain to inflight-1 so at most max_inflight minibatch outputs are
        # ever device-resident (the documented HBM bound)
        while len(window) >= inflight:
            drain_one()

    def drain_rest() -> None:
        while window:
            drain_one()

    return pieces, shapes, drain_rest


def _assemble_outputs(pieces: list) -> list[np.ndarray]:
    """Per-chunk ``pieces`` → one concatenated host array per output."""
    if not pieces:
        return []
    return [np.concatenate([p[k] for p in pieces])
            if len(pieces) > 1 else pieces[0][k]
            for k in range(len(pieces[0]))]


def pipeline_minibatches(fn: Callable, dev_params: Any, batch: np.ndarray,
                         size: int, target: Any, max_inflight: int,
                         label: str | None = None) -> list[np.ndarray]:
    """Run ``fn(dev_params, minibatch)`` over ``batch`` with the three-stage
    software pipeline: upload of batch i+1 and device→host copy of batch
    i-1 both overlap compute of batch i (async dispatch +
    ``copy_to_host_async``), so wall clock ≈ max(H2D, compute, D2H), not
    their sum. The deque caps device-resident outputs at ``max_inflight``
    minibatches, bounding HBM on very large tables.

    ``fn`` may return one array or a tuple (a fused segment materializes
    every column its stages write). Returns one trimmed, concatenated host
    array per output.

    A minibatch whose upload is long crosses in row pieces
    (:func:`piece_rows`): the window is walked a piece at a time, each its
    own upload → call → fetch round through the same seams, so the device
    works on the first piece while the rest is on the link instead of
    idling until a whole minibatch has landed. ``size`` stays the memory
    bound it is: no piece is larger, and ``max_inflight`` still counts
    minibatches' worth of outputs. Batch execution only: the serving entry
    (:func:`dispatch_segment`) compiles no shape but its bucket ladder's.
    """
    piece = piece_rows(size, batch[:1].nbytes, _target_dp(target))
    if piece < size:
        _obs_registry().counter("plan.split_minibatches").add(
            -(-len(batch) // size))
    pieces, _shapes, drain_rest = _windowed_dispatch(
        fn, dev_params, batch, piece, target,
        max_inflight * (size // piece), label=label)
    drain_rest()
    with _obs_boundary("transform/assemble", "plan"):
        return _assemble_outputs(pieces)


# ---- segment entry: host column → one stacked device-ready array ----

def stack_image_column(col: np.ndarray
                       ) -> tuple[np.ndarray, list[str]] | None:
    """Stack an image-struct column into one ``[N,H,W,C]`` uint8 batch via a
    single bulk copy; returns ``(batch, paths)`` or None when rows are
    missing, ragged, or not uint8 (host fallback)."""
    datas, paths = [], []
    for v in col:
        if not isinstance(v, dict):
            return None
        d = np.asarray(v["data"])
        if d.ndim == 2:
            d = d[:, :, None]
        datas.append(d)
        paths.append(v.get("path", ""))
    if not datas:
        return None
    shape, dtype = datas[0].shape, datas[0].dtype
    if dtype != np.uint8 or any(
            d.shape != shape or d.dtype != dtype for d in datas):
        return None
    return np.stack(datas), paths


def _entry_meta(table: DataTable, col: str) -> ArrayMeta | None:
    """Cheap first-row probe used at planning time; the full (validated)
    coercion happens in :func:`_coerce_entry` at execution time."""
    if col not in table or len(table) == 0:
        return None
    if is_image_column(table, col):
        v = table[col][0]
        if not isinstance(v, dict):
            return None
        d = np.asarray(v["data"])
        if d.dtype != np.uint8:
            return None
        shape = d.shape if d.ndim == 3 else d.shape + (1,)
        return ArrayMeta(tuple(shape), "uint8", is_image=True)
    arr = table[col]
    if arr.dtype == object:
        first = arr[0]
        if first is None:
            return None
        f = np.asarray(first)
        if not np.issubdtype(f.dtype, np.number):
            return None
        dt = "uint8" if f.dtype == np.uint8 else "float32"
        return ArrayMeta((int(f.size),), dt)
    if not np.issubdtype(arr.dtype, np.number):
        return None
    return ArrayMeta((1,), "float32")


def _coerce_entry(table: DataTable, col: str, meta: ArrayMeta
                  ) -> tuple[np.ndarray, dict] | None:
    """Materialize the segment's entry column as one contiguous array
    matching ``meta``; None on any mismatch (segment falls back to host)."""
    if meta.is_image:
        stacked = stack_image_column(table[col])
        if stacked is None:
            return None
        batch, paths = stacked
        if batch.shape[1:] != tuple(meta.shape):
            return None
        return batch, {"paths": paths}
    try:
        batch = table.column_matrix(col, dtype=np.dtype(meta.dtype))
    except (TypeError, ValueError):
        return None
    if batch.shape[1:] != tuple(meta.shape):
        return None
    return batch, {}


# ---- planning: greedy maximal runs of device-capable stages ----

# device_fn results memoized per stage (a WeakKeyDictionary so nothing
# lands in stage __dict__s, keeping pickling untouched): planning runs on
# every transform call, and a model stage's device_fn traces the forward
# with jax.eval_shape — per-chunk streaming must not re-trace when the
# stage config and incoming meta are unchanged
_DEVICE_FN_MEMO: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _stage_device_fn(s: DeviceStage, meta: ArrayMeta,
                     mesh: Any = None) -> DeviceOp | None:
    """The stage's device op for ``meta``, memoized.

    A stage whose computation depends on the concrete mesh (e.g. a
    pipeline-parallel stage wrapping
    :func:`~mmlspark_tpu.parallel.pipeline.pipeline_apply` — its
    collectives name mesh axes over specific devices) implements the
    optional ``device_fn_mesh(meta, mesh)`` hook; the planner calls it
    with the segment's resolved mesh at compile/verify time and falls
    back to the plain ``device_fn`` during mesh-less planning probes
    (shape inference only — the op's metas must match either way)."""
    fn_mesh = getattr(s, "device_fn_mesh", None)
    key = (s.device_cache_token(), meta,
           None if mesh is None or fn_mesh is None else _mesh_key(mesh))
    hit = _DEVICE_FN_MEMO.get(s)
    if hit is not None and hit[0] == key:
        return hit[1]
    if fn_mesh is not None and mesh is not None:
        op = fn_mesh(meta, mesh)
    else:
        op = s.device_fn(meta)
    _DEVICE_FN_MEMO[s] = (key, op)
    return op

class _Segment:
    """A maximal run of device-capable stages rooted at ``stages[start]``."""

    def __init__(self, start: int, stages: list, entry_col: str,
                 entry_meta: ArrayMeta, metas_in: list[ArrayMeta],
                 out_cols: list[str], emitters: dict[str, int],
                 out_metas: dict[str, ArrayMeta], mesh: Any = None,
                 shard_params: Callable | None = None,
                 precision: Any = None):
        self.start = start
        self.stages = stages
        self.entry_col = entry_col
        self.entry_meta = entry_meta
        self.metas_in = metas_in          # per-stage input meta
        self.out_cols = out_cols          # first-write order
        self.emitters = emitters          # out col → index of last writer
        self.out_metas = out_metas        # out col → final meta
        self.mesh = mesh                  # explicit mesh override (sharded
        #                                   serving: a replica's sub-mesh)
        self.shard_params = shard_params  # (mesh, params_tuple) → shardings
        self.precision = precision        # PrecisionPolicy | None (serve
        #                                   low-precision pass; None = f32)

    @property
    def end(self) -> int:
        return self.start + len(self.stages)


def collect_segment(stages: list, i: int,
                    meta_of: Callable[[str], ArrayMeta | None],
                    explain: list | None = None,
                    min_stages: int = 2, mesh: Any = None,
                    shard_params: Callable | None = None,
                    precision: Any = None) -> _Segment | None:
    """Root a maximal device segment at ``stages[i]``, resolving the entry
    column's layout through ``meta_of`` (a concrete-table probe at execution
    time; an abstract :class:`~mmlspark_tpu.analysis.info.TableSchema`
    lookup when the pre-flight analyzer replays this exact logic with no
    data). ``explain``, when given, collects human-readable reasons the
    segment broke or never formed — the device-plan audit's trace.

    ``min_stages`` defaults to 2 (in batch execution a lone device stage
    is handed to its own ``transform``); ``JaxModel.transform`` and the
    serving entry (:func:`dispatch_segment` via :func:`transform_async`)
    pass 1: a lone model is a segment of one through the same executor.

    ``mesh`` overrides the segment's inference mesh — the sharded-serving
    entry passes a replica's sub-mesh (DP-replica fan-out) or a
    model-parallel tp/pp mesh here instead of the stage-declared/default
    layout. ``shard_params`` optionally overrides param placement:
    ``(mesh, params_tuple) → shardings pytree`` (default: the generic
    :func:`mmlspark_tpu.parallel.mesh.param_shardings` rules plus any
    per-stage ``device_param_rules``). ``precision`` pins the segment's
    :class:`~mmlspark_tpu.core.precision.PrecisionPolicy` (bf16
    activations / int8 weight-only — the serve low-precision pass,
    applied by :func:`segment_composite`); None keeps the f32 plan."""

    def note(msg: str) -> None:
        if explain is not None:
            explain.append(msg)

    s0 = stages[i]
    if not isinstance(s0, DeviceStage):
        note(f"stage {i} ({type(s0).__name__}) is not a DeviceStage")
        return None
    entry_col = s0.device_input_col()
    if entry_col is None:
        note(f"stage {i} ({type(s0).__name__}) declines device execution "
             "for its current configuration (no device input column)")
        return None
    entry_meta = meta_of(entry_col)
    if entry_meta is None:
        note(f"stage {i} ({type(s0).__name__}): entry column "
             f"{entry_col!r} has no device-coercible layout "
             "(missing, ragged, non-numeric, or unknown shape)")
        return None
    env: dict[str, ArrayMeta] = {entry_col: entry_meta}
    seg_stages: list = []
    metas_in: list[ArrayMeta] = []
    out_cols: list[str] = []
    emitters: dict[str, int] = {}
    out_metas: dict[str, ArrayMeta] = {}
    j = i
    while j < len(stages):
        s = stages[j]
        if not isinstance(s, DeviceStage):
            note(f"segment breaks at stage {j}: {type(s).__name__} "
                 "is not a DeviceStage")
            break
        in_col = s.device_input_col()
        out_col = s.device_output_col()
        if in_col is None or out_col is None:
            note(f"segment breaks at stage {j}: {type(s).__name__} "
                 "declines device execution (no device input/output column)")
            break
        if in_col not in env:
            note(f"segment breaks at stage {j}: input column {in_col!r} "
                 "is not device-resident (host-produced columns are never "
                 "re-uploaded mid-run)")
            break
        op = _stage_device_fn(s, env[in_col])
        if op is None:
            note(f"segment breaks at stage {j}: "
                 f"{type(s).__name__}.device_fn declined the incoming "
                 f"layout {env[in_col]}")
            break
        metas_in.append(env[in_col])
        seg_stages.append(s)
        env[out_col] = op.out_meta
        if out_col not in emitters:
            out_cols.append(out_col)
        emitters[out_col] = j - i
        out_metas[out_col] = op.out_meta
        j += 1
    if len(seg_stages) < max(1, int(min_stages)):
        if len(seg_stages) == 1:
            note(f"stage {i} ({type(s0).__name__}) is a lone device stage "
                 "(a segment needs >= 2): it is handed to its own transform")
        return None
    return _Segment(i, seg_stages, entry_col, entry_meta, metas_in,
                    out_cols, emitters, out_metas, mesh=mesh,
                    shard_params=shard_params, precision=precision)


def describe_plan(stages: list, table: DataTable) -> list[tuple[str, list]]:
    """The segment structure the executor would use on ``table``:
    ``[("device"|"host", [stage, ...]), ...]``. Purely for introspection
    (tests, bench reporting) — segments whose entry depends on a not-yet-run
    host stage show as host here but may still fuse at execution time."""
    out: list[tuple[str, list]] = []
    i = 0
    while i < len(stages):
        seg = collect_segment(stages, i,
                              lambda col: _entry_meta(table, col))
        if seg is None:
            out.append(("host", [stages[i]]))
            i += 1
        else:
            out.append(("device", list(seg.stages)))
            i = seg.end
    return out


# ---- compilation + execution ----

def _segment_mesh(seg: _Segment):
    """The fused run's inference mesh: an explicit per-segment override
    (sharded serving pins each replica's sub-mesh here) wins, then the
    first explicit ``mesh_spec`` among the segment's stages, else DP over
    every local device (multi-host scoring = each host runs its own
    partition stream, the Spark-executor analog — so local devices, not
    the global mesh)."""
    import jax

    from mmlspark_tpu.parallel import mesh as mesh_lib

    if seg.mesh is not None:
        return seg.mesh
    spec = next((s.mesh_spec for s in seg.stages
                 if getattr(s, "mesh_spec", None)), None)
    return mesh_lib.make_mesh(spec or mesh_lib.MeshSpec(dp=-1),
                              jax.local_devices())


def _mesh_key(mesh: Any) -> tuple:
    """Hashable identity of a mesh for the compiled-segment cache: axis
    layout plus the concrete device assignment (two replicas' sub-meshes
    must never share one compiled entry — each owns its own device-
    resident params)."""
    return (tuple(sorted(mesh.shape.items())),
            tuple(getattr(d, "id", i)
                  for i, d in enumerate(mesh.devices.flat)))


def _segment_param_shardings(seg: _Segment, mesh, params_tuple):
    """Param placement for a fused run on ``mesh``: the segment's explicit
    ``shard_params`` override wins; otherwise the generic
    :func:`~mmlspark_tpu.parallel.mesh.param_shardings` rules (tp
    column-sharding, fsdp zero-sharding, replicate elsewhere — a pure-dp
    mesh replicates everything, the pre-sharded-serving behavior) with any
    per-stage ``device_param_rules(path, leaf)`` hook consulted first.
    ``params_tuple`` has one entry per segment stage, so rule paths are
    ``<stage-idx>/<leaf path>``."""
    from mmlspark_tpu.parallel import mesh as mesh_lib

    if seg.shard_params is not None:
        return seg.shard_params(mesh, params_tuple)
    stage_rules = [getattr(s, "device_param_rules", None)
                   for s in seg.stages]
    if not any(stage_rules):
        return mesh_lib.param_shardings(mesh, params_tuple)

    def rules(path: str, leaf):
        head, _, rest = path.partition("/")
        # tuple indices render as "[k]" (SequenceKey), dict keys as "k"
        fn = stage_rules[int(head.strip("[]"))]
        return fn(rest, leaf) if fn is not None else None

    return mesh_lib.param_shardings(mesh, params_tuple, rules)


def _compile_segment(seg: _Segment) -> tuple:
    """(jitted composite, device params, transfer target, dp extent). The
    composite threads the entry array through every stage op and returns a
    tuple with one array per materialized column, so fusion never changes
    which columns exist — only how many device crossings they cost.
    Params upload once (replicated over the mesh) and live
    device-resident; minibatches commit batch-sharded over the data axes
    (a single-device mesh takes plain placement and a plain jit instead
    of a one-shard NamedSharding — a second code path whose worth on the
    chip is unmeasured, ROADMAP Design 3)."""
    if _obs_rt._enabled:
        names = "→".join(type(s).__name__ for s in seg.stages)
        _obs_registry().counter("plan.segment_compiles").add()
        with _obs_span("plan/compile_segment", "plan", {"stages": names}):
            return _compile_segment_inner(seg)
    return _compile_segment_inner(seg)


def segment_composite(seg: "_Segment", mesh: Any) -> tuple:
    """(composite fn, params tuple) for a fused segment on ``mesh`` —
    the ONE builder of the function this module jits. The SPMD audit
    (``analysis.spmd.plan_segment_composite``) traces the same object,
    so the verified program can never drift from the dispatched one —
    including the low-precision pass: when ``seg.precision`` is an
    active :class:`~mmlspark_tpu.core.precision.PrecisionPolicy`, the
    returned params tuple is the quantized STORAGE form (int8 weights /
    bf16 leaves — what uploads), and the composite dequantizes inside
    the trace, casts float activations to bf16 at every stage boundary,
    and restores each output column to its declared ``ArrayMeta`` dtype
    so ``device_emit`` sees the layout the f32 plan declared."""
    ops: list[DeviceOp] = []
    for s, meta_in in zip(seg.stages, seg.metas_in):
        op = _stage_device_fn(s, meta_in, mesh)
        if op is None:  # config changed between planning and compile
            raise RuntimeError(
                f"{type(s).__name__}.device_fn declined at compile time")
        ops.append(op)

    in_cols = [s.device_input_col() for s in seg.stages]
    out_cols_per_stage = [s.device_output_col() for s in seg.stages]
    # closed over in place of ``seg``, which holds the stages: a lone model
    # is its own cache host and must not be kept alive by its own store
    entry_col, out_cols, out_metas = seg.entry_col, seg.out_cols, seg.out_metas
    policy = seg.precision
    if policy is not None and not policy.active:
        policy = None

    if policy is None:
        def composite(all_params: tuple, x: Any) -> tuple:
            vals = {entry_col: x}
            for k, op in enumerate(ops):
                vals[out_cols_per_stage[k]] = op.fn(all_params[k],
                                                    vals[in_cols[k]])
            return tuple(vals[c] for c in out_cols)

        return composite, tuple(op.params for op in ops)

    from mmlspark_tpu.core import precision as prec

    stored = tuple(prec.quantize_params(op.params, policy) for op in ops)

    def composite(all_params: tuple, x: Any) -> tuple:
        vals = {entry_col: prec.cast_activation(x, policy)}
        for k, op in enumerate(ops):
            p = prec.materialize(all_params[k], policy)
            vals[out_cols_per_stage[k]] = prec.cast_activation(
                op.fn(p, vals[in_cols[k]]), policy)
        return tuple(prec.cast_output(vals[c], out_metas[c].dtype)
                     for c in out_cols)

    return composite, stored


def _maybe_cache_jit(jitted: Any, seg: "_Segment", mesh: Any) -> Any:
    """Wrap a segment's jitted composite in the persistent AOT compile
    cache (core/compile_cache.py) when a cache is installed and every
    stage in the segment fingerprints stably. Programs then load from
    disk per concrete dispatch shape instead of re-compiling; an
    unfingerprintable segment (or no cache) compiles exactly as
    before."""
    from mmlspark_tpu.core import compile_cache as _cc
    cache = _cc.active()
    if cache is None:
        return jitted
    fp = _cc.plan_fingerprint(seg.stages, seg.entry_meta, mesh=mesh,
                              precision=seg.precision)
    if fp is None:
        return jitted
    return _cc.CachedJit(jitted, fp, cache, mesh.devices.flat)


def _compile_segment_inner(seg: "_Segment") -> tuple:
    import jax

    from mmlspark_tpu.parallel import mesh as mesh_lib

    mesh = _segment_mesh(seg)
    composite, params_tuple = segment_composite(seg, mesh)
    if mesh.devices.size == 1:
        target = mesh.devices.reshape(-1)[0]
        dev_params = jax.device_put(params_tuple, target)
        fn = _maybe_cache_jit(jax.jit(composite), seg, mesh)
        return fn, dev_params, target, 1

    data = mesh_lib.batch_sharding(mesh)
    # params place by the sharding rules (replicated on a pure-dp mesh —
    # the historical behavior; tp/pp/fsdp serve meshes shard them)
    param_shards = _segment_param_shardings(seg, mesh, params_tuple)
    dev_params = jax.device_put(params_tuple, param_shards)
    fn = jax.jit(composite, in_shardings=(param_shards, data),
                 out_shardings=data)
    fn = _maybe_cache_jit(fn, seg, mesh)
    return fn, dev_params, data, mesh_dp(mesh)


def _segment_minibatch(seg: _Segment) -> tuple[int, int]:
    """(minibatch size, max_inflight) for a fused run: the smallest explicit
    stage setting wins (it is a memory bound), else the config default."""
    sizes = [int(s.minibatch_size) for s in seg.stages
             if getattr(s, "minibatch_size", None)]
    size = min(sizes) if sizes else int(config.get("default_minibatch_size"))
    inflights = [int(s.max_inflight) for s in seg.stages
                 if getattr(s, "max_inflight", None)]
    return size, (min(inflights) if inflights else 8)


def mesh_dp(mesh: Any) -> int:
    """The data extent minibatches must divide over: 1 on a single-device
    mesh (the plain-placement fast path), else the dp×fsdp product. The
    ONE definition shared by the executor and the pre-flight predictors."""
    if mesh.devices.size == 1:
        return 1
    return mesh.shape["dp"] * mesh.shape["fsdp"]


def dp_rounded_minibatch(size: int, dp: int, n_rows: int) -> int:
    """The executor's minibatch sizing: cap at the row count, then round UP
    to a dp multiple (padding covers the excess) so every chip gets rows.
    Shared with the pre-flight crossing predictors so predictions cannot
    drift from execution."""
    return -(-min(int(size), n_rows) // dp) * dp


def _target_dp(target: Any) -> int:
    """:func:`mesh_dp` of a transfer target of :func:`_compile_segment`: a
    batch sharding over the segment's mesh, or one device."""
    mesh = getattr(target, "mesh", None)
    return 1 if mesh is None else mesh_dp(mesh)


def piece_rows(size: int, row_nbytes: int, dp: int) -> int:
    """Rows the device is handed at once of a ``size``-row minibatch of
    ``row_nbytes`` a row: ``size`` itself while the minibatch is no more
    than ``_PIECE_MAX_BYTES`` (it crosses whole), else halved until a piece
    is, as long as the half is still a dp multiple. A power-of-two fraction
    that tiles the minibatch: a call compiles one entry shape, the padded
    tail rule of :func:`minibatches` is untouched, and every chip gets
    rows. Shared with the pre-flight crossing predictor like
    :func:`dp_rounded_minibatch`, so prediction cannot drift from
    execution."""
    rows = int(size)
    while rows * row_nbytes > _PIECE_MAX_BYTES and rows % (2 * dp) == 0:
        rows //= 2
    return rows


def segment_entry_rows(seg: _Segment, n_rows: int) -> int:
    """Rows of the ONE entry shape a fused run of ``seg`` over ``n_rows``
    rows uploads and compiles for: the dp-rounded minibatch, or the piece
    of it where its bytes have it cross in pieces (:func:`piece_rows`)."""
    size, _ = _segment_minibatch(seg)
    dp = mesh_dp(_segment_mesh(seg))
    meta = seg.entry_meta
    row_nbytes = int(np.prod(meta.shape, dtype=np.int64)
                     ) * np.dtype(meta.dtype).itemsize
    return piece_rows(dp_rounded_minibatch(size, dp, n_rows), row_nbytes, dp)


def predict_segment_minibatches(seg: _Segment, n_rows: int) -> int:
    """How many uploads a fused run of ``seg`` over ``n_rows`` rows costs:
    one H2D upload and one async D2H fetch round a fixed-shape minibatch,
    or a piece of one (:func:`segment_entry_rows`). Same sizing arithmetic
    as :func:`_run_segment` via the shared helpers, without compiling or
    transferring anything. Note: reading the segment's mesh initializes
    the jax backend (device *enumeration*, not execution) — pre-flight
    callers on shared hosts should pin ``JAX_PLATFORMS=cpu``."""
    if n_rows <= 0:
        return 0
    return -(-n_rows // segment_entry_rows(seg, n_rows))


# compiled segments kept per cache_host; LRU-capped so streaming sources
# with many distinct entry shapes cannot pin an unbounded number of
# device-resident param copies (each evicted entry releases its device
# tree)
_PLAN_CACHE_MAX = 8

# a minibatch of more bytes than this crosses to the device in pieces of at
# most this many (piece_rows). Measured on a v5e with ResNet-50 on 150,528-
# byte rows (PERF.md section 6, PR 34): an upload of 18 MiB lands in 4 ms
# and one of 294 MiB in 54 ms, through which the device idles at the head
# of every call; and the composite's device time a row is least on pieces
# this size (73 us at 128 rows, 84 at 256, 80 at 64, 93 at 2,048)
_PIECE_MAX_BYTES = 32 << 20


def _reuploaded(seg: _Segment, entry: tuple, tokens: tuple) -> tuple | None:
    """The entry of a segment whose tokens moved, when only the parameter
    objects its stages hold did (a checkpoint scored every N steps): the
    composite takes the parameters as an argument, so the program stays and
    the new trees go onto the old leaves' shardings; the old device tree is
    dropped with the entry this replaces. ``None`` (recompile) when a program
    token moved too, or the trees differ in structure, shapes or dtypes."""
    import jax

    _tokens, (fn, dev_params, target, dp), pinned = entry
    if pinned[2] != tuple(s.device_program_token() for s in seg.stages):
        return None
    _composite, params = segment_composite(seg, _segment_mesh(seg))
    new, treedef = jax.tree_util.tree_flatten(params)
    old = jax.tree_util.tree_leaves(dev_params)
    if treedef != jax.tree_util.tree_structure(dev_params) or [
            (a.shape, a.dtype) for a in map(jax.typeof, new)] != [
            (o.shape, o.dtype) for o in old]:
        return None
    dev_params = jax.device_put(params, jax.tree_util.tree_unflatten(
        treedef, [o.sharding for o in old]))
    return tokens, (fn, dev_params, target, dp), pinned


def _cached_segment(seg: _Segment, cache_host: Any) -> tuple:
    """(jitted composite, device params, target, dp) for ``seg``, through
    ``cache_host``'s LRU-capped compiled-segment cache when one is given.
    Shared by batch execution (:func:`run_entered_segment`) and the serving
    dispatch entry (:func:`dispatch_segment`), so an online server and
    offline ``transform`` calls on the same model reuse ONE jitted composite
    and one device-resident param upload; the lock keeps concurrent first
    calls (the bridge's 2-worker overlap) from doing either twice."""
    if cache_host is None:
        return _compile_segment(seg)
    key = (tuple(id(s) for s in seg.stages), seg.entry_col, seg.entry_meta,
           None if seg.mesh is None else _mesh_key(seg.mesh),
           None if seg.shard_params is None else id(seg.shard_params),
           # precision is program identity: an f32 and an int8w serving
           # of one model never share a compiled entry or device params
           None if seg.precision is None or not seg.precision.active
           else seg.precision.cache_token)
    lock = cache_host.__dict__.setdefault("_plan_lock", threading.Lock())
    with lock:
        store = cache_host.__dict__.setdefault("_plan_cache", {})
        # popped and put back: LRU order = insertion order
        entry = store.pop(key, None)
        tokens = tuple(s.device_cache_token() for s in seg.stages)
        if entry is not None and entry[0] != tokens:
            # a stage changed: its parameters alone, or its program
            entry = _reuploaded(seg, entry, tokens)
        if entry is None:
            # pin the stage objects (and the shard_params override) so
            # their id()-based key components cannot be reused; the host
            # itself outlives its store, and must not be held by it
            entry = (tokens, _compile_segment(seg),
                     (tuple(s for s in seg.stages if s is not cache_host),
                      seg.shard_params,
                      tuple(s.device_program_token() for s in seg.stages)))
        store[key] = entry
        while len(store) > _PLAN_CACHE_MAX:
            store.pop(next(iter(store)))
    return entry[1]


def run_entered_segment(table: DataTable, enter: Callable[[], tuple | None],
                        cache_host: Any, dispatch: Callable
                        ) -> DataTable | None:
    """One batch ``transform`` call through a device segment: the ONE place
    that opens the call's boundary spans (the ``transform`` root with its
    ``rows`` / ``minibatches``, ``transform/coerce`` with the rows and bytes
    it copied, ``transform/assemble`` around the emit) and counts
    ``transform.rows``. ``enter()`` coerces the entry column and answers
    ``(segment, batch, ctx)``, or ``None`` to decline (the caller's host
    path scores the rows): a fused run's declining :func:`_coerce_entry`, or
    ``JaxModel.transform``'s raising ``coerce_input_matrix`` with the segment
    of one built on its result. ``dispatch`` is the caller's binding of
    :func:`pipeline_minibatches` (a seam fault-injecting tests replace)."""
    with _obs_boundary("transform", "plan", rows=len(table)) as root:
        with _obs_boundary("transform/coerce", "plan") as coerce:
            entered = enter()
            if entered is not None:  # rows coerced, bytes copied for them
                coerce.rows = len(table)
                coerce.nbytes = copied_nbytes(entered[1])
        if entered is None:
            root.rows = 0  # declined: the host path scores these rows
            return None
        seg, batch, ctx = entered
        size, max_inflight = _segment_minibatch(seg)
        fn, dev_params, target, dp = _cached_segment(seg, cache_host)

        # minibatch must divide over the data axes (shared sizing helper)
        size = dp_rounded_minibatch(size, dp, len(batch))
        root.minibatches = -(-len(batch) // size)

        names = "→".join(type(s).__name__ for s in seg.stages)
        with timed(f"FusedSegment[{names}]", _log, len(table)):
            outs = dispatch(fn, dev_params, batch, size, target,
                            max_inflight, label=names)
        with _obs_boundary("transform/assemble", "plan"):
            for col, values in zip(seg.out_cols, outs):
                emitter = seg.stages[seg.emitters[col]]
                table = emitter.device_emit(table, values,
                                            seg.out_metas[col], ctx)
    _obs_registry().counter("transform.rows").add(len(table))
    return table


def _run_segment(seg: _Segment, table: DataTable,
                 cache_host: Any) -> DataTable | None:
    """Execute a fused segment; None if entry coercion fails (host path)."""
    def enter() -> tuple | None:
        coerced = _coerce_entry(table, seg.entry_col, seg.entry_meta)
        return None if coerced is None else (seg, *coerced)

    return run_entered_segment(table, enter, cache_host,
                               pipeline_minibatches)


# ---- single-batch serving entry (the online model server's dispatch) ----

class PendingTable:
    """Handle for an asynchronously dispatched transform.

    ``result()`` blocks on the device→host fetch, emits the output columns,
    and returns the finished :class:`DataTable`; it is idempotent. A
    PendingTable built from an already-materialized table (the host
    fallback) returns immediately. ``shapes`` holds the batch shapes
    actually uploaded to the device (empty for the host path) — the
    *observed* recompile surface serving stats report, as opposed to the
    caller's intended bucket. Single-consumer: the serve batcher's
    in-flight window owns each handle."""

    __slots__ = ("_table", "_finish", "shapes")

    def __init__(self, table: DataTable | None = None,
                 finish: Callable[[], DataTable] | None = None,
                 shapes: tuple = ()):
        self._table = table
        self._finish = finish
        self.shapes = tuple(shapes)

    @property
    def dispatched(self) -> bool:
        """True while device work is still outstanding."""
        return self._finish is not None

    def result(self) -> DataTable:
        if self._finish is not None:
            self._table = self._finish()
            self._finish = None
        return self._table


def dispatch_segment(seg: _Segment, table: DataTable,
                     cache_host: Any
                     ) -> tuple[Callable[[], DataTable], tuple] | None:
    """Asynchronously dispatch ``seg`` over one packed (bucket-quantized)
    batch; returns ``(finish, observed upload shapes)``.

    The single-batch segment entry behind the online server. A batch at or
    below the stages' minibatch bound — the common case, since bucket
    ladders are sized to fit — is ONE minibatch: one H2D upload, one
    program call, one async D2H fetch round, and the call returns as soon
    as the device work is *issued* (JAX async dispatch +
    ``copy_to_host_async``), so the serve batcher can pack batch i+1 while
    the device computes batch i. A batch larger than the stages' declared
    ``minibatch_size`` (a memory bound — see :func:`_segment_minibatch`)
    is chunked at that bound with the usual ``max_inflight`` window, so
    serving can never exceed the HBM envelope batch execution honors.
    Because chunk sizes derive only from (bucket, bound, dp), compiled
    shapes stay bounded by the bucket ladder. Returns a ``finish()`` that
    blocks, trims the padding, and emits the output columns; ``None`` when
    entry coercion declines (host path)."""
    coerced = _coerce_entry(table, seg.entry_col, seg.entry_meta)
    if coerced is None:
        return None
    batch, ctx = coerced
    fn, dev_params, target, dp = _cached_segment(seg, cache_host)
    bound, max_inflight = _segment_minibatch(seg)
    size = dp_rounded_minibatch(min(bound, len(batch)), dp, len(batch))
    labels = {"rows": len(batch)} if _obs_rt._enabled else None
    seg_label = ("→".join(type(s).__name__ for s in seg.stages)
                 if _obs_rt._enabled else None)
    with _obs_span("plan/serve_dispatch", "plan", labels):
        pieces, shapes, drain_rest = _windowed_dispatch(
            fn, dev_params, batch, size, target, max_inflight,
            label=seg_label)

    def finish() -> DataTable:
        drain_rest()
        host = _assemble_outputs(pieces)
        out = table
        for k, col in enumerate(seg.out_cols):
            emitter = seg.stages[seg.emitters[col]]
            out = emitter.device_emit(out, host[k], seg.out_metas[col],
                                      ctx)
        return out

    return finish, tuple(shapes)


def transform_async(stages: list, table: DataTable,
                    cache_host: Any = None, mesh: Any = None,
                    shard_params: Callable | None = None,
                    precision: Any = None) -> PendingTable:
    """Run a fitted-transformer list over one packed batch, dispatching the
    *trailing* device segment asynchronously (the serving execution engine).

    Semantics match :func:`execute_stages` exactly — same planning, same
    fallback rules, same compiled-segment cache — except that when the
    stage list *ends* in a device-capable segment (of any length ≥ 1,
    including a lone model stage), that segment is dispatched via
    :func:`dispatch_segment` and the returned :class:`PendingTable` is
    still in flight: host packing of the next batch overlaps this batch's
    device compute, and ``result()`` performs the blocking fetch.

    ``mesh``/``shard_params`` pin the device segments to an explicit mesh
    and param placement (see :func:`collect_segment`) — the sharded
    serving entry: a DP replica's sub-mesh, or a tp/pp model-parallel
    layout for a model too big for one chip. ``precision`` pins every
    device segment's low-precision policy (bf16 activations / int8
    weight-only — :mod:`mmlspark_tpu.core.precision`); the offline
    ``execute_stages`` path never passes one, so batch transforms stay
    f32."""
    stages = list(stages)
    i = 0
    while i < len(stages):
        seg = None
        if len(table):
            seg = collect_segment(stages, i,
                                  lambda col: _entry_meta(table, col),
                                  min_stages=1, mesh=mesh,
                                  shard_params=shard_params,
                                  precision=precision)
        if seg is not None:
            if seg.end == len(stages):
                dispatched = dispatch_segment(seg, table, cache_host)
                if dispatched is not None:
                    finish, shapes = dispatched
                    return PendingTable(finish=finish, shapes=shapes)
            elif len(seg.stages) >= 2:
                fused = _run_segment(seg, table, cache_host)
                if fused is not None:
                    table = fused
                    i = seg.end
                    continue
        table = stages[i].transform(table)
        i += 1
    return PendingTable(table=table)


def execute_stages(stages: list, table: DataTable,
                   cache_host: Any = None) -> DataTable:
    """Run a fitted-transformer list over ``table``, fusing maximal runs of
    device-capable stages (the :class:`PipelineModel` execution engine).

    ``cache_host`` (typically the owning PipelineModel) carries the
    compiled-segment cache across calls, so streaming callers (the Arrow
    bridge, ``transform_stream``) pay compile + param upload once.
    """
    i = 0
    while i < len(stages):
        seg = None
        if len(table):
            seg = collect_segment(stages, i,
                                  lambda col: _entry_meta(table, col))
        if seg is not None:
            fused = _run_segment(seg, table, cache_host)
            if fused is not None:
                table = fused
                i = seg.end
                continue
            _log.info("fused segment at stage %d fell back to host "
                      "(entry coercion failed)", i)
        table = stages[i].transform(table)
        i += 1
    return table


# ---- stateful segments (device-resident state across dispatches) ----
#
# Everything above treats a compiled segment as a pure function: params
# upload once, every dispatch streams batch in → batch out, and nothing
# survives on the device between calls. Autoregressive decode breaks
# that shape — the KV-cache is device state that every token step reads
# AND rewrites, and re-uploading it per step would cost
# O(slots·layers·T_max·d) H2D per token. A *stateful segment* is the
# minimal extension: a jitted step function whose first argument is a
# device-resident buffer pytree, compiled with ``donate_argnums=(0,)``
# so XLA reuses the input cache's buffers for the output cache (an
# in-place update, no reallocation), with the rebind of the new state
# serialized under a witnessed lock. The jitted step registers in the
# owner's ``_plan_cache`` under a ``("stateful", name)`` key so
# ``obs.runtime.compiled_programs`` counts its programs on the same
# ladder budget as stateless segments.

class SegmentState:
    """Device-resident buffers owned by a stateful segment.

    ``buffers`` is an arbitrary jax pytree living on the device (for the
    serve plane: the slot-major KV-cache pair
    ``[slots, layers, heads, T_max, d]`` of one replica lane). Reads and
    rebinds go through :meth:`swap` under the witnessed lock — after a
    donated dispatch the OLD buffers are deleted by XLA, so a racing
    reader holding a stale reference would fetch a dead buffer.
    """

    __slots__ = ("name", "_buffers", "_lock")

    def __init__(self, name: str, buffers: Any):
        from mmlspark_tpu.obs.lockwitness import named_lock
        self.name = name
        self._buffers = buffers
        self._lock = named_lock("core.plan.SegmentState._lock")

    @property
    def buffers(self) -> Any:
        with self._lock:
            return self._buffers

    def swap(self, fn: Callable[[Any], tuple]) -> Any:
        """Run ``fn(buffers) -> (new_buffers, out)`` under the lock,
        rebind the state to ``new_buffers``, and return ``out``. The ONE
        mutation point: dispatches that donate the old buffers and reads
        that snapshot them serialize here."""
        with self._lock:
            self._buffers, out = fn(self._buffers)
            return out


def allocate_segment_state(name: str, shapes: dict, target: Any = None,
                           dtype: Any = None) -> SegmentState:
    """Allocate zeroed device buffers for a stateful segment.

    ``shapes`` maps buffer name → shape tuple (all sharing ``dtype``,
    default f32); ``target`` is a device or sharding for
    ``jax.device_put`` (default placement when None). Zero is the right
    init for a KV-cache: the active-slot mask keeps unwritten positions
    out of every attention denominator."""
    import jax
    import jax.numpy as jnp

    dt = jnp.float32 if dtype is None else dtype
    bufs = {k: jnp.zeros(s, dt) for k, s in shapes.items()}
    if target is not None:
        bufs = jax.device_put(bufs, target)
    return SegmentState(name, bufs)


def register_stateful_program(cache_host: Any, name: str, jitted: Any,
                              pinned: Any = None) -> Any:
    """Enter a stateful segment's jitted step into ``cache_host``'s
    compiled-segment cache under a ``("stateful", name)`` key.

    This is what keeps the serve plane's program accounting honest:
    ``obs.runtime.compiled_programs(cache_host)`` walks ``_plan_cache``
    and sums each entry's live jit-cache size, so a decode loop that
    silently retraced per batch size would blow the ladder budget the
    tier-1 gate pins. Stateful entries are pinned outside the LRU window
    (state outlives any bucket traffic pattern): the eviction loop in
    ``_cached_segment`` only pops ``while len > max``, so keep the
    stateful program count small. Returns ``jitted`` for chaining."""
    lock = cache_host.__dict__.setdefault("_plan_lock", threading.Lock())
    with lock:
        store = cache_host.__dict__.setdefault("_plan_cache", {})
        store[("stateful", name)] = (("stateful", name), (jitted,),
                                     (pinned,))
    return jitted


class StatefulSegment:
    """A compiled step function owning :class:`SegmentState`.

    ``step_fn(buffers, *args) -> (new_buffers, out)`` is jitted with the
    buffers donated (``donate_argnums=(0,)`` unless ``donate=False``),
    so each :meth:`dispatch` updates the device state in place — no
    per-step reallocation, no H2D re-upload of the cache. Dispatches
    serialize through :meth:`SegmentState.swap`; the jitted program
    registers on ``cache_host`` (when given) for
    ``compiled_programs`` accounting."""

    __slots__ = ("name", "state", "_jitted")

    def __init__(self, name: str, step_fn: Callable, state: SegmentState,
                 cache_host: Any = None, donate: bool = True,
                 static_argnums: tuple = ()):
        import jax

        self.name = name
        self.state = state
        kwargs: dict = {"static_argnums": tuple(
            n + 1 for n in static_argnums)} if static_argnums else {}
        if donate:
            kwargs["donate_argnums"] = (0,)
        self._jitted = jax.jit(step_fn, **kwargs)
        if cache_host is not None:
            register_stateful_program(cache_host, name, self._jitted,
                                      pinned=state)

    @property
    def jitted(self) -> Any:
        """The jitted step — what the SPMD audit traces and
        ``jit_cache_size`` counts."""
        return self._jitted

    def dispatch(self, *args) -> Any:
        """One step: run the donated program over the current buffers,
        rebind the new buffers, return the step outputs (still device
        arrays — async dispatch; the caller owns the fetch policy)."""
        return self.state.swap(
            lambda bufs: self._jitted(bufs, *args))
