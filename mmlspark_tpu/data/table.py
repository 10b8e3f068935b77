"""DataTable — the columnar table every pipeline stage consumes and produces.

The reference's stages operate on Spark DataFrames whose columns carry
metadata (categorical levels, score-column roles) in an ``mml`` metadata tag
(reference: core/schema/src/main/scala/SparkSchema.scala:23-129,
Categoricals.scala:21-90). JAX is Python and single-process per host, so the
TPU-native analog is a light immutable-ish columnar table:

* columns are NumPy arrays (numeric / bool / fixed-width) or object arrays
  (strings, bytes, dicts, variable-length vectors),
* per-column metadata is a plain dict carried in ``table.meta[col]`` — the
  sidecar-schema replacement for Spark's column metadata facility,
* zero-copy round-trips to/from pandas and Arrow power the Spark offload
  bridge (Arrow batches from executors) and local files.

Partitioning: Spark's RDD partitions become an optional ``num_partitions``
hint plus :meth:`partitions` iteration used by sampling/repartition stages;
compute-heavy stages instead batch rows directly into device arrays.
"""

from __future__ import annotations

import copy as _copy
from typing import Any, Callable, Iterable, Iterator, Mapping, Sequence

import numpy as np

from mmlspark_tpu.obs.metrics import registry as _obs_registry


def is_missing(v: Any) -> bool:
    """True for None and float NaN of any width (Python float or np.floating).

    The single missing-value predicate shared by all stages (imputation,
    indexing, profiling, conversion) so semantics cannot drift.
    """
    if v is None:
        return True
    if isinstance(v, (float, np.floating)):
        return bool(np.isnan(v))
    return False


def to_py_scalar(v: Any) -> Any:
    """Unwrap a NumPy scalar to the equivalent Python scalar (pass-through
    otherwise) — the shared idiom for building dict keys / JSON values from
    column cells."""
    return v.item() if isinstance(v, np.generic) else v


def _object_column(values: Any) -> np.ndarray:
    out = np.empty(len(values), dtype=object)
    for i, v in enumerate(values):
        out[i] = v
    return out


def _rows_block(col: np.ndarray) -> np.ndarray | None:
    """The ``[n, row_size]`` block an object column's rows lie in, as a
    read-only view, or None when they are not one block.

    They are one block when every row is a plain ``np.ndarray`` of one
    dtype and shape, C-contiguous, all sharing one ``.base``, and row *i*
    starts exactly ``i * row_nbytes`` after row 0: the rows of a matrix, of
    a slice of it (``data[5:50]``) or of a table's contiguous sub-range,
    in order. Decided from what the column holds NOW (a row can be
    reassigned at any time): one pass of identity, shape and address
    checks, no per-row conversion, out at the first row that fails
    (~10 ms for 8,192 rows). A filtered, permuted, reversed or strided
    selection, separately allocated rows and non-array rows are not."""
    first = col[0]
    if type(first) is not np.ndarray:
        return None
    base, dtype, shape, step = first.base, first.dtype, first.shape, first.nbytes
    if base is None or not step or dtype.hasobject \
            or not first.flags.c_contiguous:
        return None
    want = first.ctypes.data
    for v in col:
        if (type(v) is not np.ndarray or v.base is not base
                or v.dtype != dtype or v.shape != shape
                or not v.flags.c_contiguous or v.ctypes.data != want):
            return None
        want += step
    # every row is a valid view into ``base``'s one buffer and they tile
    # [row 0, row n) without a hole, so the strided view stays inside it
    return np.lib.stride_tricks.as_strided(
        first.reshape(-1), shape=(len(col), first.size),
        strides=(step, first.itemsize), writeable=False)


def copied_nbytes(matrix: np.ndarray) -> int:
    """Bytes :meth:`DataTable.column_matrix` (or a coercion built on it)
    copied to make ``matrix``: 0 for the read-only view of the table's own
    block — the only read-only result it gives, reshaped or not — and all
    of ``matrix`` otherwise. What the ``transform/coerce`` boundary record
    carries as ``nbytes``."""
    return int(matrix.nbytes) if matrix.flags.writeable else 0


def _as_column(values: Any) -> np.ndarray:
    """Coerce input values to a 1-D numpy column (object dtype if ragged)."""
    if isinstance(values, np.ndarray):
        if values.ndim == 1:
            return values
        # 2-D numeric arrays become object columns of row vectors: each
        # row is a VIEW of ``values`` (nothing is copied), which is what
        # lets ``column_matrix`` hand the block back later (_rows_block)
        return _object_column(values)
    values = list(values)
    if not values:
        return np.empty(0, dtype=object)
    first = values[0]
    if isinstance(first, (str, bytes, dict, list, tuple, np.ndarray)) or first is None:
        return _object_column(values)
    arr = np.asarray(values)
    if arr.ndim != 1:
        return _object_column(values)
    return arr


# Canonical image-struct contract. core/schema re-exports these — one
# definition of "image dict" for the whole framework (schema.py imports
# this module, so the constants must live here to avoid a cycle).
IMAGE_FIELDS = ("path", "height", "width", "channels", "data")
K_IMAGE = "is_image"            # column-meta marker for image columns
# wire format over Arrow: the ImageSchema struct plus 'mode' carrying the
# numpy dtype so float images round-trip
_IMAGE_WIRE_FIELDS = {"path", "height", "width", "channels", "mode", "data"}


def _looks_like_image_column(col: np.ndarray) -> bool:
    """Unmarked-column fallback: EVERY non-None row must be a dict with
    exactly the image fields. Subset/first-row sniffing would hijack
    generic dict columns that merely share key names (and silently drop
    their extra keys on the wire); columns marked via ``K_IMAGE`` meta
    skip this and get strict per-row validation instead."""
    want = set(IMAGE_FIELDS)
    seen = False
    for v in col:
        if v is None:
            continue
        if not (isinstance(v, dict) and set(v.keys()) == want):
            return False
        seen = True
    return seen


def _image_structs_to_arrow(name: str, col: np.ndarray) -> Any:
    import pyarrow as pa
    paths, hs, ws, cs, modes, blobs = [], [], [], [], [], []
    mask = []
    for i, v in enumerate(col):
        if v is None:
            mask.append(True)
            paths.append(None); hs.append(None); ws.append(None)
            cs.append(None); modes.append(None); blobs.append(None)
            continue
        if not (isinstance(v, dict) and set(IMAGE_FIELDS) <= set(v.keys())):
            raise ValueError(
                f"image column {name!r} row {i} is not an image struct "
                f"(need fields {IMAGE_FIELDS}, got {v!r:.120})")
        mask.append(False)
        arr = np.ascontiguousarray(np.asarray(v["data"]))
        h, w, c = int(v["height"]), int(v["width"]), int(v["channels"])
        if arr.size != h * w * c:
            raise ValueError(
                f"image column {name!r} row {i}: data has {arr.size} "
                f"values, dims say {h}x{w}x{c}")
        paths.append(v.get("path", ""))
        hs.append(h)
        ws.append(w)
        cs.append(c)
        modes.append(arr.dtype.str)
        blobs.append(arr.tobytes())
    return pa.StructArray.from_arrays(
        [pa.array(paths, pa.string()), pa.array(hs, pa.int32()),
         pa.array(ws, pa.int32()), pa.array(cs, pa.int32()),
         pa.array(modes, pa.string()), pa.array(blobs, pa.binary())],
        names=["path", "height", "width", "channels", "mode", "data"],
        mask=pa.array(mask, pa.bool_()))


def _image_structs_from_arrow(col: Any) -> list:
    out = []
    for v in col.to_pylist():
        if v is None:
            out.append(None)
            continue
        h, w, c = int(v["height"]), int(v["width"]), int(v["channels"])
        # copy: frombuffer over bytes is read-only, but image dicts are
        # writable everywhere else (in-place normalization must not crash
        # only on tables that crossed the bridge)
        data = np.frombuffer(v["data"],
                             np.dtype(v["mode"])).reshape(h, w, c).copy()
        out.append({"path": v["path"], "height": h, "width": w,
                    "channels": c, "data": data})
    return out


class DataTable:
    """An ordered mapping column-name → 1-D column, with per-column metadata."""

    def __init__(
        self,
        columns: Mapping[str, Any] | None = None,
        meta: Mapping[str, Mapping[str, Any]] | None = None,
        num_partitions: int | None = None,
    ):
        self._cols: dict[str, np.ndarray] = {}
        n = None
        for name, values in (columns or {}).items():
            col = _as_column(values)
            if n is None:
                n = len(col)
            elif len(col) != n:
                raise ValueError(
                    f"column {name!r} has {len(col)} rows, expected {n}")
            self._cols[name] = col
        self._nrows = n or 0
        # sidecar schema: per-column metadata (categorical levels, score
        # roles, image flag, …) — the `mml` metadata-tag analog
        self.meta: dict[str, dict[str, Any]] = {
            k: dict(v) for k, v in (meta or {}).items() if k in self._cols
        }
        self.num_partitions = num_partitions

    # ---- basic accessors ----

    @property
    def columns(self) -> list[str]:
        return list(self._cols)

    def __len__(self) -> int:
        return self._nrows

    @property
    def num_rows(self) -> int:
        return self._nrows

    def __contains__(self, name: str) -> bool:
        return name in self._cols

    def __getitem__(self, name: str) -> np.ndarray:
        if name not in self._cols:
            raise KeyError(
                f"no column {name!r}; available: {self.columns}")
        return self._cols[name]

    def column_meta(self, name: str) -> dict[str, Any]:
        return self.meta.get(name, {})

    def dtype(self, name: str) -> np.dtype:
        return self[name].dtype

    def __repr__(self) -> str:
        cols = ", ".join(f"{k}:{v.dtype}" for k, v in self._cols.items())
        return f"DataTable[{self._nrows} rows; {cols}]"

    # ---- functional updates (tables are treated as immutable) ----

    def with_column(
        self,
        name: str,
        values: Any,
        meta: Mapping[str, Any] | None = None,
    ) -> "DataTable":
        col = _as_column(values)
        if self._cols and len(col) != self._nrows:
            raise ValueError(
                f"column {name!r} has {len(col)} rows, expected {self._nrows}")
        out = self._shallow_copy()
        out._cols[name] = col
        if self._cols == {}:
            out._nrows = len(col)
        if meta is not None:
            out.meta[name] = dict(meta)
        return out

    def with_meta(self, name: str, **meta: Any) -> "DataTable":
        """Merge metadata entries into a column's sidecar schema."""
        if name not in self._cols:
            raise KeyError(f"no column {name!r}")
        out = self._shallow_copy()
        out.meta.setdefault(name, {})
        out.meta[name] = {**out.meta[name], **meta}
        return out

    def select(self, *names: str) -> "DataTable":
        for n in names:
            if n not in self._cols:
                raise KeyError(f"no column {n!r}; available: {self.columns}")
        return DataTable(
            {n: self._cols[n] for n in names},
            {n: self.meta[n] for n in names if n in self.meta},
            self.num_partitions,
        )

    def drop(self, *names: str) -> "DataTable":
        keep = [n for n in self.columns if n not in names]
        return self.select(*keep)

    def rename(self, mapping: Mapping[str, str]) -> "DataTable":
        cols = {mapping.get(k, k): v for k, v in self._cols.items()}
        meta = {mapping.get(k, k): v for k, v in self.meta.items()}
        return DataTable(cols, meta, self.num_partitions)

    def take(self, indices: Any) -> "DataTable":
        """Row subset/reorder by integer indices or boolean mask."""
        indices = np.asarray(indices)
        if indices.dtype == bool:
            indices = np.flatnonzero(indices)
        elif not np.issubdtype(indices.dtype, np.integer):
            indices = indices.astype(np.intp)  # e.g. empty list → float64
        return DataTable(
            {k: v[indices] for k, v in self._cols.items()},
            self.meta,
            self.num_partitions,
        )

    def head(self, n: int) -> "DataTable":
        return self.take(np.arange(min(n, self._nrows)))

    def filter(self, predicate: Callable[[dict[str, Any]], bool]) -> "DataTable":
        mask = np.fromiter(
            (bool(predicate(row)) for row in self.iter_rows()),
            dtype=bool, count=self._nrows)
        return self.take(mask)

    def concat(self, other: "DataTable") -> "DataTable":
        if set(self.columns) != set(other.columns):
            raise ValueError(
                f"column mismatch: {self.columns} vs {other.columns}")
        cols = {}
        for k in self.columns:
            a, b = self._cols[k], other._cols[k]
            if a.dtype == object or b.dtype == object:
                merged = np.empty(len(a) + len(b), dtype=object)
                merged[:len(a)] = a
                merged[len(a):] = b
                cols[k] = merged
            else:
                cols[k] = np.concatenate([a, b])
        meta = {**other.meta, **self.meta}
        return DataTable(cols, meta, self.num_partitions)

    def _shallow_copy(self) -> "DataTable":
        out = DataTable.__new__(DataTable)
        out._cols = dict(self._cols)
        out._nrows = self._nrows
        out.meta = {k: dict(v) for k, v in self.meta.items()}
        out.num_partitions = self.num_partitions
        return out

    # ---- row iteration (for host-side stages; device stages batch columns) --

    def iter_rows(self) -> Iterator[dict[str, Any]]:
        names = self.columns
        cols = [self._cols[n] for n in names]
        for i in range(self._nrows):
            yield {n: c[i] for n, c in zip(names, cols)}

    def to_rows(self) -> list[dict[str, Any]]:
        return list(self.iter_rows())

    # ---- partitioning (analog of RDD partitions for sampling stages) ----

    def partitions(self, n: int | None = None) -> list["DataTable"]:
        n = n or self.num_partitions or 1
        n = max(1, min(n, max(1, self._nrows)))
        bounds = np.linspace(0, self._nrows, n + 1).astype(int)
        return [self.take(np.arange(bounds[i], bounds[i + 1]))
                for i in range(n)]

    def repartition(self, n: int) -> "DataTable":
        out = self._shallow_copy()
        out.num_partitions = n
        return out

    # ---- conversions ----

    @staticmethod
    def from_rows(rows: Sequence[Mapping[str, Any]],
                  meta: Mapping[str, Mapping[str, Any]] | None = None
                  ) -> "DataTable":
        if not rows:
            return DataTable()
        # union of all row keys in first-encounter order — keys absent from
        # the first row must not be silently dropped; missing cells are None
        names = list(dict.fromkeys(k for r in rows for k in r))
        return DataTable({n: [r.get(n) for r in rows] for n in names}, meta)

    @staticmethod
    def from_pandas(df: Any, meta: Mapping[str, Mapping[str, Any]] | None = None
                    ) -> "DataTable":
        cols = {}
        for name in df.columns:
            s = df[name]
            if str(s.dtype) == "object" or str(s.dtype).startswith(("str", "string")):
                cols[name] = s.tolist()
            else:
                cols[name] = s.to_numpy()
        return DataTable(cols, meta)

    def to_pandas(self) -> Any:
        import pandas as pd
        return pd.DataFrame({k: v for k, v in self._cols.items()})

    @staticmethod
    def from_arrow(batch: Any, meta: Mapping[str, Mapping[str, Any]] | None = None
                   ) -> "DataTable":
        """From a pyarrow Table or RecordBatch (the Spark-bridge wire format).

        Image-struct columns (the ImageSchema wire shape:
        path/height/width/channels/mode/data-bytes) rebuild into the
        in-memory image dicts and the column is marked as an image column.
        """
        import pyarrow as pa
        cols: dict[str, Any] = {}
        image_cols: list[str] = []
        for name in batch.schema.names:
            col = batch.column(name)
            field_type = batch.schema.field(name).type
            # exact field-set match, mirroring _looks_like_image_column on
            # the serialize side — a non-image struct that happens to carry
            # these six names PLUS extras must not be rebuilt as images
            # (which would silently drop its extra fields)
            if (pa.types.is_struct(field_type)
                    and {f.name for f in field_type} == _IMAGE_WIRE_FIELDS):
                cols[name] = _image_structs_from_arrow(col)
                image_cols.append(name)
                continue
            try:
                cols[name] = col.to_numpy(zero_copy_only=False)
            except Exception:
                cols[name] = col.to_pylist()
        table = DataTable(cols, meta)
        for name in image_cols:
            table = table.with_meta(name, **{K_IMAGE: True})
        return table

    def to_arrow(self) -> Any:
        """To a pyarrow Table. Image-struct columns serialize as a struct of
        (path, height, width, channels, mode, data-bytes) — the Arrow form
        of the reference's ImageSchema (reference:
        core/schema/src/main/scala/ImageSchema.scala:12-17), so image
        tables cross the Spark bridge losslessly."""
        import pyarrow as pa
        arrays = {}
        for k, v in self._cols.items():
            is_image = self.column_meta(k).get(K_IMAGE) or (
                v.dtype == object and _looks_like_image_column(v))
            if is_image:
                arrays[k] = _image_structs_to_arrow(k, v)
            elif v.dtype == object:
                arrays[k] = pa.array(list(v))
            else:
                arrays[k] = pa.array(v)
        return pa.table(arrays)

    @staticmethod
    def from_csv(path: str, **kwargs: Any) -> "DataTable":
        import pandas as pd
        return DataTable.from_pandas(pd.read_csv(path, **kwargs))

    # ---- batch extraction for device compute ----

    def column_matrix(self, name: str, dtype: Any = np.float32) -> np.ndarray:
        """A column of equal-length vectors/scalars as a 2-D matrix.

        This is the host-side marshalling step that replaces the reference's
        per-element JNI FloatVector copies (reference:
        cntk-model/src/main/scala/CNTKModel.scala:67-74) with one contiguous
        array ready for device transfer.

        What comes back depends on what the column holds at the call:

        * an object column whose rows are, in order, the consecutive rows
          of one contiguous block (:func:`_rows_block`: a table built from
          a matrix or a slice of one, the column ``JaxModel.transform``
          wrote) gives **the block itself** as a ``[n, row_size]`` view
          marked read-only — no copy, the result aliases the table, and a
          caller that needs to write takes its own ``.copy()``. Where
          ``dtype`` differs from the block's it is one ``astype`` of the
          block: owned and writable;
        * any other object column (rows allocated apart, filtered,
          permuted, strided, lists) is stacked row by row into a fresh,
          owned, writable matrix, as is a numeric column.

        Counters ``table.matrix_rows_viewed`` / ``table.matrix_rows_copied``
        (always on) say which happened to how many rows of object columns.
        """
        col = self._cols[name]
        if col.dtype != object:
            return col.astype(dtype)[:, None] if col.ndim == 1 else col.astype(dtype)
        if self._nrows == 0:
            return np.empty((0, 0), dtype=dtype)
        block = _rows_block(col)
        viewed = block is not None and block.dtype == np.dtype(dtype)
        _obs_registry().counter(
            "table.matrix_rows_viewed" if viewed
            else "table.matrix_rows_copied").add(len(col))
        if viewed:
            return block
        if block is not None:
            return block.astype(dtype)
        return np.stack([np.asarray(v, dtype=dtype).reshape(-1) for v in col])
