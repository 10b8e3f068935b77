"""Persistent AOT compile cache — XLA programs as pipeline artifacts.

Every serving process today pays full XLA compilation for every
(bucket, model, precision) program at startup, even when an identical
process on the same host compiled the identical program seconds ago.
The reference framework's L6 premise is that accelerator programs are
*reusable pipeline artifacts*, not per-process ephemera; this module
makes that literal: compiled executables are serialized
(``jax.experimental.serialize_executable``) into a content-addressed
on-disk cache so a cold process warm-starts by *deserializing* the
ladder in milliseconds instead of re-tracing and re-compiling it.

Identity — the plan fingerprint
-------------------------------
A cached program is only reusable when everything that could change the
compiled artifact is part of the key. :func:`plan_fingerprint` hashes:

* every stage's :meth:`DeviceStage.device_fingerprint` — a *content*
  identity (weights digest, module structure, simple params), unlike
  ``device_cache_token`` whose ``id()``-based tokens are deliberately
  process-local;
* the segment's entry ``ArrayMeta`` (shape/dtype/is_image);
* the mesh spec (axis sizes + device count + platform — not device
  ids, which are process-local);
* the active ``PrecisionPolicy.cache_token``;
* the jax / jaxlib / backend-platform versions (an XLA upgrade must
  never replay stale programs).

A stage without a stable fingerprint (``device_fingerprint()`` returns
``None``) makes the whole segment unfingerprintable — the plan simply
compiles in memory, exactly as before. Per-call *shapes* are keyed
separately (one on-disk entry per concrete dispatch shape), so one
fingerprint holds the whole bucket ladder.

On-disk layout + integrity (the ``ModelRepo`` discipline)
---------------------------------------------------------
::

    <root>/<fp[:2]>/<fp>/<shape-key>/
        ENTRY.json      # versions, nbytes, sha256 per file
        program.bin     # serialized executable payload
        trees.pkl       # pickled (in_tree, out_tree)

Entries are staged in a hidden temp dir and enter the cache via one
``os.replace`` — a reader sees a whole entry or none. ``ENTRY.json``
carries a sha256 per file; :meth:`CompileCache.load` re-verifies before
deserializing anything, so a torn, truncated, or version-mismatched
entry is a typed :class:`CompileCacheError` → counted refusal +
quarantine + in-memory compile, never a silently-wrong served program.
A publish race is benign: the loser's ``os.replace`` fails against the
winner's directory and the loser adopts the winner's entry. The cache
is bounded by an LRU byte budget (entry dirs are mtime-touched on hit;
oldest evicted first).

Wiring
------
:func:`configure` installs the process-wide cache (``ServeConfig
.compile_cache`` / ``tools/serve.py --compile-cache`` /
``MMLSPARK_TPU_COMPILE_CACHE``); ``core/plan._compile_segment_inner``
wraps its jitted composite in :class:`CachedJit` whenever a cache is
active and the segment fingerprints. ``CachedJit`` mimics the jit at
the two seams the repo touches — ``__call__`` and ``_cache_size()``
(the obs compiled-program hook) — so every existing
``programs <= len(buckets)`` gate keeps counting loaded programs.
Counters: ``plan.compile_cache.{hits,misses,puts,bytes,load_ms}``
(obs registry, when enabled) mirrored by a plain ``stats`` dict that is
always live. See docs/serving.md §compile cache.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import shutil
import threading
import time
from typing import Any

from mmlspark_tpu.core import config
from mmlspark_tpu.core.logging_utils import get_logger

_log = get_logger(__name__)

ENTRY_FILE = "ENTRY.json"
PROGRAM_FILE = "program.bin"
TREES_FILE = "trees.pkl"

#: default LRU byte budget (``compile_cache_bytes`` config)
DEFAULT_MAX_BYTES = 1 << 30


class CompileCacheError(RuntimeError):
    """A cache entry that must not be served: torn, corrupt (digest
    mismatch), or compiled by a different jax/jaxlib/backend. The
    caller falls back to an in-memory compile; the entry is
    quarantined (removed) so the fresh program can be re-published."""


def _faults():
    # lazy: core must not import the serve plane at module level (the
    # models/repo.py direction discipline); the fault seam costs one
    # import-cache lookup only when a put actually runs
    from mmlspark_tpu.serve import faults
    return faults


def _obs_counter(name: str, n: float = 1.0) -> None:
    """Mirror a stat into the obs registry when the pillar is on."""
    try:
        from mmlspark_tpu.obs import runtime as _rt
        if not _rt._enabled:
            return
        from mmlspark_tpu.obs.metrics import registry
        registry().counter(f"plan.compile_cache.{name}").add(n)
    except Exception:  # pragma: no cover - observability is best-effort
        pass


def runtime_versions() -> dict:
    """The toolchain identity baked into every fingerprint and entry:
    a program compiled by a different jax/jaxlib/backend is invalid."""
    import jax
    jaxlib_v = ""
    try:
        import jaxlib
        jaxlib_v = getattr(getattr(jaxlib, "version", None),
                           "__version__", "") or ""
    except Exception:  # pragma: no cover - jaxlib always ships with jax
        pass
    try:
        backend = jax.default_backend()
    except Exception:  # pragma: no cover - no devices at all
        backend = "unknown"
    return {"jax": jax.__version__, "jaxlib": jaxlib_v,
            "backend": backend}


def params_digest(params: Any) -> str:
    """Content digest of a params pytree: sha256 over the tree
    structure plus every leaf's shape, dtype, and bytes. This is the
    cross-process identity of a model's weights — the stable
    counterpart of the ``id()``-based in-process cache token."""
    import jax
    import numpy as np
    leaves, treedef = jax.tree_util.tree_flatten(params)
    h = hashlib.sha256(repr(treedef).encode())
    for leaf in leaves:
        a = np.asarray(leaf)
        h.update(repr((a.shape, str(a.dtype))).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def bundle_digest(bundle: Any) -> str:
    """Content digest of a :class:`ModelBundle` (module structure +
    weights + preprocess + input spec). Memoized on the bundle object —
    bundles are effectively frozen after load, and hashing ResNet50
    weights on every fingerprint would dominate the compile it saves."""
    memo = getattr(bundle, "_content_digest", None)
    if memo is not None:
        return memo
    h = hashlib.sha256()
    h.update(repr((bundle.name, type(bundle.module).__name__,
                   repr(bundle.module), bundle.input_spec,
                   tuple(bundle.output_names),
                   bundle.preprocess)).encode())
    h.update(params_digest(bundle.params).encode())
    digest = h.hexdigest()
    try:
        bundle._content_digest = digest
    except Exception:  # pragma: no cover - frozen/slotted bundle
        pass
    return digest


def plan_fingerprint(stages: Any, entry_meta: Any, mesh: Any = None,
                     precision: Any = None) -> str | None:
    """The cache key for one device segment, or ``None`` when any stage
    lacks a stable content fingerprint (→ in-memory compile, exactly
    the pre-cache behavior). Derivable statically: stages + schema
    entry meta are enough — no data, no devices, no compilation."""
    parts = []
    for s in stages:
        fp_fn = getattr(s, "device_fingerprint", None)
        if fp_fn is None:
            return None
        try:
            fp = fp_fn()
        except Exception:
            _log.warning("compile cache: %s.device_fingerprint() raised"
                         " — segment compiles in memory",
                         type(s).__name__, exc_info=True)
            return None
        if fp is None:
            return None
        parts.append(fp)
    mesh_part = None
    if mesh is not None:
        mesh_part = (tuple(sorted(mesh.shape.items())),
                     int(mesh.devices.size),
                     getattr(mesh.devices.flat[0], "platform", "?"))
    prec = None
    if precision is not None and getattr(precision, "active", False):
        prec = precision.cache_token
    v = runtime_versions()
    blob = repr((tuple(parts),
                 (tuple(entry_meta.shape), str(entry_meta.dtype),
                  bool(entry_meta.is_image)),
                 mesh_part, prec,
                 (v["jax"], v["jaxlib"], v["backend"])))
    return hashlib.sha256(blob.encode()).hexdigest()


def _sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


class CompileCache:
    """The on-disk store: atomic publish, digest-verified load,
    LRU byte budget. All methods are safe under concurrent processes —
    the only cross-process coordination is ``os.replace`` atomicity."""

    def __init__(self, root: str, max_bytes: int | None = None):
        self.root = os.path.abspath(root)
        if max_bytes is None:
            max_bytes = int(config.get("compile_cache_bytes",
                                       DEFAULT_MAX_BYTES))
        self.max_bytes = int(max_bytes)
        self._lock = threading.Lock()
        self._seq = 0
        #: always-live counters (the obs registry mirrors them under
        #: ``plan.compile_cache.*`` when the pillar is enabled):
        #: ``compiles`` counts fresh XLA compiles through CachedJit —
        #: the warm-start gate asserts it stays 0 on a warm process
        self.stats = {"hits": 0, "misses": 0, "puts": 0, "bytes": 0,
                      "refused": 0, "put_races": 0, "evicted": 0,
                      "compiles": 0, "load_ms": 0.0}
        os.makedirs(self.root, exist_ok=True)

    # -- bookkeeping --

    def _bump(self, key: str, n: float = 1) -> None:
        with self._lock:
            self.stats[key] += n
        _obs_counter(key, n)

    def _entry_dir(self, fingerprint: str, shape_key: str) -> str:
        return os.path.join(self.root, fingerprint[:2], fingerprint,
                            shape_key)

    # -- load --

    def load(self, fingerprint: str, shape_key: str,
             devices: Any) -> Any | None:
        """Deserialize one cached executable onto ``devices`` (the
        program's own mesh devices, in mesh order — left to default, jax
        loads onto every device of the backend, and a one-device program
        then refuses its arguments on a multi-device host). ``None`` on a
        plain miss; :class:`CompileCacheError` (after quarantining the
        entry) when the entry exists but must not be served."""
        d = self._entry_dir(fingerprint, shape_key)
        if not os.path.isdir(d):
            return None
        epath = os.path.join(d, ENTRY_FILE)
        try:
            entry = self._verify(d, epath)
            t0 = time.perf_counter()
            with open(os.path.join(d, PROGRAM_FILE), "rb") as f:
                payload = f.read()
            with open(os.path.join(d, TREES_FILE), "rb") as f:
                in_tree, out_tree = pickle.load(f)
            from jax.experimental import serialize_executable as se
            loaded = se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=list(devices))
        except CompileCacheError:
            self._quarantine(d)
            raise
        except Exception as e:
            self._quarantine(d)
            raise CompileCacheError(
                f"compile cache entry {fingerprint[:12]}/{shape_key}: "
                f"deserialization failed ({type(e).__name__}: {e})"
            ) from e
        load_ms = (time.perf_counter() - t0) * 1e3
        self._bump("load_ms", load_ms)
        self._bump("bytes", len(payload))
        try:  # LRU touch — eviction orders by entry-dir mtime
            os.utime(d)
        except OSError:  # pragma: no cover - entry racing an eviction
            pass
        return loaded

    def _verify(self, d: str, epath: str) -> dict:
        """ENTRY.json sanity + toolchain match + per-file digests —
        all BEFORE any deserialization touches the payload."""
        if not os.path.exists(epath):
            raise CompileCacheError(
                f"{d}: torn entry ({ENTRY_FILE} missing)")
        try:
            with open(epath, encoding="utf-8") as f:
                entry = json.load(f)
        except (OSError, ValueError) as e:
            raise CompileCacheError(f"{epath}: unreadable ({e})") from e
        mine = runtime_versions()
        theirs = entry.get("versions", {})
        for k in ("jax", "jaxlib", "backend"):
            if theirs.get(k) != mine[k]:
                raise CompileCacheError(
                    f"{d}: compiled under {k}={theirs.get(k)!r}, "
                    f"running {k}={mine[k]!r}")
        for rel, want in (entry.get("files") or {}).items():
            path = os.path.join(d, rel)
            if not os.path.exists(path):
                raise CompileCacheError(f"{d}: torn entry ({rel} missing)")
            got = _sha256_file(path)
            if got != want:
                raise CompileCacheError(
                    f"{d}: digest mismatch on {rel} "
                    f"(manifest {want[:12]}…, file {got[:12]}…)")
        return entry

    def _quarantine(self, d: str) -> None:
        self._bump("refused")
        shutil.rmtree(d, ignore_errors=True)
        _log.warning("compile cache: quarantined bad entry %s", d)

    # -- put --

    def put(self, fingerprint: str, shape_key: str, payload: bytes,
            trees: tuple) -> bool:
        """Publish one serialized executable atomically. Returns False
        when the entry already exists or another process won the
        publish race (the loser adopts the winner's entry)."""
        d = self._entry_dir(fingerprint, shape_key)
        if os.path.exists(os.path.join(d, ENTRY_FILE)):
            return False
        parent = os.path.dirname(d)
        os.makedirs(parent, exist_ok=True)
        with self._lock:
            self._seq += 1
            seq = self._seq
        # pid + instance id + seq: unique across processes AND across
        # multiple in-process cache objects staging the same entry
        tmp = os.path.join(
            parent,
            f".staging-{shape_key}-{os.getpid()}-{id(self):x}-{seq}")
        os.makedirs(tmp)
        try:
            with open(os.path.join(tmp, PROGRAM_FILE), "wb") as f:
                f.write(payload)
            with open(os.path.join(tmp, TREES_FILE), "wb") as f:
                pickle.dump(trees, f)
            files = {rel: _sha256_file(os.path.join(tmp, rel))
                     for rel in (PROGRAM_FILE, TREES_FILE)}
            nbytes = sum(os.path.getsize(os.path.join(tmp, rel))
                         for rel in files)
            with open(os.path.join(tmp, ENTRY_FILE), "w",
                      encoding="utf-8") as f:
                json.dump({"fingerprint": fingerprint,
                           "shape_key": shape_key,
                           "versions": runtime_versions(),
                           "nbytes": nbytes,
                           "created": time.time(),
                           "files": files}, f, indent=1)
            # the torn-publish fault point: a crash here leaves the
            # staging dir (invisible to every load path) and no entry —
            # the next process simply compiles in memory
            _faults().hit("compile_cache_torn_put")
            try:
                os.replace(tmp, d)
            except OSError:
                # publish race lost: the winner's directory is already
                # there (non-empty → rename refuses). Adopt it.
                shutil.rmtree(tmp, ignore_errors=True)
                self._bump("put_races")
                return False
        except BaseException:
            shutil.rmtree(tmp, ignore_errors=True)
            raise
        self._bump("puts")
        self._bump("bytes", nbytes)
        self._evict_over_budget()
        return True

    # -- LRU byte budget --

    def entries(self) -> list[tuple[float, int, str]]:
        """``[(mtime, nbytes, dir), ...]`` for every published entry."""
        out = []
        for shard in os.listdir(self.root) if os.path.isdir(self.root) \
                else []:
            sdir = os.path.join(self.root, shard)
            if not os.path.isdir(sdir):
                continue
            for fp in os.listdir(sdir):
                fdir = os.path.join(sdir, fp)
                if not os.path.isdir(fdir):
                    continue
                for shape in os.listdir(fdir):
                    d = os.path.join(fdir, shape)
                    if shape.startswith(".") or not os.path.isdir(d):
                        continue
                    try:
                        nbytes = sum(
                            os.path.getsize(os.path.join(d, f))
                            for f in os.listdir(d))
                        out.append((os.path.getmtime(d), nbytes, d))
                    except OSError:  # racing another process's evict
                        continue
        return out

    def size_bytes(self) -> int:
        return sum(n for _t, n, _d in self.entries())

    def _evict_over_budget(self) -> None:
        if self.max_bytes <= 0:
            return
        entries = sorted(self.entries())
        total = sum(n for _t, n, _d in entries)
        for mtime, nbytes, d in entries:
            if total <= self.max_bytes:
                break
            shutil.rmtree(d, ignore_errors=True)
            total -= nbytes
            self._bump("evicted")
            _log.info("compile cache: evicted %s (%d B) over %d B budget",
                      d, nbytes, self.max_bytes)


class CachedJit:
    """Drop-in wrapper over one jitted segment composite that resolves
    every concrete call shape against the disk cache before compiling.

    Mimics the jit at the seams the repo touches: ``__call__(params,
    x)`` dispatches the per-shape program; ``_cache_size()`` reports
    loaded+compiled program count (the ``obs.runtime.jit_cache_size``
    hook, so ``compiled_programs`` gates keep holding); ``lower`` is
    passed through (the obs device cost-capture seam). A cache refusal
    or serialization failure degrades to the wrapped jit's own
    ``lower().compile()`` — the cache can make loads fast, never wrong.
    """

    def __init__(self, jitted: Any, fingerprint: str,
                 cache: CompileCache, devices: Any):
        self._jit = jitted
        self.fingerprint = fingerprint
        self._cache = cache
        self._devices = tuple(devices)
        self._programs: dict[str, Any] = {}
        self._lock = threading.Lock()

    def _cache_size(self) -> int:
        return len(self._programs)

    def lower(self, *args, **kw):
        return self._jit.lower(*args, **kw)

    @staticmethod
    def shape_key(args: tuple) -> str:
        import jax
        leaves = jax.tree_util.tree_leaves(args)
        blob = repr(tuple(
            (tuple(getattr(a, "shape", ())),
             str(getattr(a, "dtype", type(a).__name__)))
            for a in leaves))
        return hashlib.sha256(blob.encode()).hexdigest()[:16]

    def __call__(self, *args):
        key = self.shape_key(args)
        prog = self._programs.get(key)
        if prog is None:
            with self._lock:
                prog = self._programs.get(key)
                if prog is None:
                    prog = self._resolve(key, args)
                    self._programs[key] = prog
        return prog(*args)

    def _resolve(self, key: str, args: tuple) -> Any:
        cache = self._cache
        try:
            prog = cache.load(self.fingerprint, key, self._devices)
        except CompileCacheError as e:
            _log.warning("compile cache: %s — compiling in memory", e)
            prog = None
        if prog is not None:
            cache._bump("hits")
            return prog
        cache._bump("misses")
        compiled = self._jit.lower(*args).compile()
        cache._bump("compiles")
        # publishing is best-effort: a full disk / injected crash /
        # unserializable executable must never fail the dispatch that
        # just compiled a perfectly good program
        try:
            from jax.experimental import serialize_executable as se
            payload, in_tree, out_tree = se.serialize(compiled)
            cache.put(self.fingerprint, key, payload,
                      (in_tree, out_tree))
        except Exception as e:
            _log.warning("compile cache: publish of %s/%s failed (%s: "
                         "%s) — serving the in-memory program",
                         self.fingerprint[:12], key,
                         type(e).__name__, e)
        return compiled


# -- process-wide cache (ServeConfig.compile_cache / env) --

_active: CompileCache | None = None
_env_checked = False
_state_lock = threading.Lock()


def configure(path: str | None,
              max_bytes: int | None = None) -> CompileCache | None:
    """Install the process-wide cache rooted at ``path`` (``None``/""
    disables). An uncreatable or unwritable path degrades to a one-line
    warning and in-memory compiles — the fleet-dir tolerance rule: a
    bad cache dir must never fail a model load."""
    global _active, _env_checked
    with _state_lock:
        _env_checked = True
        if not path:
            _active = None
            return None
        try:
            cache = CompileCache(path, max_bytes=max_bytes)
            probe = os.path.join(cache.root,
                                 f".probe-{os.getpid()}-{id(cache)}")
            with open(probe, "w") as f:
                f.write("w")
            os.remove(probe)
        except OSError as e:
            _log.warning("compile cache disabled: %r not writable (%s)"
                         " — programs compile in memory", path, e)
            _active = None
            return None
        _active = cache
        _log.info("compile cache: %s (budget %d B)", cache.root,
                  cache.max_bytes)
        return cache


def active() -> CompileCache | None:
    """The installed cache, lazily honoring
    ``MMLSPARK_TPU_COMPILE_CACHE`` (the ``compile_cache`` config) on
    first consult."""
    global _env_checked
    if not _env_checked:
        _env_checked = True
        env = config.get("compile_cache", "")
        if env:
            configure(env)
    return _active


def reset() -> None:
    """Tests: drop the installed cache and re-arm the env check."""
    global _active, _env_checked
    with _state_lock:
        _active = None
        _env_checked = False
