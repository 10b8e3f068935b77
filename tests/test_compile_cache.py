"""core/compile_cache.py — the persistent AOT compile cache: stable
content-addressed fingerprints, atomic publish + digest-verified load
(the ModelRepo discipline applied to XLA programs), typed refusal of
torn/corrupt/version-mismatched entries with in-memory-compile
fallback, benign publish races, the LRU byte budget, and the
unwritable-dir degrade that must never fail a model load."""

import json
import os
import threading

import numpy as np
import pytest

from mmlspark_tpu.core import compile_cache as cc
from mmlspark_tpu.data.table import DataTable
from mmlspark_tpu.models.jax_model import JaxModel
from mmlspark_tpu.models.zoo import get_model
from mmlspark_tpu.serve import FaultPlan, FaultSpec, ModelServer, ServeConfig
from mmlspark_tpu.serve import faults as serve_faults

FP = "ab" * 32  # a syntactically-valid fingerprint for direct-API tests


@pytest.fixture(autouse=True)
def _no_process_cache():
    """Tests own the process-wide cache state; never leak it."""
    cc.reset()
    yield
    cc.reset()


def _jitted():
    import jax

    return jax.jit(lambda p, x: x * p + 1.0)


def _args():
    return (np.float32(2.0), np.arange(8, dtype=np.float32))


def _cached(tmp_path, fp=FP):
    import jax

    cache = cc.CompileCache(str(tmp_path / "cache"))
    # an uncommitted jit runs on the default device; the 8-device test
    # mesh makes "load onto the program's own devices" observable
    return cc.CachedJit(_jitted(), fp, cache, jax.devices()[:1]), cache


def _bundle():
    return get_model("ConvNet_CIFAR10", widths=(4, 8), dense_width=16)


# ---- fingerprints ----


def test_fingerprint_stable_across_fresh_objects():
    """The fingerprint is a CONTENT identity: two independently
    constructed stage objects over the same weights agree (unlike
    device_cache_token, which is deliberately id()-based)."""
    from mmlspark_tpu.core.stage import ArrayMeta

    # two INDEPENDENT object graphs over the same content (zoo init is
    # seeded): same fingerprint, different in-process cache tokens
    jm1 = JaxModel(model=_bundle(), input_col="image",
                   output_col="scores")
    jm2 = JaxModel(model=_bundle(), input_col="image",
                   output_col="scores")
    meta = ArrayMeta((32 * 32 * 3,), "uint8")
    fp1 = cc.plan_fingerprint([jm1], meta)
    fp2 = cc.plan_fingerprint([jm2], meta)
    assert fp1 is not None and fp1 == fp2
    assert jm1.device_cache_token() != jm2.device_cache_token()

    # different weights -> different program -> different key
    perturbed = _perturb(_bundle())
    jm3 = JaxModel(model=perturbed, input_col="image",
                   output_col="scores")
    assert cc.plan_fingerprint([jm3], meta) != fp1

    # a different entry layout is a different program
    meta2 = ArrayMeta((16 * 16 * 3,), "uint8")
    assert cc.plan_fingerprint([jm1], meta2) != fp1


def _perturb(bundle):
    import dataclasses

    import jax

    leaves, treedef = jax.tree_util.tree_flatten(bundle.params)
    leaves = [np.asarray(a).copy() for a in leaves]
    leaves[0] = leaves[0] + 1.0
    try:
        return dataclasses.replace(
            bundle, params=jax.tree_util.tree_unflatten(treedef, leaves))
    except TypeError:
        bundle.params = jax.tree_util.tree_unflatten(treedef, leaves)
        return bundle


def test_unfingerprintable_stage_disables_caching():
    """A stage whose device_fingerprint() is None (e.g. a closure-y
    complex param) makes the segment compile in memory — fingerprint
    None, never a wrong cache key."""
    from mmlspark_tpu.core.stage import ArrayMeta

    class NoFp:
        def device_fingerprint(self):
            return None

    meta = ArrayMeta((4,), "float32")
    assert cc.plan_fingerprint([NoFp()], meta) is None


# ---- round trip + integrity ----


def test_round_trip_hits_and_identical_outputs(tmp_path):
    fn1, cache1 = _cached(tmp_path)
    out1 = np.asarray(fn1(*_args()))
    assert cache1.stats["misses"] == 1 and cache1.stats["puts"] == 1
    assert cache1.stats["compiles"] == 1
    assert fn1._cache_size() == 1

    # a fresh CachedJit over the same dir (a new process, effectively)
    fn2, cache2 = _cached(tmp_path)
    out2 = np.asarray(fn2(*_args()))
    assert cache2.stats["hits"] == 1 and cache2.stats["compiles"] == 0
    assert cache2.stats["load_ms"] > 0
    np.testing.assert_array_equal(out1, out2)

    # a second shape is its own entry under the same fingerprint
    out3 = fn2(np.float32(2.0), np.arange(16, dtype=np.float32))
    assert np.asarray(out3).shape == (16,)
    assert cache2.stats["misses"] == 1 and cache2.stats["puts"] == 1


def test_put_is_idempotent(tmp_path):
    fn, cache = _cached(tmp_path)
    fn(*_args())
    assert cache.put(FP, cc.CachedJit.shape_key(_args()), b"x",
                     (None, None)) is False  # entry already published
    assert cache.stats["puts"] == 1


def _entry_dirs(root):
    return [d for _t, _n, d in cc.CompileCache(root).entries()]


def test_digest_tamper_refused_quarantined_then_recompiled(tmp_path):
    fn1, cache1 = _cached(tmp_path)
    out1 = np.asarray(fn1(*_args()))
    (d,) = _entry_dirs(cache1.root)
    with open(os.path.join(d, cc.PROGRAM_FILE), "r+b") as f:
        f.write(b"\xff\xff\xff\xff")  # corrupt the payload in place

    fn2, cache2 = _cached(tmp_path)
    out2 = np.asarray(fn2(*_args()))  # refusal -> in-memory compile
    np.testing.assert_array_equal(out1, out2)
    assert cache2.stats["refused"] == 1 and cache2.stats["hits"] == 0
    assert cache2.stats["compiles"] == 1
    # quarantined AND re-published: the next reader hits clean
    fn3, cache3 = _cached(tmp_path)
    np.testing.assert_array_equal(np.asarray(fn3(*_args())), out1)
    assert cache3.stats["hits"] == 1 and cache3.stats["refused"] == 0


def test_jax_version_mismatch_refused(tmp_path):
    fn1, cache1 = _cached(tmp_path)
    fn1(*_args())
    (d,) = _entry_dirs(cache1.root)
    epath = os.path.join(d, cc.ENTRY_FILE)
    with open(epath, encoding="utf-8") as f:
        entry = json.load(f)
    entry["versions"]["jax"] = "0.0.0-someone-elses-toolchain"
    with open(epath, "w", encoding="utf-8") as f:
        json.dump(entry, f)

    fn2, cache2 = _cached(tmp_path)
    fn2(*_args())
    assert cache2.stats["refused"] == 1 and cache2.stats["hits"] == 0
    assert cache2.stats["compiles"] == 1


def test_torn_entry_missing_manifest_refused(tmp_path):
    fn1, cache1 = _cached(tmp_path)
    fn1(*_args())
    (d,) = _entry_dirs(cache1.root)
    os.remove(os.path.join(d, cc.ENTRY_FILE))
    fn2, cache2 = _cached(tmp_path)
    fn2(*_args())
    assert cache2.stats["refused"] == 1 and cache2.stats["compiles"] == 1


# ---- crash + race ----


def test_torn_put_fault_degrades_and_next_process_publishes(tmp_path):
    """serve/faults.py compile_cache_torn_put: a crash after staging,
    before the atomic rename — the dispatch still serves the in-memory
    program, no partial entry is visible, and an unfaulted process
    publishes cleanly afterwards."""
    plan = FaultPlan([FaultSpec(point="compile_cache_torn_put")])
    with serve_faults.inject(plan):
        fn1, cache1 = _cached(tmp_path)
        out1 = np.asarray(fn1(*_args()))  # publish crashes, call works
    assert plan.counts() == {"compile_cache_torn_put": 1}
    assert cache1.stats["puts"] == 0 and cache1.stats["compiles"] == 1
    assert _entry_dirs(cache1.root) == []  # nothing half-published

    fn2, cache2 = _cached(tmp_path)
    np.testing.assert_array_equal(np.asarray(fn2(*_args())), out1)
    assert cache2.stats["puts"] == 1
    fn3, cache3 = _cached(tmp_path)
    fn3(*_args())
    assert cache3.stats["hits"] == 1


def test_publish_race_loser_adopts_winner(tmp_path, monkeypatch):
    """Two processes publish the same entry: both stage, one rename
    wins, the loser's rename fails against the winner's directory and
    the loser adopts it (counted, staging cleaned, no exception)."""
    root = str(tmp_path / "cache")
    loser = cc.CompileCache(root)
    winner = cc.CompileCache(root)
    real_replace = os.replace
    state = {"raced": False}

    def racing_replace(src, dst):
        if not state["raced"]:
            state["raced"] = True
            # the winner publishes in the window between the loser's
            # staging and its rename
            assert winner.put(FP, "shape0", b"WINNER", (None, None))
        return real_replace(src, dst)

    monkeypatch.setattr(cc.os, "replace", racing_replace)
    assert loser.put(FP, "shape0", b"LOSER", (None, None)) is False
    assert loser.stats["put_races"] == 1 and loser.stats["puts"] == 0
    (d,) = _entry_dirs(root)
    with open(os.path.join(d, cc.PROGRAM_FILE), "rb") as f:
        assert f.read() == b"WINNER"
    # no staging litter from the lost race
    assert not [p for p in os.listdir(os.path.dirname(d))
                if p.startswith(".staging")]


def test_concurrent_threads_share_one_publish(tmp_path):
    fn, cache = _cached(tmp_path)
    outs = [None] * 8

    def call(i):
        outs[i] = np.asarray(fn(*_args()))

    threads = [threading.Thread(target=call, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert cache.stats["compiles"] == 1 and cache.stats["puts"] == 1
    for o in outs[1:]:
        np.testing.assert_array_equal(outs[0], o)


# ---- LRU budget ----


def test_lru_byte_budget_evicts_oldest(tmp_path):
    cache = cc.CompileCache(str(tmp_path), max_bytes=3000)
    now = 1_000_000.0
    for i in range(4):
        fp = f"{i:02d}" * 32
        assert cache.put(fp, "s", b"p" * 900, (None, None))
        d = cache._entry_dir(fp, "s")
        os.utime(d, (now + i, now + i))  # deterministic LRU order
    cache._evict_over_budget()
    assert cache.stats["evicted"] >= 1
    assert cache.size_bytes() <= 3000
    survivors = {d for _t, _n, d in cache.entries()}
    assert cache._entry_dir("03" * 32, "s") in survivors  # newest lives
    assert cache._entry_dir("00" * 32, "s") not in survivors  # oldest out


# ---- process-wide wiring + degrade ----


def test_env_var_installs_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("MMLSPARK_TPU_COMPILE_CACHE",
                       str(tmp_path / "envcache"))
    cc.reset()
    cache = cc.active()
    assert cache is not None
    assert cache.root == str(tmp_path / "envcache")


def test_unwritable_dir_degrades_to_one_warning():
    import logging

    records = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    h = _Capture()  # the repo's loggers don't propagate; attach directly
    cc._log.addHandler(h)
    try:
        assert cc.configure("/proc/definitely/not/writable") is None
    finally:
        cc._log.removeHandler(h)
    assert cc.active() is None
    assert any("compile cache disabled" in m for m in records)


def test_server_load_survives_unwritable_cache_dir(rng):
    """ServeConfig.compile_cache pointing at an unwritable dir (the
    tools/serve.py --compile-cache path) must degrade to in-memory
    compiles — the model still loads and serves."""
    bundle = _bundle()
    jm = JaxModel(model=bundle, input_col="image", output_col="scores")
    img = rng.integers(0, 255, (32 * 32 * 3,)).astype(np.uint8)
    server = ModelServer(ServeConfig(
        buckets=(1,), deadline_ms=None,
        compile_cache="/proc/definitely/not/writable"))
    try:
        server.add_model("m", jm, example=DataTable({"image": [img]}))
        out = server.submit(
            "m", DataTable({"image": [img]})).result(timeout=120)
        assert len(out) == 1 and "scores" in out
    finally:
        server.close()
    assert cc.active() is None  # degraded, not installed


def test_static_fingerprint_predicts_on_disk_entry(tmp_path, rng):
    """analysis.plan_fingerprints derived over an abstract TableSchema —
    no data, no compilation — names EXACTLY the entry directory a real
    cache-backed server load writes: the static fingerprint IS the
    runtime cache key, not an approximation of it."""
    from mmlspark_tpu.analysis import TableSchema, plan_fingerprints

    img = rng.integers(0, 255, (32 * 32 * 3,)).astype(np.uint8)
    jm = JaxModel(model=_bundle(), input_col="image",
                  output_col="scores")
    schema = TableSchema.from_table(DataTable({"image": [img]}))
    fps = plan_fingerprints([jm], schema)
    assert len(fps) == 1 and isinstance(fps[0], str) and len(fps[0]) == 64
    # precision is part of the key; a policy change is a different entry
    assert plan_fingerprints([jm], schema, precision="int8w")[0] != fps[0]

    server = ModelServer(ServeConfig(buckets=(1,), deadline_ms=None,
                                     compile_cache=str(tmp_path / "c")))
    try:
        server.add_model("m", jm, example=DataTable({"image": [img]}))
    finally:
        server.close()
    on_disk = {os.path.basename(os.path.dirname(root))
               for root, _dirs, files in os.walk(tmp_path / "c")
               if cc.ENTRY_FILE in files}
    assert on_disk == {fps[0]}


def test_server_warm_start_round_trip(tmp_path, rng):
    """In-process analog of the perf_smoke cross-process gate: a second
    ModelServer over FRESH model objects and the same cache dir loads
    every program from disk (hits == first load's puts, zero fresh
    compiles) and serves bit-identical outputs."""
    img = rng.integers(0, 255, (4, 32 * 32 * 3)).astype(np.uint8)
    outs, stats = [], []
    for _round in range(2):
        cc.reset()
        jm = JaxModel(model=_bundle(), input_col="image",
                      output_col="scores")
        server = ModelServer(ServeConfig(
            buckets=(1, 4), deadline_ms=None,
            compile_cache=str(tmp_path / "cache")))
        try:
            server.add_model("m", jm,
                             example=DataTable({"image": [img[0]]}))
            out = server.submit(
                "m", DataTable({"image": list(img)})).result(timeout=300)
            outs.append(np.stack(list(out["scores"])))
            stats.append(dict(cc.active().stats))
        finally:
            server.close()
    cold, warm = stats
    assert cold["puts"] >= 1 and cold["hits"] == 0
    assert warm["compiles"] == 0 and warm["puts"] == 0
    assert warm["hits"] == cold["puts"]
    np.testing.assert_array_equal(outs[0], outs[1])
