"""Tier-1 wiring of tools/lint_jax.py.

Two gates: the codebase itself must be clean (zero findings after the
curated allowlist — DEFAULT_ALLOWLIST documents every intentional
exception), and a fixture seeded with each anti-pattern must yield
exactly the expected findings (the lint finds what it claims to find).
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

from lint_jax import (  # noqa: E402
    DEFAULT_ALLOWLIST, lint_paths, lint_source, lint_source_full,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_codebase_is_clean():
    findings = lint_paths([os.path.join(REPO, "mmlspark_tpu")])
    assert findings == [], "\n".join(str(f) for f in findings)


def test_allowlist_is_curated_not_dead():
    # every allowlist entry must still suppress something real — a stale
    # entry silently widens the gate
    for suffix, rules in DEFAULT_ALLOWLIST.items():
        path = os.path.join(REPO, suffix)
        assert os.path.exists(path), f"allowlisted file {suffix} is gone"
        raw = lint_paths([path], allowlist={})
        hit_rules = {f.rule for f in raw}
        for rule in rules:
            assert rule in hit_rules, (
                f"allowlist entry ({suffix}, {rule}) suppresses nothing")


FIXTURE = '''
import jax
import numpy as np
from functools import partial
from jax.experimental.shard_map import shard_map          # JX103
from mmlspark_tpu.core.params import Param


class BadStage:
    tags = Param(default=[], doc="mutable default")       # JX104


@jax.jit
def step(params, x):
    y = np.asarray(x) + 1                                 # JX101
    s = float(x.sum())                                    # JX101
    return y, s, x.item()                                 # JX101


@partial(jax.jit, static_argnums=(1,))
def step2(x, k):
    return x.tolist()                                     # JX101


def fit(batches):
    for b in batches:
        f = jax.jit(lambda p, v: v + b)                   # JX102
    g = jax.experimental.shard_map.shard_map(step, None)  # JX103
    h = getattr(jax, "shard_map")                         # JX103
    return f, g, h


def traced_by_name(params, x):
    return int(x[0])                                      # JX101


jitted = jax.jit(traced_by_name)


def train(batches, state, step_masked):
    history = []
    for b in batches:
        state, metrics = step_masked(state, b)
        history.append(float(metrics["loss"]))            # JX105
        pending = metrics["loss"]
        x = float(pending)                                # JX105
        y = metrics["loss"].item()                        # JX105
    final = float(metrics["loss"])  # after the loop: drains, no stall
    return state, history, x, y, final


def serve_loop(batches, dispatch_async):
    results = []
    for b in batches:
        outs = dispatch_async(b)
        results.append(np.asarray(outs))              # JX106
        v = float(outs)                               # JX106
        w = outs.item()                               # JX106
    return results, v, w


def host_side_is_fine(x):
    # not jitted: host syncs here are intentional and unflagged
    return float(np.asarray(x).sum())


@jax.jit
def allowed(params, x):
    return x.item()  # lint-jax: allow(JX101)
'''


def test_fixture_yields_exactly_the_seeded_findings():
    findings = lint_source(FIXTURE, "fixture.py")
    got = sorted((f.rule, f.line) for f in findings)
    lines = FIXTURE.splitlines()
    want = sorted(
        (rule, i + 1)
        for i, text in enumerate(lines)
        for rule in ("JX101", "JX102", "JX103", "JX104", "JX105", "JX106")
        if f"# {rule}" in text)
    assert got == want, (got, want)


def test_direct_shard_map_is_not_flagged():
    # jax.shard_map is the call the tree makes (no version shim); the
    # rule only fires on the legacy experimental spelling
    src = ("import jax\n"
           "def f(body, m, i, o):\n"
           "    return jax.shard_map(body, mesh=m, in_specs=i,\n"
           "                         out_specs=o, check_vma=False)\n")
    assert lint_source(src, "x.py") == []
    src2 = "from jax.experimental.shard_map import shard_map\n"
    assert [f.rule for f in lint_source(src2, "x.py")] == ["JX103"]


def test_jx105_lagged_fetch_is_clean():
    # the one-step-lagged idiom: record the device scalar in the loop,
    # resolve it AFTER (or pragma the in-loop resolution of the previous
    # step's scalar, as train/loop.py does)
    src = ("def fit(batches, state, step):\n"
           "    for b in batches:\n"
           "        state, m = step(state, b)\n"
           "        pending = m['loss']\n"
           "    return float(pending)\n")
    assert lint_source(src, "x.py") == []
    src_sync = src.replace("        pending = m['loss']\n",
                           "        v = float(m['loss'])\n")
    assert [f.rule for f in lint_source(src_sync, "x.py")] == ["JX105"]


def test_jx105_pragma_suppresses():
    src = ("def fit(batches, state, step):\n"
           "    for b in batches:\n"
           "        state, m = step(state, b)\n"
           "        v = float(m['loss'])  # lint-jax: allow(JX105)\n"
           "    return v\n")
    assert lint_source(src, "x.py") == []


def test_jx105_ignores_non_step_calls():
    # scalar fetches on values from non-step calls in a loop are host-side
    # bookkeeping, not a pipeline stall — out of JX105's scope
    src = ("def walk(rows, measure):\n"
           "    total = 0.0\n"
           "    for r in rows:\n"
           "        v = measure(r)\n"
           "        total += float(v)\n"
           "    return total\n")
    assert lint_source(src, "x.py") == []


def test_jx106_windowed_drain_is_clean():
    # the sanctioned serve idiom (serve/batcher.py): push the dispatched
    # handle through a bounded window and fetch the OLDEST entry — the
    # fetch target comes off the window, not the fresh dispatch, so
    # packing of batch i+1 overlaps compute of batch i
    src = ("import numpy as np\n"
           "from collections import deque\n"
           "def serve(batches, transform_async):\n"
           "    window = deque()\n"
           "    for b in batches:\n"
           "        pending = transform_async(b)\n"
           "        window.append(pending)\n"
           "        if len(window) >= 2:\n"
           "            oldest = window.popleft()\n"
           "            out = np.asarray(oldest)\n"
           "    return [np.asarray(p) for p in window]\n")
    assert lint_source(src, "x.py") == []
    # the anti-pattern: immediate full-batch fetch of the fresh dispatch
    src_sync = ("import numpy as np\n"
                "def serve(batches, transform_async):\n"
                "    out = []\n"
                "    for b in batches:\n"
                "        pending = transform_async(b)\n"
                "        out.append(np.asarray(pending))\n"
                "    return out\n")
    assert [f.rule for f in lint_source(src_sync, "x.py")] == ["JX106"]


def test_jx106_pragma_suppresses_and_ignores_plain_calls():
    src = ("import numpy as np\n"
           "def serve(batches, dispatch):\n"
           "    for b in batches:\n"
           "        outs = dispatch(b)\n"
           "        v = float(outs)  # lint-jax: allow(JX106)\n"
           "    return v\n")
    assert lint_source(src, "x.py") == []
    # fetches on values from non-dispatch calls are host bookkeeping
    src_ok = ("import numpy as np\n"
              "def walk(rows, score):\n"
              "    total = 0.0\n"
              "    for r in rows:\n"
              "        v = score(r)\n"
              "        total += float(np.asarray(v))\n"
              "    return total\n")
    assert lint_source(src_ok, "x.py") == []


def test_jx109_lagged_decode_fetch_is_clean():
    # the serve/generate.py discipline: dispatch step t+1, then consume
    # step t's output — the in-loop fetch target is the PREVIOUS
    # dispatch, so device decode overlaps host token fan-out
    src = ("import numpy as np\n"
           "def loop(engine, steps, bufs):\n"
           "    prev = None\n"
           "    for _ in range(steps):\n"
           "        bufs, out = engine._decode.dispatch(bufs)\n"
           "        if prev is not None:\n"
           "            toks = np.asarray(prev)  # lint-jax: allow(JX109)\n"
           "        prev = out\n"
           "    return np.asarray(prev)\n")
    assert lint_source(src, "x.py") == []
    # the anti-pattern: fetch the CURRENT step's tokens before the next
    # dispatch — every token pays a full device round-trip
    src_sync = ("import numpy as np\n"
                "def loop(engine, steps, bufs):\n"
                "    for _ in range(steps):\n"
                "        bufs, out = engine._decode.dispatch(bufs)\n"
                "        toks = np.asarray(out)\n"
                "    return toks\n")
    assert [f.rule for f in lint_source(src_sync, "x.py")] == ["JX109"]


def test_jx109_matches_full_dotted_spelling():
    # JX109's source predicate sees the WHOLE dotted call spelling —
    # "self._decode.jitted" is decode-flavored even though the leaf
    # attribute ("jitted") says nothing about decoding
    src = ("import numpy as np\n"
           "def loop(self, steps, bufs, carry):\n"
           "    for _ in range(steps):\n"
           "        bufs, carry = self._decode.jitted(bufs, carry)\n"
           "        tok = int(np.asarray(carry)[0])\n"
           "    return tok\n")
    assert [f.rule for f in lint_source(src, "x.py")] == ["JX109"]


def test_jx109_wins_over_jx105_and_jx106_on_decode_calls():
    # "decode_step" is both step- and decode-flavored; "decode_dispatch"
    # both dispatch- and decode-flavored — one site, one rule: the
    # decode-aware JX109 claims them and JX105/JX106 stand down
    src = ("def gen(state, steps, decode_step):\n"
           "    for _ in range(steps):\n"
           "        state, tok = decode_step(state)\n"
           "        t = int(tok)\n"
           "    return t\n")
    assert [f.rule for f in lint_source(src, "x.py")] == ["JX109"]
    src2 = ("import numpy as np\n"
            "def gen(bufs, steps, decode_dispatch):\n"
            "    for _ in range(steps):\n"
            "        out = decode_dispatch(bufs)\n"
            "        toks = np.asarray(out)\n"
            "    return toks\n")
    assert [f.rule for f in lint_source(src2, "x.py")] == ["JX109"]


def test_jx109_pragma_suppresses_and_ignores_plain_calls():
    src = ("import numpy as np\n"
           "def loop(engine, steps, bufs):\n"
           "    for _ in range(steps):\n"
           "        bufs, out = engine.decode(bufs)\n"
           "        toks = np.asarray(out)  # lint-jax: allow(JX109)\n"
           "    return toks\n")
    assert lint_source(src, "x.py") == []
    # fetches on values from non-decode calls stay out of JX109's scope
    src_ok = ("import numpy as np\n"
              "def walk(rows, score):\n"
              "    for r in rows:\n"
              "        v = score(r)\n"
              "        s = float(np.asarray(v))\n"
              "    return s\n")
    assert lint_source(src_ok, "x.py") == []


JX107_FLAGGED = '''
import cv2
from mmlspark_tpu.native import imgops
from mmlspark_tpu.train import DeviceLoader, DevicePreprocess


def fit(batches, state, step_masked):
    for b in batches:
        img = imgops.resize(b, 32, 32)                # JX107
        raw = cv2.imdecode(b, 1)                      # JX107
        state, m = step_masked(state, img, raw)
    return state


def producer(chunks):
    for c in chunks:
        yield imgops.resize(c, 32, 32)                # JX107


def run(chunks, commit):
    return DeviceLoader(producer(chunks), commit, depth=2)
'''


def test_jx107_flags_host_image_work_when_spec_active():
    findings = lint_source(JX107_FLAGGED, "fixture107.py")
    got = sorted((f.rule, f.line) for f in findings)
    lines = JX107_FLAGGED.splitlines()
    want = sorted(("JX107", i + 1) for i, text in enumerate(lines)
                  if "# JX107" in text)
    assert got == want, (got, want)


def test_jx107_clean_counterparts():
    # 1) the same host image work with NO DevicePreprocess in the module:
    #    the legacy host-preprocess path is legitimate, not a finding
    clean = JX107_FLAGGED.replace(
        "from mmlspark_tpu.train import DeviceLoader, DevicePreprocess",
        "from mmlspark_tpu.train import DeviceLoader")
    assert lint_source(clean, "x.py") == []
    # 2) spec active, but the resize happens OUTSIDE the step loop /
    #    producer (one-off warmup, eval-time thumbnailing): clean
    src = ("from mmlspark_tpu.train import DevicePreprocess\n"
           "from mmlspark_tpu.native import imgops\n"
           "def thumbnail(img):\n"
           "    return imgops.resize(img, 8, 8)\n"
           "def fit(batches, state, step):\n"
           "    for b in batches:\n"
           "        state, m = step(state, b)\n"
           "    return state\n")
    assert lint_source(src, "x.py") == []
    # 3) pragma suppresses
    src_pragma = JX107_FLAGGED.replace(
        "imgops.resize(b, 32, 32)                # JX107",
        "imgops.resize(b, 32, 32)  # lint-jax: allow(JX107)").replace(
        "cv2.imdecode(b, 1)                      # JX107",
        "cv2.imdecode(b, 1)  # lint-jax: allow(JX107)").replace(
        "imgops.resize(c, 32, 32)                # JX107",
        "imgops.resize(c, 32, 32)  # lint-jax: allow(JX107)")
    assert lint_source(src_pragma, "x.py") == []


JX108_FLAGGED = '''
import jax
import numpy as np
import jax.numpy as jnp


@jax.jit
def step(params, x):
    scale = np.float64(0.5)                           # JX108
    y = x * scale
    return jnp.zeros((4,), dtype=np.float64) + y      # JX108


class Stage:
    def device_fn(self, meta):
        offset = np.double(1.0)                       # JX108

        def fwd(params, x):
            z = jnp.asarray(0.1, dtype="float64")     # JX108
            return x * offset + z

        return fwd


def train(batches, state, step_masked):
    for b in batches:
        lr = np.float64(1e-3)                         # JX108
        state, metrics = step_masked(state, b, lr)
    return state


def serve_loop(batches, dispatch_async):
    outs = []
    for b in batches:
        outs.append(dispatch_async(b * np.float64(2)))    # JX108
    return outs
'''


def test_jx108_flags_f64_in_device_code():
    findings = lint_source(JX108_FLAGGED, "fixture108.py")
    got = sorted((f.rule, f.line) for f in findings)
    lines = JX108_FLAGGED.splitlines()
    want = sorted(("JX108", i + 1) for i, text in enumerate(lines)
                  if "# JX108" in text)
    assert got == want, (got, want)


def test_jx108_clean_counterparts():
    # f32 spellings and python literals are the prescribed fix; f64 in
    # plain host code (no step/dispatch loop, not traced) is fine
    clean = JX108_FLAGGED.replace("float64", "float32").replace(
        "np.double", "np.float32")
    assert [f.rule for f in lint_source(clean, "x.py")
            if f.rule == "JX108"] == []
    host = ("import numpy as np\n"
            "def offline_report(rows):\n"
            "    acc = np.float64(0)\n"
            "    for r in rows:\n"
            "        acc += np.mean(r, dtype=np.float64)\n"
            "    return acc\n")
    assert lint_source(host, "x.py") == []


def test_jx108_pragma_suppresses():
    src = ("import jax\nimport numpy as np\n"
           "@jax.jit\n"
           "def step(x):\n"
           "    s = np.float64(0.5)  # lint-jax: allow(JX108)\n"
           "    return x * s\n")
    assert lint_source(src, "x.py") == []


JX30X_FLAGGED = '''
import threading
import time
import subprocess


_lock = threading.Lock()


def hold():
    with _lock:
        time.sleep(0.5)                               # JX301
        subprocess.run(["true"])                      # JX301


def manual():
    _lock.acquire()                                   # JX302
    work()
    _lock.release()


def spawn():
    t = threading.Thread(target=work)                 # JX303
    t.start()
    t.join()


def work():
    pass
'''


def test_jx30x_flags_the_shallow_concurrency_face():
    findings = lint_source(JX30X_FLAGGED, "fixture30x.py")
    got = sorted((f.rule, f.line) for f in findings)
    lines = JX30X_FLAGGED.splitlines()
    want = sorted((rule, i + 1) for i, text in enumerate(lines)
                  for rule in ("JX301", "JX302", "JX303")
                  if f"# {rule}" in text)
    assert got == want, (got, want)


def test_jx30x_clean_counterparts():
    # sleep outside the critical section, acquire chained to
    # try/finally, spawn with an explicit lifecycle: all clean
    src = ("import threading\nimport time\n"
           "_lock = threading.Lock()\n"
           "def hold():\n"
           "    with _lock:\n"
           "        pass\n"
           "    time.sleep(0.5)\n"
           "def manual():\n"
           "    _lock.acquire()\n"
           "    try:\n"
           "        pass\n"
           "    finally:\n"
           "        _lock.release()\n"
           "def spawn(work):\n"
           "    t = threading.Thread(target=work, daemon=True)\n"
           "    t.start()\n")
    assert lint_source(src, "x.py") == []
    # non-lockish receivers are out of scope for the shallow face
    src2 = ("import time\n"
            "def hold(session):\n"
            "    with session:\n"
            "        time.sleep(0.5)\n")
    assert lint_source(src2, "x.py") == []


def test_jx300_unjustified_jx3xx_pragma_is_a_finding():
    src = ("import threading\nimport time\n"
           "_lock = threading.Lock()\n"
           "def hold():\n"
           "    with _lock:\n"
           "        time.sleep(0.5)  # lint-jax: allow(JX301)\n")
    assert [f.rule for f in lint_source(src, "x.py")] == ["JX300"]


def test_justified_jx3xx_pragma_suppresses_and_records():
    src = ("import threading\nimport time\n"
           "_lock = threading.Lock()\n"
           "def hold():\n"
           "    with _lock:\n"
           "        time.sleep(0.5)"
           "  # lint-jax: allow(JX301): warm wait is the contract\n")
    findings, suppressed = lint_source_full(src, "x.py")
    assert findings == []
    assert len(suppressed) == 1
    f, why = suppressed[0]
    assert f.rule == "JX301"
    assert why == "warm wait is the contract"


def test_jx1xx_pragma_needs_no_justification():
    # the justification requirement is scoped to the concurrency face;
    # the established JX1xx pragma form stays valid
    src = ("import jax\n"
           "@jax.jit\n"
           "def f(x):\n"
           "    return x.item()  # lint-jax: allow(JX101)\n")
    assert lint_source(src, "x.py") == []


def test_allowlist_justifications_are_nonempty():
    for suffix, rules in DEFAULT_ALLOWLIST.items():
        for rule, why in rules.items():
            assert why.strip(), (
                f"allowlist entry ({suffix}, {rule}) has no justification")


def test_pragma_suppresses():
    src = ("import jax\n"
           "@jax.jit\n"
           "def f(x):\n"
           "    return x.item()  # lint-jax: allow(JX101)\n")
    assert lint_source(src, "x.py") == []
    src_no = src.replace("  # lint-jax: allow(JX101)", "")
    assert [f.rule for f in lint_source(src_no, "x.py")] == ["JX101"]
