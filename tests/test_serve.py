"""Serving semantics: the online model server must be *boring* —

* served outputs are bit-identical to offline ``PipelineModel.transform``
  for the same rows, regardless of how requests were packed into buckets;
* a burst of mixed-size requests compiles at most ``len(buckets)``
  programs (asserted via the jit compile-cache counter hook);
* overload and deadline paths return typed errors (``Overloaded``,
  ``DeadlineExceeded``) — never a partial result;
* shutdown drains: every admitted request is answered, and no batcher
  thread survives ``close()``.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax

from mmlspark_tpu.core.pipeline import PipelineModel
from mmlspark_tpu.core.retry import RetryPolicy
from mmlspark_tpu.core.schema import make_image
from mmlspark_tpu.core.stage import LambdaTransformer
from mmlspark_tpu.data.table import DataTable
from mmlspark_tpu.models.bundle import ModelBundle
from mmlspark_tpu.models.jax_model import JaxModel
from mmlspark_tpu.models.zoo import MLP, get_model
from mmlspark_tpu.serve import (
    THREAD_PREFIX, BadRequest, Client, DeadlineExceeded, ModelLoadError,
    ModelNotFound, ModelServer, Overloaded, ServeConfig, ServerClosed,
)
from mmlspark_tpu.stages.image import ImageTransformer, UnrollImage


def mlp_bundle(in_dim=6, out_dim=4, seed=0):
    module = MLP(features=(8,), num_outputs=out_dim)
    params = module.init(jax.random.PRNGKey(seed),
                         np.zeros((1, in_dim), np.float32))["params"]
    return ModelBundle(
        module=module,
        params=jax.tree_util.tree_map(np.asarray, params),
        input_spec=(in_dim,),
        output_names=("features", "logits"))


def vector_table(rows):
    return DataTable({"x": list(rows)})


def image_pipeline(seed=0):
    """The canonical fused chain: resize → unroll → score (3 device
    stages, ONE compiled program through the planner)."""
    stages = [
        ImageTransformer().resize(32, 32),
        UnrollImage(input_col="image", output_col="image_vec"),
        JaxModel(model=get_model("ConvNet_CIFAR10", widths=(8, 16),
                                 dense_width=32, seed=seed),
                 input_col="image_vec", output_col="scores"),
    ]
    return PipelineModel(stages)


def image_table(n, hw=40, seed=0):
    r = np.random.default_rng(seed)
    return DataTable({"image": [
        make_image(f"p{k}", r.integers(0, 255, (hw, hw, 3)))
        for k in range(n)]})


def sleepy_model(delay_s, out_col="out"):
    """Host-path model whose transform takes a known wall time."""
    def fn(table):
        time.sleep(delay_s)
        return table.with_column(
            out_col, np.asarray(table["x"], dtype=object))
    return LambdaTransformer(fn=fn)


# ---- parity: served == offline, regardless of packing ----


class TestParity:
    def test_single_stage_bit_identical_across_packings(self):
        jm = JaxModel(model=mlp_bundle(), input_col="x",
                      output_col="scores")
        rng = np.random.default_rng(0)
        rows = rng.normal(size=(40, 6)).astype(np.float32)
        offline = jm.transform(vector_table(rows))

        with ModelServer(ServeConfig(buckets=(1, 4, 16),
                                     max_queue=128)) as server:
            server.add_model("mlp", jm, example=vector_table(rows[:1]))
            # mixed request sizes force every packing shape
            sizes = [1, 2, 3, 5, 1, 4, 7, 1, 16, 2, 3, 5]
            handles, spans = [], []
            off = 0
            for n in sizes:
                if off + n > len(rows):
                    off = 0
                handles.append(server.submit(
                    "mlp", vector_table(rows[off:off + n])))
                spans.append((off, n))
                off += n
            for h, (off, n) in zip(handles, spans):
                out = h.result(timeout=60)
                assert len(out) == n
                for k in range(n):
                    assert np.array_equal(
                        np.asarray(out["scores"][k]),
                        np.asarray(offline["scores"][off + k]))

    def test_fused_pipeline_bit_identical_across_packings(self):
        pm = image_pipeline()
        table = image_table(24)
        offline = pm.transform(table)
        with ModelServer(ServeConfig(buckets=(1, 4, 16),
                                     max_queue=64)) as server:
            server.add_model("pipe", pm, example=table.take(np.arange(1)))
            handles = [
                server.submit("pipe", table.take(np.arange(i, i + n)))
                for i, n in [(0, 1), (1, 3), (4, 5), (9, 1), (10, 7),
                             (17, 2), (19, 5)]]
            outs = [h.result(timeout=120) for h in handles]
        row = 0
        for out in outs:
            for k in range(len(out)):
                assert np.array_equal(np.asarray(out["scores"][k]),
                                      np.asarray(offline["scores"][row]))
                row += 1
        assert row == 24

    def test_host_only_model_serves_through_fallback(self):
        # a pure-host transformer serves through the same batcher (no
        # async dispatch, same semantics)
        model = sleepy_model(0.0)
        rows = np.arange(6, dtype=np.float64)
        with ModelServer(ServeConfig(buckets=(1, 4),
                                     max_queue=16)) as server:
            server.add_model("host", model)
            out = server.predict("host", vector_table(rows[:3]),
                                 timeout=30)
            assert list(out["out"]) == list(rows[:3])


# ---- the bucket ladder bounds compilation ----


class TestCompileBound:
    def test_warmup_compiles_exactly_the_ladder(self):
        jm = JaxModel(model=mlp_bundle(), input_col="x",
                      output_col="scores")
        buckets = (1, 4, 16)
        with ModelServer(ServeConfig(buckets=buckets)) as server:
            server.add_model("mlp", jm, example=vector_table(
                np.zeros((1, 6), np.float32)))
            programs = server.compiled_programs("mlp")
            # one program per *distinct dp-rounded* bucket shape: under
            # the 8-virtual-device test mesh buckets 1 and 4 both round
            # to one 8-row shard shape, so the count can be below
            # len(buckets) — never above it
            assert programs is None or 1 <= programs <= len(buckets)

    def test_mixed_size_burst_compiles_at_most_len_buckets(self):
        jm = JaxModel(model=mlp_bundle(), input_col="x",
                      output_col="scores")
        rng = np.random.default_rng(1)
        rows = rng.normal(size=(64, 6)).astype(np.float32)
        buckets = (1, 4, 16)
        with ModelServer(ServeConfig(buckets=buckets,
                                     max_queue=256)) as server:
            server.add_model("mlp", jm, example=vector_table(rows[:1]))
            sizes = [1, 2, 3, 4, 5, 8, 13, 16, 1, 6, 11, 2, 9, 16, 7, 1]
            handles = [server.submit("mlp", vector_table(
                rows[:n])) for n in sizes]
            for h in handles:
                h.result(timeout=60)
            programs = server.compiled_programs("mlp")
            snap = server.stats("mlp").snapshot()
        # the compile-counter hook: the jitted composite's own cache
        assert programs is None or programs <= len(buckets), programs
        # and the seam-counted observable: distinct dispatched shapes
        assert snap["distinct_batch_shapes"] <= len(buckets)


# ---- admission control and deadlines ----


class TestAdmission:
    def test_queue_full_returns_typed_overloaded(self):
        model = sleepy_model(0.15)
        with ModelServer(ServeConfig(buckets=(1,), max_queue=2,
                                     warmup=False)) as server:
            server.add_model("slow", model)
            accepted, rejected = [], 0
            for i in range(8):
                try:
                    accepted.append(server.submit(
                        "slow", vector_table(np.arange(1.0))))
                except Overloaded as e:
                    rejected += 1
                    assert e.model == "slow" and e.max_queue == 2
            assert rejected >= 1, "queue never filled"
            for h in accepted:
                assert len(h.result(timeout=30)) == 1
            snap = server.stats("slow").snapshot()
            assert snap["rejected_overload"] == rejected
            assert snap["completed"] == len(accepted)

    def test_deadline_expiry_in_queue_is_cancelled_before_dispatch(self):
        model = sleepy_model(0.3)
        with ModelServer(ServeConfig(buckets=(1,), max_queue=8,
                                     warmup=False)) as server:
            server.add_model("slow", model)
            first = server.submit("slow", vector_table(np.arange(1.0)))
            # wait until the first request is actually dispatched, so the
            # second provably sits in the queue past its deadline
            deadline = time.monotonic() + 5
            while first._dispatched_at is None:
                assert time.monotonic() < deadline
                time.sleep(0.005)
            doomed = server.submit("slow", vector_table(np.arange(1.0)),
                                   deadline_ms=50)
            # don't await `doomed` yet: the BATCHER must observe the
            # expiry at pack time and cancel before dispatch
            assert len(first.result(timeout=30)) == 1
            wait_until = time.monotonic() + 5
            while not doomed.done:
                assert time.monotonic() < wait_until
                time.sleep(0.005)
            with pytest.raises(DeadlineExceeded) as exc:
                doomed.result(timeout=1)
            assert exc.value.where == "queued"
            snap = server.stats("slow").snapshot()
            assert snap["expired_deadline"] == 1
            assert doomed._dispatched_at is None  # cancelled pre-dispatch

    def test_inflight_deadline_returns_timeout_never_partial(self):
        model = sleepy_model(0.3)
        with ModelServer(ServeConfig(buckets=(1,), max_queue=8,
                                     warmup=False)) as server:
            server.add_model("slow", model)
            h = server.submit("slow", vector_table(np.arange(1.0)),
                              deadline_ms=100)
            with pytest.raises(DeadlineExceeded) as exc:
                h.result()
            assert exc.value.where in ("queued", "in-flight")
            # the batch completes later; its result must be discarded —
            # re-asking can only re-raise, never hand back data
            time.sleep(0.4)
            with pytest.raises(DeadlineExceeded):
                h.result()
            snap = server.stats("slow").snapshot()
            assert snap["timed_out"] >= 1

    def test_row_count_changing_model_fails_batch_never_misattributes(
            self):
        # a model that drops rows breaks the per-request split: offsets
        # would shift and neighbors would silently get each other's rows.
        # The whole batch must fail with a typed error instead
        def drop_first(table):
            import numpy as _np
            keep = _np.arange(1, len(table)) if len(table) > 1 \
                else _np.arange(len(table))
            return table.take(keep).with_column(
                "out", np.asarray(table["x"][len(table) - len(keep):],
                                  dtype=object))
        model = LambdaTransformer(fn=drop_first)
        with ModelServer(ServeConfig(buckets=(4,), max_queue=8,
                                     warmup=False)) as server:
            server.add_model("dropper", model)
            handles = [server.submit("dropper",
                                     vector_table(np.arange(2.0)))
                       for _ in range(2)]
            for h in handles:
                with pytest.raises(BadRequest, match="row count"):
                    h.result(timeout=30)
            assert server.stats("dropper").snapshot()["failed"] == 2

    def test_client_timeout_is_terminal_not_a_hang(self):
        # a give-up is final: repeat result() calls re-raise immediately
        # instead of blocking forever on an event the discarded
        # resolution will never set (and timed_out counts the transition
        # once, not every retry)
        model = sleepy_model(0.3)
        with ModelServer(ServeConfig(buckets=(1,), max_queue=8,
                                     warmup=False)) as server:
            server.add_model("slow", model)
            h = server.submit("slow", vector_table(np.arange(1.0)))
            with pytest.raises(TimeoutError):
                h.result(timeout=0.05)
            t0 = time.monotonic()
            with pytest.raises(TimeoutError):
                h.result()  # no timeout arg: must NOT wait forever
            assert time.monotonic() - t0 < 1.0
            time.sleep(0.4)  # batch completes; result stays discarded
            with pytest.raises(TimeoutError):
                h.result()
            assert server.stats("slow").snapshot()["timed_out"] == 1

    @pytest.mark.parametrize("bad_rows", [
        lambda rng: DataTable({"wrong": [rng.normal(
            size=6).astype(np.float32)]}),     # wrong column name
        lambda rng: DataTable({"x": [rng.normal(
            size=100).astype(np.float32)]}),   # same column, wrong width
    ], ids=["wrong-column", "wrong-shape"])
    def test_mismatched_request_fails_alone(self, bad_rows):
        # a request with the wrong columns OR the wrong per-row layout is
        # never packed with (and can never fail) well-formed neighbors
        jm = JaxModel(model=mlp_bundle(), input_col="x",
                      output_col="scores")
        rng = np.random.default_rng(7)
        rows = rng.normal(size=(4, 6)).astype(np.float32)
        with ModelServer(ServeConfig(buckets=(1, 8), max_queue=16,
                                     warmup=False)) as server:
            server.add_model("mlp", jm)
            good1 = server.submit("mlp", vector_table(rows[:2]))
            bad = server.submit("mlp", bad_rows(rng))
            good2 = server.submit("mlp", vector_table(rows[3:]))
            assert len(good1.result(timeout=30)) == 2
            assert len(good2.result(timeout=30)) == 1
            with pytest.raises(Exception) as exc:
                bad.result(timeout=30)
            assert not isinstance(exc.value, (DeadlineExceeded,
                                              TimeoutError))

    def test_bad_requests_are_typed(self):
        jm = JaxModel(model=mlp_bundle(), input_col="x",
                      output_col="scores")
        with ModelServer(ServeConfig(buckets=(1, 4),
                                     warmup=False)) as server:
            server.add_model("mlp", jm)
            with pytest.raises(BadRequest):  # empty
                server.submit("mlp", DataTable({"x": []}))
            with pytest.raises(BadRequest):  # larger than the top bucket
                server.submit("mlp", vector_table(
                    np.zeros((5, 6), np.float32)))
            with pytest.raises(ModelNotFound):
                server.submit("nope", vector_table(
                    np.zeros((1, 6), np.float32)))


# ---- lifecycle ----


class TestLifecycle:
    def test_drain_on_shutdown_answers_all_admitted(self):
        model = sleepy_model(0.02)
        server = ModelServer(ServeConfig(buckets=(1, 4), max_queue=64,
                                         warmup=False))
        server.add_model("slow", model)
        handles = [server.submit("slow", vector_table(np.arange(1.0)))
                   for _ in range(10)]
        server.close(drain=True)  # blocks until the worker drained
        for h in handles:
            assert len(h.result(timeout=1)) == 1
        snap = server.stats("slow").snapshot()
        assert snap["completed"] == 10
        with pytest.raises(ServerClosed):
            server.submit("slow", vector_table(np.arange(1.0)))

    def test_abort_close_fails_queued_with_server_closed(self):
        model = sleepy_model(0.2)
        server = ModelServer(ServeConfig(buckets=(1,), max_queue=16,
                                         warmup=False))
        server.add_model("slow", model)
        handles = [server.submit("slow", vector_table(np.arange(1.0)))
                   for _ in range(6)]
        server.close(drain=False)
        outcomes = []
        for h in handles:
            try:
                h.result(timeout=5)
                outcomes.append("ok")
            except ServerClosed:
                outcomes.append("closed")
        assert "closed" in outcomes  # queued work was failed, not served

    def test_no_leaked_threads_after_close(self, assert_no_leaked_threads):
        from conftest import thread_names
        assert_no_leaked_threads(THREAD_PREFIX, timeout=1.0)
        jm = JaxModel(model=mlp_bundle(), input_col="x",
                      output_col="scores")
        server = ModelServer(ServeConfig(buckets=(1, 4)))
        server.add_model("mlp", jm,
                         example=vector_table(np.zeros((1, 6), np.float32)))
        server.predict("mlp", vector_table(np.zeros((2, 6), np.float32)),
                       timeout=30)
        assert thread_names(THREAD_PREFIX) != []
        server.close()
        assert_no_leaked_threads(THREAD_PREFIX)


# ---- load-time validation (the analyzer gate) ----


class TestLoadValidation:
    def test_model_not_set_fails_load_fast(self):
        with ModelServer(ServeConfig(warmup=False)) as server:
            with pytest.raises(ModelLoadError) as exc:
                server.add_model("broken", JaxModel(
                    input_col="x", output_col="scores"))
            assert "model-not-set" in str(exc.value)
            assert server.models() == []

    def test_schema_size_mismatch_fails_load_fast(self):
        from mmlspark_tpu.analysis import ColumnInfo, TableSchema
        jm = JaxModel(model=mlp_bundle(in_dim=6), input_col="x",
                      output_col="scores")
        schema = TableSchema({"x": ColumnInfo.vector(5, "float32")})
        with ModelServer(ServeConfig(warmup=False)) as server:
            with pytest.raises(ModelLoadError) as exc:
                server.add_model("mlp", jm, schema=schema)
            assert "input-size-mismatch" in str(exc.value)


# ---- the HTTP front end ----


@pytest.fixture()
def http_mlp_server():
    from mmlspark_tpu.serve.http import start_http_server
    server = ModelServer(ServeConfig(buckets=(1, 4, 16), max_queue=64))
    server.add_model("mlp", mlp_bundle())  # bundle wrap: input → scores
    httpd = start_http_server(server, host="127.0.0.1", port=0)
    yield server, f"http://127.0.0.1:{httpd.server_address[1]}"
    httpd.shutdown()
    httpd.server_close()
    server.close()


def _post_json(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode("utf-8"),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req) as resp:
        return resp.status, json.loads(resp.read())


class TestHTTP:
    def test_json_predict_matches_offline(self, http_mlp_server):
        server, base = http_mlp_server
        rng = np.random.default_rng(3)
        x = rng.normal(size=(3, 6)).astype(np.float32)
        status, body = _post_json(
            f"{base}/v1/models/mlp:predict",
            {"rows": [{"input": r.tolist()} for r in x],
             "columns": ["scores"]})
        assert status == 200 and len(body["rows"]) == 3
        jm = JaxModel(model=mlp_bundle(), input_col="input",
                      output_col="scores")
        ref = jm.transform(DataTable({"input": list(x)}))
        for k in range(3):
            assert np.allclose(body["rows"][k]["scores"],
                               np.asarray(ref["scores"][k]), atol=1e-6)

    def test_health_models_and_stats_endpoints(self, http_mlp_server):
        _server, base = http_mlp_server
        with urllib.request.urlopen(f"{base}/healthz") as r:
            assert json.loads(r.read())["status"] == "ok"
        with urllib.request.urlopen(f"{base}/v1/models") as r:
            assert json.loads(r.read())["models"] == ["mlp"]
        with urllib.request.urlopen(f"{base}/v1/stats") as r:
            stats = json.loads(r.read())
        assert "mlp" in stats and "admitted" in stats["mlp"]

    def test_unknown_model_is_404_and_bad_body_is_400(self,
                                                      http_mlp_server):
        _server, base = http_mlp_server
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post_json(f"{base}/v1/models/nope:predict",
                       {"rows": [{"input": [0.0] * 6}]})
        assert exc.value.code == 404
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post_json(f"{base}/v1/models/mlp:predict", {"rows": []})
        assert exc.value.code == 400

    def test_arrow_round_trip(self, http_mlp_server):
        pa = pytest.importorskip("pyarrow")
        import io
        _server, base = http_mlp_server
        rng = np.random.default_rng(4)
        x = rng.normal(size=(2, 6)).astype(np.float32)
        arrow = DataTable({"input": list(x)}).to_arrow()
        sink = io.BytesIO()
        with pa.ipc.new_stream(sink, arrow.schema) as writer:
            writer.write_table(arrow)
        ctype = "application/vnd.apache.arrow.stream"
        req = urllib.request.Request(
            f"{base}/v1/models/mlp:predict", data=sink.getvalue(),
            headers={"Content-Type": ctype, "Accept": ctype})
        with urllib.request.urlopen(req) as resp:
            assert resp.status == 200
            out = DataTable.from_arrow(
                pa.ipc.open_stream(io.BytesIO(resp.read())).read_all()
                .combine_chunks().to_batches()[0])
        assert "scores" in out and len(out) == 2


class TestRetryAfterHeader:
    """errors.py tells clients to "retry with backoff"; the HTTP front
    must give them something to act on — the Retry-After header, on
    both backpressure paths (429 Overloaded, drain-time 503)."""

    def test_429_overloaded_carries_retry_after(self):
        from mmlspark_tpu.serve.http import start_http_server
        server = ModelServer(ServeConfig(buckets=(1,), max_queue=1,
                                         max_inflight=1, warmup=False,
                                         retry_after_s=2.5))
        httpd = start_http_server(server, host="127.0.0.1", port=0)
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        try:
            server.add_model("slow", sleepy_model(1.0))
            # saturate the pipeline: lane in-flight + scheduler-held +
            # the 1-deep queue = 3 accepted; while the first batch
            # sleeps, the queue slot stays occupied and the HTTP submit
            # must see 429
            handles = []
            deadline = time.monotonic() + 5
            while len(handles) < 3 and time.monotonic() < deadline:
                try:
                    handles.append(server.submit(
                        "slow", vector_table(np.arange(1.0))))
                except Overloaded:
                    time.sleep(0.01)
            assert len(handles) == 3, "pipeline never saturated"
            with pytest.raises(urllib.error.HTTPError) as exc:
                _post_json(f"{base}/v1/models/slow:predict",
                           {"rows": [{"x": 0.0}]})
            assert exc.value.code == 429
            # whole seconds, rounded UP from retry_after_s=2.5
            assert exc.value.headers["Retry-After"] == "3"
            for h in handles:
                h.result(timeout=30)
        finally:
            httpd.shutdown()
            httpd.server_close()
            server.close()

    def test_drain_time_healthz_503_carries_retry_after(
            self, http_mlp_server):
        server, base = http_mlp_server
        with urllib.request.urlopen(f"{base}/healthz") as r:
            assert r.status == 200
            assert r.headers.get("Retry-After") is None  # ready: none
        server.close(drain=True)
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(f"{base}/healthz")
        assert exc.value.code == 503
        assert exc.value.headers["Retry-After"] == "1"  # the default
        body = json.loads(exc.value.read())
        assert body["draining"] is True


class _Resolved:
    def __init__(self, table):
        self._table = table

    def result(self, timeout=None):
        return self._table


class _ScriptedServer:
    """Submit/predict fail `failures` times with `exc`, then succeed —
    the deterministic client-retry surface (no timing, no threads)."""

    def __init__(self, failures, exc):
        self.failures = failures
        self.exc = exc
        self.calls = 0

    def predict(self, model, rows, deadline_ms=None, timeout=None):
        self.calls += 1
        if self.calls <= self.failures:
            raise self.exc
        return rows

    def submit(self, model, rows, deadline_ms=None):
        self.calls += 1
        if self.calls <= self.failures:
            raise self.exc
        return _Resolved(rows)


class TestClientRetry:
    """Client.predict/predict_async retry= (core/retry.py): transient
    serving faults only — never DeadlineExceeded/BadRequest."""

    FAST = RetryPolicy(max_attempts=3, base_delay_s=0.0, jitter=0.0,
                       retry_on=(Overloaded,))

    def test_retried_to_success(self):
        from mmlspark_tpu.serve.errors import LaneFailed
        for exc in (Overloaded("m", 8, 8), LaneFailed("m", 0, "died")):
            stub = _ScriptedServer(2, exc)
            out = Client(stub).predict("m", vector_table(np.arange(1.0)),
                                       retry=True)
            assert stub.calls == 3 and len(out) == 1

    def test_budget_exhausted_raises_the_real_error(self):
        stub = _ScriptedServer(5, Overloaded("m", 8, 8))
        with pytest.raises(Overloaded):
            Client(stub).predict("m", vector_table(np.arange(1.0)),
                                 retry=self.FAST)
        assert stub.calls == 3  # max_attempts, then the typed error

    def test_non_retryable_passthrough(self):
        for exc in (BadRequest("nope"),
                    DeadlineExceeded("m", 100.0, "queued"),
                    ModelNotFound("m", [])):
            stub = _ScriptedServer(5, exc)
            with pytest.raises(type(exc)):
                Client(stub).predict("m", vector_table(np.arange(1.0)),
                                     retry=True)
            assert stub.calls == 1, f"{type(exc).__name__} was retried"

    def test_never_retry_wins_over_a_broad_caller_policy(self):
        from mmlspark_tpu.serve.errors import ServeError
        broad = RetryPolicy(max_attempts=5, base_delay_s=0.0, jitter=0.0,
                            retry_on=(ServeError,))
        stub = _ScriptedServer(5, DeadlineExceeded("m", 100.0, "queued"))
        with pytest.raises(DeadlineExceeded):
            Client(stub).predict("m", vector_table(np.arange(1.0)),
                                 retry=broad)
        assert stub.calls == 1
        # ...while genuinely transient faults DO use the broad budget
        stub = _ScriptedServer(4, Overloaded("m", 8, 8))
        out = Client(stub).predict("m", vector_table(np.arange(1.0)),
                                   retry=broad)
        assert stub.calls == 5 and len(out) == 1

    def test_predict_async_retries_submission_only(self):
        stub = _ScriptedServer(2, Overloaded("m", 8, 8))
        handle = Client(stub).predict_async(
            "m", vector_table(np.arange(1.0)), retry=True)
        assert stub.calls == 3
        assert len(handle.result()) == 1

    def test_default_off_and_client_wide_default(self):
        stub = _ScriptedServer(1, Overloaded("m", 8, 8))
        with pytest.raises(Overloaded):
            Client(stub).predict("m", vector_table(np.arange(1.0)))
        stub = _ScriptedServer(1, Overloaded("m", 8, 8))
        client = Client(stub, retry=self.FAST)  # client-wide default
        out = client.predict("m", vector_table(np.arange(1.0)))
        assert stub.calls == 2 and len(out) == 1

    def test_retry_against_a_real_overloaded_server(self):
        """End-to-end: a 1-deep queue under a slow model rejects, the
        retrying client eventually lands every request."""
        model = sleepy_model(0.05)
        with ModelServer(ServeConfig(buckets=(1,), max_queue=1,
                                     warmup=False)) as server:
            server.add_model("slow", model)
            client = Client(server, retry=RetryPolicy(
                max_attempts=8, base_delay_s=0.05, max_delay_s=0.4,
                jitter=0.0, retry_on=(Overloaded,)))
            outs = []
            for _ in range(4):
                outs.append(client.predict(
                    "slow", vector_table(np.arange(1.0)), timeout=30))
            assert all(len(o) == 1 for o in outs)


class TestHealthAndSLOSurfaces:
    def test_healthz_is_ready_and_drain_aware(self, http_mlp_server):
        server, base = http_mlp_server
        with urllib.request.urlopen(f"{base}/healthz") as r:
            body = json.loads(r.read())
        assert r.status == 200
        assert body["status"] == "ok" and body["ready"] is True
        assert body["draining"] is False and body["models"] == ["mlp"]
        assert body["model_health"]["mlp"]["state"] == "ok"
        # draining: readiness drops to 503 while the body keeps
        # answering
        server.close(drain=True)
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(f"{base}/healthz")
        assert exc.value.code == 503
        drained = json.loads(exc.value.read())
        assert drained["status"] == "draining"
        assert drained["ready"] is False and drained["draining"] is True
        # liveness is a separate surface: /livez stays 200 through the
        # drain, so a restart probe never kills a draining server
        with urllib.request.urlopen(f"{base}/livez") as r:
            assert r.status == 200
            assert json.loads(r.read()) == {"alive": True}

    def test_slo_endpoint_reports_burn_and_budget(self, http_mlp_server):
        server, base = http_mlp_server
        rng = np.random.default_rng(5)
        for _ in range(4):
            server.predict("mlp", DataTable({"input": list(
                rng.normal(size=(2, 6)).astype(np.float32))}))
        with urllib.request.urlopen(f"{base}/slo") as r:
            body = json.loads(r.read())
        slo = body["mlp"]
        assert slo["slo"]["objective"] == 0.999
        assert slo["budget_remaining"] == 1.0  # nothing failed
        assert slo["counters"]["completed"] == 4
        assert slo["health"]["state"] == "ok"
        assert slo["queue_depth"] == 0
        # a second poll is a second burn sample over real deltas: the
        # quiet window has no verdict, never a crash
        with urllib.request.urlopen(f"{base}/slo") as r:
            again = json.loads(r.read())
        assert again["mlp"]["burn_rate_short"] is None

    def test_unhealthy_model_fails_readiness(self):
        """Burn past the fast-burn threshold -> /healthz goes 503 with
        the unhealthy verdict (the state machine is wired to the real
        counters, not a synthetic status)."""
        from mmlspark_tpu.obs.slo import SLOSpec
        from mmlspark_tpu.serve.http import start_http_server
        # 50% objective, tiny short window, verdicts from 4 requests
        # up; long_window_s stays generous so the tracker's 2x-long
        # ring pruning can never drop the baseline sample on a slow box
        spec = SLOSpec(objective=0.5, window_s=0.05, long_window_s=10.0,
                       min_requests=4, fast_burn=1.5)
        server = ModelServer(ServeConfig(buckets=(1, 4), max_queue=64,
                                         slo=spec))
        httpd = start_http_server(server, host="127.0.0.1", port=0)
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        try:
            server.add_model("mlp", mlp_bundle())
            with urllib.request.urlopen(f"{base}/healthz") as r:
                assert json.loads(r.read())["ready"] is True
            # every request fails: the bundle wants 6-wide vectors
            bad = vector_table(np.zeros((1, 3), np.float32))
            for _ in range(8):
                with pytest.raises(Exception):
                    server.predict("mlp", bad, timeout=30)
            time.sleep(0.06)  # let the short window age past window_s
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(f"{base}/healthz")
            assert exc.value.code == 503
            body = json.loads(exc.value.read())
            assert body["model_health"]["mlp"]["state"] == "unhealthy"
            assert "burn" in body["model_health"]["mlp"]["reason"]
            # an alive-but-burning server must NOT fail liveness: a
            # restart would only amplify the incident
            with urllib.request.urlopen(f"{base}/livez") as r:
                assert r.status == 200
        finally:
            httpd.shutdown()
            httpd.server_close()
            server.close()

    def test_metrics_prometheus_content_negotiation(self,
                                                    http_mlp_server):
        server, base = http_mlp_server
        rng = np.random.default_rng(6)
        server.predict("mlp", DataTable({"input": list(
            rng.normal(size=(3, 6)).astype(np.float32))}))
        req = urllib.request.Request(
            f"{base}/metrics",
            headers={"Accept": "text/plain;version=0.0.4"})
        with urllib.request.urlopen(req) as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/plain")
            text = r.read().decode("utf-8")
        assert "# TYPE serve_admitted counter" in text
        assert 'serve_admitted{model="mlp"} 1' in text
        assert 'serve_rows_dispatched{model="mlp"} 3' in text
        assert "# TYPE serve_e2e_ms summary" in text
        # the default stays the JSON snapshot, byte-compatible shape
        with urllib.request.urlopen(f"{base}/metrics") as r:
            body = json.loads(r.read())
        assert "metrics" in body and "models" in body
        assert body["models"]["mlp"]["admitted"] == 1
        assert body["models"]["mlp"]["rows_dispatched"] == 3


class TestObsEndpointsUnderTraffic:
    def test_metrics_and_trace_consistent_during_drain(self):
        """Satellite pin: /metrics and /trace polled from other threads
        while requests are in flight AND while drain-on-close runs must
        always answer (200, valid JSON, monotonic counters) and must
        never block the drain."""
        from mmlspark_tpu import obs
        from mmlspark_tpu.serve.http import start_http_server
        polls: list[tuple] = []
        stop = threading.Event()
        server = httpd = poller = None
        try:
            # everything that leaks on failure (global tracer flag,
            # batcher/HTTP threads) is created inside the try so a bind
            # error can't poison later tests in the session
            obs.enable()
            server = ModelServer(ServeConfig(
                buckets=(1, 4), max_queue=64,
                deadline_ms=None, warmup=False))
            httpd = start_http_server(server, host="127.0.0.1", port=0)
            base = f"http://127.0.0.1:{httpd.server_address[1]}"

            def poll_loop():
                while not stop.is_set():
                    for path in ("/metrics", "/trace", "/healthz",
                                 "/livez", "/slo"):
                        try:
                            with urllib.request.urlopen(
                                    base + path, timeout=10) as r:
                                polls.append((path, r.status,
                                              json.loads(r.read())))
                        except urllib.error.HTTPError as e:
                            # only the drain-aware readiness flip is
                            # legal
                            polls.append((path, e.code,
                                          json.loads(e.read())))
                    time.sleep(0.005)

            poller = threading.Thread(target=poll_loop, daemon=True)
            server.add_model("m", sleepy_model(0.03))
            rng = np.random.default_rng(7)
            rows = rng.normal(size=(16, 4)).astype(np.float32)
            handles = [server.submit("m", vector_table(rows[i:i + 1]))
                       for i in range(16)]
            poller.start()
            t0 = time.monotonic()
            server.close(drain=True)  # drains ~16 x 30 ms of work
            drain_s = time.monotonic() - t0
            for h in handles:  # every admitted request was answered
                assert len(h.result(timeout=1)) == 1
        finally:
            stop.set()
            if poller is not None and poller.ident is not None:
                poller.join(timeout=10)
            if httpd is not None:
                httpd.shutdown()
                httpd.server_close()
            if server is not None:
                server.close()
            obs.disable()
            obs.clear()
        assert drain_s < 20.0, f"drain took {drain_s:.1f}s — an obs " \
            "poll blocked the drain"
        metrics = [p for p in polls if p[0] == "/metrics"]
        traces = [p for p in polls if p[0] == "/trace"]
        healths = [p for p in polls if p[0] == "/healthz"]
        assert metrics and traces and healths, polls
        # every poll answered with valid JSON; /metrics and /trace and
        # /slo never fail, /healthz only ever flips to the typed 503
        for path, status, _body in polls:
            assert status == 200 or (path == "/healthz"
                                     and status == 503), (path, status)
        # counter consistency across concurrent snapshots: admitted and
        # completed are monotonic, and completed never exceeds admitted
        seen_admitted = seen_completed = 0
        for _path, _status, body in metrics:
            snap = body["models"].get("m")
            if snap is None:
                continue
            assert snap["completed"] <= snap["admitted"] == 16
            assert snap["admitted"] >= seen_admitted
            assert snap["completed"] >= seen_completed
            seen_admitted = snap["admitted"]
            seen_completed = snap["completed"]
        # the trace bodies are well-formed Chrome traces throughout
        for _path, _status, body in traces:
            assert isinstance(body["traceEvents"], list)


class TestStatsPreTraffic:
    def test_snapshot_safe_before_any_traffic(self):
        """Regression (obs satellite): a freshly created ServerStats —
        e.g. /v1/stats polled right after a model loads, before the first
        request — must snapshot cleanly: empty percentile windows report
        None, never an empty-array percentile or a zero division."""
        from mmlspark_tpu.serve.stats import ServerStats

        snap = ServerStats(model="pre-traffic").snapshot()
        assert snap["admitted"] == 0 and snap["completed"] == 0
        assert snap["batches"] == 0 and snap["rows_dispatched"] == 0
        assert snap["batch_occupancy_mean"] is None
        assert snap["e2e_ms"] is None
        assert snap["queue_wait_ms"] is None
        assert snap["device_ms"] is None
        assert snap["occupancy_by_bucket"] == {}
        assert snap["distinct_batch_shapes"] == 0
        import json
        json.dumps(snap)  # JSON-safe as served by the HTTP front end

    def test_snapshot_values_backed_by_obs_primitives(self):
        """ServerStats is re-backed by the shared obs metrics — the
        snapshot must stay value-compatible with the pre-obs class."""
        from mmlspark_tpu.serve.stats import ServerStats

        stats = ServerStats(window=8, model="m")
        for k in range(3):
            stats.record_admitted()
        stats.record_done(e2e_ms=10.0, queue_ms=2.0)
        stats.record_batch(bucket=8, occupancy=5, device_ms=4.0,
                           shapes=((8, 6),))
        stats.record_rejected()
        snap = stats.snapshot()
        assert snap["admitted"] == 3 and snap["completed"] == 1
        assert snap["rejected_overload"] == 1
        assert snap["rows_dispatched"] == 5 and snap["rows_padded"] == 3
        assert snap["occupancy_by_bucket"] == {8: 1}
        assert snap["batch_occupancy_mean"] == 5.0
        assert snap["e2e_ms"]["p50"] == 10.0 and snap["e2e_ms"]["n"] == 1
        assert snap["distinct_batch_shapes"] == 1
        # the per-instance registry exposes the same series for /metrics
        reg_snap = stats.registry.snapshot()
        assert reg_snap["counters"]["serve.admitted{model=m}"] == 3


# ---- sharded serving (serve.mesh): DP replicas, tp/pp segments, lockstep ----


from mmlspark_tpu.core.stage import (  # noqa: E402
    ArrayMeta, DeviceOp, DeviceStage, HasInputCol, HasOutputCol,
    Transformer,
)
from mmlspark_tpu.serve import ServeMeshSpec  # noqa: E402


class PipelinedTanh(Transformer, DeviceStage, HasInputCol, HasOutputCol):
    """Test-only pp-served model: L tanh blocks. The host ``transform``
    is the sequential reference; the mesh-aware device op runs the SAME
    blocks through ``parallel.pipeline.pipeline_apply`` on the segment's
    replica mesh (the pp serving tier), with the stacked layer axis
    placed over ``pp`` via the ``device_param_rules`` hook."""

    from mmlspark_tpu.core.params import Param
    layers = Param(default=None, is_complex=True,
                   doc="list of {'w','b'} numpy layer dicts")
    microbatches = Param(default=2, type_=int, doc="pipeline microbatches")

    def transform(self, table):
        x = table.column_matrix(self.input_col, dtype=np.float32)
        for layer in self.layers:
            x = np.tanh(x @ layer["w"] + layer["b"])
        return table.with_column(self.output_col, list(x))

    # -- DeviceStage --

    def device_cache_token(self):
        return (id(self.layers), self.microbatches, self.input_col,
                self.output_col)

    def _stacked(self):
        return {k: np.stack([np.asarray(layer[k], np.float32)
                             for layer in self.layers])
                for k in ("w", "b")}

    def _dim(self):
        return int(np.asarray(self.layers[0]["w"]).shape[0])

    def device_fn(self, meta):
        # mesh-less planning/shape probe: the sequential layer scan
        import jax
        import jax.numpy as jnp
        d = self._dim()
        if tuple(meta.shape) != (d,):
            return None

        def fwd(params, x):
            def body(h, layer):
                return jnp.tanh(h @ layer["w"] + layer["b"]), None
            h, _ = jax.lax.scan(body, x.astype(jnp.float32), params)
            return h

        return DeviceOp(fwd, ArrayMeta((d,), "float32"),
                        params=self._stacked())

    def device_fn_mesh(self, meta, mesh):
        if mesh.shape.get("pp", 1) == 1:
            return self.device_fn(meta)
        d = self._dim()
        if tuple(meta.shape) != (d,):
            return None
        m = int(self.microbatches)

        def fwd(params, x):
            import jax.numpy as jnp

            from mmlspark_tpu.parallel.pipeline import pipeline_apply

            def block(layer, h):
                return jnp.tanh(h @ layer["w"] + layer["b"])

            return pipeline_apply(block, params, x.astype(jnp.float32),
                                  mesh, num_microbatches=m)

        return DeviceOp(fwd, ArrayMeta((d,), "float32"),
                        params=self._stacked())

    def device_param_rules(self, path, leaf):
        from jax.sharding import PartitionSpec as P
        return P("pp")  # stacked layer axis over the pipeline stages


class CollectiveLeak(Transformer, DeviceStage, HasInputCol, HasOutputCol):
    """A served segment smuggling a MANUAL collective — what the
    load-time sharded SPMD audit must reject on a dp-replica mesh."""

    def transform(self, table):
        return table.with_column(
            self.output_col,
            list(table.column_matrix(self.input_col, dtype=np.float32)))

    def device_cache_token(self):
        return (self.input_col, self.output_col)

    def device_fn(self, meta):
        import jax.numpy as jnp

        def fwd(params, x):
            return x.astype(jnp.float32)

        return DeviceOp(fwd, ArrayMeta(tuple(meta.shape), "float32"),
                        params=())

    def device_fn_mesh(self, meta, mesh):
        from jax.sharding import PartitionSpec as P

        def fwd(params, x):
            import jax

            def body(v):
                return jax.lax.psum(v, "pp")

            return jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                             out_specs=P(), check_vma=False)(
                                 x.astype(np.float32))

        return DeviceOp(fwd, ArrayMeta(tuple(meta.shape), "float32"),
                        params=())


def _score_rows(outs, spans):
    """request outputs -> {source row index: [score arrays seen]}."""
    seen: dict[int, list] = {}
    for out, (off, n) in zip(outs, spans):
        for k in range(n):
            seen.setdefault(off + k, []).append(
                np.asarray(out["scores"][k]))
    return seen


class TestShardedServing:
    def _serve_packed(self, mesh, sizes, rows, buckets=(1, 4, 16)):
        jm = JaxModel(model=mlp_bundle(), input_col="x",
                      output_col="scores")
        with ModelServer(ServeConfig(buckets=buckets, max_queue=128,
                                     mesh=mesh)) as server:
            server.add_model("mlp", jm, example=vector_table(rows[:1]))
            handles, spans, off = [], [], 0
            for n in sizes:
                if off + n > len(rows):
                    off = 0
                handles.append(server.submit(
                    "mlp", vector_table(rows[off:off + n])))
                spans.append((off, n))
                off += n
            outs = [h.result(timeout=120) for h in handles]
            snap = server.stats("mlp").snapshot()
            programs = server.compiled_programs("mlp")
        return outs, spans, snap, programs

    def test_dp_outputs_bit_identical_across_replica_counts_and_packings(
            self):
        """The acceptance pin: dp=N serving is bit-identical to
        single-chip (dp=1) serving for every packing and request
        interleaving, with compiled programs on the ladder per model."""
        rng = np.random.default_rng(11)
        rows = rng.normal(size=(40, 6)).astype(np.float32)
        sizes = [1, 2, 3, 5, 1, 4, 7, 1, 16, 2, 3, 5]
        reference: dict[int, np.ndarray] = {}
        for mesh, order in (("dp=1", sizes),
                            ("dp=2", list(reversed(sizes))),
                            ("dp=4", sizes)):
            outs, spans, snap, programs = self._serve_packed(
                mesh, order, rows)
            assert programs is None or programs <= 3, (mesh, programs)
            assert snap["distinct_batch_shapes"] <= 3
            dp = int(mesh.split("=")[1])
            assert set(snap["replicas"]) <= set(range(dp))
            assert sum(v["batches"] for v in snap["replicas"].values()) \
                == snap["batches"]
            for idx, arrays in _score_rows(outs, spans).items():
                for arr in arrays:
                    ref = reference.setdefault(idx, arr)
                    assert np.array_equal(ref, arr), (
                        f"{mesh}: row {idx} diverged from dp=1 serving")

    def test_dp_fanout_spreads_load_and_labels_replica_stats(self):
        rng = np.random.default_rng(12)
        rows = rng.normal(size=(64, 6)).astype(np.float32)
        jm = JaxModel(model=mlp_bundle(), input_col="x",
                      output_col="scores")
        with ModelServer(ServeConfig(buckets=(4,), max_queue=128,
                                     mesh="dp=4")) as server:
            server.add_model("mlp", jm, example=vector_table(rows[:1]))
            handles = [server.submit("mlp", vector_table(rows[i:i + 4]))
                       for i in range(0, 64, 4)]
            for h in handles:
                h.result(timeout=120)
            snap = server.stats("mlp").snapshot()
            reg = server.stats("mlp").registry.snapshot()["counters"]
        assert snap["batches"] == 16
        assert len(snap["replicas"]) >= 2, (
            f"least-loaded scheduling never fanned out: "
            f"{snap['replicas']}")
        for idx, rep in snap["replicas"].items():
            assert rep["batches"] >= 1
            assert rep["device_ms"] is not None
            # the replica label is a first-class series in the registry
            assert reg[f"serve.replica_batches{{model=mlp,replica={idx}}}"] \
                == rep["batches"]

    def test_tp_segment_matches_offline_transform(self):
        """Model-parallel tier: a tp=2-sharded serve segment (params
        column-sharded, GSPMD resharding only) equals the offline
        transform within the plan parity tolerance."""
        jm = JaxModel(model=mlp_bundle(), input_col="x",
                      output_col="scores")
        rng = np.random.default_rng(13)
        rows = rng.normal(size=(24, 6)).astype(np.float32)
        offline = jm.transform(vector_table(rows))
        with ModelServer(ServeConfig(buckets=(1, 8), max_queue=64,
                                     mesh="dp=1,tp=2")) as server:
            server.add_model("mlp", jm, example=vector_table(rows[:1]))
            handles = [server.submit("mlp", vector_table(rows[i:i + 8]))
                       for i in range(0, 24, 8)]
            outs = [h.result(timeout=120) for h in handles]
            snap = server.snapshot()["mlp"]
        assert snap["mesh"] == "dp=1,tp=2"
        row = 0
        for out in outs:
            for k in range(len(out)):
                assert np.allclose(np.asarray(out["scores"][k]),
                                   np.asarray(offline["scores"][row]),
                                   atol=1e-5)
                row += 1
        assert row == 24

    def test_shard_params_override_reaches_the_replica_lanes(self):
        """add_model(shard_params=...) overrides every replica's param
        placement — the explicit-placement escape hatch for models the
        generic rules misplace."""
        from mmlspark_tpu.parallel import mesh as mesh_lib
        calls = []

        def override(mesh, params):
            calls.append(dict(mesh.shape))
            return mesh_lib.param_shardings(mesh, params)

        jm = JaxModel(model=mlp_bundle(), input_col="x",
                      output_col="scores")
        rng = np.random.default_rng(17)
        rows = rng.normal(size=(8, 6)).astype(np.float32)
        offline = jm.transform(vector_table(rows))
        with ModelServer(ServeConfig(buckets=(8,), max_queue=16,
                                     mesh="dp=1,tp=2")) as server:
            server.add_model("mlp", jm, example=vector_table(rows[:1]),
                             shard_params=override)
            out = server.predict("mlp", vector_table(rows), timeout=60)
        assert calls and all(c["tp"] == 2 for c in calls)
        for k in range(8):
            assert np.allclose(np.asarray(out["scores"][k]),
                               np.asarray(offline["scores"][k]),
                               atol=1e-5)

    def test_pp_segment_matches_offline_transform(self):
        """Pipeline-parallel tier: a pp=4 serve segment (stacked layers
        over the pp ring via pipeline_apply, under the same bucket
        ladder) equals the sequential host transform."""
        rng = np.random.default_rng(14)
        d, n_layers = 16, 8
        layers = [{"w": (rng.normal(size=(d, d)) / np.sqrt(d)
                         ).astype(np.float32),
                   "b": rng.normal(size=d).astype(np.float32) * 0.1}
                  for _ in range(n_layers)]
        stage = PipelinedTanh(layers=layers, microbatches=2,
                              input_col="x", output_col="y")
        rows = rng.normal(size=(16, d)).astype(np.float32)
        offline = stage.transform(vector_table(rows))
        with ModelServer(ServeConfig(buckets=(8,), max_queue=64,
                                     mesh="pp=4")) as server:
            server.add_model("pp", stage, example=vector_table(rows[:1]))
            handles = [server.submit("pp", vector_table(rows[i:i + 8]))
                       for i in range(0, 16, 8)]
            outs = [h.result(timeout=120) for h in handles]
            programs = server.compiled_programs("pp")
        assert programs is None or programs <= 1
        row = 0
        for out in outs:
            for k in range(len(out)):
                assert np.allclose(np.asarray(out["y"][k]),
                                   np.asarray(offline["y"][row]),
                                   atol=1e-5), f"row {row}"
                row += 1
        assert row == 16

    def test_mesh_that_does_not_divide_devices_is_typed_load_error(self):
        jm = JaxModel(model=mlp_bundle(), input_col="x",
                      output_col="scores")
        for bad in ("dp=3", "dp=16", "dp=2,tp=3"):
            with ModelServer(ServeConfig(warmup=False)) as server:
                with pytest.raises(ModelLoadError, match="does not divide"):
                    server.add_model("mlp", jm, mesh=bad)
                assert server.models() == []

    def test_mesh_spec_parse_round_trip_and_errors(self):
        spec = ServeMeshSpec.parse("dp=4,tp=2")
        assert (spec.dp, spec.tp, spec.pp) == (4, 2, 1)
        assert spec.chips == 8 and spec.describe() == "dp=4,tp=2"
        assert ServeMeshSpec.parse({"dp": 2}).describe() == "dp=2"
        assert ServeMeshSpec.parse("dp=1,lockstep").lockstep is True
        for bad in ("dp", "dp=x", "sp=2"):
            with pytest.raises(ValueError):
                ServeMeshSpec.parse(bad)

    def test_lockstep_rejects_dp_fanout(self):
        """Lockstep serializes dispatch behind the drain fence, so a
        dp>1 fan-out could never be used — typed load error, no device
        work."""
        jm = JaxModel(model=mlp_bundle(), input_col="x",
                      output_col="scores")
        with ModelServer(ServeConfig(warmup=False)) as server:
            with pytest.raises(ModelLoadError, match="lockstep"):
                server.add_model("mlp", jm, mesh="dp=2,lockstep")
            assert server.models() == []

    def test_compat_key_is_deterministic_and_keys_every_column(self):
        """The batch-compatibility key is a pure function of layout (the
        lockstep signature hashes it): ragged columns key by their full
        cell-by-cell layout WITHOUT dropping the other columns, so
        requests whose ragged columns agree but whose entry columns
        differ never coalesce."""
        from mmlspark_tpu.serve.batcher import _compat_key
        ragged = [np.zeros(3, np.float32), np.zeros(5, np.float32)]

        def key(width):
            return _compat_key(DataTable(
                {"x": [np.zeros(width, np.float32)] * 2,
                 "tags": list(ragged)}))

        assert key(4) == key(4)          # deterministic across tables
        assert key(4) != key(8)          # ragged col can't mask 'x'
        uniform = _compat_key(DataTable(
            {"x": [np.zeros(4, np.float32)] * 2,
             "tags": [np.zeros(3, np.float32)] * 2}))
        assert key(4) != uniform         # never packs with well-formed

    def test_sharded_audit_rejects_manual_collective_segment(self):
        from mmlspark_tpu.analysis import ColumnInfo, TableSchema
        stage = CollectiveLeak(input_col="x", output_col="y")
        schema = TableSchema({"x": ColumnInfo.vector(8, "float32")})
        with ModelServer(ServeConfig(warmup=False)) as server:
            with pytest.raises(ModelLoadError, match="SPMD"):
                server.add_model("leak", stage, schema=schema, mesh="dp=2")
            assert server.models() == []

    def test_lockstep_fences_and_agrees_every_dispatch(self):
        """Collective-lockstep serving: every dispatched batch passes the
        drain fence + signature agreement, in order (the multi-host
        discipline, exercised single-process on the dryrun mesh)."""
        jm = JaxModel(model=mlp_bundle(), input_col="x",
                      output_col="scores")
        rng = np.random.default_rng(15)
        rows = rng.normal(size=(24, 6)).astype(np.float32)
        offline = jm.transform(vector_table(rows))
        with ModelServer(ServeConfig(buckets=(8,), max_queue=64,
                                     mesh="tp=2,lockstep")) as server:
            server.add_model("mlp", jm, example=vector_table(rows[:1]))
            handles = [server.submit("mlp", vector_table(rows[i:i + 8]))
                       for i in range(0, 24, 8)]
            outs = [h.result(timeout=120) for h in handles]
            coord = server._entry("mlp").batcher._lockstep
            snap = server.stats("mlp").snapshot()
        assert coord is not None and coord.steps == snap["batches"]
        assert coord.fingerprint != 0
        row = 0
        for out in outs:
            for k in range(len(out)):
                assert np.allclose(np.asarray(out["scores"][k]),
                                   np.asarray(offline["scores"][row]),
                                   atol=1e-5)
                row += 1

    def test_replica_spans_render_one_timeline_lane_per_replica(self):
        from mmlspark_tpu import obs
        from mmlspark_tpu.obs.export import REPLICA_TID_BASE, chrome_trace
        jm = JaxModel(model=mlp_bundle(), input_col="x",
                      output_col="scores")
        rng = np.random.default_rng(16)
        rows = rng.normal(size=(32, 6)).astype(np.float32)
        obs.enable()
        try:
            obs.clear()
            with ModelServer(ServeConfig(buckets=(4,), max_queue=64,
                                         mesh="dp=2")) as server:
                server.add_model("mlp", jm,
                                 example=vector_table(rows[:1]))
                handles = [server.submit("mlp",
                                         vector_table(rows[i:i + 4]))
                           for i in range(0, 32, 4)]
                for h in handles:
                    h.result(timeout=120)
                used = sorted(server.stats("mlp").snapshot()["replicas"])
            trace = chrome_trace()
        finally:
            obs.disable()
            obs.clear()
        lanes = {e["args"]["name"]: e["tid"] for e in trace["traceEvents"]
                 if e.get("ph") == "M"}
        # one synthetic lane per (model, replica), above the tid base so
        # real worker-thread lanes can never collide with it
        for idx in used:
            name = f"serve-replica-{idx} [mlp]"
            assert name in lanes and lanes[name] >= REPLICA_TID_BASE, lanes
        # replica spans actually moved onto the synthetic lanes
        replica_tids = {e["tid"] for e in trace["traceEvents"]
                        if e.get("ph") == "X"
                        and e["args"].get("replica") is not None}
        assert replica_tids == {lanes[f"serve-replica-{i} [mlp]"]
                                for i in used}
