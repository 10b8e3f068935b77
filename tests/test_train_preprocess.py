"""On-device train preprocessing (train/preprocess.py + ops/resize.py).

Four contract families:

* **Geometry parity** — the fused crop→resize→normalize pass (pure XLA)
  agrees with itself jitted and eager, and the numpy host oracle tracks
  it to FMA tolerance.
* **Spec semantics** — validation, static geometry replay (the
  analyzer's ``infer_schema`` face), deterministic per-step PRNG folds.
* **End-to-end wire-form parity** — thin uint8 batches vs
  host-preprocessed float batches produce equal loss histories for
  fit_arrays AND fit_stream; prefetch on/off stays bit-identical; a
  changed spec refuses to resume.
* **Analyzer/byte accounting** — ``audit_train_preprocess`` predictions
  equal the bytes observed at the ``core/plan.train_commit`` seam.
"""

import numpy as np
import pytest

import jax

from mmlspark_tpu.models.zoo import ConvNetCifar
from mmlspark_tpu.ops.resize import (
    fused_resize_norm, fused_resize_norm_host,
)
from mmlspark_tpu.train import (
    DevicePreprocess, TrainConfig, Trainer, envelope_batch, host_preprocess,
)
from mmlspark_tpu.train import preprocess as pp_lib


def _images(n=6, h=24, w=20, c=3, seed=0):
    r = np.random.default_rng(seed)
    return r.integers(0, 256, (n, h, w, c)).astype(np.uint8)


class TestFusedGeometry:
    CROP, OUT = (20, 16), (8, 8)

    def _offsets(self, n, seed=1):
        r = np.random.default_rng(seed)
        return (r.integers(0, 5, n).astype(np.int32),
                r.integers(0, 5, n).astype(np.int32))

    def _run(self, x, oy, ox, jit=True):
        fn = lambda a, b, c: fused_resize_norm(  # noqa: E731
            a, b, c, self.CROP, self.OUT, 1 / 255.0)
        if jit:
            fn = jax.jit(fn)
        return np.asarray(fn(x, oy, ox))

    def test_eager_drift_bounded_by_fma_contraction(self):
        # un-jitted, the vmapped blend gets FMA-contracted differently
        # than inside one jitted program (the context the train step
        # uses): 2 ULP bound
        x = _images()
        oy, ox = self._offsets(len(x))
        np.testing.assert_array_max_ulp(
            self._run(x, oy, ox), self._run(x, oy, ox, jit=False),
            maxulp=2)

    def test_host_oracle_tracks_to_fma_tolerance(self):
        x = _images()
        oy, ox = self._offsets(len(x))
        ref = np.asarray(fused_resize_norm(
            x, oy, ox, self.CROP, self.OUT, 1 / 255.0))
        host = fused_resize_norm_host(x, oy, ox, self.CROP, self.OUT,
                                      1 / 255.0)
        # XLA contracts the 4-tap blend into FMAs; numpy cannot — one
        # extra rounding per tap bounds the drift at 2 ULP
        np.testing.assert_array_max_ulp(ref, host, maxulp=2)

    def test_identity_geometry_equals_plain_cast(self):
        x = _images(4, 8, 8)
        z = np.zeros(4, np.int32)
        out = np.asarray(fused_resize_norm(
            x, z, z, (8, 8), (8, 8), 1 / 255.0))
        np.testing.assert_array_equal(
            out, x.astype(np.float32) * np.float32(1 / 255.0))

    def test_bad_inputs_raise(self):
        x = _images(2, 8, 8)
        z = np.zeros(2, np.int32)
        with pytest.raises(ValueError, match="larger than the source"):
            fused_resize_norm(x, z, z, (16, 8), (4, 4), 1.0)


class TestDevicePreprocessSpec:
    def test_parse_dict_and_identity(self):
        spec = DevicePreprocess.parse(
            {"resize": [32, 32], "flip_lr": True, "crop_pad": 4})
        assert spec.resize == (32, 32) and spec.flip_lr
        assert DevicePreprocess.parse(spec) is spec
        assert DevicePreprocess.parse(None) is None
        with pytest.raises(TypeError, match="DevicePreprocess"):
            DevicePreprocess.parse("resize=32")

    def test_validation_rejects_bad_fields(self):
        with pytest.raises(ValueError, match="resize"):
            DevicePreprocess(resize=(0, 32))
        with pytest.raises(ValueError, match="contrast"):
            DevicePreprocess(contrast=(1.2, 0.8))
        with pytest.raises(ValueError, match="crop_pad"):
            DevicePreprocess(crop_pad=-1)
        with pytest.raises(ValueError, match="zero"):
            DevicePreprocess(std=(0.5, 0.0, 0.5))

    def test_out_shape_replays_geometry(self):
        spec = DevicePreprocess(src_crop=(28, 28), resize=(16, 16),
                                crop_pad=2)
        assert spec.out_shape((32, 32, 3)) == (16, 16, 3)
        assert DevicePreprocess().out_shape((9, 7, 1)) == (9, 7, 1)
        with pytest.raises(ValueError, match="src_crop"):
            DevicePreprocess(src_crop=(40, 40)).out_shape((32, 32, 3))
        with pytest.raises(ValueError, match="crop_pad"):
            DevicePreprocess(crop_pad=9).out_shape((8, 8, 3))
        with pytest.raises(ValueError, match="channels"):
            DevicePreprocess(mean=(0.5, 0.5)).out_shape((8, 8, 3))
        with pytest.raises(ValueError, match="image geometry"):
            DevicePreprocess().out_shape((8, 8))

    def test_fingerprint_tracks_every_field(self):
        a = DevicePreprocess(flip_lr=True)
        b = DevicePreprocess(flip_lr=True, brightness=0.1)
        assert a.fingerprint() == DevicePreprocess(
            flip_lr=True).fingerprint()
        assert a.fingerprint() != b.fingerprint()

    def test_apply_keys_fold_per_step(self):
        # same step → identical pixels; different step → different draws
        spec = DevicePreprocess(crop_pad=2, flip_lr=True, brightness=0.2)
        x = _images(8, 8, 8).astype(np.float32) / 255.0
        key0 = jax.random.fold_in(jax.random.PRNGKey(0), 0)
        key1 = jax.random.fold_in(jax.random.PRNGKey(0), 1)
        a = np.asarray(pp_lib.apply(spec, key0, x, 1.0))
        b = np.asarray(pp_lib.apply(spec, key0, x, 1.0))
        c = np.asarray(pp_lib.apply(spec, key1, x, 1.0))
        np.testing.assert_array_equal(a, b)
        assert not np.array_equal(a, c)

    def test_apply_standardizes_after_augment(self):
        spec = DevicePreprocess(mean=(0.5,), std=(0.25,))
        x = _images(4, 6, 6, 1)
        out = np.asarray(pp_lib.apply(
            spec, jax.random.PRNGKey(0), x, 1 / 255.0))
        want = (x.astype(np.float32) * np.float32(1 / 255.0) - 0.5) / 0.25
        np.testing.assert_allclose(out, want, rtol=1e-6, atol=1e-6)

    def test_host_preprocess_matches_device_geometry(self):
        # host resize+normalize vs the device fused pass: same grids,
        # FMA-tolerance agreement
        spec = DevicePreprocess(resize=(16, 12))
        x = _images(5, 40, 36)
        host = host_preprocess(spec, x, 1 / 255.0)
        z = np.zeros(5, np.int32)
        dev = np.asarray(fused_resize_norm(
            x, z, z, (40, 36), (16, 12), 1 / 255.0))
        np.testing.assert_array_max_ulp(host, dev, maxulp=2)
        with pytest.raises(ValueError, match="src_crop"):
            host_preprocess(DevicePreprocess(src_crop=(8, 8)), x, 1.0)


class TestEnvelopeBatch:
    def test_pad_and_center_small_images(self):
        imgs = [np.full((4, 4, 3), 7, np.uint8)]
        out = envelope_batch(imgs, (8, 8))
        assert out.shape == (1, 8, 8, 3)
        assert (out[0, 2:6, 2:6] == 7).all()
        assert out.sum() == 7 * 4 * 4 * 3  # zero padding elsewhere

    def test_center_crop_large_images(self):
        img = np.arange(10 * 10).reshape(10, 10, 1).astype(np.uint8)
        out = envelope_batch([img], (6, 6))
        np.testing.assert_array_equal(out[0], img[2:8, 2:8])

    def test_ragged_batch_and_grayscale(self):
        imgs = [np.zeros((12, 4), np.uint8),       # HW grayscale
                np.ones((4, 12, 3), np.uint8)]
        out = envelope_batch(imgs, (8, 8))
        assert out.shape == (2, 8, 8, 3)
        assert envelope_batch([], (8, 8)).shape == (0, 8, 8, 3)

    def test_non_uint8_input_refused(self):
        # normalized floats silently truncate to all-zero uint8 — the
        # envelope refuses them loudly instead
        with pytest.raises(TypeError, match="uint8 wire form"):
            envelope_batch([np.random.default_rng(0).random((4, 4, 3))
                            .astype(np.float32)], (8, 8))

    def test_grids_stay_float32(self):
        # the shared-constants contract: every weight array is f32, so
        # the numpy oracle blends in the same precision the device
        # paths canonicalize to
        from mmlspark_tpu.ops.resize import _grids
        for g in _grids(20, 16, 8, 8)[4:]:
            assert g.dtype == np.float32


def _cfg(spec, depth=2, **kw):
    return TrainConfig(batch_size=16, epochs=1, optimizer="momentum",
                       learning_rate=0.01, log_every=1,
                       prefetch_depth=depth, preprocess=spec, seed=0,
                       **kw)


def _module():
    return ConvNetCifar(num_classes=4, widths=(4,), dense_width=8)


class TestEndToEndParity:
    """Thin uint8 vs host-preprocessed f32: the two wire forms of the
    same spec train identically (stochastic draws fold from the global
    step, so both runs augment the same pixels the same way)."""

    N, SIDE = 64, 16

    def _data(self, side=None):
        r = np.random.default_rng(3)
        x = r.integers(0, 256, (self.N, side or self.SIDE,
                                side or self.SIDE, 3)).astype(np.uint8)
        y = r.integers(0, 4, self.N).astype(np.int64)
        return x, y

    def test_fit_arrays_resize_geometry_parity(self):
        # REAL geometry on the wire: 24x24 source → 16x16 on device vs
        # host bilinear baseline; augment still on device in both runs
        spec = DevicePreprocess(resize=(16, 16), crop_pad=2,
                                flip_lr=True, brightness=0.1)
        x, y = self._data(side=24)
        tr_thin = Trainer(_module(), _cfg(spec))
        tr_thin.fit_arrays(x, y)
        tr_host = Trainer(_module(), _cfg(spec))
        tr_host.fit_arrays(host_preprocess(spec, x, 1 / 255.0), y)
        np.testing.assert_allclose(tr_thin.history, tr_host.history,
                                   rtol=0, atol=1e-5)

    def test_fit_stream_parity_and_prefetch_bit_identity(self):
        spec = DevicePreprocess(crop_pad=2, flip_lr=True,
                                brightness=0.1, contrast=(0.9, 1.1))
        x, y = self._data()

        def chunks(data):
            def source():
                for s in range(0, self.N, 20):  # ragged vs batch_size
                    yield data[s:s + 20], y[s:s + 20]
            return source

        tr_thin = Trainer(_module(), _cfg(spec))
        tr_thin.fit_stream(chunks(x))
        tr_host = Trainer(_module(), _cfg(spec))
        tr_host.fit_stream(chunks(host_preprocess(spec, x, 1 / 255.0)))
        np.testing.assert_allclose(tr_thin.history, tr_host.history,
                                   rtol=0, atol=1e-5)
        # prefetch off: bit-identical walk (preprocess lives in-step, so
        # the loader still only moves WHEN bytes cross, never what)
        tr_sync = Trainer(_module(), _cfg(spec, depth=0))
        tr_sync.fit_stream(chunks(x))
        assert tr_sync.history == tr_thin.history
        for a, b in zip(jax.tree_util.tree_leaves(tr_sync.params),
                        jax.tree_util.tree_leaves(tr_thin.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_src_crop_random_geometry_trains(self):
        # the fused random-crop-window path end to end (no host twin —
        # the draw lives in the step); shapes and finiteness are the pin
        spec = DevicePreprocess(src_crop=(12, 12), resize=(16, 16),
                                flip_lr=True)
        x, y = self._data(side=20)
        tr = Trainer(_module(), _cfg(spec))
        tr.fit_arrays(x, y)
        assert len(tr.history) == self.N // 16
        assert all(np.isfinite(v) for v in tr.history)

    def test_changed_spec_refuses_to_resume(self, tmp_path):
        spec = DevicePreprocess(flip_lr=True)
        x, y = self._data()
        cfg = _cfg(spec, checkpoint_dir=str(tmp_path), checkpoint_every=2)
        Trainer(_module(), cfg).fit_arrays(x, y)
        changed = _cfg(DevicePreprocess(flip_lr=True, brightness=0.2),
                       checkpoint_dir=str(tmp_path))
        with pytest.raises(ValueError, match="fingerprint mismatch"):
            Trainer(_module(), changed).fit_arrays(x, y)


class TestAnalyzerAndBytes:
    def test_audit_validates_geometry(self):
        from mmlspark_tpu.analysis import (
            SchemaError, audit_train_preprocess,
        )
        spec = DevicePreprocess(resize=(16, 16))
        audit = audit_train_preprocess(spec, (32, 32, 3), 16)
        assert audit.out_shape == (16, 16, 3)
        assert audit.thin_bytes == 16 * 32 * 32 * 3
        assert audit.host_bytes == 16 * 16 * 16 * 3 * 4
        assert "uint8" in audit.describe()
        with pytest.raises(SchemaError, match="src_crop"):
            audit_train_preprocess(
                DevicePreprocess(src_crop=(64, 64)), (32, 32, 3), 16)
        with pytest.raises(SchemaError, match="needs a spec"):
            audit_train_preprocess(None, (32, 32, 3), 16)

    def test_predicted_thin_bytes_equal_observed_seam_bytes(self):
        from mmlspark_tpu.analysis import audit_train_preprocess
        from mmlspark_tpu.core import plan

        spec = DevicePreprocess(crop_pad=2, flip_lr=True)
        r = np.random.default_rng(0)
        x = r.integers(0, 256, (32, 16, 16, 3)).astype(np.uint8)
        y = r.integers(0, 4, 32).astype(np.int64)
        audit = audit_train_preprocess(spec, x.shape[1:], 16)
        tr = Trainer(_module(), _cfg(spec))
        with plan.count_crossings() as c:
            tr.fit_arrays(x, y)
        aux = 2 * 16 * (8 + 4)  # per-step labels (int64) + mask (f32)
        assert c.upload_bytes - aux == 2 * audit.thin_bytes


def test_loader_wire_bytes_decompose_the_ab():
    # the loader-side observable: uint8 wire ≈ ¼ the f32 wire for the
    # same schedule (labels/mask identical across the A/B)
    spec = DevicePreprocess(flip_lr=True)
    r = np.random.default_rng(5)
    x = r.integers(0, 256, (64, 16, 16, 3)).astype(np.uint8)
    y = r.integers(0, 4, 64).astype(np.int64)
    tr_thin = Trainer(_module(), _cfg(spec))
    tr_thin.fit_arrays(x, y)
    tr_host = Trainer(_module(), _cfg(spec))
    tr_host.fit_arrays(host_preprocess(spec, x, 1 / 255.0), y)
    thin = tr_thin.input_stats["wire_mb"]
    host = tr_host.input_stats["wire_mb"]
    assert thin < host < 4.2 * thin
