"""Pallas device kernels: fused GroupNorm (ops/group_norm.py).

Runs through jax's Pallas interpreter on the CPU backend, asked for
explicitly (the ``pallas_interpret`` fixture — the kernel itself
executes, not a shadow implementation), checking numerical equivalence
against the jnp reference, the custom-vjp gradient path, the VMEM-fit
fallback gate, and checkpoint-compatible wiring into ResNet."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mmlspark_tpu.ops import group_norm, group_norm_reference
from mmlspark_tpu.ops.group_norm import _fits_vmem

pytestmark = pytest.mark.usefixtures("pallas_interpret")


class TestKernelEquivalence:
    @pytest.mark.parametrize("shape,groups", [
        ((2, 8, 8, 32), 8), ((3, 4, 4, 16), 4), ((1, 16, 16, 64), 8),
        ((2, 5, 7, 24), 3),  # non-square, odd spatial
    ])
    def test_matches_reference(self, shape, groups):
        r = np.random.default_rng(0)
        x = jnp.asarray(r.normal(size=shape).astype(np.float32) * 3 + 1)
        s = jnp.asarray(r.normal(size=shape[-1]).astype(np.float32))
        b = jnp.asarray(r.normal(size=shape[-1]).astype(np.float32))
        for relu in (False, True):
            got = group_norm(x, s, b, groups, relu=relu)
            want = group_norm_reference(x, s, b, groups, relu=relu)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-5, atol=2e-5)

    def test_bfloat16_input(self):
        r = np.random.default_rng(1)
        x = jnp.asarray(r.normal(size=(2, 8, 8, 32))).astype(jnp.bfloat16)
        s = jnp.ones(32); b = jnp.zeros(32)
        got = group_norm(x, s, b, 8)
        assert got.dtype == jnp.bfloat16
        want = group_norm_reference(x, s, b, 8)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-2, atol=2e-2)

    def test_gradients_through_custom_vjp(self):
        r = np.random.default_rng(2)
        x = jnp.asarray(r.normal(size=(2, 4, 4, 16)).astype(np.float32))
        s, b = jnp.ones(16), jnp.zeros(16)

        def loss(x, s, b):
            return jnp.sum(group_norm(x, s, b, 4, relu=True) ** 2)

        def loss_ref(x, s, b):
            return jnp.sum(group_norm_reference(x, s, b, 4, relu=True) ** 2)

        got = jax.grad(loss, argnums=(0, 1, 2))(x, s, b)
        want = jax.grad(loss_ref, argnums=(0, 1, 2))(x, s, b)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-4, atol=1e-4)


class TestF32Accumulation:
    """Regression pins for the accumulate-in-f32 contract (round 12): a
    bf16 activation policy (docs/quantization.md) feeds norms bf16
    inputs, so the statistics math must hold up independent of the
    input's numeric range. The kernel's former one-pass E[x²]−E[x]²
    variance cancelled catastrophically in f32 for offset feature maps
    (measured max err 69.2 at mean=200, spread=0.02 — vs 1e-3 for the
    two-pass form); both implementations are now pinned against the f64
    numpy oracle."""

    @staticmethod
    def oracle_f64(x, scale, bias, groups, eps=1e-6):
        n, h, w, c = x.shape
        cg = c // groups
        xf = np.asarray(x, np.float64).reshape(n, h * w, groups, cg)
        mean = xf.mean(axis=(1, 3), keepdims=True)
        var = ((xf - mean) ** 2).mean(axis=(1, 3), keepdims=True)
        out = (xf - mean) / np.sqrt(var + eps)
        return (out.reshape(n, h, w, c)
                * np.asarray(scale, np.float64)
                + np.asarray(bias, np.float64))

    @pytest.mark.parametrize("center,spread", [
        (0.0, 1.0), (8.0, 0.05), (64.0, 0.05), (200.0, 0.02),
    ])
    def test_offset_feature_maps_match_f64_oracle(self, center, spread):
        from mmlspark_tpu.ops.group_norm import _group_norm_fwd_pallas
        r = np.random.default_rng(0)
        x = jnp.asarray(
            r.normal(center, spread, (2, 8, 8, 32)).astype(np.float32))
        s = jnp.asarray(r.normal(size=32).astype(np.float32))
        b = jnp.asarray(r.normal(size=32).astype(np.float32))
        want = self.oracle_f64(np.asarray(x, np.float64), s, b, 8)
        for got in (_group_norm_fwd_pallas(x, s, b, 8, 1e-6, False),
                    group_norm_reference(x, s, b, 8)):
            err = np.abs(np.asarray(got, np.float64) - want).max()
            assert err < 5e-3, (center, spread, err)

    def test_bf16_inputs_track_f64_oracle(self):
        # bf16 input: the error floor is the input's own quantization —
        # the f32 statistics must not add to it materially
        from mmlspark_tpu.ops.group_norm import _group_norm_fwd_pallas
        r = np.random.default_rng(1)
        for center in (0.0, 64.0):
            x = jnp.asarray(r.normal(center, 0.05, (2, 8, 8, 32)),
                            jnp.bfloat16)
            s, b = jnp.ones(32), jnp.zeros(32)
            # the oracle consumes the SAME bf16-quantized values
            want = self.oracle_f64(np.asarray(x, np.float64), s, b, 8)
            got = np.asarray(_group_norm_fwd_pallas(
                x, s, b, 8, 1e-6, False), np.float64)
            assert np.abs(got - want).max() < 3e-2, center


class TestVmemGate:
    def test_large_blocks_fall_back(self):
        # the ResNet stem shape (112·112·64): C=64 pads to 128 lanes → 2×
        assert not _fits_vmem(112, 112, 64, 2)
        assert _fits_vmem(56, 56, 256, 2)      # biggest mid-stage block
        assert _fits_vmem(28, 28, 512, 2)

    def test_fallback_still_correct_and_is_counted(self):
        # a shape routed to the reference path must match it exactly —
        # and the switch must be visible, not quiet
        from mmlspark_tpu.obs.metrics import registry
        from mmlspark_tpu.ops.pallas.budget import FALLBACK_COUNTER
        counter = registry().counter(FALLBACK_COUNTER, kernel="group_norm")
        before = counter.value
        r = np.random.default_rng(3)
        x = jnp.asarray(r.normal(size=(1, 112, 112, 64)).astype(np.float32))
        s, b = jnp.ones(64), jnp.zeros(64)
        np.testing.assert_allclose(
            np.asarray(group_norm(x, s, b, 8)),
            np.asarray(group_norm_reference(x, s, b, 8)), rtol=1e-6)
        assert counter.value == before + 1

    def test_no_interpreter_off_tpu_refuses_loudly(self):
        # the wrapper never picks interpret mode from the backend: off
        # the chip, without the explicit request, the kernel refuses
        from jax.experimental.pallas import tpu as pltpu
        x = jnp.ones((1, 8, 8, 32))
        s, b = jnp.ones(32), jnp.zeros(32)
        with pltpu.force_tpu_interpret_mode(None):
            with pytest.raises(ValueError, match="interpret mode"):
                group_norm(x, s, b, 8)


class TestResNetWiring:
    @pytest.mark.slow
    def test_pallas_gn_params_are_checkpoint_compatible(self):
        """gn_impl='pallas' must produce the identical param tree as the
        default, so published bundles load into either variant."""
        from mmlspark_tpu.models.resnet import resnet18_thin

        r = np.random.default_rng(0)
        x = jnp.asarray(r.normal(size=(2, 32, 32, 3)).astype(np.float32))
        m_x = resnet18_thin(num_classes=5)
        m_p = resnet18_thin(num_classes=5, gn_impl="pallas")
        p_x = m_x.init(jax.random.PRNGKey(0), x)["params"]
        p_p = m_p.init(jax.random.PRNGKey(0), x)["params"]
        tx = jax.tree_util.tree_structure(p_x)
        tp = jax.tree_util.tree_structure(p_p)
        assert tx == tp

        # same weights → same outputs (within bf16 tolerance)
        a = np.asarray(m_x.apply({"params": p_x}, x, output="features"))
        c = np.asarray(m_p.apply({"params": p_x}, x, output="features"))
        np.testing.assert_allclose(a, c, rtol=3e-2, atol=3e-2)

    def test_zoo_exposes_gn_impl(self):
        from mmlspark_tpu.models.zoo import get_model
        b = get_model("ResNet_Small", num_classes=3, gn_impl="pallas")
        assert b.module.gn_impl == "pallas"


def test_indivisible_groups_raise():
    x = jnp.zeros((1, 4, 4, 20))
    s, b = jnp.ones(20), jnp.zeros(20)
    with pytest.raises(ValueError, match="not divisible"):
        group_norm(x, s, b, 3)
    with pytest.raises(ValueError, match="not divisible"):
        group_norm_reference(x, s, b, 3)


def test_unknown_gn_impl_raises():
    from mmlspark_tpu.models.resnet import resnet18_thin
    m = resnet18_thin(num_classes=2, gn_impl="Pallas")  # typo'd case
    with pytest.raises(ValueError, match="unknown gn_impl"):
        m.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)))


class TestDeviceAugment:
    """Device-side batched augmentation (ops/augment.py): jit-safe,
    per-sample randomness, exact semantics (SURVEY §2.5 item 4 — the
    in-step counterpart to the host-side ImageSetAugmenter)."""

    @staticmethod
    def batch(n=8, h=8, w=6, seed=0):
        r = np.random.default_rng(seed)
        return jnp.asarray(r.normal(size=(n, h, w, 3)).astype(np.float32))

    def test_flip_semantics_and_per_sample_independence(self):
        from mmlspark_tpu.ops import random_flip_lr
        x = self.batch(64)
        out = jax.jit(random_flip_lr)(jax.random.PRNGKey(0), x)
        flipped = np.asarray(out) == np.asarray(x[:, :, ::-1, :])
        kept = np.asarray(out) == np.asarray(x)
        per_sample_flip = flipped.all(axis=(1, 2, 3))
        per_sample_keep = kept.all(axis=(1, 2, 3))
        # every sample is exactly one of the two, and both occur
        assert (per_sample_flip | per_sample_keep).all()
        assert per_sample_flip.any() and per_sample_keep.any()

    def test_crop_matches_manual_slice(self):
        from mmlspark_tpu.ops import random_crop
        x = self.batch(4, h=8, w=8)
        out = jax.jit(lambda k, b: random_crop(k, b, 2))(
            jax.random.PRNGKey(3), x)
        assert out.shape == x.shape
        # each crop must appear verbatim inside the reflect-padded image
        padded = np.pad(np.asarray(x), ((0, 0), (2, 2), (2, 2), (0, 0)),
                        mode="reflect")
        for i in range(4):
            found = any(
                np.array_equal(padded[i, y:y + 8, xo:xo + 8], out[i])
                for y in range(5) for xo in range(5))
            assert found, f"crop {i} not a valid window"

    def test_brightness_and_contrast_bounds(self):
        from mmlspark_tpu.ops import random_brightness, random_contrast
        x = self.batch(16)
        out = random_brightness(jax.random.PRNGKey(1), x, 0.5)
        shift = (np.asarray(out) - np.asarray(x)).reshape(16, -1)
        assert (np.ptp(shift, axis=1) < 1e-5).all()  # per-sample constant
        assert (np.abs(shift[:, 0]) <= 0.5).all()
        out2 = random_contrast(jax.random.PRNGKey(2), x, 0.5, 1.5)
        m_in = np.asarray(x).mean(axis=(1, 2, 3))
        m_out = np.asarray(out2).mean(axis=(1, 2, 3))
        np.testing.assert_allclose(m_out, m_in, atol=1e-5)  # mean preserved

    def test_augment_batch_composes_under_jit(self):
        from mmlspark_tpu.ops import augment_batch
        x = self.batch(8)
        fn = jax.jit(lambda k, b: augment_batch(
            k, b, flip_lr=True, crop_pad=2, brightness=0.1,
            contrast=(0.9, 1.1)))
        a = fn(jax.random.PRNGKey(0), x)
        b = fn(jax.random.PRNGKey(0), x)
        c = fn(jax.random.PRNGKey(1), x)
        assert a.shape == x.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))  # keyed
        assert not np.array_equal(np.asarray(a), np.asarray(c))

    @pytest.mark.parametrize("dtype", [np.uint8, np.int16, np.float32])
    def test_ops_equal_numpy_oracle_given_the_drawn_params(self, dtype):
        """Property pin (round 10): each augmentation, fed the SAME
        per-sample draws (replayed through the documented key schedule),
        equals its numpy oracle — EXACTLY for integer dtypes (the
        round-half-even + clip edge semantics at the dtype bounds), and
        to reduction-order ULPs for float (XLA sums the contrast mean in
        a different order than numpy)."""
        from mmlspark_tpu.ops import (
            random_brightness, random_contrast, random_crop,
        )
        from mmlspark_tpu.ops.augment import (
            host_brightness, host_contrast, host_crop,
        )

        r = np.random.default_rng(11)
        if dtype == np.float32:
            x = r.normal(size=(24, 9, 7, 3)).astype(np.float32)
            delta = 0.3
        else:
            info = np.iinfo(dtype)
            # include exact boundary pixels so the clip edges are hit
            x = r.integers(info.min, int(info.max) + 1,
                           (24, 9, 7, 3)).astype(dtype)
            x[0] = info.max
            x[1] = info.min
            delta = 25.0
        key = jax.random.PRNGKey(7)

        def check(dev, host):
            dev = np.asarray(dev)
            if dtype == np.float32:
                np.testing.assert_allclose(dev, host, rtol=1e-6,
                                           atol=1e-6)
            else:
                np.testing.assert_array_equal(dev, host)

        shift = np.asarray(jax.random.uniform(
            key, (24, 1, 1, 1), minval=-delta, maxval=delta))
        check(random_brightness(key, jnp.asarray(x), delta),
              host_brightness(x, shift))

        factor = np.asarray(jax.random.uniform(
            key, (24, 1, 1, 1), minval=0.7, maxval=1.4))
        check(random_contrast(key, jnp.asarray(x), 0.7, 1.4),
              host_contrast(x, factor))

        ky, kx = jax.random.split(key)
        oy = np.asarray(jax.random.randint(ky, (24,), 0, 5))
        ox = np.asarray(jax.random.randint(kx, (24,), 0, 5))
        # pad+crop is pure indexing: exact for EVERY dtype
        np.testing.assert_array_equal(
            np.asarray(random_crop(key, jnp.asarray(x), 2)),
            host_crop(x, 2, oy, ox))

    def test_uint8_brightness_saturates_exactly_at_bounds(self):
        # an all-255 batch under any positive shift stays exactly 255;
        # an all-0 batch under any negative shift stays exactly 0 — the
        # boundary half of the round-and-clip contract
        from mmlspark_tpu.ops import random_brightness
        top = jnp.full((8, 4, 4, 3), 255, jnp.uint8)
        bot = jnp.zeros((8, 4, 4, 3), jnp.uint8)
        for seed in range(3):
            key = jax.random.PRNGKey(seed)
            shift = np.asarray(jax.random.uniform(
                key, (8, 1, 1, 1), minval=-30.0, maxval=30.0))
            up = np.asarray(random_brightness(key, top, 30.0))
            dn = np.asarray(random_brightness(key, bot, 30.0))
            assert (up[shift[:, 0, 0, 0] >= 0.5] == 255).all()
            assert (dn[shift[:, 0, 0, 0] <= -0.5] == 0).all()

    def test_uint8_batches_clip_instead_of_wrapping(self):
        # review finding r3: integer pixels must not wrap modularly on a
        # negative brightness draw nor truncate contrast factors to 0/1
        from mmlspark_tpu.ops import random_brightness, random_contrast
        r = np.random.default_rng(5)
        x = jnp.asarray(r.integers(0, 255, (32, 6, 6, 3)), jnp.uint8)
        out = random_brightness(jax.random.PRNGKey(0), x, 25.0)
        assert out.dtype == jnp.uint8
        diff = np.asarray(out, np.int32) - np.asarray(x, np.int32)
        # shifts stay bounded (no modular wrap to ~246)
        assert np.abs(diff).max() <= 26
        assert (diff < 0).any() and (diff > 0).any()  # darken AND brighten
        out2 = random_contrast(jax.random.PRNGKey(1), x, 0.8, 1.2)
        d2 = np.asarray(out2, np.int32) - np.asarray(x, np.int32)
        # intermediate contrast jitter occurs (not all samples 0-or-mean)
        changed = np.abs(d2).reshape(32, -1).max(axis=1)
        assert ((changed > 0) & (changed < 100)).any()
