"""Fused flash-style attention (ops/pallas/attention.py, round 12).

The PR 10 kernel discipline, applied to the new kernel pair: the Pallas
kernel is pinned ≤ 1 ULP against its XLA reference UNDER JIT (eager
comparisons drift via FMA contraction — repo convention), the numpy
oracle is pinned against the jitted reference, fully-masked rows are
exact zeros, and the ring/ulysses sequence-parallel paths keep their
reference parity with ``impl="pallas"`` (the local block as a kernel).
Runs through jax's Pallas interpreter on the CPU backend, asked for
explicitly (the ``pallas_interpret`` fixture) — the kernel body itself
executes, not a shadow path."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mmlspark_tpu.ops.pallas import attention as fa
from mmlspark_tpu.parallel.ring_attention import attention_reference

pytestmark = pytest.mark.usefixtures("pallas_interpret")


def bhtd(B=2, H=3, T=48, D=16, seed=0):
    r = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(r.normal(size=(B, H, T, D)), jnp.float32)
    return mk(), mk(), mk()


class TestKernelUlpPins:
    @pytest.mark.parametrize("causal", [False, True])
    def test_kernel_matches_reference_under_jit_one_ulp(self, causal):
        q, k, v = bhtd()
        mask = jnp.asarray(
            np.arange(48)[None, :] < np.asarray([48, 37])[:, None])

        def run(impl):
            fn = jax.jit(lambda a, b, c: fa.flash_attention(
                a, b, c, kv_mask=mask, causal=causal, impl=impl,
                block_k=16))
            return np.asarray(fn(q, k, v))

        np.testing.assert_array_max_ulp(run("xla"), run("pallas"),
                                        maxulp=1)

    def test_numpy_oracle_pinned_against_jitted_reference(self):
        q, k, v = bhtd(seed=1)
        mask = jnp.asarray(
            np.arange(48)[None, :] < np.asarray([48, 30])[:, None])
        ref = np.asarray(jax.jit(
            lambda a, b, c: fa.flash_attention(
                a, b, c, kv_mask=mask, impl="xla", block_k=16))(q, k, v))
        m3 = fa.host_mask3(2, 48, 48, np.asarray(mask), False)
        host = fa.flash_attention_host(
            np.asarray(q), np.asarray(k), np.asarray(v), m3,
            fa._resolve_scale(None, 16), block_k=16)
        np.testing.assert_allclose(host, ref, rtol=1e-5, atol=1e-6)

    def test_matches_plain_softmax_reference(self):
        # the online-softmax recurrence is algebra, not an approximation
        q, k, v = bhtd(seed=2)
        out = fa.flash_attention(q, k, v, impl="pallas", block_k=16)
        ref = attention_reference(q.transpose(0, 2, 1, 3),
                                  k.transpose(0, 2, 1, 3),
                                  v.transpose(0, 2, 1, 3))
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref).transpose(0, 2, 1, 3),
            rtol=2e-5, atol=2e-6)

    def test_fully_masked_rows_are_exact_zeros(self):
        q, k, v = bhtd(B=1, seed=3)
        none = jnp.zeros((1, 48), bool)
        for impl in ("xla", "pallas"):
            out = np.asarray(fa.flash_attention(q, k, v, kv_mask=none,
                                                impl=impl))
            assert (out == 0.0).all(), impl

    def test_unknown_impl_raises(self):
        q, k, v = bhtd(B=1, H=1, T=8, D=4)
        with pytest.raises(ValueError, match="unknown attention impl"):
            fa.flash_attention(q, k, v, impl="cuda")

    def test_vmem_gate(self):
        assert fa._fits_vmem(196, 196, 64, 128)     # ViT-B serving tile
        assert not fa._fits_vmem(16384, 16384, 128, 128)


class TestDecodeAttention:
    """The KV-cache decode variant: one query row per slot against the
    slot-major cache — flash attention at ``Tq=1`` through the same
    kernel. Pallas within a stated tolerance of the jitted XLA
    reference, the numpy oracle pinned against the jitted reference,
    fully-masked slots exact zeros."""

    def shkd(self, S=4, H=2, Tk=32, D=8, seed=11):
        r = np.random.default_rng(seed)
        q = jnp.asarray(r.normal(size=(S, H, D)), jnp.float32)
        k = jnp.asarray(r.normal(size=(S, H, Tk, D)), jnp.float32)
        v = jnp.asarray(r.normal(size=(S, H, Tk, D)), jnp.float32)
        mask = jnp.asarray(np.arange(Tk)[None, :]
                           <= np.asarray([5, 31, 0, 17])[:, None])
        return q, k, v, mask

    def test_kernel_matches_reference_under_jit(self):
        # a tolerance, not a ULP pin: the kernel contracts the one query
        # row as an 8-row MXU tile (Tq padded to the sublane tile) while
        # the vmapped reference contracts an M=1 dot_general, and XLA
        # accumulates the two in different orders — D-term f32 sums
        # differ by ~D·eps relative (measured 109 ULP at D=8). Same
        # bound the recurrence-vs-plain-softmax pins above use
        q, k, v, mask = self.shkd()

        def run(impl):
            fn = jax.jit(lambda a, b, c: fa.decode_attention(
                a, b, c, kv_mask=mask, impl=impl, block_k=16))
            return np.asarray(fn(q, k, v))

        np.testing.assert_allclose(run("pallas"), run("xla"),
                                   rtol=2e-5, atol=2e-6)

    def test_numpy_oracle_pinned_against_jitted_reference(self):
        q, k, v, mask = self.shkd(seed=12)
        ref = np.asarray(jax.jit(
            lambda a, b, c: fa.decode_attention(
                a, b, c, kv_mask=mask, impl="xla", block_k=16))(q, k, v))
        m3 = fa.host_mask3(4, 1, 32, np.asarray(mask), False)
        host = fa.flash_attention_host(
            np.asarray(q)[:, :, None, :], np.asarray(k), np.asarray(v),
            m3, fa._resolve_scale(None, 8), block_k=16)[:, :, 0]
        np.testing.assert_allclose(host, ref, rtol=1e-5, atol=1e-6)

    def test_decode_is_flash_attention_at_tq_one(self):
        q, k, v, mask = self.shkd(seed=13)
        out = fa.decode_attention(q, k, v, kv_mask=mask, impl="xla",
                                  block_k=16)
        full = fa.flash_attention(q[:, :, None, :], k, v, kv_mask=mask,
                                  impl="xla", block_k=16)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(full)[:, :, 0],
                                   rtol=2e-5, atol=2e-6)

    def test_fully_masked_slots_are_exact_zeros(self):
        q, k, v, _ = self.shkd(seed=14)
        none = jnp.zeros((4, 32), bool)
        for impl in ("xla", "pallas"):
            out = np.asarray(fa.decode_attention(q, k, v, kv_mask=none,
                                                 impl=impl))
            assert (out == 0.0).all(), impl


class TestBlockUpdate:
    """The ring-hop local block: one online update as a kernel."""

    def test_xla_and_pallas_updates_agree_under_jit(self):
        B, H, T, D = 2, 2, 16, 8
        q, k, v = bhtd(B, H, T, D, seed=4)
        keep = jnp.asarray(
            np.random.default_rng(5).random((B, T, T)) > 0.2)
        m0 = jnp.full((B, H, T, 1), -jnp.inf, jnp.float32)
        d0 = jnp.zeros((B, H, T, 1), jnp.float32)
        a0 = jnp.zeros((B, H, T, D), jnp.float32)
        scale = fa._resolve_scale(None, D)

        def run(impl):
            fn = jax.jit(lambda *a: fa.attention_block_update(
                *a, scale, impl=impl))
            return [np.asarray(x) for x in fn(q, k, v, keep, m0, d0, a0)]

        for got, want in zip(run("pallas"), run("xla")):
            np.testing.assert_array_max_ulp(got, want, maxulp=1)

    def test_one_update_equals_one_flash_tile(self):
        # a single full-width update + the final division IS flash
        # attention — the recurrence the ring accumulates hop by hop
        B, H, T, D = 1, 2, 24, 8
        q, k, v = bhtd(B, H, T, D, seed=6)
        keep = jnp.ones((B, T, T), bool)
        m0 = jnp.full((B, H, T, 1), -jnp.inf, jnp.float32)
        d0 = jnp.zeros((B, H, T, 1), jnp.float32)
        a0 = jnp.zeros((B, H, T, D), jnp.float32)
        scale = fa._resolve_scale(None, D)
        m, den, acc = fa.attention_block_update(q, k, v, keep, m0, d0,
                                                a0, scale, impl="xla")
        one_shot = acc / jnp.maximum(den, np.float32(1e-30))
        full = fa.flash_attention(q, k, v, scale=scale, impl="xla",
                                  block_k=T)
        np.testing.assert_allclose(np.asarray(one_shot),
                                   np.asarray(full), rtol=1e-6, atol=0)


@pytest.fixture(scope="module")
def sp_mesh():
    from mmlspark_tpu.parallel.mesh import MeshSpec, make_mesh
    return make_mesh(MeshSpec(dp=1, sp=8))


class TestSequenceParallelImpls:
    """ring/ulysses behind ``impl: auto|xla|pallas`` — the collective
    schedule is impl-independent; parity vs the single-device reference
    must hold either way (small shapes here; the long-context pins ride
    the slow suite below)."""

    def test_ring_parity_pallas(self):
        # the xla path is covered transitively: attention_block_update's
        # xla/pallas agreement is pinned bitwise above, and the slow
        # suite (test_sequence_parallel) runs ring's default path. A
        # 4-hop ring (sp=4 on a prefix of the 8 devices): every hop is an
        # interpreted kernel call per device, and an 8-hop ring costs
        # twice the tier-1 seconds for the same coverage
        from mmlspark_tpu.parallel.mesh import MeshSpec, make_mesh
        from mmlspark_tpu.parallel.ring_attention import ring_attention
        sp_mesh = make_mesh(MeshSpec(dp=1, sp=4))
        r = np.random.default_rng(7)
        B, L, H, D = 1, 16, 2, 8
        q, k, v = (jnp.asarray(r.normal(size=(B, L, H, D)), jnp.float32)
                   for _ in range(3))
        mask = jnp.asarray(np.arange(L)[None, :] < L - 5)
        ref = attention_reference(q, k, v, causal=True, kv_mask=mask)
        out = ring_attention(q, k, v, sp_mesh, causal=True, kv_mask=mask,
                             impl="pallas")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    def test_ulysses_parity(self, sp_mesh, impl):
        from mmlspark_tpu.parallel.ring_attention import ulysses_attention
        r = np.random.default_rng(8)
        B, L, H, D = 1, 16, 8, 8
        q, k, v = (jnp.asarray(r.normal(size=(B, L, H, D)), jnp.float32)
                   for _ in range(3))
        ref = attention_reference(q, k, v)
        out = ulysses_attention(q, k, v, sp_mesh, impl=impl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.slow  # long-context oracles (the acceptance pin: slow-suite
#                    parity unchanged with impl=pallas)
class TestLongContextPallas:
    def test_ring_2k_causal_masked_pallas(self, sp_mesh):
        from mmlspark_tpu.parallel.ring_attention import ring_attention
        r = np.random.default_rng(9)
        L = 2048
        q, k, v = (jnp.asarray(r.normal(size=(2, L, 2, 8)), jnp.float32)
                   for _ in range(3))
        mask = jnp.asarray(np.arange(L)[None, :] <
                           np.asarray([L, L - 300])[:, None])
        ref = attention_reference(q, k, v, causal=True, kv_mask=mask)
        out = ring_attention(q, k, v, sp_mesh, causal=True, kv_mask=mask,
                             impl="pallas")
        np.testing.assert_allclose(np.asarray(out)[0], np.asarray(ref)[0],
                                   rtol=5e-5, atol=5e-5)
        np.testing.assert_allclose(np.asarray(out)[1, :L - 300],
                                   np.asarray(ref)[1, :L - 300],
                                   rtol=5e-5, atol=5e-5)

    def test_ulysses_2k_pallas(self, sp_mesh):
        from mmlspark_tpu.parallel.ring_attention import ulysses_attention
        r = np.random.default_rng(10)
        q, k, v = (jnp.asarray(r.normal(size=(1, 2048, 8, 8)),
                               jnp.float32) for _ in range(3))
        ref = attention_reference(q, k, v)
        out = ulysses_attention(q, k, v, sp_mesh, impl="pallas")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=5e-5, atol=5e-5)


class TestViTFlashWiring:
    """The serving-path attention of models/vit.py: same param tree as
    the einsum path (checkpoints interchangeable), flash_xla and
    flash_pallas within f32 rounding of each other under jit, outputs
    close to the bhtd baseline."""

    def test_flash_variants_share_params_and_agree(self):
        from mmlspark_tpu.models.vit import vit_tiny
        r = np.random.default_rng(0)
        x = jnp.asarray(r.normal(size=(2, 16, 16, 3)), jnp.float32)
        base_model = vit_tiny(num_classes=4, image_patch=8)
        params = base_model.init(jax.random.PRNGKey(0), x)["params"]
        base = np.asarray(base_model.apply({"params": params}, x))
        outs = {}
        for ai in ("flash_xla", "flash_pallas"):
            m = vit_tiny(num_classes=4, image_patch=8, attn_impl=ai)
            tree = jax.tree_util.tree_structure(
                m.init(jax.random.PRNGKey(0), x)["params"])
            assert tree == jax.tree_util.tree_structure(params)
            outs[ai] = np.asarray(jax.jit(
                lambda xx, m=m: m.apply({"params": params}, xx))(x))
            np.testing.assert_allclose(outs[ai], base, rtol=1e-4,
                                       atol=1e-5)
        # not bit-equal: the kernel pads T=5 to whole tiles (8 query
        # rows, one 128-key stripe), so its reductions run over a
        # different tree than the reference's — last-digit f32 drift
        np.testing.assert_allclose(outs["flash_xla"],
                                   outs["flash_pallas"],
                                   rtol=1e-5, atol=1e-6)

    def test_unknown_flash_impl_raises(self):
        from mmlspark_tpu.models.vit import vit_tiny
        m = vit_tiny(num_classes=2, image_patch=8, attn_impl="flashy")
        with pytest.raises(ValueError, match="unknown attention impl"):
            m.init(jax.random.PRNGKey(0), jnp.zeros((1, 16, 16, 3)))
