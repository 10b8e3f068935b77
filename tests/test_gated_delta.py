"""The gated delta rule (``ops/pallas/gated_delta.py``): the Pallas kernel,
interpreted, and its array form against the literal per-position recurrence
in float64, at heads of 128 (the kernel's lane tile) on the CPU: windows
that are and are not whole chunks, two rows, decays near 0 and near 1, value
head ``h`` reading key head ``h // 2``; and a state lost at chunk boundaries,
or a value head on the wrong key head, is far outside the tolerance."""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mmlspark_tpu.obs.metrics import registry
from mmlspark_tpu.ops.pallas import gated_delta as gd

pytestmark = pytest.mark.usefixtures("pallas_interpret")

HK, DK, HV, DV = 2, 128, 4, 128
SIZES = dict(key_heads=HK, key_dim=DK, value_heads=HV, value_dim=DV)


def literal(qkv, g, beta, reset: int = 0, head_of=lambda h: h // 2):
    """``o [rows, L, HV x DV]`` a position at a time in float64: ``S <- a
    S``, ``d = beta (v - S^T k)``, ``S <- S + k d^T``, ``o = S^T q``;
    ``reset`` zeroes the state every that many positions; ``head_of`` maps
    a value head to its key head."""
    rows, length, _ = qkv.shape
    grouped = np.asarray(qkv, np.float64).reshape(rows, length, HK, -1)
    q, k = grouped[..., :DK], grouped[..., DK:2 * DK]
    v = grouped[..., 2 * DK:].reshape(rows, length, HV, DV)
    q = q / np.sqrt((q * q).sum(-1, keepdims=True) + 1e-6) * DK ** -0.5
    k = k / np.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    g, beta = np.asarray(g, np.float64), np.asarray(beta, np.float64)
    out = np.zeros((rows, length, HV, DV))
    for r in range(rows):
        s = np.zeros((HV, DK, DV))
        for t in range(length):
            if reset and t % reset == 0:
                s[:] = 0
            kk, qq = k[r, t, [head_of(h) for h in range(HV)]], \
                q[r, t, [head_of(h) for h in range(HV)]]
            s *= np.exp(g[r, t])[:, None, None]
            delta = beta[r, t][:, None] * (
                v[r, t] - np.einsum("hkv,hk->hv", s, kk))
            s += kk[:, :, None] * delta[:, None, :]
            out[r, t] = np.einsum("hkv,hk->hv", s, qq)
    return out.reshape(rows, length, HV * DV)


def case(length: int, rows: int = 2, decay: tuple = (1e-3, 1.0),
         seed: int = 0):
    """Inputs of ``rows`` rows: ``alpha = exp(g)`` with ``-g`` log-uniform
    over ``decay``."""
    rng = np.random.default_rng(seed)
    qkv = jnp.asarray(rng.normal(size=(rows, length, HK * (2 * DK + 2 * DV))),
                      jnp.float32)
    g = jnp.asarray(-np.exp(rng.uniform(*np.log(decay), (rows, length, HV))),
                    jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 1, (rows, length, HV)), jnp.float32)
    return qkv, g, beta


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("length,decay", [
    (128, (1e-3, 1.0)),         # two whole chunks
    (200, (1e-3, 1.0)),         # three chunks and a tail
    (200, (1e-6, 1e-4)),        # alpha near 1: the state carries far
    (136, (3.0, 30.0)),         # alpha near 0: forgets within a position
])
def test_both_forms_match_the_literal_recurrence(impl, length, decay):
    qkv, g, beta = case(length, decay=decay)
    got = np.asarray(gd.gated_delta(qkv, g, beta, impl=impl, **SIZES))
    want = literal(qkv, g, beta)
    # float32 products on both sides: the chunked form sums in another
    # order and solves the triangle by doubling (values of ~0.1)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-4)
    assert got.shape == (2, length, HV * DV)


def test_a_state_lost_at_chunk_boundaries_or_a_wrong_key_head_fails():
    qkv, g, beta = case(200, decay=(1e-4, 1e-2), seed=1)
    got = np.asarray(gd.gated_delta(qkv, g, beta, impl="pallas", **SIZES))
    lost = literal(qkv, g, beta, reset=gd.CHUNK)
    wrong = literal(qkv, g, beta, head_of=lambda h: h % HK)
    # the tolerance above is 2e-6 absolute: both are thousands of times it
    assert np.abs(got - lost)[:, gd.CHUNK:].max() > 1e-2
    assert np.abs(got - wrong).max() > 1e-2
    # value heads 0 and 1 read key head 0: their outputs are the same
    # function of it (same decays and betas give the same answer)
    same = case(64, seed=2)
    g2 = same[1].at[..., 1].set(same[1][..., 0])
    b2 = same[2].at[..., 1].set(same[2][..., 0])
    v = np.array(same[0]).reshape(2, 64, HK, -1)
    v[..., 2 * DK + DV:2 * DK + 2 * DV] = v[..., 2 * DK:2 * DK + DV]
    o = np.asarray(gd.gated_delta(jnp.asarray(v.reshape(2, 64, -1)), g2, b2,
                                  impl="pallas", **SIZES))
    np.testing.assert_array_equal(o[..., :DV], o[..., DV:2 * DV])


def test_the_kernel_equals_the_array_form_in_float32():
    qkv, g, beta = case(256, rows=1, seed=3)
    kernel = np.asarray(gd.gated_delta(qkv, g, beta, impl="pallas", **SIZES))
    array = np.asarray(gd.gated_delta(qkv, g, beta, impl="xla", **SIZES))
    np.testing.assert_allclose(kernel, array, atol=1e-6, rtol=1e-5)


@pytest.mark.parametrize("block", [None, 64])
def test_the_doubling_solve_inverts_the_unit_lower_triangle(block):
    """A whole triangle, and (as the kernel stacks two value heads' chunks)
    one block-diagonal in blocks of 64 whose doubling stops at the block."""
    rng = np.random.default_rng(4)
    a = np.tril(rng.uniform(-1, 1, (3, 64, 64)), -1).astype(np.float32)
    if block is not None:
        # the same triangle twice down the diagonal, nothing between
        zero = np.zeros_like(a)
        a = np.concatenate([np.concatenate([a, zero], axis=2),
                            np.concatenate([zero, a], axis=2)], axis=1)
    got = np.asarray(gd.unit_lower_inverse(jnp.asarray(a), block=block))
    want = np.linalg.inv(np.eye(a.shape[-1]) + a.astype(np.float64))
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    assert np.abs(np.triu(got, 1)).max() == 0.0


def test_blocks_follow_the_shapes_and_odd_heads_give_way():
    # the cell's rows: 8192 positions in blocks of 1024 (16 chunks)
    assert gd.block_positions(8192, 2, 128, 128, 2) == 1024
    # a tail: blocks that divide the whole chunks the row needs
    assert gd.block_positions(200, 2, 128, 128, 4) == 256
    # heads that are no whole lane tile take the array form
    assert gd.block_positions(8192, 2, 16, 16, 2) == 0
    rng = np.random.default_rng(5)
    small = dict(key_heads=2, key_dim=16, value_heads=4, value_dim=16)
    qkv = jnp.asarray(rng.normal(size=(1, 70, 2 * (32 + 32))), jnp.float32)
    g = jnp.full((1, 70, 4), -0.1, jnp.float32)
    beta = jnp.full((1, 70, 4), 0.5, jnp.float32)
    with pytest.raises(ValueError, match="gated_delta"):
        gd.gated_delta(qkv, g, beta, impl="pallas", **small)
    with pytest.raises(ValueError, match="whole number"):
        gd.gated_delta(qkv, g, beta, key_heads=3, key_dim=16,
                       value_heads=4, value_dim=16)


def test_the_kernel_call_is_counted_when_traced():
    qkv, g, beta = case(128, rows=1, seed=6)
    before = registry().value(gd.GRID_STEPS_COUNTER) or 0
    jax.jit(lambda *a: gd.gated_delta(*a, impl="pallas", **SIZES)).lower(
        qkv, g, beta)
    # one row x two key heads x one block
    assert registry().value(gd.GRID_STEPS_COUNTER) - before == 2
    assert registry().value(gd.CHUNK_GAUGE) == 128
