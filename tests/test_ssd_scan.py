"""The chunked Mamba-2 (state-space dual) scan
(``ops/pallas/ssd_scan.py``): the kernel, run here through the interpreter,
and its array-code form, against the literal recurrence a position at a
time in float64; chunk and block boundaries at window lengths that are and
are not whole chunks; the state carried between chunks matters; what the
wrapper decides from the shapes."""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mmlspark_tpu.obs.metrics import registry
from mmlspark_tpu.ops.pallas import budget, ssd_scan as ss

# float32 products of a few hundred terms of order one against float64
ATOL = 5e-5
# heads, head_dim, groups, state: a tiny model's (every part cut out and
# padded to lane tiles) and one whose parts are whole lane tiles (read in
# place, two heads a tile as at the published sizes)
TINY = dict(heads=4, head_dim=16, groups=2, state=16)
IN_PLACE = dict(heads=4, head_dim=64, groups=2, state=128)


def operands(seed: int, rows: int, length: int, sizes: dict,
             dtype=jnp.float32) -> tuple:
    rng = np.random.default_rng(seed)
    h, p, g, n = (sizes[k] for k in ("heads", "head_dim", "groups", "state"))
    xbc = jnp.asarray(rng.normal(size=(rows, length, h * p + 2 * g * n)),
                      dtype)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(1e-1),
                                        size=(rows, length, h))), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, size=(h,)), jnp.float32)
    d = jnp.asarray(1 + 0.1 * rng.normal(size=(h,)), jnp.float32)
    return xbc, dt, a, d


def recurrence64(xbc, dt, a, d, sizes: dict, drop_every: int = 0):
    """The recurrence a position at a time, float64; ``drop_every`` zeroes
    the state before every such position (a kernel that loses its carry)."""
    h, p, g, n = (sizes[k] for k in ("heads", "head_dim", "groups", "state"))
    xbc, dt, a, d = (np.asarray(v, np.float64) for v in (xbc, dt, a, d))
    rows, length, _ = xbc.shape
    out = np.zeros((rows, length, h * p))
    for r in range(rows):
        s = np.zeros((h, p, n))
        for t in range(length):
            if drop_every and t % drop_every == 0:
                s[:] = 0.0
            x = xbc[r, t, :h * p].reshape(h, p)
            b = np.repeat(xbc[r, t, h * p:h * p + g * n].reshape(g, n),
                          h // g, axis=0)
            c = np.repeat(xbc[r, t, h * p + g * n:].reshape(g, n), h // g,
                          axis=0)
            s = np.exp(dt[r, t] * a)[:, None, None] * s \
                + (dt[r, t][:, None] * x)[:, :, None] * b[:, None, :]
            out[r, t] = (np.einsum("hpn,hn->hp", s, c)
                         + d[:, None] * x).reshape(-1)
    return out


def counted(name: str, **labels) -> float:
    return registry().value(name, **labels) or 0


# ---- the kernel and the array form against the literal recurrence ----

# whole chunks (two, and a block of three), a tail that fills no chunk, a
# row shorter than one chunk
LENGTHS = [256, 384, 300, 100]


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("sizes", [TINY, IN_PLACE], ids=["cut", "in_place"])
@pytest.mark.parametrize("length", LENGTHS)
def test_the_kernel_matches_the_literal_recurrence(length, sizes):
    args = operands(length, 2, length, sizes)
    got = np.asarray(ss.ssd_scan(*args, impl="pallas", **sizes))
    np.testing.assert_allclose(got, recurrence64(*args, sizes), atol=ATOL,
                               rtol=1e-5)


@pytest.mark.parametrize("sizes", [TINY, IN_PLACE], ids=["cut", "in_place"])
@pytest.mark.parametrize("length", LENGTHS)
def test_the_array_form_is_the_same_function(length, sizes):
    args = operands(length, 2, length, sizes)
    got = np.asarray(ss.ssd_scan_reference(*args, **sizes))
    np.testing.assert_allclose(got, recurrence64(*args, sizes), atol=ATOL,
                               rtol=1e-5)
    # ``auto`` on the CPU is the array form
    np.testing.assert_array_equal(np.asarray(ss.ssd_scan(*args, **sizes)),
                                  got)


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_the_state_carried_across_a_chunk_boundary_matters(impl):
    """A planted reset at every chunk boundary is far outside the
    tolerance: the comparison above would catch a lost carry."""
    args = operands(7, 2, 384, TINY)
    got = np.asarray(ss.ssd_scan(*args, impl=impl, **TINY))
    dropped = recurrence64(*args, TINY, drop_every=ss.CHUNK)
    np.testing.assert_allclose(got[:, :ss.CHUNK], dropped[:, :ss.CHUNK],
                               atol=ATOL, rtol=1e-5)
    assert np.abs(got - dropped)[:, ss.CHUNK:].max() > 1000 * ATOL


@pytest.mark.usefixtures("pallas_interpret")
def test_a_block_of_several_chunks_carries_the_state_between_blocks():
    """2,304 positions are 18 chunks: three blocks of six, so the state
    crosses chunk boundaries inside a block and block boundaries."""
    args = operands(8, 1, 2304, TINY)
    assert ss.block_positions(2304, itemsize=4, **TINY) == 6 * ss.CHUNK
    got = np.asarray(ss.ssd_scan(*args, impl="pallas", **TINY))
    want = np.asarray(ss.ssd_scan_reference(*args, **TINY))
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=1e-5)


@pytest.mark.usefixtures("pallas_interpret")
def test_the_kernel_is_causal_to_the_bit():
    xbc, dt, a, d = operands(9, 1, 300, TINY)
    later = xbc.at[:, 200:].set(-xbc[:, 200:])
    one = np.asarray(ss.ssd_scan(xbc, dt, a, d, impl="pallas", **TINY))
    two = np.asarray(ss.ssd_scan(later, dt, a, d, impl="pallas", **TINY))
    np.testing.assert_array_equal(one[:, :200], two[:, :200])
    assert np.abs(one[:, 200:] - two[:, 200:]).max() > 0.1


@pytest.mark.usefixtures("pallas_interpret")
def test_bfloat16_operands_come_back_in_bfloat16():
    """The products run on bfloat16 operands; the decays and the state stay
    float32, so the answer is near the float64 recurrence of the same
    (bfloat16) inputs."""
    args = operands(10, 1, 256, IN_PLACE, jnp.bfloat16)
    got = ss.ssd_scan(*args, impl="pallas", **IN_PLACE)
    assert got.dtype == jnp.bfloat16
    want = recurrence64(*args, IN_PLACE)
    gap = np.abs(np.asarray(got, np.float64) - want)
    assert gap.max() < 0.02 * np.abs(want).max()
    assert np.sqrt(np.mean(gap ** 2)) < 0.004 * np.sqrt(np.mean(want ** 2))


@pytest.mark.usefixtures("pallas_interpret")
def test_a_decay_that_would_overflow_is_never_exponentiated():
    """The largest step sizes and rates: ``dt * A`` sums to -200 over a
    chunk, whose negative an unmasked difference would exponentiate."""
    xbc, dt, a, d = operands(11, 1, 256, TINY)
    dt, a = jnp.full_like(dt, 0.1), jnp.full_like(a, -16.0)
    got = np.asarray(ss.ssd_scan(xbc, dt, a, d, impl="pallas", **TINY))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, recurrence64(xbc, dt, a, d, TINY),
                               atol=ATOL, rtol=1e-5)


# ---- what the wrapper decides from the shapes ----

@pytest.mark.parametrize("length,want", [
    (16384, 1024),        # the cell's window: 16 blocks of 8 chunks
    (2304, 768),          # 18 chunks: six divides, seven and eight do not
    (300, 384), (100, 128)])
def test_the_block_comes_from_the_shapes_and_the_budget(length, want):
    sizes = dict(heads=64, head_dim=64, groups=8, state=128)
    assert ss.block_positions(length, itemsize=2, **sizes) == want
    assert ss.lane_tile(64) == 128 and ss.lane_tile(256) == 256
    assert ss.lane_tile(48) == 0


def test_the_grid_steps_are_counted_when_traced():
    sizes = dict(heads=64, head_dim=64, groups=8, state=128)
    before = counted(ss.GRID_STEPS_COUNTER)
    jax.eval_shape(
        lambda *a: ss._ssd_call(*a, block=1024, **sizes),
        jax.ShapeDtypeStruct((1, 4096, 6144), jnp.bfloat16),
        jax.ShapeDtypeStruct((1, 4096, 64), jnp.float32),
        jax.ShapeDtypeStruct((64,), jnp.float32),
        jax.ShapeDtypeStruct((64,), jnp.float32))
    # one row, eight groups, four blocks
    assert counted(ss.GRID_STEPS_COUNTER) == before + 32
    assert counted(ss.CHUNK_GAUGE) == 1024


def test_a_shape_the_kernel_does_not_take_falls_back_loudly(monkeypatch):
    sizes = dict(heads=4, head_dim=48, groups=2, state=16)
    args = operands(12, 1, 64, sizes)
    assert ss.block_positions(64, itemsize=4, **sizes) == 0
    with pytest.raises(ValueError, match="VMEM budget"):
        ss.ssd_scan(*args, impl="pallas", **sizes)
    # under ``auto`` on a TPU the array form runs and the miss is counted
    from mmlspark_tpu.ops.pallas import attention as fa
    monkeypatch.setattr(fa.jax, "default_backend", lambda: "tpu")
    before = counted(budget.FALLBACK_COUNTER, kernel="ssd_scan")
    got = np.asarray(ss.ssd_scan(*args, **sizes))
    assert counted(budget.FALLBACK_COUNTER, kernel="ssd_scan") == before + 1
    np.testing.assert_allclose(got, recurrence64(*args, sizes), atol=ATOL,
                               rtol=1e-5)
    # so does a block past the budget
    monkeypatch.setattr(ss, "VMEM_BUDGET", 2 ** 16)
    assert ss.block_positions(256, itemsize=4, **TINY) == 0


def test_heads_that_fill_no_whole_group_are_refused():
    args = operands(13, 1, 32, dict(heads=4, head_dim=16, groups=2,
                                    state=16))
    with pytest.raises(ValueError, match="whole number of heads"):
        ss.ssd_scan(*args, heads=4, head_dim=16, groups=3, state=16)
