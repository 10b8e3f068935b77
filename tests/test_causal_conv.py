"""The short causal convolution kernel (``ops/pallas/causal_conv.py``)
against its array-code reference ``causal_taps``, interpreted on the CPU:
both families' fusions from a wide operand, the carry between chunks, the
row's start, the shapes that fill no whole chunk or block, and that a
model's call sites share ONE trace of the kernel."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.models import lm_conv, lm_ssm
from mmlspark_tpu.obs.metrics import registry
from mmlspark_tpu.ops.pallas import causal_conv as cc
from mmlspark_tpu.ops.pallas.budget import FALLBACK_COUNTER

# every test that RUNS the kernel; the last test only lowers it for the TPU
interpreted = pytest.mark.usefixtures("pallas_interpret")

F32 = jnp.float32
# the two families' fusions: (taps, gated before and after, bias, SiLU)
GATED = dict(taps=3, gated=True, bias=False, silu=False)
MAMBA = dict(taps=4, gated=False, bias=True, silu=True)


def counter(name, **labels) -> int:
    return registry().value(name, **labels) or 0


def fallbacks() -> int:
    return counter(FALLBACK_COUNTER, kernel="causal_conv")


def operands(seed, rows, length, channels, *, taps, gated, bias, silu,
             dtype=F32):
    """A wide operand laid out as the family's product is and the keywords
    that say where its parts lie: ``[B | C | u]`` for the gated form,
    ``[z | u]`` (the convolved half second, so that ``at`` is not 0) for
    the other."""
    r = np.random.default_rng(seed)
    parts = 3 if gated else 2
    wide = jnp.asarray(r.standard_normal((rows, length, parts * channels)),
                       dtype)
    w = jnp.asarray(r.standard_normal((taps, channels)) / 2, F32)
    kw = dict(channels=channels, at=(parts - 1) * channels, silu=silu,
              dtype=F32)
    if gated:
        kw.update(pre_at=0, post_at=channels)
    if bias:
        kw["bias"] = jnp.asarray(r.standard_normal(channels) / 4, F32)
    return wide, w, kw


def both(wide, w, kw):
    want = cc.causal_conv(wide, w, impl="xla", **kw)
    got = jax.jit(lambda a, b: cc.causal_conv(a, b, impl="pallas", **kw))(
        wide, w)
    return np.asarray(got), np.asarray(want)


@pytest.fixture()
def short_chunks(monkeypatch):
    """Chunks of one tile, so that a few dozen positions cross many chunk
    boundaries."""
    monkeypatch.setattr(cc, "_MAX_CHUNK", cc._TILE)


@interpreted
@pytest.mark.parametrize("form", [GATED, MAMBA], ids=["gated", "mamba"])
def test_kernel_answers_as_the_tap_loop_from_a_wide_operand(form):
    wide, w, kw = operands(1, 2, 64, 256, **form)
    got, want = both(wide, w, kw)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-6)
    # the reference IS the tap loop on the cut-out parts
    c = kw["channels"]
    z = wide[..., kw["at"]:kw["at"] + c]
    if form["gated"]:
        z = wide[..., :c] * z
    y = lm_conv.causal_taps(z, w, kw.get("bias"))
    if form["silu"]:
        y = jax.nn.silu(y)
    if form["gated"]:
        y = wide[..., c:2 * c] * y
    np.testing.assert_array_equal(want, np.asarray(y))


@interpreted
@pytest.mark.parametrize("form", [GATED, MAMBA], ids=["gated", "mamba"])
def test_an_impulse_at_a_chunks_last_position_reaches_the_next_chunk(
        form, short_chunks):
    taps, c = form["taps"], 128
    wide = np.zeros((1, 48, 3 * c), np.float32)
    wide[..., :2 * c] = 1.0                      # the gates, where read
    wide[0, 15, 2 * c:] = 1.0                    # chunk 0's last position
    w = jnp.asarray(np.arange(1, taps + 1, dtype=np.float32)[:, None]
                    * np.ones((1, c), np.float32))
    kw = dict(channels=c, at=2 * c, dtype=F32)
    if form["gated"]:
        kw.update(pre_at=0, post_at=c)
    got, want = both(jnp.asarray(wide), w, kw)
    np.testing.assert_array_equal(got, want)
    # out[15 + s] = taps[K - 1 - s]: the newest tap meets it first
    np.testing.assert_array_equal(
        got[0, 14:16 + taps, 0], [0.0] + list(range(taps, 0, -1)) + [0.0])
    assert (got[0, 16:16 + taps - 1] != 0).all()     # in the NEXT chunk


@interpreted
@pytest.mark.parametrize("form", [GATED, MAMBA], ids=["gated", "mamba"])
def test_a_row_starts_from_zero_and_sees_nothing_of_the_row_before(
        form, short_chunks):
    wide, w, kw = operands(2, 2, 40, 128, **form)
    got, want = both(wide, w, kw)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-6)
    # row 1 alone answers as row 1 behind row 0: nothing is carried over
    alone, _ = both(wide[1:], w, kw)
    np.testing.assert_array_equal(got[1], alone[0])
    # position 0 holds the newest tap's term alone
    loud = wide.at[0].multiply(1e6)
    np.testing.assert_array_equal(both(loud, w, kw)[0][1], got[1])


@interpreted
@pytest.mark.parametrize("length", [8, 5, 13, 300])
def test_a_length_that_fills_no_whole_chunk_takes_the_kernel(length):
    before, steps = fallbacks(), counter(cc.GRID_STEPS_COUNTER)
    wide, w, kw = operands(length, 2, length, 128, **GATED)
    got, want = both(wide, w, kw)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-6)
    assert fallbacks() == before
    assert counter(cc.GRID_STEPS_COUNTER) > steps        # the kernel ran


@interpreted
@pytest.mark.parametrize("channels", [48, 192, 320])
@pytest.mark.parametrize("form", [GATED, MAMBA], ids=["gated", "mamba"])
def test_a_channel_count_that_is_no_whole_block(form, channels):
    before = fallbacks()
    wide, w, kw = operands(channels, 1, 40, channels, **form)
    got, want = both(wide, w, kw)
    assert got.shape == (1, 40, channels)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-6)
    assert fallbacks() == before


@interpreted
def test_a_bfloat16_operand_is_summed_in_float32_and_rounded_once():
    wide, w, kw = operands(3, 1, 32, 128, dtype=jnp.bfloat16, **MAMBA)
    kw["dtype"] = jnp.bfloat16
    got, want = both(wide, w, kw)
    assert got.dtype == jnp.bfloat16
    exact = np.asarray(cc.causal_conv(wide, w, impl="xla",
                                      **dict(kw, dtype=F32)))
    np.testing.assert_array_equal(want, exact.astype(jnp.bfloat16))
    np.testing.assert_allclose(got.astype(np.float32),
                               want.astype(np.float32), rtol=2 ** -7)


@interpreted
@pytest.mark.parametrize("channels", [128, 48])
def test_a_part_that_is_only_cast_rides_as_a_second_output(channels,
                                                           short_chunks):
    """The Mamba mixer's gate: the other half of ``[u | z]``."""
    wide, w, kw = operands(9, 2, 40, channels, **MAMBA)
    kw.update(at=0, cast_at=channels, dtype=jnp.bfloat16)
    want = cc.causal_conv(wide, w, impl="xla", **kw)
    got = jax.jit(lambda a, b: cc.causal_conv(a, b, impl="pallas", **kw))(
        wide, w)
    np.testing.assert_array_equal(
        np.asarray(want[1]), np.asarray(wide[..., channels:], jnp.bfloat16))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_allclose(np.asarray(got[0], np.float32),
                               np.asarray(want[0], np.float32), rtol=2 ** -7,
                               atol=1e-6)
    alone = cc.causal_conv(wide, w, impl="xla", **dict(kw, cast_at=None))
    np.testing.assert_array_equal(np.asarray(want[0]), np.asarray(alone))


def test_chunks_come_from_the_budget_and_the_row_alone():
    # the two cells: [1, 16384, 5120] of a float32 product to bfloat16 with
    # its gate cast beside it; [2, 8192, 2048], three operands
    assert cc.chunk_positions(16384, 1024, 2 * (4 + 2)) == 512
    assert cc.chunk_positions(8192, 1024, 3 * 4 + 2) == 256
    # no longer than the row needs, never under a tile
    assert cc.chunk_positions(8, 1024, 6) == cc._TILE
    assert cc.chunk_positions(5, 128, 6) == cc._TILE
    assert cc.chunk_positions(300, 128, 6) == 512
    assert cc.chunk_positions(100, 128, 6) == 128
    assert cc.chunk_positions(100, 1024, 10 ** 5) == 0


@interpreted
def test_a_budget_miss_is_counted_under_auto_and_raises_under_pallas(
        monkeypatch):
    monkeypatch.setattr(cc, "VMEM_BUDGET", 1024)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    wide, w, kw = operands(4, 1, 16, 128, **GATED)
    before = fallbacks()
    got = cc.causal_conv(wide, w, impl="auto", **kw)
    assert fallbacks() == before + 1
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(cc.causal_conv(wide, w, impl="xla", **kw)))
    with pytest.raises(ValueError, match="VMEM budget"):
        cc.causal_conv(wide, w, impl="pallas", **kw)


def test_more_taps_than_the_carry_holds_are_refused():
    wide, w, kw = operands(5, 1, 16, 128, **dict(GATED, taps=17))
    with pytest.raises(ValueError, match="17 taps"):
        cc.causal_conv(wide, w, impl="xla", **kw)


# ---- the two callers, and what a process pays to trace them ----

def _conv_params(r, d, taps=3, layers=None):
    lead = () if layers is None else (layers,)
    return {"in_proj": jnp.asarray(r.standard_normal(lead + (d, 3 * d))
                                   / d ** 0.5, F32),
            "taps": jnp.asarray(r.standard_normal(lead + (taps, d)) / 2, F32),
            "out_proj": jnp.asarray(r.standard_normal(lead + (d, d))
                                    / d ** 0.5, F32)}


@interpreted
def test_short_conv_takes_the_kernel_on_the_tpu_and_answers_alike(
        monkeypatch):
    r = np.random.default_rng(6)
    c = types.SimpleNamespace(hidden_size=128, dtype=jnp.bfloat16)
    p = _conv_params(r, 128)
    x = jnp.asarray(r.standard_normal((2, 24, 128)), F32)
    want = np.asarray(lm_conv.short_conv(p, x, c))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    steps = counter(cc.GRID_STEPS_COUNTER)
    got = np.asarray(lm_conv.short_conv(p, x, c))
    assert counter(cc.GRID_STEPS_COUNTER) > steps
    # one bfloat16 rounding of the mixer's output apart at most
    np.testing.assert_allclose(got, want, atol=0.05, rtol=0.02)


@interpreted
def test_the_mamba_mixers_convolution_takes_the_kernel_on_the_tpu(
        monkeypatch):
    """The scope ``lm/mamba/conv`` alone (the selective scan behind it is
    interpreted at ~20 ms a position: its own tests run it)."""
    r = np.random.default_rng(7)
    d_i = 256
    uz = jnp.asarray(r.standard_normal((1, 24, 2 * d_i)), F32)
    p = {"conv_taps": jnp.asarray(r.standard_normal((4, d_i)) / 2,
                                  jnp.bfloat16),
         "conv_bias": jnp.asarray(r.standard_normal(d_i) / 4, F32)}
    before = jax.nn.silu(lm_conv.causal_taps(
        uz[..., :d_i], p["conv_taps"].astype(F32), p["conv_bias"])
    ).astype(jnp.bfloat16)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    got, gate = lm_ssm.causal_conv(
        uz, p["conv_taps"], channels=d_i, cast_at=d_i, silu=True,
        dtype=jnp.bfloat16, bias=p["conv_bias"])
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(before, np.float32), rtol=2 ** -7,
                               atol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(gate), np.asarray(uz[..., d_i:].astype(jnp.bfloat16)))


@interpreted
def test_the_init_trace_at_8_positions_counts_no_fallback(monkeypatch):
    """``token_score.make_bundle`` traces ``module.init`` at 8 positions
    and both token drivers raise if the fallback counter is non-zero."""
    import test_lm_conv_moe as conv
    from mmlspark_tpu.models import lm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before, steps = fallbacks(), counter(cc.GRID_STEPS_COUNTER)
    module = lm.from_config(conv.tiny())
    jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0),
                                       jnp.zeros((1, 8))))
    assert counter(cc.GRID_STEPS_COUNTER) > steps    # the kernel was taken
    assert fallbacks() == before


def test_four_call_sites_and_two_output_nodes_trace_the_kernel_once(
        monkeypatch):
    """A leading conv layer and a scanned period of three (the 13-layer
    stage's four sites), read through two output nodes: ``eval_shape`` and
    then each node's trace and lowering for the TPU meet ONE trace of the
    kernel's entry and of its body."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    bodies = []
    kernel = cc._conv_kernel

    def counting(*refs, **static):
        bodies.append(static)
        return kernel(*refs, **static)

    monkeypatch.setattr(cc, "_conv_kernel", counting)
    d, length = 128, 72            # a shape no other test of this file uses
    c = types.SimpleNamespace(hidden_size=d, dtype=jnp.bfloat16)
    r = np.random.default_rng(8)
    params = {"lead": _conv_params(r, d),
              "period": [_conv_params(r, d, layers=2) for _ in range(3)]}

    def stack(p, x):
        h = x + lm_conv.short_conv(p["lead"], x, c)

        def period(h, layer):
            for site in layer:
                h = h + lm_conv.short_conv(site, h, c)
            return h, None

        return jax.lax.scan(period, h, p["period"])[0]

    nodes = {"features": lambda p, x: jnp.mean(stack(p, x), axis=1),
             "energy": lambda p, x: jnp.sum(stack(p, x) ** 2, axis=(1, 2))}
    x = jax.ShapeDtypeStruct((2, length, d), F32)
    steps = counter(cc.GRID_STEPS_COUNTER)
    for node in nodes.values():
        jax.eval_shape(node, params, x)
        text = jax.jit(node).trace(params, x).lower(
            lowering_platforms=("tpu",)).as_text()
        # four sites, one lowered function of the kernel a module
        assert text.count("tpu_custom_call") == 1
        assert text.count("call @_conv_call") == 4
    assert len(bodies) == 1
    one_trace = 2 * 1 * -(-length // cc.chunk_positions(length, d, 14))
    assert counter(cc.GRID_STEPS_COUNTER) == steps + one_trace
