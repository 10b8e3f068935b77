"""The expert combine kernel (``ops/pallas/moe_combine.py``) against the
array-code combine it replaces, equal to the bit, on buffers laid out as
``moe_dropless`` lays them; and ``moe_dropless`` answering alike with the
kernel and without it.

The kernel runs interpreted here (the ``pallas_interpret`` fixture); blocks
are cut to 32 tokens so that every case walks several grid steps and both
staging slots."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mmlspark_tpu.obs.metrics import registry
from mmlspark_tpu.ops.pallas import moe_combine as mc
from mmlspark_tpu.parallel import moe

pytestmark = pytest.mark.usefixtures("pallas_interpret")


def sorted_layout(picks, held, tile=1):
    """``(back, expert, used)`` of picks ``[N, k]`` over a router whose
    first ``held`` experts are here: the pairs sorted by expert, token-major
    within one, every expert's group padded to whole ``tile`` rows, as
    ``moe_dropless`` lays its buffer; ``used`` the rows up to the last
    group's end."""
    n, k = picks.shape
    group = np.where(picks < held, picks, held).reshape(-1)
    order = np.argsort(group, kind="stable")
    rank = np.argsort(order)
    sizes = np.bincount(group, minlength=held + 1)[:held]
    padded = -(-sizes // tile) * tile
    starts = np.concatenate([[0], np.cumsum(padded)])
    before = np.concatenate([[0], np.cumsum(sizes)])
    row = np.where(group < held,
                   starts[np.minimum(group, held)] + rank
                   - before[np.minimum(group, held)], 0)
    return row.reshape(n, k), group.reshape(n, k), int(padded.sum())


def picks_of(n, k, width, seed):
    rng = np.random.default_rng(seed)
    return np.argsort(rng.random((n, width)), axis=1)[:, :k]


# name: (tokens, top_k, router width, experts held, d, row tile, rungs)
CASES = {
    # about half of each token's picks held (the Nemotron share's form),
    # groups padded to whole row tiles
    "half_held": (96, 6, 16, 8, 256, 8, 1),
    # every pick held (the LFM2 form: all experts here, packed)
    "every_pick_held": (64, 4, 8, 8, 128, 1, 1),
    # a router of which two experts of 16 are here: many tokens hold none
    "tokens_with_no_held_pick": (80, 4, 16, 2, 128, 1, 1),
    # a token count that is no multiple of the block (32): a partial tail
    "ragged_tail": (70, 4, 16, 8, 128, 1, 1),
    # the buffer of a second rung: two first rungs worked one at a time
    # through lax.map and laid end to end
    "second_rung": (64, 6, 16, 8, 256, 1, 2),
}


def case_arrays(name, seed=3):
    n, k, width, held, d, tile, rungs = CASES[name]
    back, expert, used = sorted_layout(picks_of(n, k, width, seed), held,
                                        tile)
    rng = np.random.default_rng(seed + 1)
    rows = -(-(used + 24) // (8 * rungs)) * 8 * rungs
    if rungs == 1:
        ys = jnp.asarray(rng.normal(size=(rows, d)), jnp.bfloat16)
    else:
        parts = jnp.asarray(rng.normal(size=(rungs, rows // rungs, d)),
                            jnp.bfloat16)
        ys = jax.lax.map(lambda p: p * 2.0, parts).reshape(rows, d)
    # the rows past the last group are unspecified: poisoned here
    ys = ys.at[used:].set(jnp.nan)
    weights = jnp.asarray(rng.random((n, k)), jnp.float32)
    return (ys, jnp.asarray(back, jnp.int32), jnp.asarray(expert, jnp.int32),
            weights), held


def bits(a):
    return np.asarray(jax.lax.bitcast_convert_type(a, jnp.int32))


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_kernel_is_the_array_combine_to_the_bit(name, monkeypatch):
    monkeypatch.setattr(mc, "_BLOCKS", (32,))
    args, held = case_arrays(name)
    n = args[1].shape[0]
    assert mc.block_tokens(n, args[1].shape[1], held, args[0].shape[1],
                           2) == 32
    before = registry().counter(mc.GRID_STEPS_COUNTER).value
    got = mc.moe_combine(*args, held=held, impl="pallas")
    ys, back, expert, weights = args
    want = jax.jit(mc.moe_combine_reference)(ys, back, expert < held,
                                             weights)
    np.testing.assert_array_equal(bits(got), bits(want))
    assert not np.isnan(np.asarray(got)).any()     # no poisoned row read
    assert registry().counter(mc.GRID_STEPS_COUNTER).value - before \
        == -(-n // 32)
    if name == "tokens_with_no_held_pick":
        none = ~(np.asarray(args[2]) < held).any(axis=1)
        assert none.any() and not np.asarray(got)[none].any()


@pytest.mark.parametrize("name", ["half_held", "every_pick_held"])
def test_each_block_stages_its_distinct_tiles_within_its_room(name):
    # a block's picks of one expert are consecutive rows, so the tiles
    # staged (a range an expert, two packed experts' ranges sharing at most
    # a tile) stay within k B / 8 + 2 held however the picks fall
    args, held = case_arrays(name)
    ys, back, expert, _ = args
    rows = jnp.where(expert < held, back, -1)
    most = mc.staged_tiles(32, back.shape[1], held)
    pad = ((0, -rows.shape[0] % 32), (0, 0))
    rows = jnp.pad(rows, pad, constant_values=-1)
    expert = jnp.pad(expert, pad, constant_values=held)
    tiles, counts, staged = mc._staging(rows, expert, 32, most, held)
    rows, tiles, staged = map(np.asarray, (rows, tiles, staged))
    assert 0 < int(counts.max()) <= most
    for b, count in enumerate(np.asarray(counts)):
        mine = rows[b * 32:(b + 1) * 32].reshape(-1)
        want = np.unique(mine[mine >= 0] // 8)
        np.testing.assert_array_equal(np.unique(tiles[b, :count]), want)
        assert count <= len(want) + held - 1
        # every present pick finds its row in the staging
        at = staged[b][mine >= 0]
        np.testing.assert_array_equal(tiles[b][at // 8] * 8 + at % 8,
                                      mine[mine >= 0])
        assert (staged[b][mine < 0] == -1).all()


def test_the_block_comes_from_the_shapes(monkeypatch):
    # the three cells' shapes: the largest block whose staging fits
    assert mc.block_tokens(16384, 6, 64, 2688, 2) == 128
    assert mc.block_tokens(8192, 4, 32, 4096, 2) == 128
    assert mc.block_tokens(16384, 4, 32, 2048, 2) == 512
    assert mc.block_tokens(40, 4, 8, 128, 2) == 48     # no more than needed
    assert mc.block_tokens(16384, 6, 64, 2 ** 20, 2) == 0
    # the scalar tables are a grid step's, so SMEM does not bound the tokens
    assert mc.block_tokens(2 ** 20, 6, 64, 2688, 2) == 128
    assert mc._smem_bytes(2 ** 20, 128, 6, 64) <= mc.SMEM_BUDGET
    # no staging room at all: demanded, the kernel refuses
    monkeypatch.setattr(mc, "STAGING_VMEM_BUDGET", 1)
    args, held = case_arrays("half_held")
    assert mc.block_tokens(96, 6, held, 256, 2) == 0
    with pytest.raises(ValueError, match="exceeds the VMEM budget"):
        mc.moe_combine(*args, held=held, impl="pallas")


def dropless_layer(gated):
    """A tiny expert layer: ``(x, router, experts, keywords)``, the grouped
    products ``gmm``, 8 of 16 experts held from the 5th."""
    rng = np.random.default_rng(5)
    n, d, f, width, held = 64, 64, 32, 16, 8
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(d, width)), jnp.float32)
    names = ("gate", "up", "down") if gated else ("up", "down")
    experts = {k: jnp.asarray(rng.normal(size=(held, f, d) if k == "down"
                                         else (held, d, f)) / 8, jnp.float32)
               for k in names}
    return x, router, experts, dict(top_k=6 if not gated else 4,
                                    first_expert=4, impl="gmm")


@pytest.mark.parametrize("gated", [False, True])
def test_moe_dropless_answers_alike_with_the_kernel_and_without(
        gated, monkeypatch):
    """The whole layer (grouped products interpreted too), its combine the
    kernel, against the same layer whose combine is the array form."""
    monkeypatch.setattr(mc, "_BLOCKS", (32,))
    # beside gmm the combine resolves as on the TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    x, router, experts, kw = dropless_layer(gated)

    def layer():
        return jax.jit(lambda a: moe.moe_dropless(a, router, experts,
                                                  **kw))(x)

    # the array form keeps the combine where the grouped products are
    # ragged_dot (the CPU's path)
    moe.moe_dropless(x, router, experts, **{**kw, "impl": "ragged"})
    assert registry().gauge(mc.COMBINE_GAUGE).value == 0
    with jax.default_matmul_precision("highest"):
        got, picks, bucket = layer()
        assert registry().gauge(mc.COMBINE_GAUGE).value == 1
        monkeypatch.setattr(
            mc, "moe_combine",
            lambda ys, back, expert, w, held, **_:
            mc.moe_combine_reference(ys, back, expert < held, w))
        want, _, _ = layer()
    np.testing.assert_array_equal(bits(got), bits(want))
    held_picks = (np.asarray(picks) >= 4) & (np.asarray(picks) < 4 + 8)
    assert held_picks.any() and not held_picks.all()
    assert np.abs(np.asarray(got)).max() > 0


def test_moe_dropless_gives_way_to_the_array_combine_where_no_block_fits(
        monkeypatch):
    """Beside gmm on the TPU, a layer whose smallest block cannot be staged
    takes the array form, logged and counted, and answers as the kernel
    would have."""
    from mmlspark_tpu.ops.pallas.budget import FALLBACK_COUNTER

    monkeypatch.setattr(mc, "_BLOCKS", (32,))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    x, router, experts, kw = dropless_layer(False)

    def layer():
        return jax.jit(lambda a: moe.moe_dropless(a, router, experts,
                                                  **kw))(x)

    fallbacks = registry().counter(FALLBACK_COUNTER, kernel="moe_combine")
    with jax.default_matmul_precision("highest"):
        want, _, _ = layer()
        assert registry().gauge(mc.COMBINE_GAUGE).value == 1
        before = fallbacks.value
        monkeypatch.setattr(mc, "STAGING_VMEM_BUDGET", 1)
        got, _, _ = layer()
    assert registry().gauge(mc.COMBINE_GAUGE).value == 0
    assert fallbacks.value > before
    np.testing.assert_array_equal(bits(got), bits(want))
