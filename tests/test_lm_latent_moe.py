"""The latent-attention expert LM family (``models/lm.py``), its dropless
expert layer (``parallel/moe.moe_dropless``) and the tiled attention kernel,
each against the plain reference ``benchmark/reference/mistral4.py`` on
seeded weights, at tiny sizes on the CPU."""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from flax.traverse_util import unflatten_dict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.reference import mistral4 as ref  # noqa: E402
from mmlspark_tpu.models import lm  # noqa: E402
from mmlspark_tpu.ops.pallas import attention as fa  # noqa: E402
from mmlspark_tpu.parallel import moe  # noqa: E402
from mmlspark_tpu.parallel.ring_attention import attention_reference  # noqa: E402

ROPE = dict(beta_fast=32, beta_slow=1, factor=128, llama_4_scaling_beta=0.1,
            mscale=1, mscale_all_dim=1, original_max_position_embeddings=8192,
            rope_theta=10000, rope_type="yarn", type="yarn")


def tiny(**over) -> dict:
    cfg = dict(
        family="mistral4", vocab_size=256, hidden_size=64,
        num_hidden_layers=3, num_attention_heads=4, q_lora_rank=32,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
        v_head_dim=16, moe_intermediate_size=32, n_routed_experts=16,
        router_width=16, first_expert=0, num_experts_per_tok=4,
        n_shared_experts=1, norm_topk_prob=True, routed_scaling_factor=1.0,
        rms_norm_eps=1e-6, param_dtype="bfloat16", compute_dtype="float32",
        rope_parameters=dict(ROPE))
    cfg.update(over)
    return cfg


def program_tree(params: dict) -> dict:
    """The reference's ``make_params`` in the program's tree: the layers
    stacked on a leading axis."""
    flat = dict(params["outer"])
    for k in params["layers"][0]:
        # the routed experts' stacks sit beside the scanned blocks
        path = k[len("moe/"):] if k.startswith("moe/experts/") \
            else "layers/" + k
        flat[path] = jnp.stack([p[k] for p in params["layers"]])
    return unflatten_dict({tuple(k.split("/")): v for k, v in flat.items()})


def tokens_of(seed: int, shape, vocab: int = 256) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, vocab, size=shape)


def reference_rows(params, tokens, cfg, **kw) -> dict:
    rows = [ref.forward(params, jnp.asarray(t), cfg, **kw) for t in tokens]
    return {k: np.stack([np.asarray(r[k]) for r in rows]) for k in rows[0]}


@pytest.fixture(scope="module")
def seeded():
    cfg = tiny()
    return cfg, ref.make_params(cfg, jax.random.PRNGKey(7))


def apply(cfg, params, tokens, node, **over):
    module = lm.from_config(cfg, **over)
    return np.asarray(module.apply({"params": program_tree(params)},
                                   jnp.asarray(tokens, jnp.float32),
                                   output=node))


# ---- rotary frequencies, interleaving, the position scale ----

def test_yarn_frequencies_at_the_published_sizes():
    got = lm.yarn_inv_freq(64, ROPE)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    # rotations 32 and 1 over 8192 positions put the ramp at pairs 12..25
    np.testing.assert_allclose(got[:13], plain[:13], rtol=1e-12)
    np.testing.assert_allclose(got[25:], plain[25:] / 128, rtol=1e-12)
    assert np.all(np.diff(got) < 0)
    mid = (plain[18] / 128) * (6 / 13) + plain[18] * (7 / 13)
    assert got[18] == pytest.approx(mid, rel=1e-12)
    np.testing.assert_allclose(
        got, ref.yarn_inv_freq(tiny(qk_rope_head_dim=64)), rtol=1e-12)


def test_rope_rotates_interleaved_pairs():
    cfg = tiny()
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 6, 3, 8)),
                    jnp.float32)
    pos = jnp.arange(6) + 5
    cos, sin = lm.rope_tables(pos, 8, ROPE)
    got = np.asarray(lm.apply_rope_interleaved(x, cos, sin))
    want = np.asarray(ref.rope_interleaved(x[0], pos, cfg))
    np.testing.assert_allclose(got[0], want, rtol=1e-6, atol=1e-6)
    # a rotation of the pairs (0,1), (2,3), ...: each pair keeps its norm,
    # and pair 0 turns by exactly position x inv_freq_0 = position radians
    pairs = got.reshape(1, 6, 3, 4, 2)
    np.testing.assert_allclose(
        np.linalg.norm(pairs, axis=-1),
        np.linalg.norm(np.asarray(x).reshape(1, 6, 3, 4, 2), axis=-1),
        rtol=1e-5)
    a, b = np.asarray(x)[0, 2, 0, :2]
    p = 7.0
    np.testing.assert_allclose(
        got[0, 2, 0, :2], [a * np.cos(p) - b * np.sin(p),
                           a * np.sin(p) + b * np.cos(p)], rtol=1e-5)


def test_the_query_scale_past_the_original_window(seeded):
    # original_max_position_embeddings 8: positions 8.. scale the queries
    # by 1 + 0.1 ln(1 + floor(p / 8)); with it left out the logits differ
    rope = dict(ROPE, original_max_position_embeddings=8)
    cfg = tiny(rope_parameters=rope)
    params = seeded[1]
    tokens = tokens_of(3, (2, 32))
    with jax.default_matmul_precision("highest"):
        got = apply(cfg, params, tokens, "logits", param_dtype=jnp.float32)
        want = reference_rows(params, tokens, cfg)["logits"]
        flat = reference_rows(
            params, tokens, tiny(rope_parameters=dict(
                rope, llama_4_scaling_beta=0.0)))["logits"]
    np.testing.assert_allclose(got, want, atol=3e-5)
    assert np.abs(want[:, :8] - flat[:, :8]).max() < 1e-5
    assert np.abs(want[:, 8:] - flat[:, 8:]).max() > 1e-3


# ---- the blocks ----

def test_latent_attention_block_matches_the_reference(seeded):
    cfg, params = seeded
    p = params["layers"][1]
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 24, 64)),
                    jnp.float32)
    module = lm.LatentAttention(
        lm.from_config(cfg, param_dtype=jnp.float32).cfg)
    tree = unflatten_dict({tuple(k.split("/")[1:]): v for k, v in p.items()
                           if k.startswith("mla/")})
    with jax.default_matmul_precision("highest"):
        got = module.apply({"params": tree}, x, jnp.arange(24))
        want = jnp.stack([ref.mla(p, row, jnp.arange(24), cfg) for row in x])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def moe_parts(p: dict) -> tuple:
    experts = {k: p[f"moe/experts/{k}"] for k in ("gate", "up", "down")}
    return p["moe/router/kernel"], experts


@pytest.mark.parametrize("impl", ["ragged", "gmm"])
def test_expert_layer_matches_the_reference(seeded, impl, request):
    if impl == "gmm":       # the Pallas grouped product, interpreted
        request.getfixturevalue("pallas_interpret")
    cfg, params = seeded
    p = params["layers"][0]
    x = jnp.asarray(np.random.default_rng(2).normal(size=(40, 64)),
                    jnp.float32)
    router, experts = moe_parts(p)
    with jax.default_matmul_precision("highest"):
        got, picks, _ = moe.moe_dropless(x, router, experts, top_k=4,
                                         impl=impl)
        routed, _, _ = ref.moe(p, x, cfg, parts=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(routed),
                               atol=2e-6)
    assert picks.shape == (40, 4)


@pytest.mark.parametrize("impl", ["ragged", "gmm"])
def test_a_layer_of_stacked_experts_equals_that_layer_alone(seeded, impl,
                                                            request):
    # the model hands moe_dropless every layer's stacks and a layer index
    # (read in place, no slice copied out): the same as the layer sliced
    if impl == "gmm":
        request.getfixturevalue("pallas_interpret")
    cfg, params = seeded
    x = jnp.asarray(np.random.default_rng(6).normal(size=(24, 64)),
                    jnp.float32)
    router = params["layers"][1]["moe/router/kernel"]
    stacks = {k: jnp.stack([p[f"moe/experts/{k}"][4:8]
                            for p in params["layers"]])
              for k in ("gate", "up", "down")}
    with jax.default_matmul_precision("highest"):
        alone, _, _ = moe.moe_dropless(
            x, router, {k: v[1] for k, v in stacks.items()}, top_k=4,
            first_expert=4, impl=impl)
        stacked, _, _ = jax.jit(lambda i: moe.moe_dropless(
            x, router, stacks, top_k=4, first_expert=4, impl=impl,
            layer=i))(jnp.int32(1))
    np.testing.assert_allclose(np.asarray(stacked), np.asarray(alone),
                               atol=1e-6)
    with pytest.raises(ValueError, match="unknown expert impl"):
        moe.moe_dropless(x, router, stacks, top_k=4, impl="dense")


def test_every_token_on_one_expert_loses_none(seeded):
    # a router that sends every token to the same four experts: a layer
    # with a capacity would drop most of them; this one drops none
    cfg, params = seeded
    p = dict(params["layers"][0])
    router = np.zeros((64, 16), np.float32)
    router[:, [2, 5, 6, 11]] = 4.0
    x = jnp.abs(jnp.asarray(np.random.default_rng(4).normal(size=(96, 64)),
                            jnp.float32)) + 0.1
    p["moe/router/kernel"] = jnp.asarray(router)
    _, experts = moe_parts(p)
    with jax.default_matmul_precision("highest"):
        got, picks, _ = moe.moe_dropless(x, p["moe/router/kernel"],
                                         experts, top_k=4)
        routed, _, _ = ref.moe(p, x, cfg, parts=True)
    assert set(np.asarray(picks).ravel().tolist()) == {2, 5, 6, 11}
    np.testing.assert_allclose(np.asarray(got), np.asarray(routed),
                               atol=2e-6)
    assert np.abs(np.asarray(got)).min(axis=1).max() > 0     # none is zero


# the row-count ladder. 1024 tokens x 4 picks on 4 of 16 experts expect 1024
# held pairs (256 an expert: aligned at 320 rows) and a tile and a half of
# padding, so the rungs are 1920 rows, twice and three times that (the worst
# padded load of all 4096 pairs is 5120 rows). 500 tokens expect 125 pairs
# an expert and 250
# tokens 62: packed, rungs of 256-row tiles, the last of them past all the
# pairs there are
LADDER_FIRST = 4
LADDER = (1920, 3840, 5760)
LADDERS = {1024: LADDER, 500: (768, 1536, 2304), 250: (512, 1024)}
ROUTERS = {
    # the seeded router: ~1024 held pairs, the first rung
    "balanced": (0, {}),
    # every token picks two held experts and never the other two: 2048
    # held pairs in eight tiles, the middle rung
    "skewed": (1, {4: 4.0, 5: 4.0, 6: -4.0, 7: -4.0}),
    # every pick of every token is a held expert: 4096 = N k, the last rung
    "all_held": (2, {4: 4.0, 5: 4.0, 6: 4.0, 7: 4.0}),
}


def held_share(layer: dict, first: int, count: int = 4) -> dict:
    """A layer's weights with only experts ``[first, first + count)``."""
    return {k: (v[first:first + count] if k.startswith("moe/experts/")
                else v) for k, v in layer.items()}


def padded_rows(load, pairs: int, width: int) -> np.ndarray:
    """The buffer rows of held loads ``[..., held]``: every expert's pairs
    in whole row tiles where the buffer is aligned (``moe.row_tile``)."""
    tile = moe.row_tile(pairs, width)
    return (-(-np.asarray(load) // tile) * tile).sum(axis=-1)


def ladder_case(seeded, name, tokens=1024):
    """``(p, cfg, x, router, experts)`` of the share that holds experts
    4..7 of layer 0, under the named router, for ``tokens`` tokens; where
    it has constant columns the tokens are positive, so that such a column
    decides its expert for every token."""
    _, params = seeded
    p = held_share(params["layers"][0], LADDER_FIRST)
    router = np.array(p["moe/router/kernel"], np.float32)
    for column, value in ROUTERS[name][1].items():
        router[:, column] = value
    p["moe/router/kernel"] = jnp.asarray(router)
    x = jnp.asarray(np.random.default_rng(8).normal(size=(tokens, 64)),
                    jnp.float32)
    if ROUTERS[name][1]:
        x = jnp.abs(x) + 0.1
    cfg = tiny(n_routed_experts=4, first_expert=LADDER_FIRST)
    return (p, cfg, x) + moe_parts(p)


@pytest.mark.parametrize("tokens,name", [
    (tokens, name) for tokens in (1024, 500) for name in sorted(ROUTERS)
] + [(250, "all_held")])    # 1000 pairs: the second of two packed rungs
@pytest.mark.parametrize("impl", ["ragged", "gmm"])
def test_each_rung_of_the_ladder_matches_the_reference(seeded, impl, tokens,
                                                       name, request):
    """Aligned (1024 tokens) and packed, each rung at a load that takes it:
    a packed ladder's upper rungs reach past the last pair, and the rows
    there read a clipped pair that no token reads back."""
    if impl == "gmm":
        request.getfixturevalue("pallas_interpret")
    p, cfg, x, router, experts = ladder_case(seeded, name, tokens)
    ladder = LADDERS[tokens]
    assert moe.bucket_ladder(tokens * 4, 4, 16) == ladder
    assert (moe.row_tile(tokens * 4, 16) > 1) == (tokens == 1024)
    with jax.default_matmul_precision("highest"):
        got, picks, bucket = jax.jit(lambda a: moe.moe_dropless(
            a, router, experts, top_k=4, first_expert=LADDER_FIRST,
            impl=impl))(x)
        routed, _, _ = ref.moe(p, x, cfg, parts=True)
    load = np.bincount(np.asarray(picks).ravel(), minlength=16)[4:8]
    rows = int(padded_rows(load, tokens * 4, 16))
    want_bucket = min(ROUTERS[name][0], len(ladder) - 1)
    assert int(bucket) == want_bucket
    assert ladder[want_bucket] >= rows
    assert want_bucket == 0 or rows > ladder[want_bucket - 1]
    if name == "all_held":      # the worst load: every pair, none dropped
        assert load.sum() == tokens * 4 and ladder[-1] > load.sum()
    # no pair is left out at any load: the later rungs answer like the first
    np.testing.assert_allclose(np.asarray(got), np.asarray(routed),
                               atol=2e-6)


@pytest.mark.parametrize("pairs,held,width,want", [
    (8192 * 4, 32, 128, (14080, 28160, 56320)),   # the benchmark's cell
    (1024 * 4, 4, 16, LADDER),
    (8192 * 4, 8, 128, (3520, 7040, 35200)),      # 1/16 of the experts
    (8192 * 4, 128, 128, (56320, 112640)),        # every expert held
    (8192 * 4, 96, 128, (42240, 84480)),
    # 400 pairs an expert: two tiles of 256 hold 1.25x that, not one of 320
    (12800 * 4, 32, 128, (19200, 38400, 76800)),
    (6144 * 4, 32, 128, (10752, 21504, 43008)),   # 192: one tile of 256
    (32768 * 4, 32, 128, (44800, 89600, 179200)),     # 1024: four of 320
    (512 * 4, 4, 16, (1024, 2048, 3072)),         # 128: half a tile of 256
    # under half a tile an expert: the pairs packed, rungs of 256-row tiles
    (500 * 4, 4, 16, (768, 1536, 2304)),
    (1000, 4, 16, (512, 1024)),
    (40 * 4, 4, 16, (160,)),        # under a row tile: one buffer
    # four tiles or more an expert, every expert held: the other family's
    # cell keeps its one packed buffer
    (16384 * 4, 32, 32, (65536,)),
])
def test_the_ladder_comes_from_the_shapes(pairs, held, width, want):
    ladder = moe.bucket_ladder(pairs, held, width)
    assert ladder == want
    assert list(ladder) == sorted(set(ladder)) and len(ladder) <= 3
    tile = moe.row_tile(pairs, width)
    assert tile in (1, moe.GMM_ROWS, moe.ALIGNED_ROWS)
    # the last rung holds the worst load: every pair on a held expert and,
    # aligned, every expert's last tile holding one row
    spread = np.full(held, pairs // held)
    spread[:pairs % held] += 1
    assert ladder[-1] >= int(padded_rows(spread, pairs, width))
    assert ladder[-1] >= (pairs + held * (tile - 1)) // tile * tile
    if len(ladder) > 1:     # whole tiles, with room over the expected load
        assert ladder[0] % (tile if tile > 1 else moe.GMM_ROWS) == 0
        assert ladder[0] >= (1.25 * pairs * held / width
                             + held * (3 * tile // 8))
    # a larger rung is a whole number of first rungs, worked one at a time
    assert all(rows % ladder[0] == 0 for rows in ladder)


@pytest.mark.parametrize("group,want", [
    (64, 1), (96, 1), (127, 1),         # under half a tile an expert: packed
    # the tile that holds 1.25x the expected load in the fewer rows; the
    # chip's readings (PERF.md section 6, PR 39) are 128, 192, 256, 400,
    # 640 and 1024
    (128, 256), (192, 256), (256, 320), (400, 256), (640, 320), (1024, 320),
    (1279, 320), (1280, 1), (2048, 1),  # four tiles an expert or more: packed
])
def test_the_row_tile_comes_from_the_expected_load(group, want):
    assert moe.row_tile(group * 128, 128) == want
    assert moe.row_tile(group * 32, 32) == want     # whatever the width
    if want > 1:
        room = -(-5 * group // 4)
        other = ({moe.GMM_ROWS, moe.ALIGNED_ROWS} - {want}).pop()
        assert -(-room // want) * want <= -(-room // other) * other


def test_all_the_pairs_within_one_tile_stay_packed():
    assert moe.row_tile(moe.ALIGNED_ROWS, 1) == 1
    assert moe.row_tile(moe.ALIGNED_ROWS + 1, 1) == moe.GMM_ROWS


# ---- the buffer's layout: every expert's pairs in whole row tiles ----

# 18 tokens x 4 picks over a router 16 wide, experts 4..7 held, at a row
# tile of 8 (the constant is read when the layer is traced): rungs of 40,
# 80 and 120 rows, the worst padded load of all 72 pairs being 96. Each
# case is ``{the four experts a kind of token picks: how many tokens}``
LAYOUT_TILE, LAYOUT_FIRST, LAYOUT_HELD = 8, 4, 4
LAYOUT_LADDER = (40, 80, 120)
LAYOUTS = {
    # expert 5 holds 13 pairs (over a tile), 6 exactly a tile, 7 under one,
    # 4 none: 32 rows, the first rung
    "uneven": (0, {(5, 6, 0, 1): 8, (5, 7, 0, 1): 5, (0, 1, 2, 3): 5}),
    # nine pairs each (two tiles for one row more): 64 rows, past the first
    "past_the_first": (1, {(4, 5, 6, 7): 9, (0, 1, 2, 3): 9}),
    # every pair on a held expert and every expert two rows into its third
    # tile: 96 rows for 72 pairs, the worst there is, on the last rung
    "worst": (2, {(4, 5, 6, 7): 18}),
}


def layout_case(name):
    """``(x, router, experts, load)``: tokens that are one-hot in their
    kind (then noise the router does not read), a router that gives a
    kind's four experts a high logit, seeded expert stacks ``[4, 64, 32]``
    and the held experts' loads."""
    rng = np.random.default_rng(31)
    kinds = LAYOUTS[name][1]
    x = rng.normal(size=(18, 64)).astype(np.float32)
    x[:, :8] = 0.0
    router = np.zeros((64, 16), np.float32)
    load = np.zeros(16, np.int64)
    at = 0
    for kind, (experts, count) in enumerate(kinds.items()):
        x[at:at + count, kind] = 1.0
        router[kind, list(experts)] = 8.0 + 0.1 * np.arange(4)
        load[list(experts)] += count
        at += count
    assert at == 18
    stacks = {k: jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[1]),
                             jnp.float32)
              for k, shape in (("gate", (4, 64, 32)), ("up", (4, 64, 32)),
                               ("down", (4, 32, 64)))}
    held = load[LAYOUT_FIRST:LAYOUT_FIRST + LAYOUT_HELD]
    return jnp.asarray(x[rng.permutation(18)]), jnp.asarray(router), stacks, held


def dense_layer(x, router, experts, first):
    """The routed part in its dense form: every held expert on every
    token, each token's held picks selected and weighted."""
    picks, weights = moe.route_topk(x, router, 4)
    hidden = jax.nn.silu(jnp.einsum("nd,edf->enf", x, experts["gate"])) \
        * jnp.einsum("nd,edf->enf", x, experts["up"])
    out = jnp.einsum("enf,efd->end", hidden, experts["down"])
    held = out.shape[0]
    y = jnp.zeros(x.shape, jnp.float32)
    for j in range(4):
        local = picks[:, j] - first
        ok = (local >= 0) & (local < held)
        row = out[jnp.clip(local, 0, held - 1), jnp.arange(x.shape[0])]
        y = y + jnp.where(ok[:, None], row * weights[:, j, None], 0.0)
    return y


@pytest.mark.parametrize("stacked", [False, True])
@pytest.mark.parametrize("name", sorted(LAYOUTS))
@pytest.mark.parametrize("impl", ["ragged", "gmm"])
def test_every_experts_pairs_fill_whole_row_tiles(impl, name, stacked,
                                                  request, monkeypatch):
    if impl == "gmm":
        request.getfixturevalue("pallas_interpret")
    monkeypatch.setattr(moe, "ALIGNED_ROWS", LAYOUT_TILE)
    monkeypatch.setattr(moe, "GMM_ROWS", LAYOUT_TILE)
    x, router, experts, load = layout_case(name)
    assert moe.row_tile(18 * 4, 16) == LAYOUT_TILE
    assert moe.bucket_ladder(18 * 4, LAYOUT_HELD, 16) == LAYOUT_LADDER
    # what the grouped products are handed, read where they are called
    seen = []
    grouped_dot = moe._grouped_dot

    def recorded(lhs, rhs, sizes, *args, **kw):
        jax.debug.callback(lambda s: seen.append(np.asarray(s)), sizes)
        return grouped_dot(lhs, rhs, sizes, *args, **kw)
    monkeypatch.setattr(moe, "_grouped_dot", recorded)
    kw = {}
    if stacked:     # layer 1 of three, read in place
        experts = {k: jnp.stack([v * 0.5, v, v * 2.0])
                   for k, v in experts.items()}
        kw = {"layer": jnp.int32(1)}
    with jax.default_matmul_precision("highest"):
        got, picks, bucket = jax.jit(lambda a, e: moe.moe_dropless(
            a, router, e, top_k=4, first_expert=LAYOUT_FIRST, impl=impl,
            **kw))(x, experts)
        want = dense_layer(x, router, {k: v[1] if stacked else v
                                       for k, v in experts.items()},
                           LAYOUT_FIRST)
    jax.effects_barrier()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    np.testing.assert_array_equal(
        np.bincount(np.asarray(picks).ravel(), minlength=16)[4:8], load)
    want_bucket = LAYOUTS[name][0]
    tiles = -(-load // LAYOUT_TILE)
    rows = int(tiles.sum()) * LAYOUT_TILE
    assert int(bucket) == want_bucket and rows <= LAYOUT_LADDER[want_bucket]
    assert want_bucket == 0 or rows > LAYOUT_LADDER[want_bucket - 1]
    if name == "worst":         # no padded load is larger
        assert rows == (72 + LAYOUT_HELD * (LAYOUT_TILE - 1)) \
            // LAYOUT_TILE * LAYOUT_TILE
    # three products a first rung worked; together each product's groups
    # are every expert's pairs in whole tiles, each starting on a boundary
    assert len(seen) == 3 * (want_bucket + 1)
    assert all((s % LAYOUT_TILE == 0).all() for s in seen)
    for product in range(3):
        groups = sum(seen[product::3])
        if stacked:
            groups = groups.reshape(3, LAYOUT_HELD)
            assert not groups[[0, 2]].any()
            groups = groups[1]
        np.testing.assert_array_equal(groups, tiles * LAYOUT_TILE)
        assert moe.row_tile_visits(groups, LAYOUT_TILE) == tiles.sum()


@pytest.mark.parametrize("seed", range(6))
def test_aligned_groups_are_visited_once_a_tile_of_their_own(seed):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata)

    rng = np.random.default_rng(seed)
    tile = int(rng.choice([8, 128, 256, 384]))
    sizes = rng.integers(0, 4 * tile, size=int(rng.integers(1, 40)))
    sizes[rng.random(sizes.shape) < 0.2] = 0            # experts with no pair
    sizes[rng.random(sizes.shape) < 0.2] = 2 * tile     # whole tiles
    padded = -(-sizes // tile) * tile
    starts = np.cumsum(padded) - padded
    assert (starts % tile == 0).all() and (padded[sizes == 0] == 0).all()
    assert ((padded >= sizes) & (padded - sizes < tile)).all()
    aligned = moe.row_tile_visits(padded, tile)
    assert aligned == int(np.ceil(sizes / tile).sum())
    assert aligned * tile == padded.sum()       # every visited row a group's
    # where groups start anywhere, a tile two groups share is visited twice
    packed = moe.row_tile_visits(sizes, tile)
    live = int((sizes > 0).sum())
    assert -(-sizes.sum() // tile) <= packed <= sizes.sum() // tile + live
    assert packed <= aligned + live
    # the count is the kernel's own grid
    for groups in (sizes, padded):
        m = max(int(-(-groups.sum() // tile) * tile), tile)
        _, visits = make_group_metadata(
            group_sizes=jnp.asarray(groups, jnp.int32), m=m, tm=tile,
            start_group=jnp.int32(0), num_nonzero_groups=len(groups),
            visit_empty_groups=False)
        assert int(visits) == moe.row_tile_visits(groups, tile)


def test_a_single_rung_leaves_no_conditional_in_the_program(seeded):
    _, params = seeded
    router, experts = moe_parts(params["layers"][0])

    def text(n, first, count):
        held = {k: v[first:first + count] for k, v in experts.items()}
        return jax.jit(lambda a: moe.moe_dropless(
            a, router, held, top_k=4, first_expert=first,
            impl="ragged")).lower(
                jax.ShapeDtypeStruct((n, 64), jnp.float32)).as_text()
    assert "case" not in text(500, 0, 16)       # every expert held, packed
    assert "case" not in text(40, 4, 4)         # pairs under a row tile
    assert "case" in text(512, 4, 4)


def test_the_bucket_node_and_its_counters(seeded):
    from mmlspark_tpu.obs.metrics import registry

    cfg, params = seeded
    # 4 of 16 experts held; 62 tokens x 4 picks a row, 8 rows a step: 1984
    # pairs a step, 124 an expert (packed): rungs of 768, 1536 and 2304 rows
    share_cfg = tiny(n_routed_experts=4, first_expert=LADDER_FIRST)
    share = {"outer": params["outer"], "layers": [
        held_share(p, LADDER_FIRST) for p in params["layers"]]}
    # layer 1 sends every pick of half the tokens to the held experts: its
    # steps pass the first rung
    router = np.array(share["layers"][1]["moe/router/kernel"], np.float32)
    router[:, 4:8] = 50.0 * np.abs(router[:, 4:8]).max()
    share["layers"][1] = dict(share["layers"][1],
                              **{"moe/router/kernel": jnp.asarray(router)})
    tokens = tokens_of(16, (8, 62))
    bucket = apply(share_cfg, share, tokens, "moe_bucket")
    assert bucket.shape == (8, 3) and bucket.dtype == np.int32
    assert (bucket == bucket[0]).all()          # one step: one rung a layer
    load = apply(share_cfg, share, tokens, "expert_load").reshape(8, 3, 4)
    rows = padded_rows(load.sum(axis=0), 8 * 62 * 4, 16)    # a layer-step
    ladder = np.array(moe.bucket_ladder(8 * 62 * 4, 4, 16))
    assert tuple(ladder) == (768, 1536, 2304)
    np.testing.assert_array_equal(
        bucket[0], [int(np.argmax(ladder >= r)) for r in rows])
    assert bucket[0, 0] == 0
    before = {k: registry().value(k) or 0
              for k in ("moe.bucket_steps", "moe.bucket_steps_first")}
    # scored two steps of 4 rows: every row of a step carries its rung
    out = lm.publish_bucket_steps(bucket, rows_per_step=4)
    assert out == {"moe.bucket_steps": 6,
                   "moe.bucket_steps_first": 2 * int((bucket[0] == 0).sum())}
    assert out["moe.bucket_steps_first"] < out["moe.bucket_steps"]
    for k, v in out.items():
        assert registry().value(k) == before[k] + v


def test_the_four_shares_add_up_to_the_uncut_layer(seeded):
    """The routed parts that the four shares give, with attention, the
    router and the shared expert counted once, equal the uncut reference
    layer; a share's program and its reference agree part by part."""
    cfg, params = seeded
    p = params["layers"][2]
    x = jnp.asarray(np.random.default_rng(5).normal(size=(48, 64)),
                    jnp.float32)
    pos = jnp.arange(48)
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.layer(p, x, pos, cfg)
        h = x + ref.mla(p, ref.rms_norm(x, p["input_norm/scale"], 1e-6),
                        pos, cfg)
        hn = ref.rms_norm(h, p["post_norm/scale"], 1e-6)
        total = jnp.zeros_like(x)
        for first in (0, 4, 8, 12):
            share = {k: (v[first:first + 4] if k.startswith("moe/experts/")
                         else v) for k, v in p.items()}
            share_cfg = tiny(n_routed_experts=4, first_expert=first)
            router, experts = moe_parts(share)
            got, _, _ = moe.moe_dropless(hn, router, experts, top_k=4,
                                         first_expert=first)
            want, shared, _ = ref.moe(share, hn, share_cfg, parts=True)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=2e-6)
            total = total + got
    np.testing.assert_allclose(np.asarray(h + total + shared),
                               np.asarray(whole), atol=5e-6)


# ---- the whole model ----

def test_logits_in_float32_match_the_reference_tightly(seeded):
    cfg, params = seeded
    tokens = tokens_of(11, (3, 32))
    with jax.default_matmul_precision("highest"):
        got = apply(cfg, params, tokens, "logits", param_dtype=jnp.float32)
        want = reference_rows(params, tokens, cfg)
    # float32 on both sides: what is left is the order of the sums
    np.testing.assert_allclose(got, want["logits"], atol=3e-5)
    feats = apply(cfg, params, tokens, "features", param_dtype=jnp.float32)
    np.testing.assert_allclose(feats, want["features"], atol=1e-5)


def test_logits_in_bfloat16_match_on_cleanly_routed_tokens(seeded):
    cfg, params = seeded
    tokens = tokens_of(12, (4, 32))
    want = reference_rows(params, tokens, cfg)
    got = apply(cfg, params, tokens, "logits", dtype=jnp.bfloat16)
    # bf16 operands (8 bits of mantissa) through 3 layers: under 1 % of the
    # logits' scale in the rms (read 0.4-0.9 % over seeds). A token whose
    # 4th and 5th router probabilities are nearer than rounding may pick
    # another expert, rightly (read: up to 21 % of the scale there), so the
    # worst logit is judged on tokens whose margin is at least 3e-3; those
    # still see a wrongly-picking neighbour through attention, which at 32
    # keys is a large share of a key (read 1.9-6.3 %), hence 10 %
    scale = np.abs(want["logits"]).max()
    err = np.abs(got - want["logits"])
    assert np.sqrt(np.mean(err ** 2)) < 0.02 * scale
    clean = want["margin"] >= 3e-3
    assert clean.mean() > 0.3
    assert err[clean].max() < 0.1 * scale


def test_token_logprob_chunked_equals_unchunked(seeded):
    cfg, params = seeded
    tokens = tokens_of(13, (2, 32))
    with jax.default_matmul_precision("highest"):
        whole = apply(cfg, params, tokens, "token_logprob",
                      param_dtype=jnp.float32, logprob_chunk=32)
        chunked = apply(cfg, params, tokens, "token_logprob",
                        param_dtype=jnp.float32, logprob_chunk=8)
        want = reference_rows(params, tokens, cfg)["token_logprob"]
    np.testing.assert_allclose(chunked, whole, atol=1e-5)
    np.testing.assert_allclose(whole, want, atol=3e-5)
    assert (whole[:, 0] == 0).all() and (whole[:, 1:] < 0).all()


def test_expert_load_counts_the_picks_on_held_experts(seeded):
    cfg, params = seeded
    share_cfg = tiny(n_routed_experts=4, first_expert=8)
    share = {"outer": params["outer"], "layers": [
        {k: (v[8:12] if k.startswith("moe/experts/") else v)
         for k, v in p.items()} for p in params["layers"]]}
    tokens = tokens_of(14, (3, 32))
    with jax.default_matmul_precision("highest"):
        load = apply(share_cfg, share, tokens, "expert_load",
                     param_dtype=jnp.float32).reshape(3, 3, 4)
        # the reference's picks, layer by layer, on the same partial states
        want = np.zeros((3, 3, 4))
        for r, row in enumerate(tokens):
            x = share["outer"]["embed/embedding"][row]
            for i, p in enumerate(share["layers"]):
                h = x + ref.mla(p, ref.rms_norm(x, p["input_norm/scale"],
                                                1e-6), jnp.arange(32),
                                share_cfg)
                picks, _, _ = ref.route(
                    p, ref.rms_norm(h, p["post_norm/scale"], 1e-6),
                    share_cfg, lambda a: a)
                for e in range(4):
                    want[r, i, e] = int((np.asarray(picks) == 8 + e).sum())
                x, _ = ref.layer(p, x, jnp.arange(32), share_cfg)
    np.testing.assert_array_equal(load, want)
    from mmlspark_tpu.obs.metrics import registry
    before = registry().value("moe.held_pairs") or 0
    out = lm.publish_expert_load(load.sum(axis=0), 3 * 32 * 3)
    assert out["moe.held_pairs"] == int(want.sum())
    assert out["moe.expert_load_max"] == int(want.sum(axis=0).max())
    assert registry().value("moe.held_pairs") == before + want.sum()
    assert registry().value("moe.expert_load_max") == want.sum(axis=0).max()


def test_a_token_table_through_transform_equals_the_module(seeded):
    from mmlspark_tpu.data.table import DataTable
    from mmlspark_tpu.models.bundle import ModelBundle
    from mmlspark_tpu.models.jax_model import JaxModel

    cfg, params = seeded
    module = lm.from_config(cfg, dtype=jnp.bfloat16, logprob_chunk=8)
    tree = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16) if a.ndim >= 2 else a,
        program_tree(params))
    bundle = ModelBundle(module=module, params=tree, input_spec=(32,),
                         output_names=module.OUTPUT_NAMES, name="tiny_lm")
    tokens = tokens_of(15, (7, 32)).astype(np.int32)
    table = DataTable({"tokens": tokens})
    out = JaxModel(model=bundle, input_col="tokens", output_col="lp",
                   minibatch_size=4, output_node="token_logprob",
                   mesh_spec={"dp": 1}).transform(table)
    direct = np.asarray(module.apply({"params": tree},
                                     jnp.asarray(tokens, jnp.float32),
                                     output="token_logprob"))
    got = np.stack(list(out["lp"]))
    assert got.shape == (7, 32) and got.dtype == np.float32
    # the same program at another batch size: sums in another order
    np.testing.assert_allclose(got, direct, atol=2e-2)
    with pytest.raises(ValueError, match="unknown output node"):
        bundle.resolve_output("expert_mass")


def test_a_padded_tail_step_takes_a_later_rung_and_answers_alike(seeded):
    """A short ``transform`` call pads its tail step with rows of id 0:
    every token of such a row routes alike, so the step's held pairs pass
    the first rung. No pair is left out: the tail row answers as it does
    alone (one row is under a row tile: the plain program)."""
    from mmlspark_tpu.data.table import DataTable
    from mmlspark_tpu.models.bundle import ModelBundle
    from mmlspark_tpu.models.jax_model import JaxModel

    _, params = seeded
    first = 12
    cfg = tiny(n_routed_experts=4, first_expert=first)
    share = {"outer": params["outer"],
             "layers": [held_share(p, first) for p in params["layers"]]}
    module = lm.from_config(cfg, param_dtype=jnp.float32)
    tree = program_tree(share)
    bundle = ModelBundle(module=module, params=tree, input_spec=(64,),
                         output_names=module.OUTPUT_NAMES, name="tiny_lm")
    tokens = tokens_of(17, (9, 64)).astype(np.int32)   # 8 + a tail of 1

    def column(node):
        out = JaxModel(model=bundle, input_col="tokens", output_col="out",
                       minibatch_size=8, output_node=node,
                       mesh_spec={"dp": 1}).transform(
                           DataTable({"tokens": tokens}))
        return np.stack(list(out["out"]))

    with jax.default_matmul_precision("highest"):
        bucket = column("moe_bucket")
        got = column("token_logprob")
        alone = np.asarray(module.apply(
            {"params": tree}, jnp.asarray(tokens[8:], jnp.float32),
            output="token_logprob"))
        whole = np.asarray(module.apply(
            {"params": tree}, jnp.asarray(tokens[:8], jnp.float32),
            output="token_logprob"))
    # 128 pairs an expert: every expert's pairs in whole tiles of 256 rows
    assert moe.bucket_ladder(8 * 64 * 4, 4, 16) == (1024, 2048, 3072)
    assert (bucket[:8] == 0).all()              # the full step: first rung
    assert bucket[8].max() > 0                  # the padded one: a later one
    np.testing.assert_allclose(got[8:], alone, atol=3e-5)
    np.testing.assert_allclose(got[:8], whole, atol=3e-5)


# ---- the tiled attention kernel ----

@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("causal", [True, False])
def test_tiled_attention_past_the_old_vmem_bound(causal):
    r = np.random.default_rng(21)
    b, h, t, d = 1, 2, 2100, 16
    assert not fa._fits_vmem(t, t, d, fa.DEFAULT_BLOCK_K)
    q, k, v = (jnp.asarray(r.normal(size=(b, h, t, d)), jnp.float32)
               for _ in range(3))
    mask = jnp.asarray(r.random((b, t)) > 0.1)
    got = fa.flash_attention(q, k, v, kv_mask=mask, causal=causal,
                             impl="pallas")
    want = attention_reference(q.transpose(0, 2, 1, 3),
                               k.transpose(0, 2, 1, 3),
                               v.transpose(0, 2, 1, 3), causal=causal,
                               kv_mask=mask).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.usefixtures("pallas_interpret")
def test_a_tile_that_fits_keeps_the_whole_tile_kernel(monkeypatch):
    # flash_attention's behaviour at the sizes that fit must not change
    def refuse(*a, **k):
        raise AssertionError("the tiled kernel ran at a size that fits")
    monkeypatch.setattr(fa, "_tiled_call", refuse)
    r = np.random.default_rng(22)
    q, k, v = (jnp.asarray(r.normal(size=(1, 2, 48, 8)), jnp.float32)
               for _ in range(3))
    got = fa.flash_attention(q, k, v, causal=True, impl="pallas")
    want = fa.flash_attention(q, k, v, causal=True, impl="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.usefixtures("pallas_interpret")
def test_tiled_attention_takes_bf16_operands_and_wider_values():
    r = np.random.default_rng(23)
    q = jnp.asarray(r.normal(size=(1, 1, 2048, 32)), jnp.bfloat16)
    k = jnp.asarray(r.normal(size=(1, 1, 2048, 32)), jnp.bfloat16)
    v = jnp.asarray(r.normal(size=(1, 1, 2048, 16)), jnp.bfloat16)
    got = fa.flash_attention(q, k, v, causal=True, impl="pallas")
    want = fa.flash_attention(q, k, v, causal=True, impl="xla")
    assert got.shape == (1, 1, 2048, 16) and got.dtype == jnp.float32
    # the weights are rounded to bf16 for the p.v product: 2^-9 relative
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-2)


def _xla_attention(q, k, v, **kw):
    return np.asarray(fa.flash_attention(q, k, v, impl="xla", **kw))


def test_a_block_with_no_mask_is_the_masked_arithmetic_to_the_bit():
    # the claim the interior tiles rest on, where each operation runs alone
    # (numpy, and jax op by op): dropping the selects and the guard of an
    # all-true block changes no bit, whether the carry is fresh or live
    r = np.random.default_rng(27)
    q, ks, vs = (r.normal(size=(48, 16)).astype(np.float32)
                 for _ in range(3))
    keep = np.ones((48, 48), bool)
    fresh = (np.full((48, 1), -np.inf, np.float32),
             np.zeros((48, 1), np.float32), np.zeros((48, 16), np.float32))
    live = fa._online_update(q, ks[::-1], vs, keep, *fresh,
                             np.float32(0.25), np)
    for carry in (fresh, live):
        want = fa._online_update(q, ks, vs, keep, *carry,
                                 np.float32(0.25), np)
        got = fa._online_update(q, ks, vs, None, *carry,
                                np.float32(0.25), np)
        with jax.disable_jit():
            on_jax = [fa._online_update(
                *(jnp.asarray(a) for a in (q, ks, vs)), mask,
                *(jnp.asarray(a) for a in carry), np.float32(0.25), jnp)
                for mask in (jnp.asarray(keep), None)]
        for w, g, jw, jg in zip(want, got, *on_jax):
            np.testing.assert_array_equal(w, g)
            np.testing.assert_array_equal(np.asarray(jw), np.asarray(jg))


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("dtype,ulp,atol", [(jnp.float32, 2e-6, 2e-6),
                                            (jnp.bfloat16, 2e-4, 1e-2)])
def test_tiled_attention_whole_tiles_with_and_without_a_key_row(dtype, ulp,
                                                                atol):
    # no key row: interior tiles with no mask, the diagonal's local
    # triangle; an all-true kv_mask: the general path. On the chip the two
    # are equal to the bit (chip_smoke.py asserts it there); XLA's CPU
    # backend, which runs the interpreted kernel, fuses the two forms
    # differently and may differ in the last place of a weight, which bf16
    # operands widen to one rounding of a p.v weight
    r = np.random.default_rng(24)
    q, k, v = (jnp.asarray(r.normal(size=(1, 2, 2048, 32)), dtype)
               for _ in range(3))
    bare = np.asarray(fa.flash_attention(q, k, v, causal=True,
                                         impl="pallas"))
    rowed = np.asarray(fa.flash_attention(
        q, k, v, kv_mask=jnp.ones((1, 2048), bool), causal=True,
        impl="pallas"))
    np.testing.assert_allclose(bare, rowed, rtol=0, atol=ulp)
    want = _xla_attention(q, k, v, causal=True)
    for got in (bare, rowed):
        assert got.dtype == np.float32 and np.isfinite(got).all()
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=atol)


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("causal", [True, False])
def test_tiled_attention_keeps_its_guards_where_a_key_can_be_masked(causal):
    # row 0 of the batch masks its whole first key tile (and more): under
    # causal its first 640 queries keep no key at all and come back as
    # exact zeros; the rest, whose first tile is wholly masked, are finite.
    # Row 1 masks nothing
    r = np.random.default_rng(25)
    b, h, t, d = 2, 1, 1536, 16
    q, k, v = (jnp.asarray(r.normal(size=(b, h, t, d)), jnp.float32)
               for _ in range(3))
    mask = np.ones((b, t), bool)
    mask[0, :640] = False
    got = np.asarray(fa.flash_attention(q, k, v, kv_mask=jnp.asarray(mask),
                                        causal=causal, impl="pallas"))
    assert np.isfinite(got).all()
    if causal:
        assert (got[0, :, :640] == 0).all()
    assert (np.abs(got[0, :, 640:]).max(axis=-1) > 0).all()
    np.testing.assert_allclose(
        got, _xla_attention(q, k, v, kv_mask=jnp.asarray(mask),
                            causal=causal), rtol=2e-5, atol=2e-6)


def _tile_counts(fn, *specs):
    from mmlspark_tpu.obs.metrics import registry

    def read():
        return [registry().value(fa.TILES_COUNTER, kind=kind) or 0
                for kind in fa.TILE_KINDS] \
            + [registry().value(fa.GRID_STEPS_COUNTER) or 0]
    before = read()
    jax.eval_shape(fn, *specs)
    return [int(a - b) for a, b in zip(read(), before)]


@pytest.mark.parametrize("case,want", [
    # the language-model cell's layer-window: 36 of the 64 tile pairs, and
    # a grid step a query tile (its keys stay in VMEM, the blocks a loop)
    ("lm_window", [28, 8, 0, 8]),
    # a key row makes every pair general; the pairs above the diagonal
    # still are not run
    ("kv_mask", [0, 0, 36, 8]),
    # 2100 keys pad to 5 tiles: padded keys are a key row too
    ("ragged", [0, 0, 15, 5]),
    # not causal: the whole rectangle, and no mask to build
    ("not_causal", [64, 0, 0, 8]),
    ("not_causal_ragged", [0, 0, 25, 5]),
    # 32 key blocks in float32 outgrow one resident stretch: five of 7, 7,
    # 7, 7 and 4 blocks; a query tile's steps end at its diagonal's stretch
    ("long_f32", [496, 32, 0, 90]),
])
def test_tiled_attention_counts_its_tiles_by_kind_when_traced(case, want):
    s = jax.ShapeDtypeStruct
    t = {"ragged": 2100, "not_causal_ragged": 2100,
         "long_f32": 16384}.get(case, 4096)
    dtype = jnp.float32 if case == "long_f32" else jnp.bfloat16
    qkv = (s((2, 32, t, 128), dtype),) * 3
    causal = "not_causal" not in case
    if case == "kv_mask":
        got = _tile_counts(
            lambda q, k, v, m: fa.flash_attention(
                q, k, v, kv_mask=m, causal=True, impl="pallas"),
            *qkv, s((2, t), jnp.bool_))
    else:
        got = _tile_counts(
            lambda q, k, v: fa.flash_attention(q, k, v, causal=causal,
                                               impl="pallas"), *qkv)
    assert got == want


def test_the_tile_plan_covers_the_causal_triangle_once():
    # whatever the stretch, a query tile runs exactly the key blocks that
    # hold a key at or below its last row, the mask-free ones first and only
    # where every key is kept, and ends exactly once, on its last step.
    # Unequal tiles (a short window: bq 112, bk 128) straddle the diagonal
    # anywhere
    for nq, blocks, sub, bq, bk in [(8, 8, 8, 512, 512), (8, 8, 3, 512, 512),
                                    (3, 5, 2, 512, 512), (1, 1, 1, 112, 128),
                                    (4, 2, 2, 256, 512), (5, 5, 1, 512, 512)]:
        qi, kj, free, masked, last = fa._tile_plan(nq, blocks, sub, bq, bk,
                                                   True, False)
        assert last.sum() == nq and (np.diff(qi) >= 0).all()
        assert (last[np.flatnonzero(np.diff(qi))] == 1).all() and last[-1]
        ran = {}
        for i, j, f, m in zip(qi, kj, free, masked):
            assert f + m > 0 and j * sub + f + m <= blocks
            for n, c in enumerate(range(j * sub, j * sub + f + m)):
                assert (i, c) not in ran
                ran[i, c] = n < f
        for i in range(nq):
            for c in range(blocks):
                rows = np.arange(i * bq, (i + 1) * bq)[:, None]
                keep = np.arange(c * bk, (c + 1) * bk)[None, :] <= rows
                assert ((i, c) in ran) == keep.any()
                if (i, c) in ran:
                    assert ran[i, c] == keep.all()
                    if bq == bk and not keep.all():
                        assert (keep == np.tril(np.ones((bq, bk), bool))).all()
    # a key row: nothing is mask-free; not causal: every block runs
    _, _, free, masked, _ = fa._tile_plan(3, 5, 2, 512, 512, True, True)
    assert free.sum() == 0 and masked.sum() == 1 + 2 + 3
    _, _, free, masked, _ = fa._tile_plan(3, 5, 2, 512, 512, False, False)
    assert free.sum() == 15 and masked.sum() == 0


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("masked", [False, True])
def test_tiled_attention_across_several_resident_stretches(monkeypatch,
                                                           masked):
    # a VMEM budget that holds two key blocks a stretch: five blocks lie in
    # stretches of 2, 2 and 1 (the last one short), the carry crosses them
    monkeypatch.setattr(fa, "_resident_blocks", lambda blocks, *a: 2)
    r = np.random.default_rng(26)
    t = 2100 if masked else 2560
    q, k, v = (jnp.asarray(r.normal(size=(1, 2, t, 16)), jnp.float32)
               for _ in range(3))
    mask = jnp.asarray(r.random((1, t)) > 0.1) if masked else None
    for causal in (True, False):
        got = fa.flash_attention(q, k, v, kv_mask=mask, causal=causal,
                                 impl="pallas")
        np.testing.assert_allclose(
            np.asarray(got), _xla_attention(q, k, v, kv_mask=mask,
                                            causal=causal),
            rtol=2e-5, atol=2e-6)
