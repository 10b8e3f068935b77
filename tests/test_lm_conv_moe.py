"""The conv / grouped-query / sigmoid-routed LM family (``models/lm_conv.py``,
``model_type: lfm2_moe``) against the plain reference
``benchmark/reference/lfm2.py`` on seeded weights, at tiny sizes on the CPU;
with it what the family forced elsewhere: grouped key/value heads in
``flash_attention``, the router's score function and selection bias in
``parallel/moe.route_topk``, and the grouped product's tile rule."""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from flax.traverse_util import flatten_dict, unflatten_dict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.reference import lfm2 as ref  # noqa: E402
from mmlspark_tpu.models import lm, lm_conv  # noqa: E402
from mmlspark_tpu.obs.metrics import registry  # noqa: E402
from mmlspark_tpu.ops.pallas import attention as fa  # noqa: E402
from mmlspark_tpu.parallel import moe  # noqa: E402
from mmlspark_tpu.parallel.ring_attention import attention_reference  # noqa: E402

PERIOD = ["full_attention", "conv", "conv", "conv"]
# the cell's stage: one leading conv layer (dense), then three whole periods
STAGE13 = ["conv"] + PERIOD * 3
# the published list: its tail (full, conv, conv, full, conv, conv) breaks
# the period of four
PUBLISHED24 = (["conv", "conv"] + PERIOD * 4
               + ["full_attention", "conv", "conv"] * 2)
PATTERNS = {"stage13": (STAGE13, 1), "published24": (PUBLISHED24, 2)}


def tiny(pattern: str = "stage13", **over) -> dict:
    layer_types, dense = PATTERNS[pattern]
    cfg = dict(
        family="lfm2", model_type="lfm2_moe", vocab_size=256, hidden_size=64,
        intermediate_size=96, moe_intermediate_size=32,
        num_hidden_layers=len(layer_types), num_dense_layers=dense,
        layer_types=list(layer_types), num_attention_heads=8,
        num_key_value_heads=2, num_experts=8, num_experts_per_tok=4,
        conv_L_cache=3, conv_bias=False, norm_eps=1e-5, norm_topk_prob=True,
        routed_scaling_factor=1, use_expert_bias=True, rope_theta=1000000,
        param_dtype="bfloat16", compute_dtype="float32")
    cfg.update(over)
    return cfg


def program_tree(cfg: dict, params: dict) -> dict:
    """The reference's ``make_params`` in the program's tree: every leaf of
    a layer stacked, in layer order, over the layers of its kind."""
    flat = dict(params["outer"])
    for path, (kind, _) in ref.layer_paths(cfg).items():
        layers = ref.layers_of(cfg, kind)
        if layers:
            flat[path] = jnp.stack([params["layers"][i][path]
                                    for i in layers])
    return unflatten_dict(flat, sep="/")


def tokens_of(seed: int, shape, vocab: int = 256) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, vocab, size=shape)


def reference_rows(params, tokens, cfg, **kw) -> dict:
    rows = [ref.forward(params, jnp.asarray(t), cfg, **kw) for t in tokens]
    return {k: np.stack([np.asarray(r[k]) for r in rows]) for k in rows[0]}


@pytest.fixture(scope="module", params=sorted(PATTERNS))
def seeded(request):
    cfg = tiny(request.param)
    params = ref.make_params(cfg, jax.random.PRNGKey(7))
    return cfg, params, program_tree(cfg, params)


def apply(cfg, tree, tokens, node, **over):
    module = lm.from_config(cfg, **over)
    return np.asarray(module.apply({"params": tree},
                                   jnp.asarray(tokens, jnp.float32),
                                   output=node))


# ---- the configuration, the tree and how the stack is cut ----

def test_from_config_builds_each_family_by_model_type():
    module = lm.from_config(tiny())
    assert isinstance(module, lm_conv.ConvMoELM)
    assert module.cfg.n_routed_experts == 8 and module.cfg.head_dim == 8
    assert type(module).OUTPUT_NAMES == lm.LatentMoELM.OUTPUT_NAMES
    import test_lm_latent_moe as latent
    assert isinstance(lm.from_config(latent.tiny()), lm.LatentMoELM)
    with pytest.raises(ValueError, match="layer_types"):
        lm.from_config(tiny(num_hidden_layers=12))
    with pytest.raises(ValueError, match="unknown layer types"):
        lm.from_config(tiny(layer_types=["conv"] * 12 + ["sliding"]))


def test_the_stack_is_cut_into_runs_of_a_repeated_period():
    stage = lm.from_config(tiny("stage13")).cfg.kinds
    # one leading layer, then (attention, conv, conv, conv) three times
    assert lm_conv.segments(stage) == [(0, 1, 1), (1, 4, 3)]
    published = lm.from_config(tiny("published24")).cfg.kinds
    # two dense conv layers, the period of four four times, the tail's
    # period of three twice
    assert lm_conv.segments(published) == [(0, 1, 2), (2, 4, 4), (18, 3, 2)]
    for kinds in (stage, published):
        covered = [start + j for start, period, repeats
                   in lm_conv.segments(kinds)
                   for j in range(period * repeats)]
        assert covered == list(range(len(kinds)))
    # a list with no repeat at all is run layer by layer
    odd = (("conv", "dense"), ("full_attention", "moe"), ("conv", "moe"))
    assert lm_conv.segments(odd) == [(0, 1, 1), (1, 1, 1), (2, 1, 1)]


def test_the_reference_makes_every_leaf_of_the_programs_tree(seeded):
    # what benchmark/drivers/token_score.py asks of the reference: each
    # path of the program's tree whole from ``outer_leaf``
    cfg, _, tree = seeded
    key = jax.random.PRNGKey(7)
    module = lm.from_config(cfg)
    want = flatten_dict(jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8))))
        ["params"], sep="/")
    have = flatten_dict(tree, sep="/")
    assert sorted(want) == sorted(have)
    for path, leaf in want.items():
        made = np.asarray(ref.outer_leaf(cfg, key, path).astype(jnp.float32))
        assert made.shape == leaf.shape, path
        # the same values as a layer at a time gives, but for a bf16 ulp on
        # a few elements in ten thousand: XLA's CPU backend compiles the
        # generator inside ``lax.map`` differently, a float32 ulp before
        # the rounding
        layerwise = np.asarray(have[path])
        off = made != layerwise
        assert off.mean() < 1e-3, path
        np.testing.assert_allclose(made, layerwise, rtol=2 ** -7)


def test_the_tree_holds_its_layers_by_kind(seeded):
    cfg, _, tree = seeded
    flat = flatten_dict(tree, sep="/")
    n_attn = cfg["layer_types"].count("full_attention")
    n_moe = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    assert flat["attn/k"].shape == (n_attn, 64, 2 * 8)
    assert flat["conv/in_proj"].shape == (
        cfg["num_hidden_layers"] - n_attn, 64, 3 * 64)
    assert flat["dense/gate"].shape == (cfg["num_dense_layers"], 64, 96)
    assert flat["routed/down"].shape == (n_moe, 8, 32, 64)
    assert flat["router/bias"].shape == (n_moe, 8)
    assert flat["norms/ffn_norm"].shape == (cfg["num_hidden_layers"], 64)
    # the head is the embedding: no second [d, V] matrix
    assert not any("head" in path for path in flat)
    # no name the token driver would map over all num_hidden_layers
    assert not any(p.startswith(("layers/", "experts/")) for p in flat)
    reg = registry()
    assert reg.value("lm.layers", kind="full_attention") == n_attn
    assert reg.value("lm.ff", kind="moe") == n_moe


# ---- the whole model against the reference ----

def test_float32_matches_the_reference_tightly(seeded):
    cfg, params, tree = seeded
    tokens = tokens_of(11, (2, 32))
    # the values held in float32: the expert stacks reach the grouped
    # product in the type they are stored in
    f32 = dict(param_dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        logits = apply(cfg, tree, tokens, "logits", **f32)
        logprob = apply(cfg, tree, tokens, "token_logprob", **f32)
        want = reference_rows(params, tokens, cfg)
    # float32 on both sides, the residual stream too: what is left is the
    # order of the sums, which 13 or 24 layers of random weights amplify
    # (a 1e-7 change of the embedding moves the reference's own logits by
    # 5e-6 after 5 layers; read here: 4e-6 to 2e-5)
    np.testing.assert_allclose(logits, want["logits"], atol=1e-4)
    np.testing.assert_allclose(logprob, want["token_logprob"], atol=1e-4)
    assert (logprob[:, 0] == 0).all() and (logprob[:, 1:] < 0).all()


def test_bfloat16_stays_near_the_reference(seeded):
    cfg, params, tree = seeded
    tokens = tokens_of(12, (2, 32))
    want = reference_rows(params, tokens, cfg)
    logits = apply(cfg, tree, tokens, "logits", dtype=jnp.bfloat16)
    logprob = apply(cfg, tree, tokens, "token_logprob", dtype=jnp.bfloat16)
    # bf16 operands and a bf16 residual stream (8 bits of mantissa) through
    # 13 or 24 layers, at widths where one of 8 experts is an eighth of the
    # layer: a token whose 4th and 5th biased scores are nearer than
    # rounding picks another expert, rightly, and its neighbours see it
    # through the convolution's two taps back and through attention, so no
    # margin rule keeps a token clean here (read over seeds: rms 2.3-6.0 %
    # of the logits' scale, the worst logit 20-66 %). The rms says the
    # mathematics is the same; how close the chip comes at the published
    # widths is the benchmark cell's ``correct``
    scale = np.abs(want["logits"]).max()
    err = logits - want["logits"]
    assert np.sqrt(np.mean(err ** 2)) < 0.1 * scale
    gap = (logprob - want["token_logprob"])[:, 1:]
    assert np.sqrt(np.mean(gap ** 2)) < 0.1 * scale
    assert (logprob[:, 0] == 0).all()


def test_expert_load_counts_four_picks_a_token_a_layer(seeded):
    cfg, _, tree = seeded
    tokens = tokens_of(13, (3, 32))
    n_moe = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    load = apply(cfg, tree, tokens, "expert_load").reshape(3, n_moe, 8)
    # every expert is held: each token's four picks land in every layer
    np.testing.assert_array_equal(load.sum(axis=-1), 32 * 4)
    bucket = apply(cfg, tree, tokens, "moe_bucket")
    assert bucket.shape == (3, n_moe) and (bucket == 0).all()


def test_every_expert_held_leaves_no_conditional_in_the_program():
    cfg = tiny()
    module = lm.from_config(cfg)
    tree = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8))))["params"]
    program = jax.make_jaxpr(lambda p, x: module.apply(
        {"params": p}, x, output="token_logprob"))(
            tree, jnp.zeros((2, 32), jnp.float32))
    # the leading layer runs alone, the period as ONE scan over its three
    # repeats (the other scan is the head's chunk loop)
    assert [e.params["length"] for e in program.jaxpr.eqns
            if e.primitive.name == "scan"] == [3, 1]
    text = str(program)
    assert " cond[" not in text


def test_a_token_table_through_transform_equals_the_module():
    from mmlspark_tpu.data.table import DataTable
    from mmlspark_tpu.models.bundle import ModelBundle
    from mmlspark_tpu.models.jax_model import JaxModel

    cfg = tiny()
    tree = program_tree(cfg, ref.make_params(cfg, jax.random.PRNGKey(7)))
    module = lm.from_config(cfg)
    tokens = tokens_of(14, (5, 32)).astype(np.int32)
    bundle = ModelBundle(module=module, params=tree, input_spec=(32,),
                         output_names=type(module).OUTPUT_NAMES, name="tiny")
    model = JaxModel(model=bundle, input_col="tokens", output_col="lp",
                     minibatch_size=2, output_node="token_logprob",
                     mesh_spec={"dp": 1})
    out = model.transform(DataTable({"tokens": tokens}))["lp"]
    want = apply(cfg, tree, tokens[:2], "token_logprob")
    # float32 both ways; compiled as one program against op by op
    np.testing.assert_allclose(np.stack(list(out))[:2], want, atol=5e-5)
    assert len(out) == 5


# ---- the gated short convolution ----

def conv_weights(seed: int, d: int = 16) -> dict:
    r = np.random.default_rng(seed)
    return {"in_proj": jnp.asarray(r.normal(size=(d, 3 * d)) / 4, jnp.float32),
            "taps": jnp.asarray(r.normal(size=(3, d)), jnp.float32),
            "out_proj": jnp.asarray(r.normal(size=(d, d)) / 4, jnp.float32)}


def conv_cfg(d: int = 16):
    return lm.from_config(tiny(hidden_size=d, num_attention_heads=2,
                               num_key_value_heads=1)).cfg


def test_the_conv_is_causal_to_the_bit():
    p, c = conv_weights(1), conv_cfg()
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 12, 16)),
                    jnp.float32)
    base = np.asarray(lm_conv.short_conv(p, x, c))
    for t in (0, 5, 11):
        moved = np.asarray(lm_conv.short_conv(p, x.at[:, t].add(1.0), c))
        # nothing before t may know; t and the two after must
        np.testing.assert_array_equal(moved[:, :t], base[:, :t])
        assert (moved[:, t:t + 3] != base[:, t:t + 3]).any(axis=-1).all()
        np.testing.assert_array_equal(moved[:, t + 3:], base[:, t + 3:])


def test_the_conv_pads_with_zeros_before_the_rows_start():
    p, c = conv_weights(3), conv_cfg()
    x = jnp.asarray(np.random.default_rng(4).normal(size=(1, 6, 16)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(lm_conv.short_conv(p, x, c))[0]
        bcu = np.asarray(jnp.dot(x[0], p["in_proj"]))
        z = bcu[:, :16] * bcu[:, 32:]
        w = np.asarray(p["taps"])
        # the explicit three-term sum, the terms before the row left out
        mixed = [w[2] * z[0], w[1] * z[0] + w[2] * z[1],
                 w[0] * z[0] + w[1] * z[1] + w[2] * z[2]]
        want = np.asarray(jnp.dot(jnp.asarray(
            bcu[:3, 16:32] * np.stack(mixed)), p["out_proj"]))
    # float32 both ways; the sums run in another order
    np.testing.assert_allclose(got[:3], want, atol=1e-5)
    # and the reference's explicit shifted copies say the same of every row
    ref_p = {"conv/" + k: v for k, v in p.items()}
    with jax.default_matmul_precision("highest"):
        ref_out = ref.short_conv(ref_p, x[0], {"hidden_size": 16})
    np.testing.assert_allclose(got, np.asarray(ref_out), atol=1e-5)


# ---- grouped key/value heads in the attention kernels ----

@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("head", [64, 128])
@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("kernel", ["whole", "tiled"])
def test_grouped_heads_match_the_reference_with_repeated_keys(
        kernel, group, head, monkeypatch):
    r = np.random.default_rng(31)
    b, h, t = 1, 4, 640 if kernel == "tiled" else 40
    if kernel == "tiled":       # what a window past the VMEM bound takes
        monkeypatch.setattr(fa, "_fits_vmem", lambda *a, **k: False)
    q = jnp.asarray(r.normal(size=(b, h, t, head)), jnp.float32)
    k, v = (jnp.asarray(r.normal(size=(b, h // group, t, head)), jnp.float32)
            for _ in range(2))
    fn = jax.jit(lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                                    impl="pallas"))
    got = fn(q, k, v)
    assert registry().value(fa.KV_GROUP_GAUGE) == group
    # no copy of K or V with the queries' head count reaches the kernel
    call = [e for e in jax.make_jaxpr(fn)(q, k, v).jaxpr.eqns[0]
            .params["jaxpr"].eqns if e.primitive.name == "pallas_call"]
    assert len(call) == 1
    assert sum(v.aval.ndim == 4 and v.aval.shape[1] == h // group
               for v in call[0].invars) == 2 + (group == 1)
    want = attention_reference(
        q.transpose(0, 2, 1, 3),
        jnp.repeat(k, group, axis=1).transpose(0, 2, 1, 3),
        jnp.repeat(v, group, axis=1).transpose(0, 2, 1, 3),
        causal=True).transpose(0, 2, 1, 3)
    # float32 operands; the online softmax sums in blocks
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)
    # the XLA path (what the CPU runs) agrees too
    xla = fa.flash_attention(q, k, v, causal=True, impl="xla")
    np.testing.assert_allclose(np.asarray(xla), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_key_heads_must_divide_the_query_heads():
    q = jnp.zeros((1, 6, 8, 8))
    with pytest.raises(ValueError, match="divisor"):
        fa.flash_attention(q, q[:, :4], q[:, :4], impl="xla")
    with pytest.raises(ValueError, match="same"):
        fa.flash_attention(q, q[:, :3], q[:, :2], impl="xla")


# ---- the router: sigmoid scores, a selection bias ----

def router_case(seed: int = 41, n: int = 64, d: int = 16, e: int = 8):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(size=(n, d)), jnp.float32)
    w = jnp.asarray(r.normal(size=(d, e)) / 4, jnp.float32)
    scores = np.asarray(jax.nn.sigmoid(jnp.dot(
        x, w, precision=jax.lax.Precision.HIGHEST)))
    return x, w, scores


def test_no_bias_picks_the_plain_top_four_of_the_sigmoid():
    x, w, scores = router_case()
    picks, weights = moe.route_topk(x, w, 4, score="sigmoid",
                                    bias=jnp.zeros((8,)), norm_eps=1e-6)
    want = np.argsort(-scores, axis=-1)[:, :4]
    np.testing.assert_array_equal(np.asarray(picks), want)
    picked = np.take_along_axis(scores, want, axis=-1)
    total = picked.sum(-1)
    # the weights sum to sum(s) / (sum(s) + 1e-6), not to 1
    np.testing.assert_allclose(np.asarray(weights).sum(-1),
                               total / (total + 1e-6), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(weights), picked / (total[:, None] + 1e-6), rtol=1e-6)
    none = moe.route_topk(x, w, 4, score="sigmoid", norm_eps=1e-6)
    np.testing.assert_array_equal(np.asarray(none[0]), np.asarray(picks))


def test_a_bias_moves_the_pick_and_never_a_weights_numerator():
    x, w, scores = router_case(42)
    order = np.argsort(-scores, axis=-1)
    fourth, fifth = order[0, 3], order[0, 4]
    gap = scores[0, fourth] - scores[0, fifth]
    # lift token 0's fifth expert just over its fourth
    bias = np.zeros((8,), np.float32)
    bias[fifth] = gap * 1.5
    picks, weights = moe.route_topk(x, w, 4, norm_topk=False,
                                    score="sigmoid", bias=jnp.asarray(bias))
    plain, plain_w = moe.route_topk(x, w, 4, norm_topk=False,
                                    score="sigmoid")
    got, was = set(np.asarray(picks)[0]), set(np.asarray(plain)[0])
    assert got == (was - {fourth}) | {fifth}
    by_expert = dict(zip(np.asarray(picks)[0], np.asarray(weights)[0]))
    # the numerators are the unbiased scores: the three that stayed keep
    # theirs to the bit, the newcomer gets its own score, not score + bias
    for e, s in zip(np.asarray(plain)[0], np.asarray(plain_w)[0]):
        if e != fourth:
            assert by_expert[e] == s
    assert by_expert[fifth] == pytest.approx(scores[0, fifth], rel=1e-6)
    with pytest.raises(ValueError, match="router score"):
        moe.route_topk(x, w, 4, score="tanh")


def test_the_expert_layer_matches_the_reference():
    cfg = tiny()
    key = jax.random.PRNGKey(5)
    p = ref.make_layer_params(cfg, key, 3, ("conv", "moe"))
    x = jnp.asarray(np.random.default_rng(6).normal(size=(48, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, margin = ref.moe(p, x, cfg)
        got, picks, bucket = moe.moe_dropless(
            x, p["router/kernel"],
            {k: p["routed/" + k] for k in ("gate", "up", "down")}, top_k=4,
            impl="ragged", score="sigmoid", bias=p["router/bias"],
            norm_eps=1e-6)
    # float32 on both sides: the order of the sums over the picks
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert int(bucket) == 0 and float(jnp.min(margin)) >= 0
    # the stand-in bias does change some token's picks
    plain, _ = moe.route_topk(x, p["router/kernel"], 4, score="sigmoid")
    wide = tiny(num_experts=32)
    p32 = ref.make_layer_params(wide, key, 3, ("conv", "moe"))
    xs = jnp.asarray(np.random.default_rng(7).normal(size=(512, 64)),
                     jnp.float32)
    with_bias, _ = moe.route_topk(xs, p32["router/kernel"], 4,
                                  score="sigmoid", bias=p32["router/bias"])
    without, _ = moe.route_topk(xs, p32["router/kernel"], 4, score="sigmoid")
    changed = (np.sort(np.asarray(with_bias)) != np.sort(
        np.asarray(without))).any(axis=-1).mean()
    assert 0.02 < changed < 0.9
    assert plain.shape == picks.shape


@pytest.mark.parametrize("stacked", [False, True])
@pytest.mark.parametrize("impl", ["ragged", "gmm"])
def test_every_expert_held_the_padded_buffer_matches_the_reference(
        impl, stacked, request):
    """Every expert held at under four row tiles an expert: the buffer
    holds every expert's pairs in whole row tiles (the sigmoid router with
    its bias loads the eight experts unevenly), and its one rung holds the
    worst of them."""
    if impl == "gmm":
        request.getfixturevalue("pallas_interpret")
    cfg = tiny()
    p = ref.make_layer_params(cfg, jax.random.PRNGKey(5), 3, ("conv", "moe"))
    x = jnp.asarray(np.random.default_rng(9).normal(size=(1040, 64)),
                    jnp.float32)
    experts = {k: p["routed/" + k] for k in ("gate", "up", "down")}
    kw = {}
    if stacked:
        experts = {k: jnp.stack([v * 2.0, v]) for k, v in experts.items()}
        kw = {"layer": jnp.int32(1)}
    # 4160 pairs on 8 experts, 520 each: 1.25x that in three tiles of 256
    # (two of 320 would be more rows); at most 4160 + 8 x 255 rows, in
    # whole tiles, which the first rung would hold: one rung
    tile = moe.row_tile(1040 * 4, 8)
    assert tile == moe.GMM_ROWS == 256
    assert moe.bucket_ladder(1040 * 4, 8, 8) == (6144,)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.moe(p, x, cfg)
        fn = jax.jit(lambda a: moe.moe_dropless(
            a, p["router/kernel"], experts, top_k=4, impl=impl,
            score="sigmoid", bias=p["router/bias"], norm_eps=1e-6, **kw))
        got, picks, bucket = fn(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    load = np.bincount(np.asarray(picks).ravel(), minlength=8)
    assert int(bucket) == 0 and load.sum() == 4160
    assert (load % tile).all() and load.max() > 1.2 * load.min()
    padded = -(-load // tile) * tile
    assert moe.row_tile_visits(padded, tile) \
        < moe.row_tile_visits(load, tile)
    assert padded.sum() <= 6144


# ---- the grouped product's tiles ----

@pytest.mark.parametrize("m,k,n,rows,want", [
    # the Mistral cell's three products on its aligned buffer: 320 rows a
    # tile, and the weight tile that fits VMEM beside them (PR 39's probe)
    (14080, 4096, 2048, 320, (320, 2048, 1024)),
    (14080, 2048, 4096, 320, (320, 2048, 1024)),
    # the same on a packed buffer: the tiles PR 28 read on the chip
    (10240, 4096, 2048, 256, (256, 1024, 2048)),
    (10240, 2048, 4096, 256, (256, 2048, 1024)),
    # widths that are whole lane rows and no power of two: 1792 = 14 x 128
    (65536, 2048, 1792, 256, (256, 2048, 896)),
    (65536, 1792, 2048, 256, (256, 1792, 1024)),
    (16384, 2048, 7168, 256, (256, 2048, 1024)),
    (16384, 7168, 2048, 256, (256, 1024, 2048)),
    (16384, 7168, 2048, 320, (320, 1792, 1024)),
    # tiny test shapes: the axes whole
    (64, 64, 32, 256, (64, 64, 32)),
])
def test_the_grouped_products_tiles_come_from_the_shapes(m, k, n, rows,
                                                         want):
    tm, tk, tn = moe.gmm_tiles(m, k, n, rows)
    assert (tm, tk, tn) == want
    assert moe.gmm_tiles(m, k, n)[0] == min(m, moe.GMM_ROWS)
    assert moe._gmm_vmem(tm, tk, tn) <= moe.GMM_VMEM
    if k >= 128 and n >= 128:
        assert tk % 128 == 0 and tn % 128 == 0
        assert k % tk == 0 and n % tn == 0
        assert tk * tn <= moe.GMM_WEIGHT_TILE
    if rows == moe.GMM_ROWS and k > moe.GMM_WHOLE_K:
        # a packed buffer's long contraction keeps its half-length tiles
        assert tk <= moe.GMM_WHOLE_K // 2


def test_a_width_no_lane_multiple_divides_still_gets_whole_lane_rows():
    # 5000 = 2^3 x 5^4: no multiple of 128 divides it
    assert moe.gmm_tiles(512, 5000, 5000) == (256, 1024, 2048)
    assert moe.gmm_tiles(512, 2048, 5000) == (256, 2048, 1024)
    assert moe.gmm_tiles(640, 5000, 5000, 320) == (320, 2048, 1024)


# ---- the other family's program is untouched ----

def _old_route_topk(x, router, top_k, norm_topk=True, scaling=1.0, *_):
    """``route_topk`` as it was before it took a score function."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    weights, picks = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if norm_topk:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return picks.astype(jnp.int32), weights * scaling


def test_the_latent_familys_answers_are_equal_to_the_bit(monkeypatch):
    import test_lm_latent_moe as latent

    cfg = latent.tiny(compute_dtype="bfloat16")
    params = latent.ref.make_params(cfg, jax.random.PRNGKey(7))
    tokens = tokens_of(15, (3, 32))

    def run():
        module = lm.from_config(cfg)
        fn = jax.jit(lambda p, x: module.apply({"params": p}, x,
                                               output="token_logprob"))
        tree = latent.program_tree(params)
        return (np.asarray(fn(tree, jnp.asarray(tokens, jnp.float32))),
                str(jax.make_jaxpr(fn)(tree, jnp.asarray(tokens,
                                                         jnp.float32))))

    new, new_program = run()
    monkeypatch.setattr(moe, "route_topk", _old_route_topk)
    old, old_program = run()
    np.testing.assert_array_equal(new, old)
    # and it is the same program, operation for operation
    assert new_program == old_program
