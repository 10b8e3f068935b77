"""The Mamba-2 / ungated-expert / grouped-query hybrid LM family
(``models/lm_mamba2.py``, ``model_type: nemotron_h``) against the plain
reference ``benchmark/reference/nemotron_h.py`` on seeded weights, at tiny
widths on the CPU (hidden 64, 4 heads of 16, state 16, 2 groups, 8 experts
top-2, pattern ``MEM*E``); with it what the family forced elsewhere: the
ungated path of ``moe_dropless`` against a dense loop at packed and aligned
loads, and the share test (the shares' routed parts, with the shared expert
counted once, add up to the uncut reference layer)."""

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

from benchmark.reference import nemotron_h as ref  # noqa: E402
from mmlspark_tpu.models import lm, lm_mamba2  # noqa: E402
from mmlspark_tpu.models.lm_conv import grouped_attention  # noqa: E402
from mmlspark_tpu.obs.metrics import registry  # noqa: E402
from mmlspark_tpu.ops.pallas import ssd_scan as ss  # noqa: E402
from mmlspark_tpu.ops.pallas.causal_conv import causal_conv  # noqa: E402
from mmlspark_tpu.parallel import moe  # noqa: E402

# the row of /opt/skills/guides/model-configs/architectures.jsonl
PUBLISHED = dict(
    attention_bias=False, chunk_size=128, conv_kernel=4, expand=2,
    head_dim=128, hidden_size=2688,
    hybrid_override_pattern=(
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"),
    intermediate_size=1856, layer_norm_epsilon=1e-05, mamba_head_dim=64,
    mamba_hidden_act="silu", mamba_num_heads=64, mamba_proj_bias=False,
    max_position_embeddings=262144, mlp_bias=False, mlp_hidden_act="relu2",
    model_type="nemotron_h", moe_intermediate_size=1856,
    moe_shared_expert_intermediate_size=3712, n_group=1, n_groups=8,
    n_routed_experts=128, n_shared_experts=1, norm_eps=1e-05,
    norm_topk_prob=True, num_attention_heads=32, num_experts_per_tok=6,
    num_hidden_layers=52, num_key_value_heads=2, num_logits_to_keep=1,
    partial_rotary_factor=1, rescale_prenorm_residual=True,
    residual_in_fp32=False, rope_theta=10000, routed_scaling_factor=2.5,
    sliding_window=None, ssm_state_size=128, tie_word_embeddings=False,
    time_step_floor=0.0001, time_step_max=0.1, time_step_min=0.001,
    topk_group=1, use_bias=False, use_conv_bias=True, use_mamba_kernels=True,
    vocab_size=131072)


def tiny(**over) -> dict:
    cfg = dict(
        family="nemotron_h", model_type="nemotron_h", vocab_size=256,
        hidden_size=64, num_hidden_layers=5, hybrid_override_pattern="MEM*E",
        mamba_num_heads=4, mamba_head_dim=16, n_groups=2, ssm_state_size=16,
        conv_kernel=4, use_conv_bias=True, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, n_routed_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=48,
        moe_shared_expert_intermediate_size=96, n_shared_experts=1,
        norm_topk_prob=True, routed_scaling_factor=2.5,
        layer_norm_epsilon=1e-5, time_step_min=0.001, time_step_max=0.1,
        time_step_floor=1e-4, tie_word_embeddings=False,
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
    forward = jax.jit(lambda p, t: ref.forward(p, t, cfg, **kw))
    rows = [forward(params, jnp.asarray(t)) for t in tokens]
    return {k: np.stack([np.asarray(r[k]) for r in rows]) for k in rows[0]}


def apply(cfg, tree, tokens, node, **over):
    module = lm.from_config(cfg, **over)
    return np.asarray(module.apply({"params": tree},
                                   jnp.asarray(tokens, jnp.float32), node))


@pytest.fixture(scope="module")
def seeded():
    cfg = tiny()
    with jax.default_matmul_precision("highest"):
        params = ref.make_params(cfg, jax.random.PRNGKey(7))
    return cfg, params, program_tree(cfg, params)


# ---- the configuration and the tree ----

def count_parameters(cfg: dict) -> int:
    module = lm.from_config(cfg)
    shapes = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0),
                                                jnp.zeros((1, 8))))
    return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))


def test_the_published_keys_build_the_published_model():
    module = lm.from_config(PUBLISHED)
    assert isinstance(module, lm_mamba2.NemotronHLM)
    c = module.cfg
    assert (c.d_inner, c.conv_dim) == (4096, 6144)      # NOT expand x d
    assert c.kinds.count("mamba2") == 23 and c.kinds.count("moe") == 23
    assert [i for i, k in enumerate(c.kinds) if k == "attention"] == [
        5, 12, 19, 26, 33, 42]
    assert c.routed_width == 128 and c.first_expert == 0
    # 31.58 B: the row's ``described_as`` says 31.6 B
    assert count_parameters(PUBLISHED) == 31_577_940_288


def test_the_cut_configuration_counts_3_926_018_560_parameters():
    cut = dict(PUBLISHED, num_hidden_layers=13,
               hybrid_override_pattern="MEMEM*EMEMEM*", n_routed_experts=64,
               router_width=128, first_expert=0, vocab_size=65536)
    assert count_parameters(cut) == 3_926_018_560
    module = lm.from_config(cut)
    assert module.cfg.n_routed_experts == 64
    assert module.cfg.routed_width == 128


@pytest.mark.parametrize("over,match", [
    (dict(hybrid_override_pattern="ME-*E"), "dense MLP"),
    (dict(hybrid_override_pattern="MEM*"), "4 characters"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(mlp_hidden_act="silu"), "mlp_hidden_act"),
    (dict(n_group=2), "n_group"),
    (dict(n_groups=3), "groups"),
    (dict(n_shared_experts=2), "shared expert")])
def test_what_is_not_built_is_refused(over, match):
    with pytest.raises(ValueError, match=match):
        lm.from_config(tiny(**over))


def test_the_reference_makes_every_leaf_of_the_programs_tree(seeded):
    cfg, _, tree = seeded
    module = lm.from_config(cfg)
    want = flatten_dict(jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8))))
        ["params"], sep="/")
    got = flatten_dict(tree, sep="/")
    assert sorted(got) == sorted(want)
    for path, leaf in want.items():
        assert got[path].shape == leaf.shape, path
        made = ref.outer_leaf(cfg, jax.random.PRNGKey(7), path)
        assert made.shape == leaf.shape, path
    # by kind: two Mamba-2 layers, two expert layers, one of attention
    assert want["mamba2/in_proj"].shape == (2, 64, 64 + 128 + 4)
    assert want["routed/up"].shape == (2, 8, 64, 48)
    assert "routed/gate" not in want and want["attn/q"].shape == (1, 64, 64)
    for kind, count in (("mamba2", 2), ("moe", 2), ("attention", 1)):
        assert registry().value("lm.layers", kind=kind) == count


def test_the_stack_is_one_scan_with_one_switch(seeded):
    cfg, _, tree = seeded
    module = lm.from_config(cfg)
    text = jax.jit(lambda p, t: module.apply({"params": p}, t, "logits")
                   ).lower(tree, jnp.zeros((1, 32))).as_text()
    # the scan over layers, and in its Mamba-2 branch the array form's scan
    # over chunks (on the chip that branch holds the kernel instead)
    assert text.count("stablehlo.while") == 2
    # the three mixers are the branches of ONE conditional in its body
    assert text.count("stablehlo.case") == 1


# ---- the mixers against the reference ----

def test_the_mamba2_mixer_matches_the_reference_and_a_lost_state_fails(
        seeded, request):
    cfg, params, tree = seeded
    c = lm.from_config(cfg).cfg
    p = params["layers"][0]
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 300, 64)),
                    jnp.float32)
    own = {k.split("/")[1]: v for k, v in p.items()
           if k.startswith("mamba2/")}
    with jax.default_matmul_precision("highest"):
        got = np.asarray(lm_mamba2.mamba2_mixer(own, x, c))
        want = np.stack([np.asarray(ref.mamba2_mixer(p, row, cfg))
                         for row in x])
        dropped = np.stack([np.asarray(ref.mamba2_mixer(
            p, row, cfg, fault="state_dropped")) for row in x])
    # float32 on both sides; the chunked form sums in another order
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)
    assert np.abs(got - dropped)[:, ref.DROP_EVERY:].max() > 1e-2
    # the kernel itself inside the mixer, interpreted
    request.getfixturevalue("pallas_interpret")
    zxd = jnp.dot(x, own["in_proj"], precision="highest")
    xbc = causal_conv(zxd, own["conv_taps"], channels=128, at=64, silu=True,
                      bias=own["conv_bias"], dtype=jnp.float32, impl="xla")
    dt = jax.nn.softplus(zxd[..., 192:] + own["dt_bias"])
    kw = dict(heads=4, head_dim=16, groups=2, state=16)
    args = (xbc, dt, -jnp.exp(own["A_log"]), own["D"])
    np.testing.assert_allclose(
        np.asarray(ss.ssd_scan(*args, impl="pallas", **kw)),
        np.asarray(ss.ssd_scan(*args, impl="xla", **kw)), atol=2e-5,
        rtol=1e-5)


def test_the_gate_comes_before_the_grouped_norm():
    rng = np.random.default_rng(4)
    y, z = (jnp.asarray(rng.normal(size=(3, 64)), jnp.float32)
            for _ in range(2))
    scale = jnp.asarray(1 + 0.1 * rng.normal(size=(64,)), jnp.float32)
    got = np.asarray(lm_mamba2.gated_group_norm(y, z, scale, 2, 1e-5))
    u = np.asarray(y) * np.asarray(jax.nn.silu(z))
    groups = u.reshape(3, 2, 32)
    want = (groups / np.sqrt((groups ** 2).mean(-1, keepdims=True) + 1e-5)
            ).reshape(3, 64) * np.asarray(scale)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # norm-then-gate, or one group of 64, is another function
    whole = u / np.sqrt((u ** 2).mean(-1, keepdims=True) + 1e-5)
    assert np.abs(got - whole * np.asarray(scale)).max() > 0.05


def test_attention_with_a_head_dim_of_its_own_matches_the_reference(seeded):
    cfg, params, _ = seeded
    wide = tiny(head_dim=32)            # 4 heads of 32 on a hidden 64
    with jax.default_matmul_precision("highest"):
        p = ref.make_layer_params(wide, jax.random.PRNGKey(5), 3,
                                  "attention")
        c = lm.from_config(wide).cfg
        x = jnp.asarray(np.random.default_rng(6).normal(size=(2, 40, 64)),
                        jnp.float32)
        own = {k.split("/")[1]: v for k, v in p.items()
               if k.startswith("attn/")}
        assert own["q"].shape == (64, 128) and own["k"].shape == (64, 64)
        got = np.asarray(grouped_attention(own, x, None, c))
        want = np.stack([np.asarray(ref.attention(p, row, wide))
                         for row in x])
    np.testing.assert_allclose(got, want, atol=2e-5)


# ---- the ungated experts ----

def dense_ungated(x, router, bias, experts, first, top_k=2,
                  act=lambda v: jnp.square(jax.nn.relu(v))):
    """The routed part in its dense form: every held expert on every
    token, each token's held picks selected and weighted."""
    picks, weights = moe.route_topk(x, router, top_k, True, 2.5, "sigmoid",
                                    bias, 1e-20)
    out = jnp.einsum("enf,efd->end",
                     act(jnp.einsum("nd,edf->enf", x, experts["up"])),
                     experts["down"])
    held = out.shape[0]
    y = jnp.zeros(x.shape, jnp.float32)
    for j in range(top_k):
        local = picks[:, j] - first
        ok = (local >= 0) & (local < held)
        row = out[jnp.clip(local, 0, held - 1), jnp.arange(x.shape[0])]
        y = y + jnp.where(ok[:, None], row * weights[:, j, None], 0.0)
    return y


def ungated_case(tokens: int, seed: int = 21):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(tokens, 64)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(64, 8)) / 8, jnp.float32)
    bias = jnp.asarray(0.05 * rng.normal(size=(8,)), jnp.float32)
    experts = {"up": jnp.asarray(rng.normal(size=(4, 64, 48)) / 8,
                                 jnp.float32),
               "down": jnp.asarray(rng.normal(size=(4, 48, 64)) / 7,
                                   jnp.float32)}
    return x, router, bias, experts


@pytest.mark.parametrize("stacked", [False, True])
@pytest.mark.parametrize("layout", ["packed", "aligned"])
@pytest.mark.parametrize("impl", ["ragged", "gmm"])
def test_ungated_experts_match_a_dense_loop(impl, layout, stacked, request,
                                            monkeypatch):
    """``moe_dropless`` handed stacks without a ``gate``: two grouped
    products with ``relu(up)^2`` between them, the share holding experts
    2..5 of 8, packed (40 tokens: all pairs within a tile) and aligned
    (every expert's pairs in whole tiles of 8 rows, over two rungs)."""
    if impl == "gmm":
        request.getfixturevalue("pallas_interpret")
    tokens = 40
    if layout == "aligned":
        monkeypatch.setattr(moe, "ALIGNED_ROWS", 8)
        monkeypatch.setattr(moe, "GMM_ROWS", 8)
    x, router, bias, experts = ungated_case(tokens)
    assert moe.row_tile(tokens * 2, 8) == (8 if layout == "aligned" else 1)
    seen = []
    grouped_dot = moe._grouped_dot

    def recorded(lhs, rhs, *args, **kw):
        seen.append(rhs.shape)
        return grouped_dot(lhs, rhs, *args, **kw)
    monkeypatch.setattr(moe, "_grouped_dot", recorded)
    kw, handed = {}, experts
    if stacked:     # layer 1 of three, read in place
        handed = {k: jnp.stack([v * 0.5, v, v * 2.0])
                  for k, v in experts.items()}
        kw = {"layer": jnp.int32(1)}
    with jax.default_matmul_precision("highest"):
        got, picks, _ = moe.moe_dropless(
            x, router, handed, top_k=2, first_expert=2, scaling=2.5,
            score="sigmoid", bias=bias, norm_eps=1e-20, impl=impl, **kw)
        want = dense_ungated(x, router, bias, experts, 2)
        gated = dense_ungated(x, router, bias, experts, 2,
                              act=lambda v: jax.nn.silu(v) * v)
    # float32 sums of 64 and 48 terms in another order, at values of a few
    # units (the squared activation and the scaling of 2.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5,
                               rtol=1e-5)
    # the experts computed gated (the other families' form) are far outside
    assert np.abs(np.asarray(got) - np.asarray(gated)).max() > 0.05
    # two products a rung worked, never a third
    assert len(seen) % 2 == 0 and {s[-2:] for s in seen} == {(64, 48),
                                                            (48, 64)}
    assert picks.shape == (tokens, 2)


def test_the_experts_form_follows_the_stacks_it_is_handed():
    x, router, bias, experts = ungated_case(24)
    kw = dict(top_k=2, score="sigmoid", bias=bias)
    with jax.default_matmul_precision("highest"):
        ungated, _, _ = moe.moe_dropless(x, router, experts, **kw)
        # a gate stack makes the expert gated: act(gate) * up, SiLU unless
        # told otherwise
        both = dict(experts, gate=experts["up"])
        gated, _, _ = moe.moe_dropless(x, router, both, **kw)
        named, _, _ = moe.moe_dropless(x, router, both, activation="silu",
                                       **kw)
        silu, _, _ = moe.moe_dropless(x, router, experts, activation="silu",
                                      **kw)
    np.testing.assert_array_equal(np.asarray(gated), np.asarray(named))
    assert np.abs(np.asarray(gated) - np.asarray(ungated)).max() > 0.01
    assert np.abs(np.asarray(silu) - np.asarray(ungated)).max() > 0.01
    with pytest.raises(ValueError, match="unknown expert activation"):
        moe.moe_dropless(x, router, experts, activation="gelu", **kw)


def test_the_two_shares_add_up_to_the_uncut_layer(seeded):
    """The routed parts that the shares holding experts 0..3 and 4..7 give,
    with the shared expert counted once, equal the uncut reference layer; a
    share's program and its reference agree part by part."""
    cfg, params, _ = seeded
    p = params["layers"][1]
    x = jnp.asarray(np.random.default_rng(5).normal(size=(48, 64)),
                    jnp.float32)
    eps = cfg["layer_norm_epsilon"]
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.layer(p, x, cfg, "moe")
        normed = ref.rms_norm(x, p["norms/norm"], eps)
        total = jnp.zeros_like(x)
        for first in (0, 4):
            share = {k: (v[first:first + 4] if k.startswith("routed/")
                         else v) for k, v in p.items()}
            share_cfg = tiny(n_routed_experts=4, router_width=8,
                             first_expert=first)
            c = lm.from_config(share_cfg).cfg
            assert (c.n_routed_experts, c.routed_width) == (4, 8)
            routed = {k.split("/")[1]: v[None] for k, v in share.items()
                      if k.startswith("routed/")}
            got, load, _ = lm_mamba2.expert_layer(
                {"kernel": p["router/kernel"], "bias": p["router/bias"]},
                routed, {}, 0, normed[None], c)
            want, shared, _ = ref.moe(share, normed, share_cfg, parts=True)
            np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                                       atol=1e-5, rtol=1e-5)
            assert load.shape == (1, 4)
            total = total + got[0]
    np.testing.assert_allclose(np.asarray(x + total + shared),
                               np.asarray(whole), atol=2e-5, rtol=1e-5)


# ---- the whole model ----

def test_float32_matches_the_reference_tightly(seeded):
    cfg, params, tree = seeded
    tokens = tokens_of(11, (3, 300))
    with jax.default_matmul_precision("highest"):
        want = reference_rows(params, tokens, cfg)
        logits = apply(cfg, tree, tokens, "logits")
        logprob = apply(cfg, tree, tokens, "token_logprob")
        feats = apply(cfg, tree, tokens, "features")
    # float32 on both sides: what is left is the order of the sums (the
    # chunked scan, the sorted grouped products, the tiled softmax)
    np.testing.assert_allclose(logits, want["logits"], atol=2e-4)
    np.testing.assert_allclose(logprob, want["token_logprob"], atol=2e-4)
    np.testing.assert_allclose(feats, want["features"], atol=2e-5)
    assert (logprob[:, 0] == 0).all()


def test_a_lost_state_or_gated_experts_fail_the_comparison(seeded):
    cfg, params, tree = seeded
    tokens = tokens_of(12, (2, 300))
    with jax.default_matmul_precision("highest"):
        logprob = apply(cfg, tree, tokens, "token_logprob")
        dropped = reference_rows(params, tokens, cfg, fault="state_dropped")
        swapped = reference_rows(params, tokens, cfg, fault="expert_swapped")
    assert np.abs(logprob - dropped["token_logprob"]).max() > 0.01
    assert np.abs(logprob - swapped["token_logprob"]).max() > 0.01


def test_bfloat16_stays_near_the_reference_and_under_the_control(seeded):
    cfg, params, tree = seeded
    tokens = tokens_of(13, (3, 300))
    with jax.default_matmul_precision("highest"):
        want = reference_rows(params, tokens, cfg)
        control = reference_rows(params, tokens, cfg, quant="float8_e4m3fn")
    got = apply(dict(cfg, compute_dtype="bfloat16"), tree, tokens,
                "token_logprob")
    # top-2 is discrete: compare where no layer's 2nd-3rd gap is within
    # bfloat16's reach (most tokens at this size)
    clean = np.zeros(got.shape, bool)
    clean[:, 1:] = want["margin"][:, :-1] >= 1e-2
    assert clean.mean() > 0.5
    gap = np.abs(got - want["token_logprob"])[clean]
    low = np.abs(control["token_logprob"] - want["token_logprob"])[clean]
    # bfloat16 operands (8 bits) through five layers at a hidden size of
    # 64; the e4m3 control (4 bits) reads over ten times that
    # (read here: 0.18 at most and 0.014 rms against 2.9 and 0.28; the
    # limits lie between the two readings)
    assert gap.max() < 0.5 and np.sqrt(np.mean(gap ** 2)) < 0.04
    assert low.max() > 0.5 and np.sqrt(np.mean(low ** 2)) > 0.04


def test_the_model_is_causal_to_the_bit(seeded):
    cfg, _, tree = seeded
    tokens = tokens_of(14, (1, 200))
    later = tokens.copy()
    later[:, 150:] = (later[:, 150:] + 1) % 256
    one = apply(cfg, tree, tokens, "logits")
    two = apply(cfg, tree, later, "logits")
    np.testing.assert_array_equal(one[:, :150], two[:, :150])
    assert np.abs(one[:, 150:] - two[:, 150:]).max() > 0.1


def test_expert_load_and_bucket_nodes_count_the_held_picks(seeded):
    cfg, params, tree = seeded
    tokens = tokens_of(15, (2, 64))
    share = tiny(n_routed_experts=4, router_width=8, first_expert=4)
    cut = flatten_dict(tree, sep="/")
    cut = unflatten_dict({k: (v[:, 4:8] if k.startswith("routed/") else v)
                          for k, v in cut.items()}, sep="/")
    load = apply(share, cut, tokens, "expert_load")
    assert load.shape == (2, 2 * 4)             # two expert layers x held
    with jax.default_matmul_precision("highest"):
        whole = apply(cfg, tree, tokens, "expert_load")
    # every token picks two of eight in each of the two expert layers
    np.testing.assert_array_equal(whole.sum(axis=1), [64 * 2 * 2] * 2)
    assert 0 < load.sum() < whole.sum()
    bucket = apply(share, cut, tokens, "moe_bucket")
    assert bucket.shape == (2, 2) and bucket.dtype == np.int32
    out = lm.publish_expert_load(load.sum(axis=0).reshape(2, 4), 2 * 64 * 2)
    assert out["moe.held_pairs"] == int(load.sum())


def test_a_token_table_through_transform_equals_the_module(seeded):
    from mmlspark_tpu.data.table import DataTable
    from mmlspark_tpu.models.bundle import ModelBundle
    from mmlspark_tpu.models.jax_model import JaxModel

    cfg, _, tree = seeded
    module = lm.from_config(cfg)
    tokens = tokens_of(16, (5, 48)).astype(np.int32)
    bundle = ModelBundle(module=module, params=tree, input_spec=(48,),
                         output_names=type(module).OUTPUT_NAMES,
                         name="tiny_nemotron_h")
    model = JaxModel(model=bundle, input_col="tokens",
                     output_col="token_logprob", minibatch_size=2,
                     output_node="token_logprob")
    out = model.transform(DataTable({"tokens": tokens}))["token_logprob"]
    want = apply(cfg, tree, tokens, "token_logprob")
    assert len(out) == 5
    # a step of two rows and the module on all five: the same program on
    # other batch shapes (the expert layer's buffer follows the token count)
    np.testing.assert_allclose(np.stack(list(out)), want, atol=1e-5)
