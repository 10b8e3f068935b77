"""The Gated DeltaNet / gated-attention / expert LM family
(``models/lm_gdn.py``, ``model_type: qwen3_next``) against the plain
reference ``benchmark/reference/qwen3_next.py`` on seeded weights, at tiny
widths on the CPU (hidden 64; 4 key heads on 8 value heads of 16; 4 query
heads on 2 key/value heads of 32 with rotary on 8; 16 experts top-4; 8
layers = two periods); with it the split of the fused projection into the
key-head-grouped ``[q | k | v]`` and the gates, and the share test
(the routed parts of the shares holding experts 0-3, 4-7, 8-11 and 12-15,
with the gated shared expert counted once, add up to the uncut reference
layer). Each planted departure from the equations (a lost state, no delta
correction, the gate before the norm, no output gate, no shared gate) moves
the answers far past the comparison's tolerance."""

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

from benchmark.reference import qwen3_next as ref  # noqa: E402
from mmlspark_tpu.models import lm, lm_gdn  # noqa: E402
from mmlspark_tpu.obs.metrics import registry  # noqa: E402
from mmlspark_tpu.ops.pallas.causal_conv import causal_conv  # noqa: E402

# the row of the model-configs catalog (Qwen3-Next-80B-A3B-Instruct)
PUBLISHED = dict(
    decoder_sparse_step=1, full_attention_interval=4, head_dim=256,
    hidden_act="silu", hidden_size=2048, intermediate_size=5120,
    linear_conv_kernel_dim=4, linear_key_head_dim=128,
    linear_num_key_heads=16, linear_num_value_heads=32,
    linear_value_head_dim=128, max_position_embeddings=262144,
    mlp_only_layers=[], model_type="qwen3_next", moe_intermediate_size=512,
    norm_topk_prob=True, num_attention_heads=16, num_experts=512,
    num_experts_per_tok=10, num_hidden_layers=48, num_key_value_heads=2,
    partial_rotary_factor=0.25, rms_norm_eps=1e-06, rope_scaling=None,
    rope_theta=10000000, shared_expert_intermediate_size=512,
    tie_word_embeddings=False, use_sliding_window=False, vocab_size=151936)

# the float32 tolerance of the whole model against the reference: the
# chunked recurrence, the sorted grouped products and the tiled softmax sum
# in another order (read: 2.6e-5 on logits of ~5)
ATOL = 2e-4


def tiny(**over) -> dict:
    cfg = dict(
        family="qwen3_next", model_type="qwen3_next", vocab_size=256,
        hidden_size=64, num_hidden_layers=8, num_attention_heads=4,
        num_key_value_heads=2, head_dim=32, partial_rotary_factor=0.25,
        linear_num_key_heads=4, linear_num_value_heads=8,
        linear_key_head_dim=16, linear_value_head_dim=16,
        linear_conv_kernel_dim=4, full_attention_interval=4, num_experts=16,
        router_width=16, num_experts_per_tok=4, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, rms_norm_eps=1e-6,
        rope_theta=1e7, mlp_only_layers=[], decoder_sparse_step=1,
        norm_topk_prob=True, tie_word_embeddings=False,
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


def own(p: dict, kind: str) -> dict:
    """A reference layer's leaves of one kind, under the program's names."""
    return {k.split("/")[1]: v for k, v in p.items()
            if k.startswith(kind + "/")}


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
    assert isinstance(module, lm_gdn.Qwen3NextLM)
    c = module.cfg
    # layer i is full attention where (i + 1) % 4 == 0
    assert [i for i, k in enumerate(c.kinds) if k == "attention"] == list(
        range(3, 48, 4))
    assert c.kinds.count("gdn") == 36 and c.rotary_dim == 64
    assert (c.key_group, c.conv_group, c.per) == (768, 512, 2)
    assert c.routed_width == 512 and c.n_routed_experts == 512
    # 79.67 B: the row says 80B
    assert count_parameters(PUBLISHED) == 79_674_391_296


def test_the_cut_configuration_counts_3_879_901_440_parameters():
    cut = dict(PUBLISHED, num_hidden_layers=16, num_experts=64,
               router_width=512, first_expert=0, vocab_size=18992)
    assert count_parameters(cut) == 3_879_901_440
    c = lm.from_config(cut).cfg
    assert (c.n_routed_experts, c.routed_width) == (64, 512)
    assert [i for i, k in enumerate(c.kinds) if k == "attention"] == [
        3, 7, 11, 15]


@pytest.mark.parametrize("over,match", [
    (dict(decoder_sparse_step=2), "decoder_sparse_step"),
    (dict(mlp_only_layers=[0]), "mlp_only_layers"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(linear_num_value_heads=6), "value heads"),
    (dict(partial_rotary_factor=0.1), "rotary")])
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
    # by kind: six Gated DeltaNet layers, two of attention, eight expert
    # layers with a gated shared expert each
    assert want["gdn/in_proj_qkvz"].shape == (6, 64, 4 * (32 + 64))
    assert want["attn/q"].shape == (2, 64, 2 * 4 * 32)
    assert want["routed/gate"].shape == (8, 16, 64, 32)
    assert want["shared/expert_gate"].shape == (8, 64, 1)
    for kind, count in (("gdn", 6), ("attention", 2), ("moe", 8)):
        assert registry().value("lm.layers", kind=kind) == count


def test_the_stack_is_one_scan_with_one_switch(seeded):
    cfg, _, tree = seeded
    module = lm.from_config(cfg)
    text = jax.jit(lambda p, t: module.apply({"params": p}, t, "logits")
                   ).lower(tree, jnp.zeros((1, 64))).as_text()
    # the scan over layers, in its Gated DeltaNet branch the array form's
    # scan over chunks (on the chip that branch holds the kernel), and in
    # its expert layer the array combine's loop over the picks (on the chip
    # the combine kernel)
    assert text.count("stablehlo.while") == 3
    # the two mixers are the branches of ONE conditional in its body (16
    # experts held of 16, 64 tokens: the expert layer has one rung)
    assert text.count("stablehlo.case") == 1


# ---- the mixers against the reference ----

def mixer_case(seeded, seed: int = 3):
    cfg, params, _ = seeded
    c = lm.from_config(cfg).cfg
    x = jnp.asarray(np.random.default_rng(seed).normal(size=(2, 200, 64)),
                    jnp.float32)
    return cfg, params, c, x


def test_the_gdn_mixer_matches_the_reference(seeded, monkeypatch):
    cfg, params, c, x = mixer_case(seeded)
    p = params["layers"][0]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(lm_gdn.gdn_mixer(own(p, "gdn"), x, c))
        want = np.stack([np.asarray(ref.gdn_mixer(p, row, cfg))
                         for row in x])

        def planted(**kw):
            return np.stack([np.asarray(ref.gdn_mixer(p, row, cfg, **kw))
                             for row in x])
        dropped = planted(fault="state_dropped")

        # S <- a S + beta k v^T: the state never read along the key
        monkeypatch.setattr(ref, "recurrence", lambda *a, **kw: _literal(
            *a, correct=False))
        uncorrected = planted()
        monkeypatch.setattr(ref, "recurrence", _literal)
        np.testing.assert_allclose(planted(), want, atol=1e-5)
        monkeypatch.setattr(ref, "gated_norm", lambda o, z, w, eps:
                            ref.rms_norm(o * jax.nn.silu(z), w, eps))
        gate_first = planted()
    # float32 on both sides; the chunked form sums in another order
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)
    assert np.abs(got - dropped)[:, ref.DROP_EVERY:].max() > 1e-2
    assert np.abs(got - uncorrected).max() > 1e-2
    assert np.abs(got - gate_first).max() > 1e-2


def _literal(q, k, v, g, beta, fault=None, correct=True):
    """The reference's recurrence, with the delta correction optional."""
    def step(s, at):
        t, qq, kk, vv, gg, bb = at
        s = jnp.exp(gg)[:, None, None] * s
        read = jnp.sum(s * kk[:, :, None], axis=1) if correct else 0.0
        s = s + kk[:, :, None] * (bb[:, None] * (vv - read))[:, None, :]
        return s, jnp.sum(s * qq[:, :, None], axis=1)

    _, o = jax.lax.scan(step, jnp.zeros(k.shape[1:] + v.shape[2:]),
                        (jnp.arange(q.shape[0]), q, k, v, g, beta))
    return o


def test_the_projection_splits_into_grouped_qkv_and_the_gates(request):
    request.getfixturevalue("pallas_interpret")
    c = lm.from_config(tiny()).cfg
    # four key heads of [q 16 | k 16 | v 32 | z 32]: column names
    w = jnp.arange(4 * 96, dtype=jnp.float32)[None, :]
    qkv, z = (np.asarray(a)[0] for a in lm_gdn.split_qkvz(w, c))
    j = 2
    np.testing.assert_array_equal(qkv[64 * j:64 * j + 64],
                                  96 * j + np.arange(64))
    np.testing.assert_array_equal(z[32 * j:32 * j + 32],
                                  96 * j + 64 + np.arange(32))
    # the kernel convolves the grouped product as the array form does
    rng = np.random.default_rng(8)
    wide = jnp.asarray(rng.normal(size=(2, 100, 1024)), jnp.float32)
    taps = jnp.asarray(rng.normal(size=(4, 1024)) / 2, jnp.float32)
    kw = dict(channels=1024, silu=True, dtype=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(causal_conv(wide, taps, impl="pallas", **kw)),
        np.asarray(causal_conv(wide, taps, impl="xla", **kw)),
        atol=1e-6, rtol=1e-6)


def test_the_taps_follow_the_channels_into_key_head_order():
    c = lm.from_config(tiny()).cfg
    # every head's q (4 x 16), k (4 x 16), v (8 x 16): channel names
    taps = jnp.arange(256, dtype=jnp.float32)[None, :]
    got = np.asarray(lm_gdn.grouped_taps(taps, c))[0]
    j = 1
    np.testing.assert_array_equal(got[64 * j:64 * j + 16],
                                  np.arange(16 * j, 16 * j + 16))
    np.testing.assert_array_equal(got[64 * j + 16:64 * j + 32],
                                  64 + np.arange(16 * j, 16 * j + 16))
    np.testing.assert_array_equal(got[64 * j + 32:64 * j + 64],
                                  128 + np.arange(32 * j, 32 * j + 32))


def test_gated_attention_matches_the_reference(seeded, monkeypatch):
    cfg, params, c, x = mixer_case(seeded, seed=4)
    p = params["layers"][3]
    assert ref.layer_kind(cfg, 3) == "attention"
    with jax.default_matmul_precision("highest"):
        got = np.asarray(lm_gdn.gated_attention(own(p, "attn"), x, c))
        want = np.stack([np.asarray(ref.attention(p, row, cfg))
                         for row in x])
        monkeypatch.setattr(ref, "output_gate", lambda out, gate: out)
        ungated = np.stack([np.asarray(ref.attention(p, row, cfg))
                            for row in x])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)
    assert np.abs(got - ungated).max() > 1e-2


# ---- the experts ----

def test_the_four_shares_add_up_to_the_uncut_layer(seeded):
    """The routed parts that the shares holding experts 0-3, 4-7, 8-11 and
    12-15 give, with the gated shared expert counted once, equal the uncut
    reference layer; a share's program and its reference agree part by
    part."""
    cfg, params, _ = seeded
    p = params["layers"][1]
    x = jnp.asarray(np.random.default_rng(5).normal(size=(48, 64)),
                    jnp.float32)
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        h = x + ref.gdn_mixer(p, ref.rms0(x, p["norms/input_norm"], eps), cfg)
        whole, _ = ref.layer(p, x, cfg, "gdn")
        normed = ref.rms0(h, p["norms/post_norm"], eps)
        total = jnp.zeros_like(x)
        for first in (0, 4, 8, 12):
            share = {k: (v[first:first + 4] if k.startswith("routed/")
                         else v) for k, v in p.items()}
            share_cfg = tiny(num_experts=4, router_width=16,
                             first_expert=first)
            c = lm.from_config(share_cfg).cfg
            assert (c.n_routed_experts, c.routed_width) == (4, 16)
            routed = {k: v[None] for k, v in own(share, "routed").items()}
            # what every chip computes alike, the gated shared expert,
            # counted once: on the first share
            shared = own(p, "shared") if first == 0 else {}
            got, load, _ = lm_gdn.expert_layer(
                {"kernel": p["router/kernel"]}, routed, shared, 0,
                normed[None], c)
            want, gated_shared, _ = ref.moe(share, normed, share_cfg,
                                            parts=True)
            np.testing.assert_allclose(
                np.asarray(got[0]),
                np.asarray(want + (gated_shared if shared else 0.0)),
                atol=1e-5, rtol=1e-5)
            assert load.shape == (1, 4)
            total = total + got[0]
        ungated = ref.gated(normed, p["shared/gate"], p["shared/up"],
                            p["shared/down"], lambda a: a)
    np.testing.assert_allclose(np.asarray(h + total), np.asarray(whole),
                               atol=2e-5, rtol=1e-5)
    # the sigmoid gate scales the shared expert's part: leaving it out fails
    assert np.abs(np.asarray(ungated - gated_shared)).max() > 1e-2


# ---- the whole model ----

def test_float32_matches_the_reference_tightly(seeded):
    cfg, params, tree = seeded
    tokens = tokens_of(11, (3, 300))
    with jax.default_matmul_precision("highest"):
        want = reference_rows(params, tokens, cfg)
        logits = apply(cfg, tree, tokens, "logits")
        logprob = apply(cfg, tree, tokens, "token_logprob")
        feats = apply(cfg, tree, tokens, "features")
    np.testing.assert_allclose(logits, want["logits"], atol=ATOL)
    np.testing.assert_allclose(logprob, want["token_logprob"], atol=ATOL)
    np.testing.assert_allclose(feats, want["features"], atol=2e-5)
    assert (logprob[:, 0] == 0).all()


def test_each_planted_departure_fails_the_comparison(seeded, monkeypatch):
    cfg, params, tree = seeded
    tokens = tokens_of(12, (2, 300))
    with jax.default_matmul_precision("highest"):
        logprob = apply(cfg, tree, tokens, "token_logprob")
        planted = {f: reference_rows(params, tokens, cfg, fault=f)
                   for f in ref.FAULTS}
        monkeypatch.setattr(ref, "shared_expert", lambda p, x, q: ref.gated(
            x, p["shared/gate"], p["shared/up"], p["shared/down"], q))
        planted["shared_ungated"] = reference_rows(params, tokens, cfg)
    for name, rows in planted.items():
        assert np.abs(logprob - rows["token_logprob"]).max() > 50 * ATOL, \
            name


def test_bfloat16_stays_near_the_reference_and_under_the_control(seeded):
    cfg, params, tree = seeded
    tokens = tokens_of(13, (3, 300))
    with jax.default_matmul_precision("highest"):
        want = reference_rows(params, tokens, cfg)
        control = reference_rows(params, tokens, cfg, quant="float8_e4m3fn")
    got = apply(dict(cfg, compute_dtype="bfloat16"), tree, tokens,
                "token_logprob")
    # top-4 of 16 is discrete: compare where no layer's 4th-5th gap is
    # within bfloat16's reach
    clean = np.zeros(got.shape, bool)
    clean[:, 1:] = want["margin"][:, :-1] >= 1e-3
    assert clean.mean() > 0.5
    gap = np.abs(got - want["token_logprob"])[clean]
    low = np.abs(control["token_logprob"] - want["token_logprob"])[clean]
    # bfloat16 operands (8 bits) and stream through eight layers that hold
    # every expert at a hidden size of 64: the reference so rounded reads
    # 0.60 at most and 0.076 rms, the program 0.65 and 0.10; the e4m3
    # control (4 bits) 2.3 and 0.45. The limits lie between the readings
    assert gap.max() < 1.0 and np.sqrt(np.mean(gap ** 2)) < 0.2
    assert low.max() > 1.0 and np.sqrt(np.mean(low ** 2)) > 0.2


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
    cfg, _, tree = seeded
    tokens = tokens_of(15, (2, 64))
    share = tiny(num_experts=4, router_width=16, first_expert=4)
    cut = flatten_dict(tree, sep="/")
    cut = unflatten_dict({k: (v[:, 4:8] if k.startswith("routed/") else v)
                          for k, v in cut.items()}, sep="/")
    load = apply(share, cut, tokens, "expert_load")
    assert load.shape == (2, 8 * 4)             # eight expert layers x held
    with jax.default_matmul_precision("highest"):
        whole = apply(cfg, tree, tokens, "expert_load")
    # every token picks four of sixteen in each of the eight layers
    np.testing.assert_array_equal(whole.sum(axis=1), [64 * 4 * 8] * 2)
    assert 0 < load.sum() < whole.sum()
    bucket = apply(share, cut, tokens, "moe_bucket")
    assert bucket.shape == (2, 8) and bucket.dtype == np.int32
    out = lm.publish_expert_load(load.sum(axis=0).reshape(8, 4), 2 * 64 * 8)
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
                         name="tiny_qwen3_next")
    model = JaxModel(model=bundle, input_col="tokens",
                     output_col="token_logprob", minibatch_size=2,
                     output_node="token_logprob")
    out = model.transform(DataTable({"tokens": tokens}))["token_logprob"]
    want = apply(cfg, tree, tokens, "token_logprob")
    assert len(out) == 5
    np.testing.assert_allclose(np.stack(list(out)), want, atol=1e-5)
