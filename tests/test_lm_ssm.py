"""The state-space / multi-query hybrid LM family (``models/lm_ssm.py``,
``model_type: jamba``) against the plain reference
``benchmark/reference/jamba.py`` on seeded weights, at tiny widths on the
CPU; with it what the family forced elsewhere: the chunked selective-scan
kernel (``ops/pallas/selective_scan.py``, run here through the interpreter
against a per-position float64 recurrence), the attention function that
follows what it is handed, the one depthwise tap loop both families call."""

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

from benchmark.reference import jamba as ref  # noqa: E402
from mmlspark_tpu.models import lm, lm_conv, lm_layers, lm_ssm  # noqa: E402
from mmlspark_tpu.obs.metrics import registry  # noqa: E402
from mmlspark_tpu.ops.pallas import budget, selective_scan as ss  # noqa: E402

PUBLISHED = dict(
    attn_layer_offset=7, attn_layer_period=14, expert_layer_offset=1,
    expert_layer_period=2, hidden_act="silu", hidden_size=2560,
    intermediate_size=8192, mamba_conv_bias=True, mamba_d_conv=4,
    mamba_d_state=16, mamba_dt_rank=160, mamba_expand=2,
    mamba_proj_bias=False, max_position_embeddings=262144,
    model_type="jamba", num_attention_heads=20, num_experts=1,
    num_experts_per_tok=1, num_hidden_layers=28, num_key_value_heads=1,
    num_logits_to_keep=1, rms_norm_eps=1e-06, sliding_window=None,
    tie_word_embeddings=True, use_mamba_kernels=True, vocab_size=65536)


def tiny(**over) -> dict:
    """Nine layers, attention at 2 and 7: Mamba runs of 2, 4 and 1."""
    cfg = dict(
        family="jamba", model_type="jamba", vocab_size=256, hidden_size=64,
        intermediate_size=96, num_hidden_layers=9, num_attention_heads=4,
        num_key_value_heads=1, attn_layer_period=5, attn_layer_offset=2,
        mamba_d_state=16, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=8,
        mamba_conv_bias=True, mamba_proj_bias=False, num_experts=1,
        num_experts_per_tok=1, rms_norm_eps=1e-6, tie_word_embeddings=True,
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
                                   jnp.asarray(tokens, jnp.float32),
                                   output=node))


@pytest.fixture(scope="module")
def seeded():
    cfg = tiny()
    params = ref.make_params(cfg, jax.random.PRNGKey(7))
    return cfg, params, program_tree(cfg, params)


def counted(name: str, **labels) -> float:
    """A counter's value, 0 before its first count."""
    return registry().value(name, **labels) or 0


def rel_gaps(got, want) -> tuple:
    """``(max, rms)`` of the gap, each over the same statistic of ``want``
    (the benchmark's ``logit_gap_max`` / ``logit_gap_rms``)."""
    err = np.asarray(got, np.float64) - np.asarray(want, np.float64)
    return (np.abs(err).max() / np.abs(want).max(),
            np.sqrt(np.mean(err ** 2)) / np.sqrt(np.mean(
                np.asarray(want, np.float64) ** 2)))


# ---- the selective-scan kernel ----

def scan_operands(seed: int, rows: int, length: int, channels: int,
                  states: int = 16, dtype=jnp.float32) -> tuple:
    """Operands as the mixer hands them: step sizes log-uniform over
    ``[1e-3, 1e-1]`` and ``A = -(1..N)``, so that the slowest state lives
    1,000 positions and the fastest under one."""
    r = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(r.standard_normal(shape), jnp.float32)

    delta = jnp.asarray(np.exp(r.uniform(np.log(1e-3), np.log(1e-1),
                                         (rows, length, channels))),
                        jnp.float32)
    a = -jnp.asarray(np.tile(np.arange(1, states + 1, dtype=np.float32),
                             (channels, 1)))
    return (normal(rows, length, channels).astype(dtype), delta, a,
            normal(rows, length, states), normal(rows, length, states),
            1.0 + 0.1 * normal(channels),
            normal(rows, length, channels).astype(dtype))


def recurrence64(u, delta, a, b, c, d, z, drop_every: int = 0) -> np.ndarray:
    """The recurrence a position at a time in float64 NumPy; ``drop_every``
    zeroes the state before every such position (a lost carry)."""
    u, delta, a, b, c, d, z = (np.asarray(v, np.float64)
                               for v in (u, delta, a, b, c, d, z))
    out = np.zeros(u.shape)
    for r in range(u.shape[0]):
        s = np.zeros(a.shape)
        for t in range(u.shape[1]):
            if drop_every and t % drop_every == 0:
                s[:] = 0.0
            s = np.exp(delta[r, t][:, None] * a) * s \
                + (delta[r, t] * u[r, t])[:, None] * b[r, t][None, :]
            y = s @ c[r, t] + d * u[r, t]
            out[r, t] = y / (1.0 + np.exp(-z[r, t])) * z[r, t]
    return out


# a length that is no multiple of the chunk (128), three chunks; channels
# that are no whole block (96 of 1,024)
SCAN_LENGTH, SCAN_CHANNELS = 300, 96
SCAN_ATOL = 2e-5


@pytest.fixture(scope="module")
def scanned():
    """One interpreted kernel call and its operands."""
    from jax.experimental.pallas import tpu as pltpu

    args = scan_operands(1, 1, SCAN_LENGTH, SCAN_CHANNELS)
    before = counted(ss.GRID_STEPS_COUNTER)
    with pltpu.force_tpu_interpret_mode():
        got = np.asarray(ss.selective_scan(*args, impl="pallas"))
    steps = counted(ss.GRID_STEPS_COUNTER) - before
    return args, got, steps


def test_the_kernel_matches_a_float64_recurrence_over_three_chunks(scanned):
    args, got, steps = scanned
    chunk = ss.chunk_positions(SCAN_LENGTH, 4)
    assert chunk == 128 and SCAN_LENGTH % chunk and -(-SCAN_LENGTH // chunk) == 3
    # one row, one channel block, three chunks; the gauge holds the chunk
    assert steps == 3 and registry().value(ss.CHUNK_GAUGE) == 128
    want = recurrence64(*args)
    assert got.shape == want.shape and got.dtype == np.float32
    # float32 state against float64 over 300 positions of outputs O(1-10)
    np.testing.assert_allclose(got, want, atol=SCAN_ATOL, rtol=1e-5)


def test_the_state_carried_across_a_chunk_boundary_matters(scanned):
    args, got, _ = scanned
    dropped = recurrence64(*args, drop_every=128)
    # the first chunk knows no difference; from the second on a kernel that
    # zeroed its state at a boundary would be off by a thousand tolerances
    np.testing.assert_allclose(got[:, :128], dropped[:, :128],
                               atol=SCAN_ATOL, rtol=1e-5)
    assert np.abs(got[:, 128:] - dropped[:, 128:]).max() > 1000 * SCAN_ATOL


def test_the_xla_reference_is_the_same_recurrence(scanned):
    args, got, _ = scanned
    want = np.asarray(ss.selective_scan(*args, impl="xla"))
    np.testing.assert_allclose(got, want, atol=SCAN_ATOL, rtol=1e-5)


def test_the_kernel_is_causal_to_the_bit():
    from jax.experimental.pallas import tpu as pltpu

    args = scan_operands(2, 1, 200, 64)
    moved = list(args)
    t = 150                                   # in the second chunk
    for i in (0, 1, 3, 4, 6):                 # u, delta, B, C, z
        moved[i] = moved[i].at[:, t:].add(0.5)
    with pltpu.force_tpu_interpret_mode():
        base = np.asarray(ss.selective_scan(*args, impl="pallas"))
        after = np.asarray(ss.selective_scan(*moved, impl="pallas"))
    np.testing.assert_array_equal(after[:, :t], base[:, :t])
    assert (after[:, t:] != base[:, t:]).any(axis=-1).all()


def test_bfloat16_operands_come_back_in_bfloat16():
    from jax.experimental.pallas import tpu as pltpu

    args = scan_operands(3, 2, 128, 64, dtype=jnp.bfloat16)
    with pltpu.force_tpu_interpret_mode():
        got = ss.selective_scan(*args, impl="pallas")
    assert got.dtype == jnp.bfloat16 and got.shape == (2, 128, 64)
    want = recurrence64(*args)
    # the output rounded once to 8 bits of mantissa
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               atol=1e-3, rtol=2 ** -8)
    with pytest.raises(ValueError, match="one type"):
        ss.selective_scan(args[0], *args[1:6], args[6].astype(jnp.float32))


@pytest.mark.parametrize("length,itemsize,want", [
    (16384, 2, 256),      # the cell's window: 64 chunks, none padded
    (8192, 2, 256), (4096, 4, 256),
    (600, 2, 128),        # five lane rows: only one divides
    (384, 2, 384), (100, 4, 128)])
def test_the_chunk_comes_from_the_shapes_and_the_budget(length, itemsize,
                                                        want):
    chunk = ss.chunk_positions(length, itemsize)
    assert chunk == want
    # it tiles the sequence rounded up to whole lane rows, and fits
    assert (-(-length // 128) * 128) % chunk == 0
    assert ss.BLOCK_CHANNELS * chunk * (2 * (3 * itemsize + 4) + 12) \
        <= budget.VMEM_BUDGET


def test_a_chunk_past_the_budget_falls_back_loudly(monkeypatch):
    args = scan_operands(4, 1, 32, 16)
    monkeypatch.setattr(ss, "VMEM_BUDGET", 2 ** 20)
    assert ss.chunk_positions(32, 4) == 0
    with pytest.raises(ValueError, match="VMEM budget"):
        ss.selective_scan(*args, impl="pallas")
    # under ``auto`` on a TPU the reference runs and the miss is counted
    from mmlspark_tpu.ops.pallas import attention as fa
    monkeypatch.setattr(fa.jax, "default_backend", lambda: "tpu")
    before = counted(budget.FALLBACK_COUNTER, kernel="selective_scan")
    got = np.asarray(ss.selective_scan(*args))
    assert counted(budget.FALLBACK_COUNTER,
                   kernel="selective_scan") == before + 1
    np.testing.assert_allclose(got, recurrence64(*args), atol=SCAN_ATOL,
                               rtol=1e-5)


# ---- the configuration and the tree ----

def test_the_published_keys_give_attention_at_layers_7_and_21():
    module = lm.from_config(dict(PUBLISHED, family="jamba"))
    assert isinstance(module, lm_ssm.JambaLM)
    kinds = module.cfg.kinds
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [7, 21]
    assert kinds.count("mamba") == 26 and len(kinds) == 28
    assert module.cfg.head_dim == 128 and module.cfg.d_inner == 5120
    assert type(module).OUTPUT_NAMES == ("features", "token_logprob",
                                         "logits")


def test_the_published_model_counts_3_029_337_472_parameters():
    module = lm.from_config(PUBLISHED)
    tree = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8))))["params"]
    leaves = jax.tree.leaves(tree)
    assert sum(int(np.prod(v.shape)) for v in leaves) == 3_029_337_472
    # by kind: 26 Mamba mixers, 2 attention layers, 28 MLPs
    assert tree["mamba"]["A_log"].shape == (26, 5120, 16)
    assert tree["attn"]["k"].shape == (2, 2560, 128)
    assert tree["dense"]["gate"].shape == (28, 2560, 8192)
    assert "head" not in tree and "q_norm" not in tree["attn"]


def test_routed_experts_and_other_unbuilt_settings_are_refused():
    with pytest.raises(ValueError, match="num_experts"):
        lm.from_config(tiny(num_experts=16, num_experts_per_tok=2))
    with pytest.raises(ValueError, match="mamba_proj_bias"):
        lm.from_config(tiny(mamba_proj_bias=True))
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        lm.from_config(tiny(tie_word_embeddings=False))
    # the table keeps the other families where they were
    import test_lm_conv_moe as conv
    import test_lm_latent_moe as latent
    assert isinstance(lm.from_config(conv.tiny()), lm_conv.ConvMoELM)
    assert isinstance(lm.from_config(latent.tiny()), lm.LatentMoELM)
    assert set(lm.FAMILIES) == {"lfm2_moe", "jamba", "nemotron_h",
                                "qwen3_next"}


def test_the_reference_makes_every_leaf_of_the_programs_tree(seeded):
    cfg, _, tree = seeded
    module = lm.from_config(cfg)
    want = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8))))["params"]
    assert jax.tree.structure(want) == jax.tree.structure(tree)
    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(tree)):
        assert w.shape == g.shape
    # and a leaf at a time, as the token driver's ``make_bundle`` asks
    key = jax.random.PRNGKey(7)
    stack = np.asarray(ref.outer_leaf(cfg, key, "mamba/A_log"), np.float32)
    np.testing.assert_array_equal(stack, np.asarray(tree["mamba"]["A_log"]))
    assert stack.shape == (7, 128, 16)
    # the stand-ins: A_log near log(1..16), step sizes within their range
    assert np.abs(stack - np.log(np.arange(1, 17))).max() < 0.6
    dt0 = np.log1p(np.exp(np.asarray(tree["mamba"]["dt_bias"], np.float64)))
    assert 0.9e-3 < dt0.min() < 2e-3 and 0.05 < dt0.max() < 0.11
    reg = registry()
    assert reg.value("lm.layers", kind="mamba") == 7
    assert reg.value("lm.layers", kind="attention") == 2


def test_the_stack_is_one_scan_with_one_conditional():
    module = lm.from_config(tiny())
    tree = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8))))["params"]
    program = jax.make_jaxpr(lambda p, x: module.apply(
        {"params": p}, x, output="token_logprob"))(
            tree, jnp.zeros((2, 32), jnp.float32))
    # nine layers in one scan (the other is the head's chunk loop), the
    # mixer picked inside it
    assert [e.params["length"] for e in program.jaxpr.eqns
            if e.primitive.name == "scan"] == [9, 1]
    assert str(program).count(" cond[") == 1
    # all layers of one kind: no conditional at all
    only = lm.from_config(tiny(attn_layer_period=99, attn_layer_offset=50))
    tree = jax.eval_shape(lambda: only.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8))))["params"]
    assert "attn" not in tree
    assert " cond[" not in str(jax.make_jaxpr(lambda p, x: only.apply(
        {"params": p}, x, output="features"))(
            tree, jnp.zeros((2, 32), jnp.float32)))


# ---- each part and the whole model against the reference ----

# float32 on both sides; what is left is the order of the sums. The e4m3
# control (operands of every product rounded to 3 bits of mantissa) has to
# fail the same tolerance
PART_TOL = 2e-5


def test_the_mixer_matches_the_reference_and_the_control_fails(seeded):
    cfg, params, tree = seeded
    c = lm.from_config(cfg).cfg
    x = jnp.asarray(np.random.default_rng(5).standard_normal((2, 40, 64)),
                    jnp.float32)
    layer = 3                                  # the fourth layer, a Mamba one
    at = ref.layers_of(cfg, "mamba").index(layer)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(lm_ssm.mamba_mixer(
            lm_layers._at(tree["mamba"], at), x, c))
        want = np.stack([np.asarray(ref.mamba_mixer(
            params["layers"][layer], row, cfg)) for row in x])
        control = np.stack([np.asarray(ref.mamba_mixer(
            params["layers"][layer], row, cfg, quant="float8_e4m3fn"))
            for row in x])
    assert rel_gaps(got, want)[0] < PART_TOL
    assert rel_gaps(control, want)[0] > 100 * PART_TOL


def test_attention_without_positions_matches_the_reference(seeded):
    cfg, params, tree = seeded
    c = lm.from_config(cfg).cfg
    x = jnp.asarray(np.random.default_rng(6).standard_normal((2, 40, 64)),
                    jnp.float32)
    layer = 7
    at = ref.layers_of(cfg, "attention").index(layer)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(lm_layers.grouped_attention(
            lm_layers._at(tree["attn"], at), x, None, c))
        want = np.stack([np.asarray(ref.attention(
            params["layers"][layer], row, cfg)) for row in x])
        control = np.stack([np.asarray(ref.attention(
            params["layers"][layer], row, cfg, quant="float8_e4m3fn"))
            for row in x])
    assert rel_gaps(got, want)[0] < PART_TOL
    assert rel_gaps(control, want)[0] > 100 * PART_TOL


def test_float32_matches_the_reference_tightly(seeded):
    cfg, params, tree = seeded
    tokens = tokens_of(11, (2, 48))
    with jax.default_matmul_precision("highest"):
        logits = apply(cfg, tree, tokens, "logits")
        logprob = apply(cfg, tree, tokens, "token_logprob")
        feats = apply(cfg, tree, tokens, "features")
        want = reference_rows(params, tokens, cfg)
        control = reference_rows(params, tokens, cfg, quant="float8_e4m3fn")
    np.testing.assert_allclose(logits, want["logits"], atol=1e-4)
    np.testing.assert_allclose(logprob, want["token_logprob"], atol=1e-4)
    np.testing.assert_allclose(feats, want["features"], atol=1e-4)
    assert (logprob[:, 0] == 0).all() and (logprob[:, 1:] < 0).all()
    # the control fails that tolerance a hundred times over
    assert np.abs(control["token_logprob"]
                  - want["token_logprob"]).max() > 1e-2


def test_bfloat16_stays_near_the_reference_and_under_the_control(seeded):
    cfg, params, tree = seeded
    tokens = tokens_of(12, (2, 48))
    want = reference_rows(params, tokens, cfg)["token_logprob"][:, 1:]
    control = reference_rows(params, tokens, cfg,
                             quant="float8_e4m3fn")["token_logprob"][:, 1:]
    got = apply(cfg, tree, tokens, "token_logprob",
                dtype=jnp.bfloat16)[:, 1:]
    # bf16 operands and residual stream through nine layers: read 0.3-0.5 %
    # rms; the control 5-8 %
    gap_max, gap_rms = rel_gaps(got, want)
    assert gap_rms < 0.012 and gap_max < 0.05
    assert rel_gaps(control, want)[1] > 0.03


def test_the_model_is_causal_to_the_bit(seeded):
    cfg, _, tree = seeded
    tokens = tokens_of(13, (2, 40))
    base = apply(cfg, tree, tokens, "token_logprob")
    t = 25
    moved = tokens.copy()
    moved[:, t + 1:] = (moved[:, t + 1:] + 7) % 256
    after = apply(cfg, tree, moved, "token_logprob")
    # out[<= t] reads tokens[<= t] only
    np.testing.assert_array_equal(after[:, :t + 1], base[:, :t + 1])
    assert (after[:, t + 1:] != base[:, t + 1:]).all()


def test_a_token_table_through_transform_equals_the_module(seeded):
    from mmlspark_tpu.data.table import DataTable
    from mmlspark_tpu.models.bundle import ModelBundle
    from mmlspark_tpu.models.jax_model import JaxModel

    cfg, _, tree = seeded
    module = lm.from_config(cfg)
    tokens = tokens_of(14, (5, 32)).astype(np.int32)
    bundle = ModelBundle(module=module, params=tree, input_spec=(32,),
                         output_names=type(module).OUTPUT_NAMES, name="tiny")
    model = JaxModel(model=bundle, input_col="tokens", output_col="lp",
                     minibatch_size=2, output_node="token_logprob",
                     mesh_spec={"dp": 1})
    out = model.transform(DataTable({"tokens": tokens}))["lp"]
    want = apply(cfg, tree, tokens[:2], "token_logprob")
    np.testing.assert_allclose(np.stack(list(out))[:2], want, atol=5e-5)
    assert len(out) == 5
    with pytest.raises(ValueError, match="unknown output node"):
        module.apply({"params": tree}, jnp.zeros((1, 8)),
                     output="expert_load")


# ---- what the two families share ----

def test_short_conv_answers_to_the_bit_as_before_the_tap_loop_moved():
    import test_lm_conv_moe as conv

    p, c = conv.conv_weights(1), conv.conv_cfg()
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 12, 16)),
                    jnp.float32)

    def before(p, x):
        # ``lm_conv.short_conv`` as it stood before ``causal_taps``
        d, taps = c.hidden_size, p["taps"].astype(jnp.float32)
        n = x.shape[1]
        bcu = lm_layers._dot(x, p["in_proj"], c.dtype)
        gate_b, gate_c, u = bcu[..., :d], bcu[..., d:2 * d], bcu[..., 2 * d:]
        z = gate_b * u
        lead = taps.shape[0] - 1
        padded = jnp.pad(z, ((0, 0), (lead, 0), (0, 0)))
        mixed = taps[lead] * z
        for j in range(lead):
            mixed = mixed + taps[j] * padded[:, j:j + n]
        return lm_layers._dot((gate_c * mixed).astype(c.dtype),
                              p["out_proj"], c.dtype)

    np.testing.assert_array_equal(
        np.asarray(jax.jit(lm_conv.short_conv, static_argnums=2)(p, x, c)),
        np.asarray(jax.jit(before)(p, x)))


def test_the_tap_loop_takes_four_taps_and_a_bias():
    r = np.random.default_rng(8)
    z = jnp.asarray(r.standard_normal((1, 6, 5)), jnp.float32)
    taps = jnp.asarray(r.standard_normal((4, 5)), jnp.float32)
    bias = jnp.asarray(r.standard_normal(5), jnp.float32)
    got = np.asarray(lm_conv.causal_taps(z, taps, bias))[0]
    zz, w, b = np.asarray(z)[0], np.asarray(taps), np.asarray(bias)
    want = np.stack([b + sum(w[j] * zz[t - 3 + j] for j in range(4)
                             if t - 3 + j >= 0) for t in range(6)])
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(lm_conv.causal_taps(z, taps)) + b,
        np.asarray(lm_conv.causal_taps(z, taps, bias)))


def test_attention_follows_what_it_is_handed():
    """With norm leaves and positions it is the conv family's operator (to
    the bit: its tests pin it to the reference); without either it adds
    nothing to the projections and the core."""
    import test_lm_conv_moe as conv

    c = lm.from_config(conv.tiny()).cfg
    r = np.random.default_rng(9)
    d, hd = c.hidden_size, c.head_dim
    p = {"q": r.standard_normal((d, 8 * hd)) / 8,
         "k": r.standard_normal((d, 2 * hd)) / 8,
         "v": r.standard_normal((d, 2 * hd)) / 8,
         "o": r.standard_normal((8 * hd, d)) / 8}
    p = {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}
    x = jnp.asarray(r.standard_normal((1, 10, d)), jnp.float32)
    plain = np.asarray(lm_layers.grouped_attention(p, x, None, c))
    ones = dict(p, q_norm=jnp.ones(hd), k_norm=jnp.ones(hd))
    normed = np.asarray(lm_layers.grouped_attention(ones, x, None, c))
    roped = np.asarray(lm_layers.grouped_attention(p, x, jnp.arange(10), c))
    # position 0 attends to itself alone and its rotation is the identity;
    # later positions see the norm and the rotation
    np.testing.assert_allclose(roped[:, 0], plain[:, 0], atol=1e-6)
    assert np.abs(roped[:, 1:] - plain[:, 1:]).max() > 1e-3
    assert np.abs(normed - plain).max() > 1e-3
