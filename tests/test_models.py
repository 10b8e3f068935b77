"""JaxModel inference, model zoo, trainer, and mesh tests (CPU backend,
8 virtual devices — the local[*] analog)."""

import numpy as np
import pytest

from mmlspark_tpu.core.stage import PipelineStage
from mmlspark_tpu.data.table import DataTable
from mmlspark_tpu.core.schema import make_image
from mmlspark_tpu.models.jax_model import JaxModel, coerce_input_matrix, minibatches
from mmlspark_tpu.models.zoo import ZOO, get_model
from mmlspark_tpu.parallel.mesh import MeshSpec, make_mesh


def small_cifar_bundle():
    return get_model("ConvNet_CIFAR10", widths=(8, 16), dense_width=32)


def image_table(n=10, seed=0):
    r = np.random.default_rng(seed)
    imgs = [make_image(f"img{i}", r.integers(0, 255, (32, 32, 3)))
            for i in range(n)]
    return DataTable({"image": imgs})


# ---- minibatch iterator ----

def test_minibatches_pads_tail():
    batch = np.arange(10, dtype=np.float32).reshape(10, 1)
    chunks = list(minibatches(batch, 4))
    assert [v for _, v in chunks] == [4, 4, 2]
    assert all(c.shape == (4, 1) for c, _ in chunks)
    assert chunks[-1][0][2:].sum() == 0  # zero padding


def test_coerce_image_column():
    # uint8 image bytes stay uint8 (¼ the transfer bytes; device upcasts)
    t = image_table(3)
    m = coerce_input_matrix(t, "image", (32, 32, 3))
    assert m.shape == (3, 32, 32, 3)
    assert m.dtype in (np.uint8, np.float32)
    src = np.asarray(t["image"][0]["data"])
    assert m.dtype == (np.uint8 if src.dtype == np.uint8 else np.float32)


def test_coerce_vector_column_reshape():
    t = DataTable({"v": [np.arange(12.0) for _ in range(4)]})
    m = coerce_input_matrix(t, "v", (3, 4))
    assert m.shape == (4, 3, 4)


def test_coerce_wrong_size_raises():
    t = DataTable({"v": [np.arange(5.0)]})
    with pytest.raises(ValueError):
        coerce_input_matrix(t, "v", (3, 4))


# ---- JaxModel ----

def test_jax_model_logits_and_nodes():
    bundle = small_cifar_bundle()
    t = image_table(7)
    jm = JaxModel(input_col="image", output_col="scores",
                  minibatch_size=4)
    jm.set(model=bundle)
    out = jm.transform(t)
    scores = np.stack(list(out["scores"]))
    assert scores.shape == (7, 10)
    # features node by name
    jm2 = JaxModel(input_col="image", output_col="feat",
                   output_node="features", minibatch_size=4)
    jm2.set(model=bundle)
    feats = np.stack(list(jm2.transform(t)["feat"]))
    assert feats.shape == (7, 32)
    # node by index
    jm3 = jm2.copy()
    jm3.set(output_node=None, output_node_index=0)
    feats2 = np.stack(list(jm3.transform(t)["feat"]))
    np.testing.assert_allclose(feats, feats2)


def test_jax_model_batch_size_invariance():
    """Output must not depend on minibatch slicing (padding correctness)."""
    bundle = small_cifar_bundle()
    t = image_table(5)
    outs = []
    for bs in (2, 5, 64):
        jm = JaxModel(input_col="image", output_col="s", minibatch_size=bs)
        jm.set(model=bundle)
        outs.append(np.stack(list(jm.transform(t)["s"])))
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(outs[0], outs[2], rtol=1e-4, atol=1e-4)


def test_jax_model_empty_table():
    bundle = small_cifar_bundle()
    jm = JaxModel(input_col="image", output_col="s")
    jm.set(model=bundle)
    out = jm.transform(DataTable({"image": []}))
    assert len(out) == 0 and "s" in out


def test_jax_model_bad_node():
    bundle = small_cifar_bundle()
    jm = JaxModel(input_col="image", output_col="s", output_node="nope")
    jm.set(model=bundle)
    with pytest.raises(ValueError):
        jm.transform(image_table(2))


def test_patch_conv_matches_direct_conv():
    """PatchConv3x3 must be numerically the same op as nn.Conv 3x3 SAME —
    identical params, identical output (it's a layout trick, not a model
    change)."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models.zoo import PatchConv3x3

    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(size=(2, 8, 8, 3)), jnp.float32)
    pc = PatchConv3x3(16, dtype=jnp.float32)
    params = pc.init(jax.random.PRNGKey(0), x)["params"]
    direct = nn.Conv(16, (3, 3), dtype=jnp.float32)
    out_patch = pc.apply({"params": params}, x)
    out_direct = direct.apply({"params": params}, x)
    np.testing.assert_allclose(np.asarray(out_patch),
                               np.asarray(out_direct), rtol=1e-5, atol=1e-5)


def test_jax_model_inference_is_mesh_sharded():
    """Scoring must use every device: batches commit to the dp sharding and
    params upload once, replicated (CNTKModel's DP inference, mesh-native)."""
    import jax

    bundle = small_cifar_bundle()
    jm = JaxModel(input_col="image", output_col="s", minibatch_size=16)
    jm.set(model=bundle)
    t = image_table(16)
    single = np.stack(list(jm.transform(t)["s"]))
    # the planner's compiled entry for the segment of one carries a
    # replicated device param tree and a dp extent covering all local devices
    (entry,) = jm.__dict__["_plan_cache"].values()
    fn, dev_params, data, dp = entry[1]
    assert dp == jax.local_device_count() == 8
    leaf = jax.tree_util.tree_leaves(dev_params)[0]
    assert len(leaf.sharding.device_set) == 8
    # a sharded batch placed through the advertised sharding spans all chips
    probe = jax.device_put(np.zeros((16, 32, 32, 3), np.float32), data)
    assert len(probe.sharding.device_set) == 8
    # numerics match an explicit single-device mesh
    jm1 = JaxModel(input_col="image", output_col="s", minibatch_size=16,
                   mesh_spec={"dp": 1})
    jm1.set(model=bundle)
    one = np.stack(list(jm1.transform(t)["s"]))
    np.testing.assert_allclose(single, one, rtol=1e-4, atol=1e-4)
    (entry1,) = jm1.__dict__["_plan_cache"].values()
    _fn, params1, target1, dp1 = entry1[1]
    assert dp1 == 1 and target1 == jax.local_devices()[0]
    assert jax.tree_util.tree_leaves(params1)[0].sharding.device_set \
        == {target1}


def test_jax_model_tiny_table_pads_to_mesh():
    # fewer rows than devices: padding must cover the dp extent
    bundle = small_cifar_bundle()
    jm = JaxModel(input_col="image", output_col="s", minibatch_size=64)
    jm.set(model=bundle)
    out = jm.transform(image_table(3))
    assert np.stack(list(out["s"])).shape == (3, 10)


def test_jax_model_save_load(tmp_path):
    bundle = small_cifar_bundle()
    t = image_table(3)
    jm = JaxModel(input_col="image", output_col="s", minibatch_size=4)
    jm.set(model=bundle)
    p = str(tmp_path / "jm")
    jm.save(p)
    loaded = PipelineStage.load(p)
    a = np.stack(list(jm.transform(t)["s"]))
    b = np.stack(list(loaded.transform(t)["s"]))
    np.testing.assert_allclose(a, b, rtol=1e-5)


# ---- zoo ----

def test_zoo_registry():
    assert "ConvNet_CIFAR10" in ZOO and "MLP" in ZOO
    b = get_model("MLP", input_dim=4, num_outputs=3)
    assert b.num_params() > 0
    with pytest.raises(KeyError):
        get_model("nonexistent")


# ---- mesh ----

def test_mesh_spec_resolution():
    assert MeshSpec(dp=-1).resolve(8)["dp"] == 8
    sizes = MeshSpec(dp=-1, tp=2).resolve(8)
    assert sizes["dp"] == 4 and sizes["tp"] == 2
    with pytest.raises(ValueError):
        MeshSpec(dp=3).resolve(8)


def test_make_mesh_8_devices():
    mesh = make_mesh(MeshSpec(dp=-1, fsdp=2))
    assert mesh.shape["dp"] == 4 and mesh.shape["fsdp"] == 2


# ---- trainer ----

def test_trainer_loss_decreases():
    from mmlspark_tpu.models.zoo import MLP
    from mmlspark_tpu.train.loop import TrainConfig, Trainer

    r = np.random.default_rng(0)
    x = r.normal(size=(256, 8)).astype(np.float32)
    w = r.normal(size=(8,))
    y = (x @ w > 0).astype(np.int64)
    cfg = TrainConfig(batch_size=64, epochs=30, learning_rate=5e-3,
                      log_every=1)
    tr = Trainer(MLP(features=(32,), num_outputs=2), cfg)
    tr.fit_arrays(x, y)
    assert tr.history[-1] < tr.history[0] * 0.7


def test_graft_entry_single():
    import __graft_entry__ as ge
    import jax
    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (8, 10)


@pytest.mark.slow  # the driver runs dryrun_multichip separately too
def test_graft_entry_multichip():
    import __graft_entry__ as ge
    ge.dryrun_multichip(8)


# ---- round-3 regression tests (ADVICE r2) ----

def test_params_reassign_no_stale_cache_no_second_compile():
    """Reassigning bundle.params must not serve stale device weights, even
    if CPython reuses the freed dict's id (ADVICE r2: the cache entry pins
    the stage it is keyed by, and a changed token is a re-upload), and must
    neither compile again nor grow the store: the planner keeps the jitted
    composite and puts the new tree onto the old leaves' shardings."""
    import jax

    from mmlspark_tpu import obs
    from mmlspark_tpu.obs.runtime import compiled_programs, jit_cache_size

    bundle = small_cifar_bundle()
    jm = JaxModel(model=bundle, input_col="image", output_col="scores",
                  minibatch_size=4)
    t = image_table(4)
    out1 = np.stack(jm.transform(t)["scores"])
    cache = jm.__dict__["_plan_cache"]
    (before,) = cache.values()
    fn = before[1][0]
    assert jit_cache_size(fn) == 1
    obs.enable()
    try:
        compiles = obs.registry().counter("plan.segment_compiles")
        compiled_before = compiles.value
        # mutate the model the way tools/build_model_repo does: new params
        for _ in range(3):
            bundle.params = jax.tree_util.tree_map(
                lambda p: p * 0.0, bundle.params)
            out2 = np.stack(jm.transform(t)["scores"])
        assert compiles.value == compiled_before
    finally:
        obs.disable()
    assert not np.allclose(out1, out2)  # zeroed weights → different scores
    np.testing.assert_array_equal(
        out2, np.stack(JaxModel(model=bundle, input_col="image",
                                output_col="scores", minibatch_size=4)
                       .transform(t)["scores"]))
    # repeated reassignment must not grow the store (stale device trees
    # would otherwise accumulate until OOM) nor compile a second program
    (after,) = cache.values()
    assert after is not before and after[1][0] is fn
    assert jit_cache_size(fn) == 1 and compiled_programs(jm) == 1
    # the old device tree went with the entry it was in
    old_leaf = jax.tree_util.tree_leaves(before[1][1])[0]
    new_leaf = jax.tree_util.tree_leaves(after[1][1])[0]
    assert new_leaf is not old_leaf
    assert new_leaf.sharding == old_leaf.sharding
    assert float(np.abs(np.asarray(new_leaf)).max()) == 0.0
    # a new module is another program: that does compile
    jm.set(model=small_cifar_bundle())
    jm.transform(t)
    assert next(iter(cache.values()))[1][0] is not fn and len(cache) == 1


def test_coerce_heterogeneous_image_dtypes_fall_back_to_float32():
    r = np.random.default_rng(0)
    flt = make_image("b", r.integers(0, 255, (8, 8, 3)))
    # e.g. a normalized image struct: float data in the same schema
    flt["data"] = flt["data"].astype(np.float32) / 255.0 - 0.5
    imgs = [make_image("a", r.integers(0, 255, (8, 8, 3))), flt]
    t = DataTable({"image": imgs})
    m = coerce_input_matrix(t, "image", (8, 8, 3))
    assert m.dtype == np.float32
    assert np.allclose(m[1], np.asarray(t["image"][1]["data"]))


def test_make_mesh_explicit_spec_uses_device_prefix():
    import jax
    n = jax.device_count()
    if n < 2:
        pytest.skip("needs >1 device")
    mesh = make_mesh(MeshSpec(dp=1, fsdp=1))
    assert mesh.devices.size == 1
    mesh2 = make_mesh(MeshSpec(dp=2))
    assert mesh2.devices.size == 2


# ---- frozen-BN fold (the ResNet inference variant) ----

def test_fold_batchnorm_numerics_parity():
    """Folded frozen-BN net must equal the BN net in inference mode —
    the fold is algebra, not an approximation (models/resnet.py). Stats
    are perturbed away from the init (mean 0 / var 1) so the fold is
    non-trivial."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models.resnet import fold_batchnorm, resnet18_thin

    bn = resnet18_thin(norm="batch", dtype=jnp.float32)
    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(size=(4, 32, 32, 3)).astype(np.float32))
    variables = bn.init(jax.random.PRNGKey(0), x)
    rs = np.random.default_rng(1)
    stats = jax.tree_util.tree_map(
        lambda a: jnp.abs(a + rs.normal(size=a.shape).astype(np.float32)
                          * 0.3) + 0.05,
        variables["batch_stats"])
    variables = {"params": variables["params"], "batch_stats": stats}

    ref = bn.apply(variables, x, train=False)
    folded = fold_batchnorm(variables)
    nf = resnet18_thin(norm="none", dtype=jnp.float32)
    got = nf.apply({"params": folded}, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_fold_batchnorm_bf16_keeps_constants_f32_and_tracks_reference():
    """The folded-BN constants contract (round 12): under
    ``param_dtype=bf16`` only the ≥2-D kernels narrow — the μ/σ-derived
    ``fold*`` biases (and every 1-D leaf) stay float32 and are added at
    an explicit f32 site, so a bf16 inference variant's error is bounded
    by the conv-output quantization alone, never by quantized
    normalization constants. Pinned on trained-scale statistics (means
    far from 0) against the f32 BN net in inference mode."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models.resnet import fold_batchnorm, resnet18_thin

    bn = resnet18_thin(norm="batch", dtype=jnp.float32)
    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(size=(4, 32, 32, 3)).astype(np.float32)
                    * 50 + 100)  # raw-pixel-scale input
    variables = bn.init(jax.random.PRNGKey(0), x)
    rs = np.random.default_rng(1)

    def inflate(tree):  # trained-like stats: means ~20, vars ~5
        out = {}
        for k, v in tree.items():
            if isinstance(v, dict):
                out[k] = inflate(v)
            elif k == "mean":
                out[k] = jnp.asarray(rs.normal(20, 10, v.shape),
                                     jnp.float32)
            else:
                out[k] = jnp.asarray(
                    np.abs(rs.normal(5, 2, v.shape)) + 0.5, jnp.float32)
        return out

    variables = {"params": variables["params"],
                 "batch_stats": inflate(variables["batch_stats"])}
    ref = np.asarray(bn.apply(variables, x, train=False,
                              output="features"))
    folded = fold_batchnorm(variables, param_dtype=jnp.bfloat16)
    for path, leaf in jax.tree_util.tree_flatten_with_path(folded)[0]:
        name = "/".join(str(k) for k in path)
        if "fold" in name or leaf.ndim < 2:
            assert leaf.dtype == jnp.float32, (name, leaf.dtype)
        else:
            assert leaf.dtype == jnp.bfloat16, (name, leaf.dtype)
    nf = resnet18_thin(norm="none", dtype=jnp.bfloat16)
    got = np.asarray(nf.apply({"params": folded}, x, output="features"))
    scale = np.abs(ref).max()
    assert np.abs(got - ref).max() / scale < 2e-2, (
        np.abs(got - ref).max(), scale)


def test_s2d_stem_matches_direct_stem():
    """The space-to-depth stem is a layout trick: same params, same output
    as the direct 7x7/s2 conv stem."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models.resnet import resnet18_thin

    direct = resnet18_thin(norm="none", dtype=jnp.float32, stem="direct")
    s2d = resnet18_thin(norm="none", dtype=jnp.float32, stem="s2d")
    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(size=(2, 32, 32, 3)).astype(np.float32))
    variables = direct.init(jax.random.PRNGKey(0), x)
    out_d = direct.apply(variables, x)
    out_s = s2d.apply(variables, x)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_d),
                               rtol=1e-4, atol=1e-5)


def test_resnet_infer_zoo_bundle():
    """The zoo inference variant: bf16 folded KERNELS, runnable end to
    end through the bundle API, feature dim matches the train variant.
    The μ/σ-derived fold constants (and every 1-D leaf) stay float32 —
    the accumulate-in-f32 contract of fold_batchnorm: a bf16 centering
    bias added in bf16 silently degraded normalization numerics."""
    import jax
    import jax.numpy as jnp

    b = get_model("ResNet_Small_Infer")
    flat = jax.tree_util.tree_flatten_with_path(b.params)[0]
    for path, leaf in flat:
        want = jnp.bfloat16 if leaf.ndim >= 2 else jnp.float32
        name = "/".join(str(k) for k in path)
        assert leaf.dtype == want, (name, leaf.dtype)
    out = b.apply(np.zeros((2, 32, 32, 3), np.float32), output="features")
    assert out.shape == (2, 128)
    # no norm params anywhere in the folded tree (the fold* sites hold
    # only the f32 constants)
    names = {"/".join(str(k) for k in path) for path, _ in flat}
    assert not any("gn" in n or "bn" in n for n in names), names
    assert any("fold" in n for n in names), names


def test_resnet_infer_featurizer_product_path():
    """ImageFeaturizer with the folded bundle — the BASELINE config-3
    product path (featurize via the zoo inference variant)."""
    from mmlspark_tpu.models.image_featurizer import ImageFeaturizer

    feat = ImageFeaturizer(input_col="image", output_col="features")
    feat.set_model_by_name("ResNet_Small_Infer")
    out = feat.transform(image_table(6))
    mat = out.column_matrix("features")
    assert mat.shape == (6, 128)
    assert np.isfinite(mat).all()
