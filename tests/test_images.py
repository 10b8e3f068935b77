"""Image ingest tests: readers (globs, zip, sampling), native decode,
ImageTransformer ops, UnrollImage, ImageSetAugmenter, ImageFeaturizer,
ModelDownloader."""

import os
import zipfile

import numpy as np
import pytest

from mmlspark_tpu.core.schema import is_image_column, make_image
from mmlspark_tpu.core.stage import PipelineStage
from mmlspark_tpu.data.downloader import (
    ModelDownloader, ModelSchema, load_bundle_file, publish_model,
)
from mmlspark_tpu.data.readers import (
    decode_image, read_binary_files, read_images,
)
from mmlspark_tpu.data.table import DataTable
from mmlspark_tpu.models.image_featurizer import ImageFeaturizer
from mmlspark_tpu.models.zoo import get_model
from mmlspark_tpu.native import imgops
from mmlspark_tpu.stages.image import (
    ImageSetAugmenter, ImageTransformer, UnrollImage,
)


@pytest.fixture(scope="module")
def image_dir(tmp_path_factory):
    """Directory of jpg/png files + a zip archive + a junk file."""
    import cv2
    root = tmp_path_factory.mktemp("imgs")
    r = np.random.default_rng(0)
    for i in range(4):
        img = r.integers(0, 255, (24 + i, 36, 3)).astype(np.uint8)
        cv2.imwrite(str(root / f"im{i}.jpg"), img)
    cv2.imwrite(str(root / "p.png"),
                r.integers(0, 255, (20, 20, 3)).astype(np.uint8))
    (root / "notes.txt").write_text("not an image")
    sub = root / "sub"
    sub.mkdir()
    cv2.imwrite(str(sub / "deep.png"),
                r.integers(0, 255, (16, 16, 3)).astype(np.uint8))
    with zipfile.ZipFile(root / "arch.zip", "w") as zf:
        ok, buf = cv2.imencode(".jpg",
                               r.integers(0, 255, (12, 12, 3)).astype(np.uint8))
        zf.writestr("zipped1.jpg", buf.tobytes())
        zf.writestr("zipped2.jpg", buf.tobytes())
        zf.writestr("readme.md", "skip me")
    return str(root)


def rand_images(n=6, h=28, w=28, seed=0):
    r = np.random.default_rng(seed)
    return DataTable({"image": [
        make_image(f"i{k}", r.integers(0, 255, (h, w, 3))) for k in range(n)
    ]})


# ---- native ops ----

def test_native_available():
    assert imgops.available()


def test_native_unroll_matches_numpy():
    r = np.random.default_rng(1)
    img = r.integers(0, 255, (9, 7, 3)).astype(np.uint8)
    got = imgops.unroll(img, to_rgb=True, scale=1 / 255.0, offset=-0.5)
    want = (np.transpose(img[:, :, ::-1], (2, 0, 1)).astype(np.float32)
            / 255.0 - 0.5)
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_native_unroll_batch():
    r = np.random.default_rng(2)
    batch = r.integers(0, 255, (5, 8, 8, 3)).astype(np.uint8)
    got = imgops.unroll_batch(batch, scale=2.0)
    want = np.transpose(batch, (0, 3, 1, 2)).astype(np.float32) * 2.0
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_native_decode_jpeg_png_match_cv2():
    import cv2
    r = np.random.default_rng(3)
    img = r.integers(0, 255, (30, 40, 3)).astype(np.uint8)
    _, png = cv2.imencode(".png", img)
    assert np.array_equal(imgops.decode(png.tobytes()), img)
    _, jpg = cv2.imencode(".jpg", img)
    ours = imgops.decode(jpg.tobytes())
    ref = cv2.imdecode(jpg, cv2.IMREAD_COLOR)
    assert np.abs(ours.astype(int) - ref.astype(int)).max() <= 1


# ---- readers ----

def test_read_binary_files(image_dir):
    t = read_binary_files(image_dir)
    names = [os.path.basename(p) for p in t["path"]]
    assert "notes.txt" in names  # binary reader takes everything
    assert any(n.endswith(".zip") or "zipped" in n for n in names)


def test_read_images_flat(image_dir):
    t = read_images(image_dir, inspect_zip=False)
    assert is_image_column(t, "image")
    assert len(t) == 5  # 4 jpg + 1 png; txt and zip skipped; sub/ skipped


def test_read_images_recursive_and_zip(image_dir):
    t = read_images(image_dir, recursive=True, inspect_zip=True)
    # 4 jpg + 1 png + 1 deep.png + 2 zip entries (readme.md filtered)
    assert len(t) == 8
    paths = [v["path"] for v in t["image"]]
    assert any("arch.zip/zipped1.jpg" in p for p in paths)


def test_read_images_sampling_deterministic(image_dir):
    a = read_images(image_dir, recursive=True, sample_ratio=0.5, seed=7)
    b = read_images(image_dir, recursive=True, sample_ratio=0.5, seed=7)
    assert [v["path"] for v in a["image"]] == [v["path"] for v in b["image"]]
    assert len(a) < 8
    c = read_images(image_dir, recursive=True, sample_ratio=0.5, seed=8)
    assert [v["path"] for v in c["image"]] != [v["path"] for v in a["image"]]


def test_read_images_sharding(image_dir):
    t0 = read_images(image_dir, recursive=True, shard_index=0, num_shards=2)
    t1 = read_images(image_dir, recursive=True, shard_index=1, num_shards=2)
    p0 = {v["path"] for v in t0["image"]}
    p1 = {v["path"] for v in t1["image"]}
    assert not (p0 & p1)
    assert len(p0) + len(p1) == 8


def test_read_images_bad_path():
    with pytest.raises(FileNotFoundError):
        read_images("/definitely/not/here")
    with pytest.raises(ValueError):
        read_binary_files(".", sample_ratio=2.0)


def test_decode_garbage_returns_none():
    assert decode_image(b"this is not an image") is None


# ---- ImageTransformer ----

def test_transformer_resize_crop_flip():
    t = rand_images(3, 20, 30)
    it = (ImageTransformer().resize(10, 12).crop(2, 2, 6, 8).flip(1))
    out = it.transform(t)
    img = out["image"][0]
    assert (img["height"], img["width"]) == (6, 8)
    # flip of a flip is identity
    it2 = ImageTransformer().flip(1)
    once = it2.transform(t)["image"][0]["data"]
    twice = it2.transform(it2.transform(t))["image"][0]["data"]
    np.testing.assert_array_equal(twice, t["image"][0]["data"])


def test_transformer_color_and_blur():
    t = rand_images(2)
    out = ImageTransformer().color_format("gray").transform(t)
    assert out["image"][0]["channels"] == 1
    out2 = ImageTransformer().blur(3, 3).transform(t)
    assert out2["image"][0]["data"].shape == (28, 28, 3)
    out3 = ImageTransformer().threshold(127, 255).transform(t)
    vals = np.unique(out3["image"][0]["data"])
    assert set(vals.tolist()) <= {0, 255}
    out4 = ImageTransformer().gaussian_kernel(5, 1.0).transform(t)
    assert out4["image"][0]["data"].shape == (28, 28, 3)


def test_transformer_decode_if_binary():
    import cv2
    r = np.random.default_rng(5)
    img = r.integers(0, 255, (14, 14, 3)).astype(np.uint8)
    _, jpg = cv2.imencode(".png", img)
    t = DataTable({"image": [jpg.tobytes()]})
    out = ImageTransformer().resize(7, 7).transform(t)
    assert out["image"][0]["height"] == 7


def test_transformer_bad_op_and_crop():
    t = rand_images(1, 10, 10)
    bad = ImageTransformer(ops=[{"op": "nope"}])
    with pytest.raises(ValueError):
        bad.transform(t)
    with pytest.raises(ValueError):
        ImageTransformer().crop(8, 8, 10, 10).transform(t)


def test_transformer_save_load(tmp_path):
    it = ImageTransformer().resize(8, 9).flip(1)
    p = str(tmp_path / "it")
    it.save(p)
    loaded = PipelineStage.load(p)
    t = rand_images(2)
    a = it.transform(t)["image"][0]["data"]
    b = loaded.transform(t)["image"][0]["data"]
    np.testing.assert_array_equal(a, b)


# ---- UnrollImage / Augmenter ----

def test_unroll_stage():
    t = rand_images(3, 8, 8)
    out = UnrollImage(scale=1 / 255.0).transform(t)
    v = out["features"][0]
    assert v.shape == (3 * 8 * 8,) and v.dtype == np.float32
    assert v.max() <= 1.0


def test_augmenter_doubles_rows():
    t = rand_images(4)
    out = ImageSetAugmenter().transform(t)
    assert len(out) == 8
    out2 = ImageSetAugmenter(flip_up_down=True).transform(t)
    assert len(out2) == 12
    # flipped copy really is flipped
    orig = t["image"][0]["data"]
    flipped = out["image"][4]["data"]
    np.testing.assert_array_equal(flipped, orig[:, ::-1])


# ---- ImageFeaturizer ----

def test_image_featurizer_cut_layers():
    bundle = get_model("ConvNet_CIFAR10", widths=(8, 16), dense_width=24)
    t = rand_images(5, 40, 40)  # wrong size on purpose; featurizer resizes
    f = ImageFeaturizer(cut_output_layers=1, minibatch_size=4)
    f.set(model=bundle)
    out = f.transform(t)
    feats = np.stack(list(out["features"]))
    assert feats.shape == (5, 24)
    # cut=0 keeps the classifier head
    f2 = ImageFeaturizer(cut_output_layers=0, minibatch_size=4)
    f2.set(model=bundle)
    logits = np.stack(list(f2.transform(t)["features"]))
    assert logits.shape == (5, 10)
    with pytest.raises(ValueError):
        f3 = ImageFeaturizer(cut_output_layers=5)
        f3.set(model=bundle)
        f3.transform(t)


# ---- ModelDownloader ----

def test_downloader_roundtrip(tmp_path):
    repo = str(tmp_path / "repo")
    cache = str(tmp_path / "cache")
    bundle = get_model("MLP", input_dim=6, num_outputs=3)
    entry = publish_model(bundle, repo)
    assert entry.hash and entry.size > 0

    dl = ModelDownloader(repo, cache_dir=cache)
    assert [m.name for m in dl.list_models()] == ["MLP"]
    path = dl.download_by_name("MLP")
    loaded = load_bundle_file(path)
    assert loaded.input_spec == (6,)
    x = np.zeros((2, 6), np.float32)
    np.testing.assert_allclose(np.asarray(bundle.apply(x)),
                               np.asarray(loaded.apply(x)), atol=1e-6)
    # cache hit: second download returns same path without refetch
    assert dl.download_by_name("MLP") == path


def test_downloader_hash_mismatch(tmp_path):
    repo = str(tmp_path / "repo")
    bundle = get_model("MLP", input_dim=4)
    entry = publish_model(bundle, repo)
    # corrupt the repo file
    with open(os.path.join(repo, entry.uri), "ab") as f:
        f.write(b"tamper")
    dl = ModelDownloader(repo, cache_dir=str(tmp_path / "cache"))
    with pytest.raises(IOError):
        dl.download_by_name("MLP")
    with pytest.raises(KeyError):
        dl.download_by_name("missing")


# ---- round-3 regression tests (review findings of round 2) ----

def test_hashless_cache_entry_is_verified(tmp_path):
    """Empty manifest hash: a corrupted cache entry must never be served
    (round-2 review finding — sidecar self-hash restores the guarantee)."""
    repo = str(tmp_path / "repo")
    cache = str(tmp_path / "cache")
    bundle = get_model("MLP", input_dim=4)
    publish_model(bundle, repo)
    # strip the hash from the manifest (hashless deployment)
    import json
    mpath = os.path.join(repo, "MANIFEST.json")
    with open(mpath) as f:
        entries = json.load(f)
    for e in entries:
        e["hash"] = ""
    with open(mpath, "w") as f:
        json.dump(entries, f)

    dl = ModelDownloader(repo, cache_dir=cache)
    path = dl.download_by_name("MLP")
    assert os.path.exists(path + ".sha256")
    good = open(path, "rb").read()
    # second hit serves the verified cache
    assert dl.download_by_name("MLP") == path

    # truncate the cached file: next download must detect + refetch
    with open(path, "wb") as f:
        f.write(good[: len(good) // 2])
    path2 = dl.download_by_name("MLP")
    assert open(path2, "rb").read() == good
    load_bundle_file(path2)  # loads cleanly

    # sidecar missing entirely → refuse the cache, refetch
    os.remove(path + ".sha256")
    with open(path, "wb") as f:
        f.write(b"garbage")
    path3 = dl.download_by_name("MLP")
    assert open(path3, "rb").read() == good


def test_unroll_batch_fast_path_matches_per_row():
    t = rand_images(5)
    u = UnrollImage(input_col="image", output_col="f", scale=1 / 255.0,
                    offset=-0.5, to_rgb=True)
    out = u.transform(t)["f"]
    for i, v in enumerate(t["image"]):
        want = imgops.unroll(np.asarray(v["data"]), to_rgb=True,
                             scale=1 / 255.0, offset=-0.5).reshape(-1)
        np.testing.assert_allclose(out[i], want, atol=1e-6)


def test_unroll_mixed_shapes_and_none_rows():
    r = np.random.default_rng(3)
    rows = [make_image("a", r.integers(0, 255, (8, 8, 3))),
            None,
            make_image("b", r.integers(0, 255, (6, 10, 3)))]
    t = DataTable({"image": rows})
    out = UnrollImage(input_col="image", output_col="f").transform(t)["f"]
    assert out[1] is None
    assert out[0].shape == (3 * 8 * 8,)
    assert out[2].shape == (3 * 6 * 10,)


def test_image_transformer_threaded_matches_sequential():
    from mmlspark_tpu.core import config as cfg
    t = rand_images(8)
    tr = ImageTransformer().resize(12, 14).flip(1)
    cfg.set("image_threads", 1)
    try:
        seq = tr.transform(t)["image"]
    finally:
        cfg.reset("image_threads")
    par = tr.transform(t)["image"]  # default: thread pool
    for a, b in zip(seq, par):
        np.testing.assert_array_equal(a["data"], b["data"])


def test_unroll_uniform_grayscale_fast_path():
    r = np.random.default_rng(4)
    rows = [make_image("g", r.integers(0, 255, (9, 7))) for _ in range(3)]
    t = DataTable({"image": rows})
    out = UnrollImage(input_col="image", output_col="f").transform(t)["f"]
    assert all(v.shape == (9 * 7,) for v in out)
    # single-row column too
    t1 = DataTable({"image": rows[:1]})
    out1 = UnrollImage(input_col="image", output_col="f").transform(t1)["f"]
    np.testing.assert_allclose(out1[0], out[0])
