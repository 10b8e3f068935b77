"""Tests for the distributed training backend (SURVEY §2.5/§7.5 parity):
mesh-sharded training, fsdp parameter sharding, checkpoint/resume, and the
JaxLearner estimator (CNTKLearner analog — the ValidateCntkTrain mirror,
run on the virtual 8-device CPU mesh like all 'distributed' reference tests
run on local[*])."""

import os

import numpy as np
import pytest

import jax

from mmlspark_tpu.data.table import DataTable
from mmlspark_tpu.models.zoo import MLP
from mmlspark_tpu.parallel.mesh import (
    MeshSpec, make_mesh, param_shardings,
)
from mmlspark_tpu.train import (
    JaxLearner, TrainCheckpointer, TrainConfig, Trainer,
)


def xor_data(n=256, seed=0):
    r = np.random.default_rng(seed)
    x = r.normal(size=(n, 8)).astype(np.float32)
    y = ((x[:, 0] > 0) ^ (x[:, 1] > 0)).astype(np.int64)
    return x, y


class TestMeshTraining:
    def test_dp_mesh_trains(self):
        x, y = xor_data()
        mesh = make_mesh(MeshSpec(dp=-1))
        cfg = TrainConfig(batch_size=64, epochs=30, learning_rate=5e-3)
        tr = Trainer(MLP(features=(32,), num_outputs=2), cfg, mesh=mesh)
        tr.fit_arrays(x, y)
        assert tr.history[0] > tr.history[-1]
        assert np.isfinite(tr.history[-1])

    def test_fsdp_params_actually_sharded(self):
        mesh = make_mesh(MeshSpec(dp=2, fsdp=4))
        x, y = xor_data(128)
        cfg = TrainConfig(batch_size=32, epochs=2)
        tr = Trainer(MLP(features=(16,), num_outputs=2), cfg, mesh=mesh)
        tr.fit_arrays(x, y)
        # at least one param leaf must be sharded over fsdp
        leaves = jax.tree_util.tree_leaves(tr.params)
        assert any(
            "fsdp" in str(l.sharding.spec) for l in leaves
            if hasattr(l, "sharding")), \
            [str(l.sharding) for l in leaves]
        assert np.isfinite(tr.history[-1])

    def test_fsdp_matches_dp_numerics(self):
        # same data+seed on dp-only vs dp×fsdp meshes → same loss trajectory
        x, y = xor_data(128)
        losses = {}
        for name, spec in [("dp", MeshSpec(dp=-1)),
                           ("fsdp", MeshSpec(dp=2, fsdp=4))]:
            cfg = TrainConfig(batch_size=64, epochs=3, log_every=1, seed=7)
            tr = Trainer(MLP(features=(16,), num_outputs=2), cfg,
                         mesh=make_mesh(spec))
            tr.fit_arrays(x, y)
            losses[name] = tr.history
        np.testing.assert_allclose(losses["dp"], losses["fsdp"],
                                   rtol=1e-4, atol=1e-5)

    def test_param_shardings_rule(self):
        mesh = make_mesh(MeshSpec(dp=2, fsdp=4))
        params = {"w": np.zeros((8, 3)), "b": np.zeros((3,)),
                  "scalar": np.zeros(())}
        sh = param_shardings(mesh, params)
        assert "fsdp" in str(sh["w"].spec)      # 8 % 4 == 0 → sharded
        assert str(sh["b"].spec) == "PartitionSpec()"   # 3 % 4 != 0
        assert str(sh["scalar"].spec) == "PartitionSpec()"


class TestCheckpointResume:
    def test_save_restore_roundtrip(self, tmp_path):
        ck = TrainCheckpointer(str(tmp_path / "ck"), max_to_keep=2)
        state = {"params": {"w": np.arange(6, dtype=np.float32).reshape(2, 3)},
                 "step": np.asarray(5, dtype=np.int32)}
        ck.save(state)
        assert ck.steps() == [5]
        restored = ck.restore()
        np.testing.assert_allclose(restored["params"]["w"],
                                   state["params"]["w"])
        assert int(restored["step"]) == 5

    def test_max_to_keep(self, tmp_path):
        ck = TrainCheckpointer(str(tmp_path / "ck"), max_to_keep=2)
        for s in (1, 2, 3):
            ck.save({"x": np.zeros(2)}, step=s)
        assert ck.steps() == [2, 3]

    def test_trainer_resume_continues_from_step(self, tmp_path):
        x, y = xor_data(128)
        ckdir = str(tmp_path / "run")
        cfg = TrainConfig(batch_size=32, epochs=2, checkpoint_dir=ckdir,
                          seed=3)
        tr1 = Trainer(MLP(features=(16,), num_outputs=2), cfg)
        tr1.fit_arrays(x, y)
        saved_step = int(np.asarray(tr1.state["step"]))
        assert saved_step == 2 * (128 // 32)

        # a fresh trainer with the same config resumes instead of restarting
        tr2 = Trainer(MLP(features=(16,), num_outputs=2), cfg)
        tr2.state = tr2.init_state(x.shape[1:])
        resumed = tr2.maybe_restore()
        assert resumed == saved_step
        np.testing.assert_allclose(
            np.asarray(tr2.state["params"]["dense0"]["kernel"]),
            np.asarray(tr1.state["params"]["dense0"]["kernel"]),
            rtol=1e-6)

    def test_resume_completes_remainder_not_double(self, tmp_path):
        # a completed run re-executed with the same checkpoint_dir must NOT
        # train the configured schedule again on top of the restored state
        x, y = xor_data(128)
        ckdir = str(tmp_path / "run")
        cfg = TrainConfig(batch_size=32, epochs=2, checkpoint_dir=ckdir,
                          seed=3)
        tr1 = Trainer(MLP(features=(16,), num_outputs=2), cfg)
        tr1.fit_arrays(x, y)
        done = int(np.asarray(tr1.state["step"]))

        tr2 = Trainer(MLP(features=(16,), num_outputs=2), cfg)
        tr2.fit_arrays(x, y)
        assert int(np.asarray(tr2.state["step"])) == done
        np.testing.assert_allclose(
            np.asarray(tr2.state["params"]["dense0"]["kernel"]),
            np.asarray(tr1.state["params"]["dense0"]["kernel"]), rtol=1e-6)

    def test_resume_schedule_mismatch_raises(self, tmp_path):
        # resuming with a changed batch size would silently replay the wrong
        # batches; the recorded schedule fingerprint must catch it
        x, y = xor_data(128)
        ckdir = str(tmp_path / "run")
        cfg = TrainConfig(batch_size=32, epochs=2, checkpoint_dir=ckdir,
                          seed=3)
        Trainer(MLP(features=(16,), num_outputs=2), cfg).fit_arrays(x, y)

        cfg2 = TrainConfig(batch_size=64, epochs=2, checkpoint_dir=ckdir,
                           seed=3)
        tr = Trainer(MLP(features=(16,), num_outputs=2), cfg2)
        with pytest.raises(ValueError, match="fingerprint mismatch"):
            tr.fit_arrays(x, y)

    def test_resume_false_ignores_checkpoints(self, tmp_path):
        x, y = xor_data(64)
        ckdir = str(tmp_path / "run")
        cfg = TrainConfig(batch_size=32, epochs=1, checkpoint_dir=ckdir)
        Trainer(MLP(features=(8,), num_outputs=2), cfg).fit_arrays(x, y)
        cfg2 = TrainConfig(batch_size=32, epochs=1, checkpoint_dir=ckdir,
                           resume=False)
        tr = Trainer(MLP(features=(8,), num_outputs=2), cfg2)
        tr.state = tr.init_state(x.shape[1:])
        assert tr.maybe_restore() is None


class TestCheckpointIntegrity:
    """Round-11 hardening: torn/corrupt step dirs are detected by the
    per-step digest and fall back to the previous manifest step; GC is
    crash-safe (manifest rewritten BEFORE deletes)."""

    @staticmethod
    def _truncate_largest_leaf(step_dir):
        import glob as _glob
        files = [p for p in _glob.glob(os.path.join(step_dir, "**"),
                                       recursive=True) if os.path.isfile(p)]
        victim = max(files, key=os.path.getsize)
        with open(victim, "r+b") as f:
            f.truncate(max(os.path.getsize(victim) // 2, 1))
        return victim

    def _two_step_ckpt(self, tmp_path):
        from mmlspark_tpu.train.checkpoint import TrainCheckpointer
        ck = TrainCheckpointer(str(tmp_path / "ck"), max_to_keep=3)
        for s in (1, 2):
            ck.save({"w": np.full((64,), float(s), np.float32),
                     "step": np.asarray(s, np.int32)}, step=s)
        return ck

    def test_truncated_leaf_falls_back_to_previous_step(self, tmp_path):
        ck = self._two_step_ckpt(tmp_path)
        self._truncate_largest_leaf(os.path.join(ck.directory, "step_2"))
        assert ck.verify_step(2) is not None
        assert ck.verify_step(1) is None
        restored = ck.restore()  # recovery path: digest-validated
        assert int(np.asarray(restored["step"])) == 1

    def test_explicit_corrupt_step_raises_typed(self, tmp_path):
        from mmlspark_tpu.train.checkpoint import CheckpointCorruptError
        ck = self._two_step_ckpt(tmp_path)
        self._truncate_largest_leaf(os.path.join(ck.directory, "step_2"))
        with pytest.raises(CheckpointCorruptError, match="digest"):
            ck.restore(step=2)

    def test_all_steps_corrupt_raises_typed(self, tmp_path):
        from mmlspark_tpu.train.checkpoint import CheckpointCorruptError
        ck = self._two_step_ckpt(tmp_path)
        for s in (1, 2):
            self._truncate_largest_leaf(
                os.path.join(ck.directory, f"step_{s}"))
        with pytest.raises(CheckpointCorruptError, match="every manifest"):
            ck.restore()

    def test_missing_step_dir_falls_back(self, tmp_path):
        import shutil as _shutil
        ck = self._two_step_ckpt(tmp_path)
        _shutil.rmtree(os.path.join(ck.directory, "step_2"))
        restored = ck.restore()
        assert int(np.asarray(restored["step"])) == 1

    def test_corruption_records_event_and_counter(self, tmp_path):
        from mmlspark_tpu import obs
        ck = self._two_step_ckpt(tmp_path)
        self._truncate_largest_leaf(os.path.join(ck.directory, "step_2"))
        obs.disable()
        obs.clear()
        obs.registry().reset()
        obs.enable()
        try:
            ck.restore()
            assert obs.registry().value("train.checkpoint_corrupt") == 1
            names = {getattr(r, "name", "") for r in obs.captured()}
            assert "train/checkpoint_corrupt" in names
        finally:
            obs.disable()
            obs.clear()
            obs.registry().reset()

    def test_gc_crash_between_manifest_and_delete_is_restorable(
            self, tmp_path, monkeypatch):
        """max_to_keep pruning interrupted between manifest rewrite and
        directory delete must leave a restorable manifest (the manifest
        commits FIRST; orphan dirs are swept by the next save)."""
        import shutil as _shutil

        from mmlspark_tpu.train import checkpoint as ckpt_mod
        ck = ckpt_mod.TrainCheckpointer(str(tmp_path / "ck"),
                                        max_to_keep=2)
        for s in (1, 2):
            ck.save({"w": np.full((8,), float(s), np.float32),
                     "step": np.asarray(s, np.int32)}, step=s)

        real_rmtree = _shutil.rmtree

        def crash_on_prune(path, *a, **kw):
            if os.path.basename(path) == "step_1":
                raise RuntimeError("induced crash mid-GC")
            return real_rmtree(path, *a, **kw)

        monkeypatch.setattr(ckpt_mod.shutil, "rmtree", crash_on_prune)
        with pytest.raises(RuntimeError, match="mid-GC"):
            ck.save({"w": np.full((8,), 3.0, np.float32),
                     "step": np.asarray(3, np.int32)}, step=3)
        monkeypatch.undo()

        # the manifest never points at the dropped step, and the latest
        # checkpoint restores
        assert ck.steps() == [2, 3]
        restored = ck.restore()
        assert int(np.asarray(restored["step"])) == 3
        # the orphan dir from the interrupted delete is swept next save
        assert os.path.isdir(os.path.join(ck.directory, "step_1"))
        ck.save({"w": np.full((8,), 4.0, np.float32),
                 "step": np.asarray(4, np.int32)}, step=4)
        assert not os.path.exists(os.path.join(ck.directory, "step_1"))
        assert ck.steps() == [3, 4]

    def test_trainer_resumes_past_torn_latest(self, tmp_path):
        """End-to-end: a fit whose LATEST checkpoint was torn by a crash
        resumes from the previous one instead of dying mid-recovery."""
        x, y = xor_data(128)
        ckdir = str(tmp_path / "run")
        cfg = TrainConfig(batch_size=32, epochs=2, checkpoint_dir=ckdir,
                          checkpoint_every=2, seed=3, max_to_keep=4)
        tr1 = Trainer(MLP(features=(16,), num_outputs=2), cfg)
        tr1.fit_arrays(x, y)
        from mmlspark_tpu.train.checkpoint import TrainCheckpointer
        ck = TrainCheckpointer(ckdir)
        latest = ck.latest_step()
        self._truncate_largest_leaf(
            os.path.join(ck.directory, f"step_{latest}"))
        tr2 = Trainer(MLP(features=(16,), num_outputs=2), cfg)
        tr2.state = tr2.init_state(x.shape[1:])
        resumed = tr2.maybe_restore()
        assert resumed is not None and resumed < latest
        assert resumed in ck.steps()


class TestJaxLearner:
    def test_fit_on_featurized_table(self):
        r = np.random.default_rng(0)
        n = 300
        y = r.integers(0, 2, n)
        t = DataTable({
            "a": r.normal(size=n) + 2.0 * y,
            "b": r.normal(size=n),
            "cat": [["u", "v"][int(v)] for v in r.integers(0, 2, n)],
            "label": y,
        })
        model = JaxLearner(label_col="label", epochs=80,
                           learning_rate=0.01).fit(t)
        # JaxLearnerModel featurizes internally
        scored = model.transform(t)
        logits = scored.column_matrix("scores")
        acc = (logits.argmax(axis=1) == y).mean()
        assert acc > 0.85, acc
        assert model.label_levels == [0, 1]

    def test_fit_on_vector_column_with_mesh(self):
        x, y = xor_data(256)
        t = DataTable({"vec": list(x), "label": y})
        model = JaxLearner(label_col="label", input_col="vec", epochs=30,
                           learning_rate=5e-3, batch_size=64,
                           mesh_spec={"dp": 4, "fsdp": 2}).fit(t)
        scored = model.transform(t)
        logits = scored.column_matrix("scores")
        assert (logits.argmax(axis=1) == y).mean() > 0.8

    def test_regression_loss(self):
        r = np.random.default_rng(1)
        x = r.normal(size=(200, 4)).astype(np.float32)
        y = x @ np.array([1.0, -2.0, 0.5, 3.0]) + 1.0
        t = DataTable({"vec": list(x), "target": y})
        model = JaxLearner(label_col="target", input_col="vec", loss="mse",
                           epochs=200, learning_rate=0.01).fit(t)
        pred = model.transform(t).column_matrix("scores").reshape(-1)
        assert np.sqrt(np.mean((pred - y) ** 2)) < 1.0

    def test_checkpointing_through_learner(self, tmp_path):
        x, y = xor_data(128)
        t = DataTable({"vec": list(x), "label": y})
        ckdir = str(tmp_path / "jl")
        JaxLearner(label_col="label", input_col="vec", epochs=2,
                   batch_size=32, checkpoint_dir=ckdir).fit(t)
        assert TrainCheckpointer(ckdir).latest_step() is not None

    def test_learner_model_roundtrip(self, tmp_path):
        from mmlspark_tpu.core.stage import PipelineStage
        r = np.random.default_rng(4)
        n = 100
        y = r.integers(0, 2, n)
        t = DataTable({"a": r.normal(size=n) + 2.0 * y, "label": y})
        model = JaxLearner(label_col="label", epochs=20).fit(t)
        p = str(tmp_path / "jl_model")
        model.save(p)
        loaded = PipelineStage.load(p)
        np.testing.assert_allclose(
            loaded.transform(t).column_matrix("scores"),
            model.transform(t).column_matrix("scores"), rtol=1e-5)
        assert loaded.label_levels == model.label_levels

    def test_conv_module_with_input_shape(self):
        from mmlspark_tpu.models.zoo import ConvNetCifar
        r = np.random.default_rng(2)
        n = 64
        x = r.normal(size=(n, 8 * 8 * 3)).astype(np.float32)
        y = r.integers(0, 2, n)
        t = DataTable({"v": list(x), "label": y})
        model = JaxLearner(
            label_col="label", input_col="v", input_shape=[8, 8, 3],
            module=ConvNetCifar(num_classes=2, widths=(4,), dense_width=8),
            epochs=1, batch_size=16).fit(t)
        out = model.transform(
            DataTable({"v": list(x.reshape(n, -1))}).with_column("label", y))
        assert out.column_matrix("scores").shape == (n, 2)


class TestTailBatches:
    """Round-3 fix: the final partial batch is padded + masked, not dropped
    (round-2 review finding)."""

    def test_tail_rows_are_trained(self):
        x, y = xor_data(80)  # 80 rows, bs 64 → 64 + padded 16
        cfg = TrainConfig(batch_size=64, epochs=3)
        tr = Trainer(MLP(features=(16,), num_outputs=2), cfg,
                     mesh=make_mesh(MeshSpec(dp=-1)))
        tr.fit_arrays(x, y)
        # 2 steps per epoch (ceil(80/64)), not 1 (drop_remainder behavior)
        assert int(tr.state["step"]) == 6

    def test_padded_tail_matches_exact_batch_numerics(self):
        # one masked step over a padded tail must equal one step over just
        # the real rows (same weights out), proving the mask removes the
        # padding's influence on loss AND gradients
        import jax
        from mmlspark_tpu.parallel.mesh import batch_sharding

        x, y = xor_data(64)
        mesh = make_mesh(MeshSpec(dp=-1))
        cfg = TrainConfig(batch_size=64, epochs=1, learning_rate=1e-2,
                          donate_state=False)
        tr = Trainer(MLP(features=(16,), num_outputs=2), cfg, mesh=mesh)
        tr.state = tr.init_state(x.shape[1:])
        data = batch_sharding(mesh)

        # padded: 48 real rows + 16 zero rows, mask zeros the padding
        pad_x = np.concatenate([x[:48], np.zeros((16, 8), np.float32)])
        pad_y = np.concatenate([y[:48], np.zeros(16, np.int64)])
        w = np.concatenate([np.ones(48, np.float32),
                            np.zeros(16, np.float32)])
        s_pad, m_pad = tr.step_masked(
            tr.state, jax.device_put(pad_x, data),
            jax.device_put(pad_y, data), jax.device_put(w, data))

        # against a direct unmasked 48-row step
        cfg48 = TrainConfig(batch_size=48, epochs=1, learning_rate=1e-2,
                            donate_state=False)
        tr48 = Trainer(MLP(features=(16,), num_outputs=2), cfg48, mesh=mesh)
        tr48.state = tr48.init_state(x.shape[1:])
        s48, m48 = tr48.step(
            tr48.state, jax.device_put(x[:48], data),
            jax.device_put(y[:48], data))
        np.testing.assert_allclose(float(m_pad["loss"]), float(m48["loss"]),
                                   rtol=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(s_pad["params"]),
                        jax.tree_util.tree_leaves(s48["params"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)


def test_multilabel_sigmoid_loss_trains_with_tail():
    # [B,K] sigmoid labels through the masked step (review finding r3)
    r = np.random.default_rng(0)
    x = r.normal(size=(40, 6)).astype(np.float32)
    y = (r.normal(size=(40, 3)) > 0).astype(np.float32)
    cfg = TrainConfig(batch_size=32, epochs=2, loss="sigmoid_xent")
    tr = Trainer(MLP(features=(8,), num_outputs=3), cfg,
                 mesh=make_mesh(MeshSpec(dp=-1)))
    tr.fit_arrays(x, y)  # 40 % 32 != 0 → exercises pad+mask with [B,K]
    assert np.isfinite(tr.history[-1])


class TestTensorParallel:
    """Round-3: the tp axis is wired — last param dim column-shards and
    GSPMD inserts the collectives (round-2 review finding)."""

    def test_param_shardings_tp_rule(self):
        mesh = make_mesh(MeshSpec(dp=2, tp=4))
        params = {"kernel": np.zeros((8, 16)), "bias": np.zeros((16,)),
                  "odd": np.zeros((8, 5))}
        sh = param_shardings(mesh, params)
        assert "'tp'" in str(sh["kernel"].spec)
        assert str(sh["bias"].spec) == "PartitionSpec()"  # 1-D replicates
        assert str(sh["odd"].spec) == "PartitionSpec()"   # 5 % 4 != 0

    def test_param_shardings_tp_and_fsdp_compose(self):
        mesh = make_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
        sh = param_shardings(mesh, {"k": np.zeros((8, 16))})
        s = str(sh["k"].spec)
        assert "'tp'" in s and "'fsdp'" in s and s.index("fsdp") < s.index(
            "tp")  # fsdp on dim 0, tp on dim 1

    def test_tp_training_matches_dp_numerics(self):
        x, y = xor_data(128)
        losses = {}
        for name, spec in [("dp", MeshSpec(dp=-1)),
                           ("tp", MeshSpec(dp=2, tp=4)),
                           ("dp_fsdp_tp", MeshSpec(dp=2, fsdp=2, tp=2))]:
            cfg = TrainConfig(batch_size=64, epochs=3, log_every=1, seed=7)
            tr = Trainer(MLP(features=(16,), num_outputs=2), cfg,
                         mesh=make_mesh(spec))
            tr.fit_arrays(x, y)
            losses[name] = tr.history
        np.testing.assert_allclose(losses["dp"], losses["tp"],
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(losses["dp"], losses["dp_fsdp_tp"],
                                   rtol=1e-4, atol=1e-5)

    def test_tp_params_actually_sharded(self):
        mesh = make_mesh(MeshSpec(dp=2, tp=4))
        x, y = xor_data(64)
        cfg = TrainConfig(batch_size=32, epochs=1)
        tr = Trainer(MLP(features=(16,), num_outputs=2), cfg, mesh=mesh)
        tr.fit_arrays(x, y)
        leaves = jax.tree_util.tree_leaves(tr.params)
        assert any("tp" in str(l.sharding.spec) for l in leaves
                   if hasattr(l, "sharding"))


class TestSingleDeviceFastPathAndParamDtype:
    def test_single_device_mesh_trains_and_matches_multi(self):
        """The 1-device plain-jit fast path (no NamedSharding machinery)
        must produce the same loss walk as the 8-device dp mesh."""
        from mmlspark_tpu.models.zoo import MLP
        x, y = xor_data(96)
        losses = {}
        for name, spec in [("one", MeshSpec(dp=1)), ("all", MeshSpec(dp=-1))]:
            cfg = TrainConfig(batch_size=32, epochs=2, log_every=1, seed=3)
            tr = Trainer(MLP(features=(16,), num_outputs=2), cfg,
                         mesh=make_mesh(spec))
            tr.fit_arrays(x, y)
            losses[name] = tr.history
        np.testing.assert_allclose(losses["one"], losses["all"],
                                   rtol=1e-4, atol=1e-5)

    def test_single_device_checkpoint_resume(self, tmp_path):
        """Resume must work through the fast path (plain device arrays,
        no NamedSharding) — restore targets carry SingleDeviceShardings."""
        from mmlspark_tpu.models.zoo import MLP
        x, y = xor_data(64)
        cfg = TrainConfig(batch_size=32, epochs=2, log_every=1, seed=1,
                          checkpoint_dir=str(tmp_path / "ck"),
                          checkpoint_every=1, donate_state=False)
        tr = Trainer(MLP(features=(16,), num_outputs=2), cfg,
                     mesh=make_mesh(MeshSpec(dp=1)))
        tr.fit_arrays(x, y)
        full = [np.asarray(l) for l in jax.tree_util.tree_leaves(tr.params)]
        # fresh trainer resumes from the final checkpoint: no extra steps,
        # params identical
        tr2 = Trainer(MLP(features=(16,), num_outputs=2), cfg,
                      mesh=make_mesh(MeshSpec(dp=1)))
        tr2.fit_arrays(x, y)
        for a, b in zip(full,
                        jax.tree_util.tree_leaves(tr2.params)):
            np.testing.assert_array_equal(a, np.asarray(b))

    def test_param_dtype_bfloat16_halves_state_and_trains(self):
        """Master-free bf16 fine-tune: params AND momentum come out
        bfloat16 (the zeros_like inheritance), and the loss still falls."""
        import jax.numpy as jnp

        from mmlspark_tpu.models.zoo import MLP
        x, y = xor_data(96)
        cfg = TrainConfig(batch_size=32, epochs=4, log_every=1, seed=0,
                          optimizer="momentum", learning_rate=5e-2,
                          param_dtype="bfloat16")
        tr = Trainer(MLP(features=(32,), num_outputs=2), cfg)
        tr.fit_arrays(x, y)
        for leaf in jax.tree_util.tree_leaves(tr.params):
            assert leaf.dtype == jnp.bfloat16
        mom_leaves = [l for l in jax.tree_util.tree_leaves(
            tr.state["opt_state"]) if hasattr(l, "dtype") and l.ndim > 0]
        assert any(l.dtype == jnp.bfloat16 for l in mom_leaves)
        assert tr.history[-1] < tr.history[0]
