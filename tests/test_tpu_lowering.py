"""What tier-1 can check about the chip path without a chip.

* **Cross-lowering** — every Pallas kernel in the tree, and the
  ``generate.decode`` program the token engine runs, lowered for
  ``platforms=["tpu"]`` through ``jax.export`` at the shapes
  ``chip_smoke.py`` uses on the chip. This runs the Pallas→Mosaic
  lowering on the CPU box, so a block-spec refusal or a primitive with no
  TPU lowering rule fails here; it does NOT run the Mosaic compiler
  proper (only the chip does — ``chip_smoke.py``'s kernels phase).
* **No fallback** — ``chip_smoke.py`` exits non-zero, with the reason and
  no result line, when JAX finds no TPU and when the repo is not next to
  it.
* **The compile cache is placed from outside** —
  ``place_compilation_cache`` leaves ``JAX_COMPILATION_CACHE_DIR`` alone
  when it is set and otherwise picks the one fixed in-checkout directory.
* **One process per chip** — the multi-process tiers refuse, on a TPU
  host, what they cannot honour on real chips (decided from device files;
  the host is faked here).
"""

import functools
import os
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp
from jax import export

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402 — the shapes under test are the smoke's own

from mmlspark_tpu.ops.group_norm import group_norm  # noqa: E402
from mmlspark_tpu.ops.pallas import attention as fa  # noqa: E402
from mmlspark_tpu.ops.pallas.causal_conv import causal_conv  # noqa: E402
from mmlspark_tpu.ops.pallas.gated_delta import gated_delta  # noqa: E402
from mmlspark_tpu.obs.metrics import registry  # noqa: E402
from mmlspark_tpu.ops.pallas.budget import FALLBACK_COUNTER  # noqa: E402
from mmlspark_tpu.ops.pallas.moe_combine import (  # noqa: E402
    COMBINE_GAUGE, moe_combine)
from mmlspark_tpu.ops.pallas.selective_scan import selective_scan  # noqa: E402
from mmlspark_tpu.ops.pallas.ssd_scan import ssd_scan  # noqa: E402
from mmlspark_tpu.parallel.moe import moe_dropless  # noqa: E402

S = jax.ShapeDtypeStruct
BF16, F32 = jnp.bfloat16, jnp.float32


def lower_for_tpu(fn, *specs) -> str:
    """StableHLO text of ``fn`` exported for the TPU platform."""
    return export.export(jax.jit(fn), platforms=["tpu"])(*specs).mlir_module()


# ---- every kernel, at the smoke's shapes ----

def _moe_layers(x, router, gate, up, down):
    def layer(h, i):
        y, _, bucket = moe_dropless(
            h, router, {"gate": gate, "up": up, "down": down}, top_k=4,
            impl="gmm", layer=i)
        return (h + y).astype(h.dtype), bucket
    return jax.lax.scan(layer, x, jnp.arange(gate.shape[0]))


KERNEL_CASES = {
    "decode_attention": (
        lambda q, k, v, m: fa.decode_attention(q, k, v, kv_mask=m,
                                               impl="pallas"),
        (S((8, 8, 64), BF16), S((8, 8, 1024, 64), BF16),
         S((8, 8, 1024, 64), BF16), S((8, 1024), jnp.bool_))),
    "flash_attention_vit_t197": (
        lambda q, k, v: fa.flash_attention(q, k, v, impl="pallas"),
        (S((64, 12, 197, 64), BF16),) * 3),
    "flash_attention_causal_t1024": (
        lambda q, k, v, m: fa.flash_attention(q, k, v, kv_mask=m,
                                              causal=True, impl="pallas"),
        (S((2, 8, 1024, 64), BF16),) * 3 + (S((2, 1024), jnp.bool_),)),
    "attention_block_update": (
        lambda q, k, v, keep, m, d, a: fa.attention_block_update(
            q, k, v, keep, m, d, a, 0.125, impl="pallas"),
        (S((2, 8, 512, 64), F32),) * 3 + (S((2, 512, 512), jnp.bool_),)
        + (S((2, 8, 512, 1), F32),) * 2 + (S((2, 8, 512, 64), F32),)),
    # the language-model cell's window: past the whole-tile VMEM bound, so
    # the tiled kernel (a grid step a query tile over keys resident in
    # VMEM, its key blocks a loop: none above the diagonal, no mask below)
    "flash_attention_tiled_lm_t4096": (
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                           impl="pallas"),
        (S((2, 32, 4096, 128), BF16),) * 3),
    # the same kernel where keys can be masked: a key row, a window that is
    # not a whole number of tiles (2100 keys pad to five), every tile pair
    # of the triangle general
    "flash_attention_tiled_masked_t2100": (
        lambda q, k, v, m: fa.flash_attention(q, k, v, kv_mask=m,
                                              causal=True, impl="pallas"),
        (S((2, 8, 2100, 128), BF16),) * 3 + (S((2, 2100), jnp.bool_),)),
    # the dropless expert layer's grouped products, reading one layer of
    # the stacked expert weights in place
    "moe_dropless_grouped_products": (
        lambda x, r, g, u, d: moe_dropless(
            x, r, {"gate": g, "up": u, "down": d}, top_k=4, impl="gmm",
            layer=1)[0],
        (S((8192, 4096), BF16), S((4096, 128), F32),
         S((2, 32, 4096, 2048), BF16), S((2, 32, 4096, 2048), BF16),
         S((2, 32, 2048, 4096), BF16))),
    # the same layer as the model runs it: under the layer scan, where its
    # row-count ladder (14080 padded rows, twice and four times that at
    # these shapes) puts the three grouped products of each rung in a branch
    # of a conditional
    "moe_dropless_ladder_under_the_layer_scan": (
        _moe_layers,
        (S((8192, 4096), BF16), S((4096, 128), F32),
         S((2, 32, 4096, 2048), BF16), S((2, 32, 4096, 2048), BF16),
         S((2, 32, 2048, 4096), BF16))),
    # the conv / grouped-query family's cell: 32 query heads over 8 shared
    # key/value heads of 64 at 8192 positions (K and V are read by the
    # kernel's index map, never repeated), and its three grouped products
    # at d 2048, f 1792: a width that is 14 lane rows and no power of two
    "flash_attention_tiled_grouped_heads_t8192": (
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                           impl="pallas"),
        (S((2, 32, 8192, 64), BF16), S((2, 8, 8192, 64), BF16),
         S((2, 8, 8192, 64), BF16))),
    "moe_dropless_grouped_products_f1792": (
        lambda x, r, b, g, u, d: moe_dropless(
            x, r, {"gate": g, "up": u, "down": d}, top_k=4, impl="gmm",
            layer=1, score="sigmoid", bias=b, norm_eps=1e-6)[0],
        (S((16384, 2048), BF16), S((2048, 32), F32), S((32,), F32),
         S((2, 32, 2048, 1792), BF16), S((2, 32, 2048, 1792), BF16),
         S((2, 32, 1792, 2048), BF16))),
    # the state-space family's cell: one row of 16,384 positions, 5,120
    # channels (five blocks of 1,024), 16 states; 64 chunks of 256 carry the
    # state in VMEM scratch, B and C arrive as scalars in SMEM
    "selective_scan_t16384_d5120_n16": (
        lambda u, dt, a, b, c, d, z: selective_scan(u, dt, a, b, c, d, z,
                                                    impl="pallas"),
        (S((1, 16384, 5120), BF16), S((1, 16384, 5120), F32),
         S((5120, 16), F32), S((1, 16384, 16), F32), S((1, 16384, 16), F32),
         S((5120,), F32), S((1, 16384, 5120), BF16))),
    # the short causal convolution of both families' cells, handed the wide
    # float32 product and the channel each part starts at: the Mamba
    # mixer's (4 taps, bias, SiLU, the gate cast beside it) and the gated
    # one (3 taps, [B | C | u])
    "causal_conv_t16384_c5120_of_10240": (
        lambda uz, w, b: causal_conv(uz, w, channels=5120, cast_at=5120,
                                     bias=b, silu=True, dtype=BF16,
                                     impl="pallas"),
        (S((1, 16384, 10240), F32), S((4, 5120), BF16), S((5120,), F32))),
    "causal_conv_t8192_c2048_of_6144": (
        lambda bcu, w: causal_conv(bcu, w, channels=2048, at=4096, pre_at=0,
                                   post_at=2048, dtype=BF16, impl="pallas"),
        (S((2, 8192, 6144), F32), S((3, 2048), BF16))),
    # the Mamba-2 family's cell: one row of 16,384 positions, 64 heads of 64
    # in 8 groups, state 128; [x | B | C] read where they lie in the
    # convolved [1, 16384, 6144], 16 blocks of 8 chunks carry a group's
    # state in VMEM scratch; and its two UNGATED grouped products at d 2688,
    # f 1856: a width that is 14.5 lane rows, taken whole
    "ssd_scan_t16384_h64_p64_g8_n128": (
        lambda xbc, dt, a, d: ssd_scan(xbc, dt, a, d, heads=64, head_dim=64,
                                       groups=8, state=128, impl="pallas"),
        (S((1, 16384, 6144), BF16), S((1, 16384, 64), F32), S((64,), F32),
         S((64,), F32))),
    "moe_dropless_ungated_products_f1856": (
        lambda x, r, b, u, d: moe_dropless(
            x, r, {"up": u, "down": d}, top_k=6, impl="gmm", layer=1,
            scaling=2.5, score="sigmoid", bias=b, norm_eps=1e-20)[0],
        (S((16384, 2688), BF16), S((2688, 128), F32), S((128,), F32),
         S((2, 64, 2688, 1856), BF16), S((2, 64, 1856, 2688), BF16))),
    # the expert combine at the Nemotron cell's first rung: each token's
    # held picks of six read from the 354 MiB sorted buffer where it lies
    "moe_combine_ys69120_d2688_k6": (
        lambda ys, back, expert, w: moe_combine(ys, back, expert, w,
                                                held=64, impl="pallas"),
        (S((69120, 2688), BF16), S((16384, 6), jnp.int32),
         S((16384, 6), jnp.int32), S((16384, 6), F32))),
    # and at the Qwen3-Next cell's first rung (bucket_ladder(163840, 64,
    # 512)): picks of ten, about one in eight held, so a group of tokens
    # runs to the most picks one of its tokens holds, not to ten
    "moe_combine_ys31744_d2048_k10": (
        lambda ys, back, expert, w: moe_combine(ys, back, expert, w,
                                                held=64, impl="pallas"),
        (S((31744, 2048), BF16), S((16384, 10), jnp.int32),
         S((16384, 10), jnp.int32), S((16384, 10), F32))),
    # the Gated DeltaNet family's cell: two rows of 8192 positions, 16 key
    # heads of 128 on 32 value heads of 128; [q_j | k_j | v of j's value
    # heads] read where they lie in the convolved [2, 8192, 8192], blocks of
    # 1,024 positions in chunks of 64 carry two value heads' states in VMEM
    # scratch; the convolution that makes that array from the key-head
    # grouped [q | k | v] product; and its attention,
    # 16 query heads on 2 key/value heads of 256 (the widest head the tiled
    # kernel has met)
    "gated_delta_t8192_hk16_hv32_d128": (
        lambda qkv, g, b: gated_delta(qkv, g, b, key_heads=16, key_dim=128,
                                      value_heads=32, value_dim=128,
                                      impl="pallas"),
        (S((2, 8192, 8192), BF16), S((2, 8192, 32), F32),
         S((2, 8192, 32), F32))),
    "causal_conv_qkv_t8192_c8192": (
        lambda qkv, w: causal_conv(qkv, w, channels=8192, silu=True,
                                   dtype=BF16, impl="pallas"),
        (S((2, 8192, 8192), F32), S((4, 8192), BF16))),
    "flash_attention_tiled_hd256_t8192": (
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                           impl="pallas"),
        (S((2, 16, 8192, 256), BF16), S((2, 2, 8192, 256), BF16),
         S((2, 2, 8192, 256), BF16))),
    "group_norm_56x56x256": (
        lambda x, s, b: group_norm(x, s, b, 32, relu=True),
        (S((8, 56, 56, 256), BF16), S((256,), F32), S((256,), F32))),
    # spatial sizes that are not sublane multiples (14, 7): the wrapper
    # hands the kernel [N, H*W, C], so none needs an in-kernel relayout
    "group_norm_7x7x2048": (
        lambda x, s, b: group_norm(x, s, b, 32, relu=True),
        (S((8, 7, 7, 2048), BF16), S((2048,), F32), S((2048,), F32))),
}


@pytest.mark.parametrize("name", sorted(KERNEL_CASES))
def test_kernel_lowers_for_tpu(name, monkeypatch):
    fn, specs = KERNEL_CASES[name]
    if name.startswith("moe_dropless"):
        # beside gmm the combine resolves by the backend: the chip's here
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fallbacks = registry().counter(FALLBACK_COUNTER, kernel="moe_combine")
    fallen = fallbacks.value
    text = lower_for_tpu(fn, *specs)
    # the kernel itself is in the program — not its XLA reference
    assert "tpu_custom_call" in text
    if name == "moe_dropless_ladder_under_the_layer_scan":
        # the rungs' grouped products: two kernels in all (gate and up
        # have one signature and share a function; a higher rung is worked
        # a first rung at a time and shares the first's kernels); and the
        # combine once a rung (three): its buffer's row count is the rung's
        assert "stablehlo.case" in text and "stablehlo.while" in text
        assert text.count("stablehlo.custom_call @tpu_custom_call") == 5
    if name == "moe_dropless_grouped_products_f1792":
        # every expert held: one rung, so no conditional; gate and up share
        # a kernel, down has its own, and the combine is the third
        assert "stablehlo.case" not in text
        assert text.count("stablehlo.custom_call @tpu_custom_call") == 3
    if name == "selective_scan_t16384_d5120_n16":
        # the operands go in as they are: no padded or re-laid copy of a
        # [1, 16384, 5120] array, nothing of shape [L, 5120, 16]
        assert "stablehlo.pad" not in text
        assert "16384x5120x16" not in text
    if name == "ssd_scan_t16384_h64_p64_g8_n128":
        # the wide operand goes in as it is, three times: no part of it is
        # cut out or padded to feed the call
        assert "stablehlo.slice" not in text and "stablehlo.pad" not in text
        assert text.count("stablehlo.custom_call @tpu_custom_call") == 1
    if name == "moe_dropless_ungated_products_f1856":
        # half the experts held: a ladder of two rungs, so a conditional;
        # each rung's two products (no third: the experts have no gate),
        # the higher rung sharing the first's kernels; and a combine a rung
        assert "stablehlo.case" in text
        assert text.count("stablehlo.custom_call @tpu_custom_call") == 4
    if name.startswith("moe_combine_ys"):
        # one kernel, handed the buffer as it is: what touches the buffer
        # is ONE call into the jitted entry and the kernel itself (no
        # slice, pad, copy or gather of it), and no fallback was taken
        buffer = {"moe_combine_ys69120_d2688_k6": "69120x2688",
                  "moe_combine_ys31744_d2048_k10": "31744x2048"}[name]
        assert text.count("stablehlo.custom_call @tpu_custom_call") == 1
        uses = [line.split("=", 1)[1].split("(")[0].strip()
                for line in text.splitlines()
                if buffer in line and line.lstrip().startswith("%")]
        assert sorted(uses) == ["call @_combine_call",
                                "stablehlo.custom_call @tpu_custom_call"]
        assert registry().gauge(COMBINE_GAUGE).value == 1
        assert fallbacks.value == fallen
    if name == "gated_delta_t8192_hk16_hv32_d128":
        # the wide operand goes in as it is, three times, and nothing of
        # the value heads' width is repeated to the key heads' count
        assert text.count("stablehlo.custom_call @tpu_custom_call") == 1
        assert "tensor<2x8192x8192xbf16>, tensor<2x8192x8192xbf16>, " \
            "tensor<2x8192x8192xbf16>" in text
        assert "stablehlo.pad" not in text
    if name == "flash_attention_tiled_hd256_t8192":
        assert "tensor<2x2x8192x256xbf16>" in text
    if name.startswith("causal_conv"):
        # the parts are read where they lie: no slice of the wide product
        # is cut out (or padded) to feed the call
        assert "stablehlo.slice" not in text
        assert "stablehlo.pad" not in text
    if name == "flash_attention_tiled_grouped_heads_t8192":
        # the kernel's K and V operands keep their 8 heads
        assert "tensor<2x8x8192x64xbf16>" in text
        assert "tensor<2x32x8192x64xbf16>, tensor<2x32x8192x64xbf16>, " \
            "tensor<2x32x8192x64xbf16>" not in text


def test_no_kernel_wrapper_chooses_interpret_mode():
    """The wrappers leave ``interpret`` to the caller: nothing under
    ``ops/`` may decide it from the backend (the fallback that let a
    kernel the compiler refuses look like a working one)."""
    ops = os.path.join(REPO, "mmlspark_tpu", "ops")
    offenders = []
    for root, _dirs, files in os.walk(ops):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f), encoding="utf-8") as fh:
                    for n, line in enumerate(fh, 1):
                        if "interpret=" in line.replace(" ", ""):
                            offenders.append(f"{f}:{n}: {line.strip()}")
    assert offenders == []


# ---- the decode program of the token engine ----

def test_generate_decode_program_lowers_for_tpu_with_the_kernel():
    from mmlspark_tpu.models.sequence import TransformerTagger
    from mmlspark_tpu.serve.generate import build_decode_step

    g = chip_smoke.GEN
    model = TransformerTagger(
        vocab_size=g["vocab"], embed_dim=g["embed"], num_heads=g["heads"],
        num_layers=g["layers"], mlp_dim=g["mlp"], num_tags=g["vocab"],
        max_len=g["t_max"], causal=True)
    # abstract params (nothing initializes), in the smoke's bf16
    params = jax.tree_util.tree_map(
        lambda a: S(a.shape, BF16 if jnp.issubdtype(a.dtype, jnp.floating)
                    else a.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0),
                       S((1, 8), jnp.int32))["params"])
    slots = g["slots"]
    cache = S((slots, g["layers"], g["heads"], g["t_max"],
               g["embed"] // g["heads"]), BF16)
    i32, flag = S((slots,), jnp.int32), S((slots,), jnp.bool_)
    # on the TPU backend the default decode_attention resolves
    # impl="auto" to the kernel; the export runs on the CPU backend, so
    # the same choice is spelled out
    step = build_decode_step(model, functools.partial(
        fa.decode_attention, impl="pallas"))
    text = lower_for_tpu(step, {"k": cache, "v": cache}, params,
                         i32, i32, flag, i32, flag)
    assert text.count("tpu_custom_call") >= g["layers"]


# ---- chip_smoke.py never falls back ----

def _run_smoke(cwd, script, env_extra):
    env = dict(os.environ)
    env.update(env_extra)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_fails_on_the_cpu_with_the_reason():
    res = _run_smoke(REPO, "chip_smoke.py", {"JAX_PLATFORMS": "cpu"})
    assert res.returncode != 0
    assert "platform=cpu" in res.stdout
    assert "no TPU" in res.stderr
    assert '"ok"' not in res.stdout      # no result line


def test_chip_smoke_fails_outside_a_checkout(tmp_path):
    import shutil
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    res = _run_smoke(str(tmp_path), "chip_smoke.py", {})
    assert res.returncode != 0
    assert "the repo is not here" in res.stderr
    assert res.stdout == ""


# ---- the compile cache is placed from outside ----

@pytest.fixture()
def restore_cache_config():
    """place_compilation_cache edits process-wide jax config; put it back
    so the rest of the suite does not start writing a cache."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    before = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in before.items():
        jax.config.update(n, v)


def test_cache_helper_leaves_a_set_env_var_alone(monkeypatch, tmp_path,
                                                 restore_cache_config):
    from mmlspark_tpu.utils import jit_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(jit_cache.ENV_VAR, str(tmp_path / "outside"))
    monkeypatch.setenv(jit_cache.MIN_COMPILE_TIME_ENV_VAR, "2.5")
    threshold = jax.config.jax_persistent_cache_min_compile_time_secs
    assert jit_cache.place_compilation_cache() == str(tmp_path / "outside")
    # jax reads both variables itself (at import); the helper set nothing
    assert jax.config.jax_compilation_cache_dir == before
    assert jax.config.jax_persistent_cache_min_compile_time_secs == threshold


def test_cache_helper_picks_the_fixed_in_checkout_dir(monkeypatch,
                                                      restore_cache_config):
    from mmlspark_tpu.utils import jit_cache
    monkeypatch.delenv(jit_cache.ENV_VAR, raising=False)
    monkeypatch.delenv(jit_cache.MIN_COMPILE_TIME_ENV_VAR, raising=False)
    assert jit_cache.place_compilation_cache() == jit_cache.DEFAULT_DIR
    assert jax.config.jax_compilation_cache_dir == jit_cache.DEFAULT_DIR
    # every program is written, not only those over jax's 1 s default
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    # fixed, inside the checkout, and git-ignored
    assert jit_cache.DEFAULT_DIR == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore"), encoding="utf-8") as fh:
        assert ".jax_cache/" in fh.read().split()


# ---- one process per chip: the multi-process tiers on a TPU host ----

@pytest.fixture()
def tpu_host(monkeypatch):
    """Make this box look like a one-chip TPU host whose children would
    bring up the TPU backend (device file present, JAX_PLATFORMS not
    pinned to cpu in the child environment)."""
    from mmlspark_tpu.utils import env
    monkeypatch.setattr(env, "tpu_chips_on_host", lambda: ["/dev/vfio/1"])
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")


def test_children_reach_tpu_reads_device_files_and_the_platform_pin(
        monkeypatch):
    from mmlspark_tpu.utils import env
    monkeypatch.setattr(env, "tpu_chips_on_host", lambda: ["/dev/vfio/1"])
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert env.children_reach_tpu()
    assert env.children_reach_tpu({"JAX_PLATFORMS": "tpu,cpu"})
    assert not env.children_reach_tpu({"JAX_PLATFORMS": "cpu"})
    # the parent's own pin is inherited; extra_env overrides it
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert not env.children_reach_tpu()
    assert env.children_reach_tpu({"JAX_PLATFORMS": "tpu"})
    monkeypatch.setattr(env, "tpu_chips_on_host", lambda: [])
    assert not env.children_reach_tpu({"JAX_PLATFORMS": "tpu"})


def test_train_supervisor_refuses_virtual_devices_and_multi_worker(
        tpu_host, tmp_path):
    from mmlspark_tpu.train.service import (
        ServiceConfig, Topology, TrainSupervisor,
    )

    def cfg(topo, **kw):
        return ServiceConfig(cmd=["true"], service_dir=str(tmp_path / "s"),
                             topologies=(topo,), **kw)

    with pytest.raises(ValueError, match="virtual CPU"):
        TrainSupervisor(cfg(Topology(world=1, devices=4)))
    with pytest.raises(ValueError, match="one process per chip"):
        TrainSupervisor(cfg(Topology(world=2)))
    TrainSupervisor(cfg(Topology(world=1)))           # runs on the chips
    # the explicit rehearsal: children pinned to the CPU
    TrainSupervisor(cfg(Topology(world=2, devices=4),
                        extra_env={"JAX_PLATFORMS": "cpu"}))


def test_serve_supervisor_refuses_a_second_backend(tpu_host, tmp_path):
    from mmlspark_tpu.serve.fleet import FleetConfig, ServeSupervisor

    sup = ServeSupervisor(FleetConfig(service_dir=str(tmp_path / "f"),
                                      initial_backends=2))
    with pytest.raises(RuntimeError, match="one process per chip"):
        sup.start()
    sup.close()
    assert sup._chip_refusal() is not None
    rehearsal = ServeSupervisor(FleetConfig(
        service_dir=str(tmp_path / "g"), initial_backends=2,
        extra_env={"JAX_PLATFORMS": "cpu"}))
    assert rehearsal._chip_refusal() is None
    rehearsal.close()


def test_launch_refuses_local_multi_process(tpu_host):
    from mmlspark_tpu.tools.launch import launch_local

    with pytest.raises(ValueError, match="claim its chips"):
        launch_local(["true"], num_processes=2)
