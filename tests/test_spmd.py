"""The symbolic SPMD verifier (analysis/spmd.py + analysis/collectives.py).

Three tiers of evidence, all on the 8-virtual-device CPU mesh:

* **predictions = observations** — the verifier's collective schedule
  for every ``parallel/`` entry point, a fused plan segment, and the
  Trainer's jitted step on the MULTICHIP dryrun meshes (dp×pp pipelined
  ViT, dp×ep MoE tagger — the configs MULTICHIP_r05.json trains) equals
  the StableHLO collective ops of the actually-lowered program;
* **the pre-fix implementations are flagged** — fixtures reproducing
  the two seed-failing bugs (per-source-shard MoE capacity slots; the
  trace-time-stacked pipeline params fed to shard_map unpinned) draw
  SPMD104 / SPMD103 findings, while the fixed modules verify clean;
* **each rule fires on its fixture** — SPMD101–SPMD203 semantic checks
  and the JX201–JX204 AST lint rules, with clean counterparts.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

from jax.sharding import PartitionSpec as P  # noqa: E402

from mmlspark_tpu.analysis.collectives import (  # noqa: E402
    check_fence_discipline, compare_schedules, extract_schedule,
    lowered_collective_counts,
)
from mmlspark_tpu.analysis.spmd import (  # noqa: E402
    ENTRY_POINTS, ShardState, audit_plan_spmd, check_divisibility,
    verify_entry_point, verify_function,
)
from mmlspark_tpu.parallel.mesh import MeshSpec, make_mesh  # noqa: E402

shard_map = jax.shard_map

from lint_jax import lint_source  # noqa: E402


# ---- predictions = observations: the parallel layer ----

EXPECTED_SCHEDULES = {
    # (kind, axes) sequences — the declared collective contract of each
    # parallel module; a change here is a change to the wire protocol
    "moe_apply": [("all_gather", ("ep",)), ("psum_scatter", ("ep",)),
                  ("all_gather", ("ep",)),
                  ("psum", ("dp", "fsdp", "ep")),
                  ("psum", ("dp", "fsdp", "ep")),
                  ("psum", ("dp", "fsdp", "ep"))],
    "pipeline_apply": [("ppermute", ("pp",)), ("psum", ("pp",))],
    "ring_attention": [("ppermute", ("sp",))] * 9,
    "ulysses_attention": [("all_to_all", ("sp",))] * 3
                         + [("all_gather", ("sp",)),
                            ("all_to_all", ("sp",))],
    # the sharded serve entries: a DP replica's segment and the
    # GSPMD-tp-sharded segment are manual-collective-FREE by contract
    # (XLA-inserted resharding only); the pipelined pp serve segment
    # speaks the pipeline_apply wire protocol over pp alone
    "serve_dp_replica": [],
    "serve_tp_segment": [],
    "serve_pp_segment": [("ppermute", ("pp",)), ("psum", ("pp",))],
    # the int8w+bf16 quantized serve segments: the precision pass
    # (dequant + activation casts) is pure elementwise math — it must
    # introduce NO collectives on a replica nor under GSPMD-tp
    "serve_int8w_replica": [],
    "serve_int8w_tp": [],
    # the continuous-batching decode step: a DP replica owns its slot
    # table and KV cache, so its token loop is manual-collective-free —
    # a collective here would lockstep independent replicas' decodes
    "serve_decode_replica": [],
}

# shard_map sites per entry point: 1 for every manual-collective module,
# 0 for the GSPMD-only serve segments (no shard_map at all)
EXPECTED_SITES = {"serve_dp_replica": 0, "serve_tp_segment": 0,
                  "serve_int8w_replica": 0, "serve_int8w_tp": 0,
                  "serve_decode_replica": 0}


@pytest.mark.parametrize("ep", ENTRY_POINTS, ids=lambda e: e.name)
def test_entry_point_verifies_clean_and_matches_lowered_program(ep):
    report = verify_entry_point(ep)
    assert report.findings == [], "\n".join(str(f) for f in
                                            report.findings)
    assert len(report.sites) == EXPECTED_SITES.get(ep.name, 1)
    got = [(op.kind, op.axes) for op in report.schedule.ops]
    assert got == EXPECTED_SCHEDULES[ep.name], got
    # the contract: the module communicates only over its declared axes
    assert report.schedule.axes_used() <= set(ep.expect_axes)
    # predicted = observed: the jaxpr schedule equals the StableHLO
    # collectives of the lowered program, op for op
    mesh = make_mesh(ep.mesh_spec)
    fn, args = ep.build(mesh)
    observed = lowered_collective_counts(jax.jit(fn).lower(*args).as_text())
    assert report.schedule.stablehlo_counts() == observed


def test_cross_host_agreement_of_entry_point_schedules():
    """Two independent traces of the same entry point must fingerprint
    identically — the property that keeps multi-host processes in
    collective lockstep."""
    for ep in ENTRY_POINTS:
        a = verify_entry_point(ep).schedule
        b = verify_entry_point(ep).schedule
        assert compare_schedules(a, b, ep.name) == []


# ---- predictions = observations: the fused plan segment ----

def _canonical_pipeline():
    from perf_smoke import canonical_pipeline
    return canonical_pipeline()


def test_fused_plan_segment_is_collective_free_and_dp_divisible():
    from mmlspark_tpu.core import plan

    pm, table, n, minibatch = _canonical_pipeline()
    audit = audit_plan_spmd(pm.stages,
                            lambda col: plan._entry_meta(table, col),
                            n_rows=n)
    assert audit.ok, audit.format()
    assert len(audit.segments) == 1
    seg = audit.segments[0]
    assert seg.stages == ["ImageTransformer", "UnrollImage", "JaxModel"]
    assert seg.schedule.ops == []          # inference: XLA-inserted only
    assert seg.minibatches == -(-n // minibatch)
    assert seg.entry_state.dims[0] == ("dp", "fsdp")
    # observed: the segment's composite lowers with zero manual
    # collectives too
    pseg = plan.collect_segment(pm.stages, 0,
                                lambda col: plan._entry_meta(table, col))
    fn, dev_params, _target, _dp = plan._compile_segment(pseg)
    entry = jax.ShapeDtypeStruct(
        (16,) + tuple(pseg.entry_meta.shape), pseg.entry_meta.dtype)
    low = fn.lower(dev_params, entry).as_text()
    assert lowered_collective_counts(low) == {}


def test_lone_model_stage_audits_as_one_segment():
    """Serving dispatches even a single JaxModel through the fused path
    (transform_async, min_stages=1), so the multi-chip audit must cover
    a one-stage plan instead of silently reporting zero segments."""
    from mmlspark_tpu.core import plan
    from mmlspark_tpu.data.table import DataTable
    from mmlspark_tpu.models.jax_model import JaxModel
    from mmlspark_tpu.models.zoo import get_model

    jm = JaxModel(model=get_model("ConvNet_CIFAR10", widths=(8, 16),
                                  dense_width=32),
                  input_col="image", output_col="scores")
    table = DataTable({"image": [np.zeros(32 * 32 * 3, np.float32)]})
    audit = audit_plan_spmd([jm],
                            lambda col: plan._entry_meta(table, col),
                            n_rows=48)
    assert len(audit.segments) == 1, audit.format()
    assert audit.ok and audit.segments[0].schedule.ops == []


def test_stateful_decode_audit_pins_donation_safety():
    """audit_stateful_spmd on the REAL continuous-batching decode build
    (the same program serve/generate.py jits with donate_argnums=(0,)):
    collective-free AND donation-safe — the returned KV-cache subtree
    matches the input leaf-for-leaf, so XLA aliases the buffers in
    place. A step that shrinks the cache draws SPMD106: donation would
    silently degrade to a full cache copy per token."""
    from mmlspark_tpu.analysis.spmd import (audit_stateful_spmd,
                                            serve_decode_build)

    step, args = serve_decode_build(None)
    bufs, rest = args[0], args[1:]
    report = audit_stateful_spmd(step, bufs, rest, name="decode_step")
    assert report.findings == [], "\n".join(str(f) for f in
                                            report.findings)
    assert report.schedule.ops == []

    def shrinking(state, *a):
        new_state, nxt = step(state, *a)
        return {"k": new_state["k"][:2], "v": new_state["v"]}, nxt

    bad = audit_stateful_spmd(shrinking, bufs, rest, name="shrunk")
    assert [f.code for f in bad.findings] == ["SPMD106"]
    assert "donated" in bad.findings[0].message


# ---- predictions = observations: Trainer steps on the dryrun meshes ----

def _step_args(tr, input_shape, y_dtype=jnp.int64):
    state = tr.init_state(input_shape)
    bs = tr.cfg.batch_size
    return (state,
            jax.ShapeDtypeStruct((bs,) + tuple(input_shape), jnp.float32),
            jax.ShapeDtypeStruct((bs,), y_dtype),
            jax.ShapeDtypeStruct((bs,), jnp.float32))


def test_trainer_dp_pp_step_verifies_and_matches_lowered_program():
    """The dp×pp pipelined ViT step (the MULTICHIP_r05 dryrun config):
    clean under the verifier — including the commit_replicated pin on
    the trace-stacked layer params — with schedule = lowered program."""
    from mmlspark_tpu.models.vit import ViT
    from mmlspark_tpu.train.loop import TrainConfig, Trainer

    module = ViT(num_classes=4, patch=8, dim=32, depth=4, heads=4,
                 mlp_dim=64, dtype=jnp.float32, pipeline_microbatches=4)
    tr = Trainer(module, TrainConfig(batch_size=16,
                                     mesh_spec={"dp": 2, "pp": 4}))
    args = _step_args(tr, (16, 16, 3))
    report = verify_function(tr.step_masked, *args, name="vit_dp_pp_step")
    assert report.findings == [], "\n".join(str(f) for f in
                                            report.findings)
    assert len(report.sites) == 2          # forward + its transpose
    counts = report.schedule.counts()
    assert counts["ppermute"] == 2         # fwd ring + reversed bwd ring
    observed = lowered_collective_counts(
        tr.step_masked.lower(*args).as_text())
    assert report.schedule.stablehlo_counts() == observed
    # two traces agree — the multi-host lockstep pin
    again = verify_function(tr.step_masked, *args, name="vit_dp_pp_step")
    assert compare_schedules(report.schedule, again.schedule) == []


def test_trainer_dp_ep_step_verifies_and_matches_lowered_program():
    """The dp×ep MoE tagger step (the MULTICHIP_r05 dryrun config):
    clean — including the capacity-dispatch count-exchange rule the old
    per-shard slot arithmetic violates — with schedule = lowered."""
    from mmlspark_tpu.models.sequence import TransformerTagger
    from mmlspark_tpu.train.loop import TrainConfig, Trainer

    module = TransformerTagger(vocab_size=64, embed_dim=16, num_heads=2,
                               num_layers=1, mlp_dim=32, num_tags=4,
                               max_len=16, moe_experts=4, pad_token_id=0,
                               dtype=jnp.float32)
    tr = Trainer(module, TrainConfig(batch_size=16,
                                     mesh_spec={"dp": 2, "ep": 2}))
    state = tr.init_state((16,))
    args = (state, jax.ShapeDtypeStruct((16, 16), jnp.int32),
            jax.ShapeDtypeStruct((16, 16), jnp.int64),
            jax.ShapeDtypeStruct((16,), jnp.float32))
    report = verify_function(tr.step_masked, *args, name="tagger_dp_ep",
                             capacity_dispatch=True)
    assert report.findings == [], "\n".join(str(f) for f in
                                            report.findings)
    kinds = {op.kind for op in report.schedule.ops}
    assert {"all_gather", "psum_scatter"} <= kinds
    observed = lowered_collective_counts(
        tr.step_masked.lower(*args).as_text())
    assert report.schedule.stablehlo_counts() == observed


# ---- the pre-fix implementations are statically flagged ----

def _old_moe_body_fn(mesh):
    """The pre-fix MoE dispatch: capacity slots from a LOCAL cumsum,
    all_to_all regrouping, no cross-shard count exchange — a token's
    survival depended on which shard its padding landed on."""
    E, C, ep = 8, 2, mesh.shape["ep"]

    def body(p, xl):
        d = xl.shape[-1]
        onehot = jax.nn.one_hot(jnp.argmax(xl @ p["gate"], -1), E,
                                dtype=jnp.int32)
        pos = (jnp.cumsum(onehot, axis=0) - onehot) * onehot
        keep = (jnp.sum(pos, axis=-1) < C).astype(jnp.float32)
        slots = jnp.einsum("ne,nd->ed",
                           onehot.astype(jnp.float32) * keep[:, None], xl)
        slots = jax.lax.all_to_all(
            slots[:, None, :].reshape(ep, E // ep, d), "ep",
            split_axis=0, concat_axis=0, tiled=False)
        return jnp.broadcast_to(slots.reshape(E, d).sum(0), xl.shape)

    def fn(p, xs):
        return shard_map(body, mesh=mesh,
                         in_specs=({"gate": P()}, P(("dp", "fsdp", "ep"))),
                         out_specs=P(("dp", "fsdp", "ep")),
                         check_vma=False)(p, xs)

    return fn


def test_pre_fix_moe_capacity_is_flagged_fixed_is_clean():
    mesh = make_mesh(MeshSpec(dp=1, ep=4))
    fn = _old_moe_body_fn(mesh)
    p = {"gate": jax.ShapeDtypeStruct((16, 8), jnp.float32)}
    xs = jax.ShapeDtypeStruct((64, 16), jnp.float32)
    report = verify_function(fn, p, xs, name="old_moe",
                             capacity_dispatch=True)
    codes = [f.code for f in report.findings]
    assert "SPMD104" in codes, codes
    assert "count exchange" in \
        next(f for f in report.findings if f.code == "SPMD104").message
    # the fixed module's dispatch passes the same rule (entry-point test
    # asserts zero findings with capacity_dispatch=True)
    fixed = verify_entry_point(ENTRY_POINTS[0])   # moe_apply
    assert fixed.findings == []


def test_pre_fix_pipeline_stacking_is_flagged_fixed_is_clean():
    """The dp×pp seed bug: layer params stacked at trace time and fed to
    shard_map with dp unmentioned in their in_spec hit the GSPMD
    full-to-shard edge (each shard sees dp-extent × the true value).
    The verifier flags the unpinned operand; the fixed pipeline_apply
    (commit_replicated) verifies clean."""
    mesh = make_mesh(MeshSpec(dp=2, pp=4))

    def old_pipeline(per_layer, x):
        stacked = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls),
                                         *per_layer)

        def body(st, xl):
            def blk(h, layer):
                return h + jnp.tanh(h @ layer["w"]), None
            h, _ = jax.lax.scan(blk, xl, st)
            h = jnp.where(jax.lax.axis_index("pp") == 3, h, 0.0)
            return jax.lax.psum(h, "pp")

        return shard_map(body, mesh=mesh,
                         in_specs=(P("pp"), P(None, ("dp",))),
                         out_specs=P(None, ("dp",)),
                         check_vma=False)(stacked, x)

    layers = [{"w": jax.ShapeDtypeStruct((16, 16), jnp.float32)}
              for _ in range(8)]
    x = jax.ShapeDtypeStruct((4, 16, 16), jnp.float32)
    report = verify_function(old_pipeline, layers, x, name="old_pipeline")
    codes = [f.code for f in report.findings]
    assert codes == ["SPMD103"], codes
    assert "UNREDUCED PARTIAL SUM" in report.findings[0].message
    # the fixed pipeline_apply — same trace-time stacking, now pinned —
    # is clean (ENTRY_POINTS builds it exactly that way)
    fixed = verify_entry_point(ENTRY_POINTS[1])   # pipeline_apply
    assert fixed.findings == []


# ---- each semantic rule fires on its fixture ----

@pytest.fixture(scope="module")
def mesh_dp_pp():
    return make_mesh(MeshSpec(dp=2, pp=4))


def test_spmd201_collective_under_data_dependent_cond(mesh_dp_pp):
    def fn(x, pred):
        def body(v, pr):
            return jax.lax.cond(pr[0] > 0,
                                lambda u: jax.lax.psum(u, "pp"),
                                lambda u: u, v)
        return shard_map(body, mesh=mesh_dp_pp, in_specs=(P(), P()),
                         out_specs=P(), check_vma=False)(x, pred)

    report = verify_function(fn, jax.ShapeDtypeStruct((4,), jnp.float32),
                             jax.ShapeDtypeStruct((1,), jnp.int32),
                             name="cond_coll")
    assert [f.code for f in report.findings] == ["SPMD201"]
    op = report.schedule.conditional_ops()[0]
    assert op.kind == "psum"
    assert any(c.startswith("cond.branch") for c in op.context)


def test_spmd202_divergent_schedules(mesh_dp_pp):
    def mk(coll):
        def fn(x):
            return shard_map(lambda v: coll(v, "pp"), mesh=mesh_dp_pp,
                             in_specs=(P(),), out_specs=P(),
                             check_vma=False)(x)
        return fn

    x = jax.ShapeDtypeStruct((4,), jnp.float32)
    a = extract_schedule(mk(jax.lax.psum), x)
    b = extract_schedule(mk(jax.lax.pmax), x)
    assert [f.code for f in compare_schedules(a, b)] == ["SPMD202"]
    assert compare_schedules(a, a) == []


def test_spmd203_fence_discipline():
    bad = ("def run(loader, blocks):\n"
           "    for block in blocks:\n"
           "        counts = multihost_utils.process_allgather(block)\n"
           "        step(counts)\n")
    assert [f.code for f in check_fence_discipline(bad)] == ["SPMD203"]
    good = ("def run(loader, blocks):\n"
            "    for block in blocks:\n"
            "        loader.drain_barrier()\n"
            "        counts = multihost_utils.process_allgather(block)\n"
            "        step(counts)\n")
    assert check_fence_discipline(good) == []


def test_spmd103_partial_sum_escape_from_body(mesh_dp_pp):
    """The replication-claim check check_vma=False turns off, done
    statically: an output varying over dp escaping as replicated."""
    def fn(x):
        def body(xl):
            return xl.sum(0, keepdims=True) \
                * (jax.lax.axis_index("dp") + 1)
        return shard_map(body, mesh=mesh_dp_pp, in_specs=(P(("dp",)),),
                         out_specs=P(), check_vma=False)(x)

    report = verify_function(fn, jax.ShapeDtypeStruct((8,), jnp.float32),
                             name="escape")
    assert [f.code for f in report.findings] == ["SPMD103"]
    # the out state reports the partial axes
    assert report.sites[0].out_states[0].partial == frozenset({"dp"})
    # reducing before returning clears it
    def fixed(x):
        def body(xl):
            return jax.lax.psum(xl.sum(0, keepdims=True), "dp")
        return shard_map(body, mesh=mesh_dp_pp, in_specs=(P(("dp",)),),
                         out_specs=P(), check_vma=False)(x)

    assert verify_function(fixed, jax.ShapeDtypeStruct((8,), jnp.float32),
                           name="fixed").findings == []


def test_spmd101_contract_violation(mesh_dp_pp):
    def fn(x):
        return shard_map(lambda v: jax.lax.psum(v, "dp"),
                         mesh=mesh_dp_pp, in_specs=(P(),), out_specs=P(),
                         check_vma=False)(x)

    report = verify_function(fn, jax.ShapeDtypeStruct((4,), jnp.float32),
                             name="contract", expect_axes=("pp",))
    assert [f.code for f in report.findings] == ["SPMD101"]


def test_spmd104_divisibility():
    state = ShardState((("ep",), ()))
    finds = check_divisibility(state, (10, 3), {"ep": 4}, "x")
    assert [f.code for f in finds] == ["SPMD104"]
    assert check_divisibility(state, (12, 3), {"ep": 4}, "x") == []


def test_obs_counters_register_through_the_substrate(mesh_dp_pp):
    """Verification work records through mmlspark_tpu/obs — the one
    telemetry substrate — when tracing is on, and not otherwise."""
    from mmlspark_tpu import obs
    from mmlspark_tpu.obs.metrics import registry

    def fn(x):
        return shard_map(lambda v: jax.lax.psum(v, "pp"),
                         mesh=mesh_dp_pp, in_specs=(P(),), out_specs=P(),
                         check_vma=False)(x)

    x = jax.ShapeDtypeStruct((4,), jnp.float32)
    registry().reset()
    obs.enable()
    try:
        verify_function(fn, x, name="probe")
        counters = registry().snapshot()["counters"]
        spans = [s.name for s in obs.captured()]
    finally:
        obs.disable()
        obs.clear()
        registry().reset()
    assert counters.get("analysis.spmd.functions_verified") == 1
    assert counters.get("analysis.spmd.findings", 0) == 0
    assert "spmd/verify" in spans


# ---- the JX201–JX204 lint rules: fixture modules ----

FIXTURE_JX201 = '''
import jax

def step(v, pred):
    def reduce_all(u):
        return jax.lax.psum(u, "pp")
    def keep(u):
        return u
    return jax.lax.cond(pred, reduce_all, keep, v)
'''

FIXTURE_JX202 = '''
import jax

def body(v):
    i = jax.lax.axis_index("batch")
    return jax.lax.psum(v, "model") + i
'''

FIXTURE_JX203 = '''
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P

def apply(params, x, mesh):
    def body(p, xl):
        return (xl @ p).sum(0, keepdims=True)
    return jax.shard_map(body, mesh=mesh, in_specs=(P("pp"), P(None, ("dp",))),
                     out_specs=P(None, ("dp",)), check_vma=False)(params, x)
'''

FIXTURE_JX204 = '''
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P

def dispatch(params, x, mesh):
    def body(p, xl):
        onehot = jax.nn.one_hot(jnp.argmax(xl @ p, -1), 8, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - onehot
        slots = jnp.einsum("ne,nd->ed", onehot.astype(jnp.float32), xl)
        slots = jax.lax.all_to_all(slots.reshape(4, 2, -1), "ep", 0, 0)
        return slots.reshape(xl.shape[0], -1) + pos.sum()
    return jax.shard_map(body, mesh=mesh, in_specs=(P(), P(("ep",))),
                     out_specs=P(("ep",)), check_vma=False)(params, x)
'''


def test_jx201_collective_in_cond_branch():
    assert [f.rule for f in lint_source(FIXTURE_JX201)] == ["JX201"]
    clean = FIXTURE_JX201.replace(
        "return jax.lax.cond(pred, reduce_all, keep, v)",
        "return jax.lax.psum(jax.lax.cond(pred, keep, keep, v), \"pp\")")
    assert [f.rule for f in lint_source(clean)] == []


def test_jx202_non_canonical_axis_names():
    findings = lint_source(FIXTURE_JX202)
    assert [f.rule for f in findings] == ["JX202", "JX202"]
    canon = FIXTURE_JX202.replace('"batch"', '"dp"').replace(
        '"model"', '"tp"')
    assert lint_source(canon) == []


def test_jx203_unreduced_axis_escape():
    findings = lint_source(FIXTURE_JX203)
    assert [f.rule for f in findings] == ["JX203"]
    assert "'pp'" in findings[0].message
    fixed = FIXTURE_JX203.replace(
        "return (xl @ p).sum(0, keepdims=True)",
        "return jax.lax.psum((xl @ p).sum(0, keepdims=True), \"pp\")")
    assert lint_source(fixed) == []


def test_jx204_per_shard_capacity_cumsum():
    findings = lint_source(FIXTURE_JX204)
    assert [f.rule for f in findings] == ["JX204"]
    fixed = FIXTURE_JX204.replace(
        "pos = jnp.cumsum(onehot, axis=0) - onehot",
        "counts = jax.lax.all_gather(onehot.sum(0), \"ep\")\n"
        "        pos = jnp.cumsum(onehot, axis=0) - onehot + counts.sum()")
    assert lint_source(fixed) == []


def test_jx2xx_pragma_suppresses():
    src = FIXTURE_JX202.replace(
        'i = jax.lax.axis_index("batch")',
        'i = jax.lax.axis_index("batch")  # lint-jax: allow(JX202)')
    assert [f.rule for f in lint_source(src)] == ["JX202"]  # the psum one


def test_parallel_modules_pass_their_own_lint():
    """The real (fixed) parallel sources pass JX201–JX204 — the moe fix
    is exactly what turns JX204 off (all_gather of the routed counts)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for mod in ("moe", "pipeline", "ring_attention", "mesh"):
        path = os.path.join(repo, "mmlspark_tpu", "parallel", f"{mod}.py")
        with open(path, "r", encoding="utf-8") as fh:
            src = fh.read()
        findings = [f for f in lint_source(src, path)
                    if f.rule.startswith("JX2")]
        assert findings == [], findings
