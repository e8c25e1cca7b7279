"""Static contract checker tier (DESIGN.md §4.13).

Two layers: unit tests of the jaxpr-walk / VMEM-model / report machinery
against *synthetic violations* of every contract class (injected psum in
a TP serving jaxpr, unpinned arena jit, uncovered dispatch shape,
over-VMEM tile, closure-captured megaconstant, f64 widen), and an
integration sweep that builds the real engine matrix + trainer and
asserts the analyzer is green on main modulo the checked-in baseline.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import jaxpr_utils as ju
from repro.analysis import passes, registry, report, verify, vmem
from repro.kernels import autotune, gemm_core, introspect
from repro.launch.mesh import make_tp_mesh
from repro.launch.scheduler import chunk_buckets, chunk_plan, \
    reachable_chunk_shapes
from repro.launch.speculative import pow2_floor, reachable_spec_ks

BASELINE = os.path.join(os.path.dirname(__file__), "..",
                        "analysis_baseline.json")


def _entry(name, fn, args, kind="serving", group="test", expected_out=None,
           static_argnums=(), launches=(), tp=1):
    """A synthetic TracedEntry around `jax.make_jaxpr` output."""
    jaxpr = jax.make_jaxpr(fn, static_argnums=static_argnums)(*args)
    return registry.TracedEntry(
        group=group, name=name, kind=kind, fn=fn, args=tuple(args),
        static_argnums=static_argnums, expected_out=expected_out,
        jaxpr=jaxpr, launches=list(launches), tp=tp)


# --------------------------------------------------- jaxpr walk utilities
def test_walk_finds_psum_inside_shard_map():
    mesh = make_tp_mesh(1)
    f = jax.jit(jax.shard_map(lambda x: jax.lax.psum(x, "model"),
                              mesh=mesh, in_specs=P("model"), out_specs=P()))
    jaxpr = jax.make_jaxpr(f)(jnp.ones((4,)))
    hits = ju.find_prims(jaxpr, passes.REDUCTION_PRIMS)
    assert hits, "walk must descend through jit into the shard_map body"
    assert all(ju.in_shard_map(path) for _, path in hits)
    assert "jit" in hits[0][1]


def test_walk_descends_into_scan():
    def f(x):
        return jax.lax.scan(lambda c, _: (jnp.sin(c), None), x,
                            None, length=3)[0]
    jaxpr = jax.make_jaxpr(jax.jit(f))(jnp.ones((2,)))
    assert ju.prim_counts(jaxpr)["sin"] >= 1
    (eqn, path), = ju.find_prims(jaxpr, {"sin"})
    assert "scan" in path and not ju.in_shard_map(path)


def test_outer_pjit_and_unspecified_out_shardings():
    f = jax.jit(lambda x: x * 2)
    jaxpr = jax.make_jaxpr(f)(jnp.ones((2,)))
    eqn = ju.outer_jit_eqn(jaxpr)
    assert eqn is not None
    outs = ju.out_shardings_of(eqn)
    assert len(outs) == 1 and ju.is_unspecified(outs[0])

    mesh = make_tp_mesh(1)
    sh = NamedSharding(mesh, P())
    g = jax.jit(lambda x: x * 2, out_shardings=sh)
    eqn2 = ju.outer_jit_eqn(jax.make_jaxpr(g)(jnp.ones((2,))))
    outs2 = ju.out_shardings_of(eqn2)
    assert not ju.is_unspecified(outs2[0])
    assert ju.spec_of(outs2[0]) == P()


def test_collect_consts_sees_closure_capture():
    big = np.arange(1_000_000, dtype=np.float32)
    f = jax.jit(lambda x: x + jnp.asarray(big)[:2])
    jaxpr = jax.make_jaxpr(f)(jnp.ones((2,)))
    consts = ju.collect_consts(jaxpr, min_elems=1 << 16)
    assert any(np.size(c) == 1_000_000 for _, c in consts)


# ------------------------------------------------ pass 1: identity audit
def test_identity_flags_injected_psum_in_serving():
    mesh = make_tp_mesh(1)
    f = jax.jit(jax.shard_map(lambda x: jax.lax.psum(x, "model"),
                              mesh=mesh, in_specs=P("model"), out_specs=P()))
    te = _entry("decode", f, (jnp.ones((4,)),), kind="serving")
    findings = passes.audit_identity([te])
    assert findings and findings[0].pass_name == "identity"
    assert any(f.fid.endswith(":psum") or ":psum" in f.fid
               for f in findings)


def test_identity_allows_training_all_gather_in_shard_map_only():
    mesh = make_tp_mesh(1)

    def gather(x):
        return jax.lax.all_gather(x, "model")

    f = jax.jit(jax.shard_map(gather, mesh=mesh, in_specs=P("model"),
                              out_specs=P(None, "model")))
    te_train = _entry("train_step", f, (jnp.ones((4,)),), kind="training")
    assert passes.audit_identity([te_train]) == []
    # the same jaxpr viewed as a serving entry is a violation
    te_serve = _entry("decode", f, (jnp.ones((4,)),), kind="serving")
    assert passes.audit_identity([te_serve])


def test_identity_flags_training_psum_anywhere():
    mesh = make_tp_mesh(1)
    f = jax.jit(jax.shard_map(lambda x: jax.lax.psum(x, "model"),
                              mesh=mesh, in_specs=P("model"), out_specs=P()))
    te = _entry("train_step", f, (jnp.ones((4,)),), kind="training")
    findings = passes.audit_identity([te])
    assert findings and "psum" in findings[0].fid


# -------------------------------------------- pass 2: sharding-pin audit
def test_sharding_audit_flags_unpinned_jit():
    mesh = make_tp_mesh(1)
    want = NamedSharding(mesh, P())
    f = jax.jit(lambda x: x * 2)          # no out_shardings: the old
    te = _entry("insert", f, (jnp.ones((4,)),),  # `_insert` pattern
                expected_out=want)
    findings = passes.audit_sharding_pins([te])
    assert len(findings) == 1
    assert "unpinned" in findings[0].fid


def test_sharding_audit_accepts_pinned_and_flags_mismatch():
    mesh = make_tp_mesh(1)
    want = NamedSharding(mesh, P())
    pinned = jax.jit(lambda x: x * 2, out_shardings=want)
    te = _entry("insert", pinned, (jnp.ones((4,)),), expected_out=want)
    assert passes.audit_sharding_pins([te]) == []

    want_other = NamedSharding(mesh, P("data"))
    te2 = _entry("insert", pinned, (jnp.ones((4,)),),
                 expected_out=want_other)
    findings = passes.audit_sharding_pins([te2])
    assert len(findings) == 1 and "mismatch" in findings[0].fid


# --------------------------------------------- pass 3: compile-set audit
def test_reachable_spec_ks_matches_dispatch_quantizer():
    for draft_k in (1, 3, 4, 7):
        reach = reachable_spec_ks(draft_k, 32)
        assert reach == {pow2_floor(min(draft_k, rem - 1))
                         for rem in range(1, 33)}
        assert all(k == 0 or k & (k - 1) == 0 for k in reach)


def test_reachable_chunk_shapes_covered_by_buckets():
    for chunk in (4, 8, 16):
        reach = reachable_chunk_shapes(64, chunk)
        assert reach <= set(chunk_buckets(chunk))
        # every plan's pieces really are in the reachable set
        for s in (1, 5, 17, 64):
            assert set(chunk_plan(s, chunk)) <= reach


def test_compile_set_flags_uncovered_window(analysis_matrix):
    engines, _ = analysis_matrix
    eng = engines["dense"]
    orig = eng.warmed_window_ks
    # instance-attribute shadow: warmup "forgets" every window above 1
    eng.warmed_window_ks = lambda: [1]
    try:
        findings = [f for f in passes.audit_compile_set({"dense": eng})
                    if f.entry == "decode_window"]
    finally:
        eng.warmed_window_ks = orig
    assert findings, "uncovered pow2 windows must be flagged"
    assert passes.audit_compile_set({"dense": eng}) == []


def test_compile_set_flags_uncovered_chunk_bucket(analysis_matrix,
                                                  monkeypatch):
    engines, _ = analysis_matrix
    eng = engines["chunked"]
    # warmup "forgets" the pow2 remainder buckets: only the full chunk
    monkeypatch.setattr("repro.launch.scheduler.chunk_buckets",
                        lambda c: [c])
    findings = passes.audit_compile_set({"chunked": eng})
    assert any(f.entry == "prefill_chunk" for f in findings)


def test_compile_set_flags_uncovered_spec_k(analysis_matrix):
    engines, _ = analysis_matrix
    eng = engines["speculative"]
    orig = eng._spec_ks
    eng._spec_ks = lambda: [0]
    try:
        findings = passes.audit_compile_set({"speculative": eng})
    finally:
        eng._spec_ks = orig
    assert any(f.entry == "spec" for f in findings)


# ------------------------------------------------- pass 4: VMEM budgeter
def _gemm_launch(blocks, k_pack=1, **kw):
    d = dict(M=1024, N=1024, K=1024, k_pack=k_pack, n_col=0, n_scalar=0,
             ops="", backend="static", blocks=blocks)
    d.update(kw)
    return introspect.GemmLaunch(**d)


def test_vmem_model_flags_oversized_tile():
    small = _gemm_launch((64, 128, 128, 128))
    huge = _gemm_launch((512, 2048, 1024, 1024))
    assert not introspect.over_budget(small)
    assert introspect.over_budget(huge)
    te = _entry("decode", jax.jit(lambda x: x), (jnp.ones((2,)),),
                launches=[small, huge])
    findings = vmem.audit_vmem([te])
    assert len(findings) == 1
    assert "gemm:1024x1024x1024" in findings[0].fid


def test_vmem_packed_tile_counts_decoded_blowup():
    # bits=4 packs 8 codes/word. The packed body decodes one (bkw, bn)
    # field plane at a time: the model counts that plane (an int32 plane
    # and its copy in x's dtype) beside the streamed blocks, and no
    # decoded (bk, bn) f32 tile.
    for x_itemsize in (2, 4):
        plan = gemm_core.plan_blocks(256, 256, 768, k_pack=8,
                                     blocks=(64, 128, 128),
                                     x_itemsize=x_itemsize)
        bm, bn, bk, bkw = plan
        assert bk % 8 == 0 and bkw == bk // 8
        packed = _gemm_launch(plan, k_pack=8, x_itemsize=x_itemsize)
        streamed = 2 * (bm * bk * x_itemsize + bkw * bn * 4) + 2 * bm * bn * 4
        plane = bkw * bn * (4 + x_itemsize)
        assert introspect.gemm_vmem_bytes(packed) == streamed + plane
        assert plane < bk * bn * 4


def test_autotune_rejects_oversized_candidates():
    fits, rejected = autotune.vmem_filter(
        [(64, 128, 128), (512, 2048, 2048)], 1024, 2048, 2048)
    assert (64, 128, 128) in fits
    assert rejected and all(v > introspect.VMEM_BUDGET_BYTES
                            for v in rejected.values())

    x = jnp.ones((16, 32), jnp.float32)
    w = jnp.ones((32, 128), jnp.float32)
    with pytest.raises(ValueError, match="VMEM budget"):
        autotune.autotune_gemm(x, w, backend="pallas-interpret",
                               vmem_budget=1)


# --------------------------------- pass 5: constants / dtype-widen audit
def test_constants_audit_flags_megaconstant():
    big = np.zeros(1_000_000, dtype=np.float32)
    f = jax.jit(lambda x: x + jnp.asarray(big)[:2])
    te = _entry("prefill", f, (jnp.ones((2,)),))
    findings = passes.audit_constants([te])
    assert len(findings) == 1
    assert "const-1000000" in findings[0].fid
    # same trace under the default threshold=tiny consts: clean
    g = jax.jit(lambda x: x + 1.0)
    assert passes.audit_constants(
        [_entry("prefill", g, (jnp.ones((2,)),))]) == []


def test_constants_audit_flags_f64_widen():
    with jax.enable_x64(True):
        f = jax.jit(lambda x: x.astype(jnp.float64).sum())
        te = _entry("decode", f, (jnp.ones((2,), jnp.float32),))
    findings = passes.audit_constants([te])
    assert any("f64-widen" in f.fid for f in findings)


# --------------------------------------------- report / baseline contract
def test_report_is_deterministic_and_timestamp_free():
    f1 = report.make_finding("vmem", "dense", "decode", "slug", "msg",
                             detail={"bytes": 1})
    f2 = report.make_finding("identity", "train", "train_step", "psum",
                             "msg2")
    base = {f1.fid: "known"}
    cfg = {"devices": 1, "groups": ["dense"]}
    a = report.dumps(report.make_report([f1, f2], base, cfg))
    b = report.dumps(report.make_report([f2, f1], base, cfg))
    assert a == b, "report must not depend on finding discovery order"
    loaded = json.loads(a)
    assert loaded["new"] == [f2.fid]
    assert loaded["suppressed"] == [f1.fid]
    assert not any("time" in k or "date" in k for k in loaded)


def test_baseline_roundtrip(tmp_path):
    f1 = report.make_finding("vmem", "dense", "decode", "slug", "msg")
    path = str(tmp_path / "b.json")
    report.save_baseline([f1], path, reason="why")
    base = report.load_baseline(path)
    assert base == {f1.fid: "why"}
    new, sup = report.split_findings([f1], base)
    assert new == [] and sup == [f1]
    assert report.load_baseline(str(tmp_path / "missing.json")) == {}


# ------------------------------------------------------- integration/CLI
@pytest.fixture(scope="module")
def analysis_matrix():
    return registry.build_serving()


def test_engine_matrix_entry_coverage(analysis_matrix):
    engines, traced = analysis_matrix
    names = {t.key for t in traced}
    assert {"dense:prefill", "dense:insert", "dense:decode",
            "dense:decode_window", "paged:decode_paged",
            "speculative:spec", "chunked:prefill_chunk"} <= names
    # every serving entry that returns sharded state declares its contract
    for t in traced:
        if t.name.startswith(("insert", "prefill")):
            assert t.expected_out is not None, t.key


def test_analyzer_green_on_main(analysis_matrix):
    engines, traced = analysis_matrix
    traced = list(traced) + [registry.build_training()]
    findings = passes.run_all(engines, traced)
    base = report.load_baseline(BASELINE)
    new, _ = report.split_findings(findings, base)
    assert new == [], [f.fid for f in new]


def test_insert_is_pinned_on_every_group(analysis_matrix):
    """The satellite fix: arena-returning jits pin out_shardings (the old
    `_insert` relied on operand propagation and must never come back)."""
    _, traced = analysis_matrix
    checked = 0
    for t in traced:
        if not t.name.startswith("insert"):
            continue
        eqn = ju.outer_jit_eqn(t.jaxpr)
        assert eqn is not None, t.key
        outs = ju.out_shardings_of(eqn)
        assert outs and not any(ju.is_unspecified(s) for s in outs), t.key
        checked += 1
    assert checked >= 4     # contiguous+paged arenas, target+draft


def test_cli_exit_codes(monkeypatch):
    rc = verify.main(["--configs", "dense", "--no-train",
                      "--fail-on-new", "--baseline", BASELINE])
    assert rc == 0

    bad = report.make_finding("identity", "dense", "decode", "psum", "x")
    monkeypatch.setattr(passes, "run_all",
                        lambda *a, **k: [bad])
    assert verify.main(["--configs", "dense", "--no-train",
                        "--baseline", BASELINE]) == 0
    assert verify.main(["--configs", "dense", "--no-train",
                        "--fail-on-new", "--baseline", BASELINE]) == 1


def test_cli_update_baseline(tmp_path, monkeypatch):
    bad = report.make_finding("identity", "dense", "decode", "psum", "x")
    monkeypatch.setattr(passes, "run_all", lambda *a, **k: [bad])
    path = str(tmp_path / "base.json")
    assert verify.main(["--configs", "dense", "--no-train",
                        "--baseline", path, "--update-baseline"]) == 0
    assert verify.main(["--configs", "dense", "--no-train",
                        "--fail-on-new", "--baseline", path]) == 0
