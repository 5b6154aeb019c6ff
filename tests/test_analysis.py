"""Tier-1 gate for `repro.analysis`: the passes prove the repo clean, the
adversarial fixture corpus proves the passes can still see, and the
defects this PR fixed stay fixed (each with the pre-fix code preserved as
a fixture the pass must flag).
"""
import ast
import dataclasses
import pathlib
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import __main__ as analysis_cli
from repro.analysis import kernelcheck, tracelint
from repro.analysis.findings import (RULES, Finding, Suppression,
                                     apply_suppressions, load_suppressions)

FIXTURES = pathlib.Path(__file__).parent / "analysis_fixtures"
sys.path.insert(0, str(FIXTURES))

import broken_specs  # noqa: E402
import routing_broken  # noqa: E402


# ---------------------------------------------------------------------------
# the acceptance gate: strict run is clean, coverage is total
# ---------------------------------------------------------------------------

def test_strict_run_is_clean(analysis_results):
    r = analysis_results
    assert not r["malformed"], [f.render() for f in r["malformed"]]
    assert not r["active"], [f.render() for f in r["active"]]
    assert not r["stale"], [f.render() for f in r["stale"]]
    # suppressions exist and every one is live (matched a real finding)
    assert r["suppressed"], "expected justified suppressed findings"


def test_every_pallas_kernel_covered(analysis_results):
    cov = analysis_results["coverage"]
    # every kernel module with a pallas_call is in the capture set
    assert set(cov["kernelcheck.kernel_modules"]) == {
        "src/repro/kernels/acam_attention.py",
        "src/repro/kernels/acam_lut.py",
        "src/repro/kernels/acam_mvm.py",
        "src/repro/kernels/acam_softmax.py",
    }
    assert cov["kernelcheck.spec_sites"] >= 26
    # scalar operands are whole-array SMEM specs, with no index map
    assert cov["kernelcheck.index_map_sites"] >= 18
    assert cov["kernelcheck.frontier_domains"] >= 60
    assert cov["kernelcheck.grid_points"] >= 200


def test_probe_matrix_spans_required_domains():
    names = [p.name for p in kernelcheck._probes()]
    fams = {p.name: p for p in kernelcheck._probes()}
    # scalar AND per-group-vector kv_len, paged AND contiguous, gqa,
    # chunked prefill with mask, one-tile degenerate grid
    assert any("scalar" in n for n in names)
    assert any("rows" in n for n in names)
    assert any("onetile" in n for n in names)
    assert sum(1 for p in fams.values() if p.paged) >= 3
    assert any("gqa_paged" in n for n in names)
    assert any("chunk" in n and fams[n].paged for n in names)
    scalar = next(p for p in fams.values() if "scalar" in p.name)
    assert not scalar.kv_vector
    rows = next(p for p in fams.values() if "rows" in p.name)
    assert rows.kv_vector


def test_dispatch_audit_confirms_totality(analysis_results):
    cov = analysis_results["coverage"]
    assert cov["plan_audit.unreachable"] == []
    assert cov["plan_audit.backends"] >= 20
    assert cov["plan_audit.plans_resolved"] == (
        cov["plan_audit.models"] * cov["plan_audit.exec_configs"])
    assert not any(f.rule.startswith("PA")
                   for f in analysis_results["findings"])


def test_cli_strict_exit_codes(analysis_results, monkeypatch, capsys):
    r = analysis_results
    monkeypatch.setattr(
        analysis_cli, "run_all",
        lambda: (r["findings"], r["coverage"], r["contracts"]))
    assert analysis_cli.main(["--strict"]) == 0
    out = capsys.readouterr().out
    assert "analysis: CLEAN" in out
    # an unsuppressed finding must flip strict to exit 1 (and only strict)
    bad = Finding("kernelcheck", "KC101", "src/x.py", 1, "s", "boom")
    monkeypatch.setattr(
        analysis_cli, "run_all",
        lambda: (r["findings"] + [bad], r["coverage"], r["contracts"]))
    assert analysis_cli.main(["--strict"]) == 1
    assert analysis_cli.main([]) == 0


# ---------------------------------------------------------------------------
# adversarial fixtures: every planted violation must be flagged
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fixture", broken_specs.ALL,
                         ids=lambda f: f.__name__)
def test_kernelcheck_flags_broken_fixture(fixture):
    probe, call, expected_rule = fixture()
    findings, _ = kernelcheck.analyze_call(probe, call)
    rules = {f.rule for f in findings}
    assert expected_rule in rules, (
        f"{fixture.__name__}: expected {expected_rule}, got "
        f"{[f.render() for f in findings] or 'nothing'}")


def test_kernelcheck_fixture_rules_span_the_ruleset():
    expected = {f()[2] for f in broken_specs.ALL}
    assert expected >= {"KC101", "KC102", "KC104", "KC105", "KC106",
                        "KC109"}


def test_write_fence_flags_prefix_routing():
    # the exact routing shipped before this PR, preserved as a fixture
    f_chunk = kernelcheck.check_write_fence(
        route_chunk=routing_broken.chunk_targets_unfenced)
    assert any(x.rule == "KC107" and "chunk" in x.site for x in f_chunk)
    f_dec = kernelcheck.check_write_fence(
        route_decode=routing_broken.decode_targets_unfenced)
    assert any(x.rule == "KC107" and "decode" in x.site for x in f_dec)


def test_write_fence_passes_fixed_routing():
    assert kernelcheck.check_write_fence() == []


def test_allocator_never_issues_trash_page():
    assert kernelcheck.check_allocator() == []


def test_tracelint_flags_tainted_fixture():
    src = (FIXTURES / "tainted_trace.py").read_text()
    findings, stats = tracelint.lint_source(src, "tainted_trace.py",
                                            in_kernels=True)
    by_site = {}
    for f in findings:
        by_site.setdefault(f.site, set()).add(f.rule)
    assert "TL101" in by_site.get("branch_on_traced", set())
    assert "TL101" in by_site.get("while_on_traced", set())
    assert "TL101" in by_site.get("_tainted_kernel", set())
    assert "TL102" in by_site.get("concretize_int", set())
    assert "TL102" in by_site.get("concretize_item", set())
    assert "clean_static_branches" not in by_site, by_site
    assert stats["traced_scopes"] >= 6


def test_tracelint_flags_broken_cache_key():
    src = (FIXTURES / "tainted_trace.py").read_text()
    tree = ast.parse(src)
    cls = next(n for n in ast.walk(tree) if isinstance(n, ast.ClassDef)
               and n.name == "BrokenCacheKey")
    findings = tracelint._lint_cache_key_class(cls, "tainted_trace.py")
    msgs = [f.message for f in findings]
    assert all(f.rule == "TL104" for f in findings)
    assert any("unhashable" in m for m in msgs)              # tags: list
    assert any("hash(self.noise)" in m for m in msgs)        # opaque noise
    assert any("op_overrides" in f.site and "canonicalize" in f.message
               for f in findings)                            # order
    assert len(findings) == 3


def test_tracelint_accepts_fixed_execconfig():
    # the shipped ExecConfig (post __post_init__ guards) must lint clean
    findings, stats = tracelint.run()
    assert "ExecConfig" in stats["cache_key_classes"]
    assert not [f for f in findings if f.rule == "TL104"], \
        [f.render() for f in findings]


# ---------------------------------------------------------------------------
# regression tests for the defects the passes surfaced (satellite a)
# ---------------------------------------------------------------------------

def test_paged_write_overflow_routes_to_trash_page():
    """Pre-fix: a slot filled past block-table capacity wrote into its own
    last live page (silent corruption); fixed routing fences to page 0."""
    from repro.models.layers import (paged_write_targets_chunk,
                                     paged_write_targets_decode)
    ps, mp = 4, 2
    bt = jnp.asarray([[3, 5]], jnp.int32)
    cap = ps * mp

    # decode one past capacity: fenced -> trash, unfenced -> last live page
    pages, _ = paged_write_targets_decode(bt, jnp.asarray([cap + 1]), ps)
    assert int(pages[0]) == 0
    old_pages, _ = routing_broken.decode_targets_unfenced(
        bt, jnp.asarray([cap + 1]), ps)
    assert int(old_pages[0]) == 5      # the corruption the fence prevents

    # chunk write straddling capacity: overflow columns -> trash only
    pages, slots = paged_write_targets_chunk(
        bt, jnp.asarray([cap + 2]), jnp.asarray([cap - 1]), 4, ps)
    assert pages.tolist() == [[5, 0, 0, 0]]
    old_pages, _ = routing_broken.chunk_targets_unfenced(
        bt, jnp.asarray([cap + 2]), jnp.asarray([cap - 1]), 4, ps)
    # cols cap..cap+1 are "live" pre-fix and clamp into live page 5
    assert old_pages.tolist() == [[5, 5, 5, 0]]

    # in-capacity behavior identical to the pre-fix routing
    lens, offs = jnp.asarray([6]), jnp.asarray([3])
    new = paged_write_targets_chunk(bt, lens, offs, 4, ps)
    old = routing_broken.chunk_targets_unfenced(bt, lens, offs, 4, ps)
    assert np.array_equal(new[0], old[0]) and np.array_equal(new[1], old[1])


def test_execconfig_overrides_are_order_canonical():
    """Pre-fix: permuted op_overrides minted distinct plan-cache keys."""
    from repro.configs.base import ExecConfig
    a = ExecConfig(op_overrides=(("softmax", "digital"),
                                 ("lm_head", "raceit_q8")))
    b = ExecConfig(op_overrides=(("lm_head", "raceit_q8"),
                                 ("softmax", "digital")))
    assert a == b and hash(a) == hash(b)
    # later pins win on duplicate slots, matching with_ops semantics
    c = ExecConfig(op_overrides=(("lm_head", "digital"),
                                 ("lm_head", "raceit_q8")))
    assert dict(c.op_overrides) == {"lm_head": "raceit_q8"}
    assert a == a.with_ops(lm_head="raceit_q8")


def test_execconfig_rejects_unhashable_noise():
    """Pre-fix: an unhashable noise value exploded at first resolve_plan
    deep inside dispatch; now it fails fast at construction."""
    from repro.configs.base import ExecConfig
    with pytest.raises(TypeError, match="noise must be hashable"):
        ExecConfig(noise={"sigma": 0.1})
    from repro.hw.noise import NoiseConfig
    ExecConfig(noise=NoiseConfig.preset("nominal"))   # frozen: fine


# ---------------------------------------------------------------------------
# suppression hygiene
# ---------------------------------------------------------------------------

def test_stale_suppression_is_a_finding(tmp_path):
    f = Finding("kernelcheck", "KC101", "src/a.py", 3, "site", "msg")
    live = Suppression("KC101", "src/a.py", "site", "why", 1)
    stale = Suppression("KC102", "src/b.py", "gone", "why", 2)
    active, suppressed, stale_out = apply_suppressions([f], [live, stale])
    assert active == [] and suppressed == [f]
    assert len(stale_out) == 1 and stale_out[0].rule == "SUP001"
    assert stale_out[0].line == 2


def test_malformed_and_unknown_rule_suppressions(tmp_path):
    p = tmp_path / "sups.txt"
    p.write_text("# comment\n"
                 "KC101 | src/a.py | frag | justified\n"
                 "not enough fields\n"
                 "NOPE99 | src/a.py | frag | why\n"
                 "KC101 | src/a.py | frag |\n")
    sups, bad = load_suppressions(p)
    assert len(sups) == 1
    assert len(bad) == 3 and all(f.rule == "SUP002" for f in bad)


def test_rule_registry_is_closed():
    assert all(r in RULES for r in
               ("KC101", "KC107", "TL101", "TL104", "PA101", "SUP001"))
    # every committed suppression names a known rule and parses clean
    sups, bad = load_suppressions()
    assert not bad and sups, "committed suppression file must parse clean"


# ---------------------------------------------------------------------------
# interval/symbolic domain unit checks (the proof substrate)
# ---------------------------------------------------------------------------

def test_interval_arithmetic_soundness():
    from repro.analysis.intervals import Iv
    assert (Iv(0, 5) + 3) == Iv(3, 8)
    assert (Iv(2, 7) - Iv(1, 2)) == Iv(0, 6)
    assert (Iv(-2, 3) * 4) == Iv(-8, 12)
    assert (Iv(5, 13) // 4) == Iv(1, 3)
    assert (Iv(5, 7) % 4) == Iv(1, 3)        # same quotient: exact
    assert (Iv(3, 9) % 4) == Iv(0, 3)        # quotient straddles: widen
    assert Iv.min2(Iv(0, 9), 4) == Iv(0, 4)
    assert Iv.max2(Iv(0, 9), Iv(-1, 2)) == Iv(0, 9)
    with pytest.raises(ValueError):
        Iv(0, 4) // Iv(1, 2)                 # non-constant divisor


def test_symbolic_fixed_point_equality():
    from repro.analysis.intervals import Sym
    a = Sym.var(("bt", 0, 3)) * 2 + 1
    b = Sym.var(("bt", 0, 3)) * 2 + 1
    c = Sym.var(("bt", 0, 4)) * 2 + 1
    assert a == b
    assert a != c                            # different table cell read
