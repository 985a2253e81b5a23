"""reprolint: fixture positives/negatives per checker, repo self-run vs the
committed baseline, baseline staleness, CLI exit codes, and the fast jaxpr
harness check (the full serve/train cache-reuse harness runs in the CI
``lint-invariants`` lane)."""
from pathlib import Path

import pytest

from repro.analysis import apply_baseline, load_baseline, run_checks
from repro.analysis.__main__ import main
from repro.analysis.baseline import BaselineEntry, save_baseline
from repro.analysis.core import REGISTRY, Finding

FIXTURES = Path(__file__).parent / "analysis_fixtures"


def codes(findings):
    return {f.code for f in findings}


class TestFixtures:
    """Positive + negative pair per checker: each violation class fires, and
    the sanctioned idioms stay silent."""

    @pytest.mark.parametrize(
        "name, expected",
        [
            ("bad_retrace.py", {"RT101", "RT102", "RT103", "RT104", "RT105", "RT106"}),
            ("bad_retrace_spec.py", {"RT101", "RT102"}),
            ("bad_hostdevice_host.py", {"HD201"}),
            ("bad_hostdevice_device.py", {"HD202"}),
            # pragma-free on purpose: the repro/router/ path segment alone
            # must pin the host role (HOST_PREFIXES)
            ("repro/router/bad_hostdevice_router.py", {"HD201"}),
            ("bad_donation.py", {"DN301", "DN302"}),
            ("bad_pallas.py", {"PL401", "PL402", "PL403", "PL404"}),
        ],
    )
    def test_positive_fixture_fires_exactly(self, name, expected):
        assert codes(run_checks(paths=[FIXTURES / name])) == expected

    @pytest.mark.parametrize(
        "name",
        [
            "good_retrace.py",
            "good_retrace_spec.py",
            "good_hostdevice.py",
            "repro/router/good_hostdevice_router.py",
            "good_donation.py",
            "good_pallas.py",
        ],
    )
    def test_negative_fixture_is_clean(self, name):
        assert run_checks(paths=[FIXTURES / name]) == []

    def test_router_package_resolves_to_host_role(self):
        # the shipped router modules themselves, not just the fixtures: every
        # file under src/repro/router/ is host-scoped by path, no pragma needed
        from repro.analysis.core import SourceModule
        from repro.analysis.hostdevice import _module_role

        import repro.router

        pkg = Path(repro.router.__file__).parent
        files = sorted(pkg.glob("*.py"))
        assert files, "router package has no modules?"
        for p in files:
            mod = SourceModule.load(p, pkg.parents[2])
            assert _module_role(mod) == "host", p.name

    def test_tau_as_python_value_caught_statically(self):
        # the acceptance-criterion fixture: a tau that is a static Python
        # value (static_argnames + literal call) is flagged without running jax
        fs = run_checks(paths=[FIXTURES / "bad_retrace.py"])
        assert any(f.code == "RT101" and "'tau'" in f.message for f in fs)
        assert any(f.code == "RT102" and "'tau'" in f.message for f in fs)

    def test_inline_suppression(self, tmp_path):
        bad = (FIXTURES / "bad_pallas.py").read_text().replace(
            "interpret=True,  # PL404",
            "interpret=True,  # reprolint: disable=PL404",
        )
        p = tmp_path / "suppressed.py"
        p.write_text(bad)
        assert "PL404" not in codes(run_checks(paths=[p]))

    @pytest.mark.parametrize(
        "value, flagged",
        [("resolve_interpret(interpret)", False), ("resolve_interpret(True)", True),
         ("resolve_interpret(not on_tpu())", True)],
    )
    def test_platform_resolver_routes_only_policy_values(self, tmp_path, value, flagged):
        src = (FIXTURES / "bad_pallas.py").read_text().replace(
            "interpret=True,  # PL404", f"interpret={value},  # PL404"
        )
        p = tmp_path / "resolver.py"
        p.write_text(src)
        assert ("PL404" in codes(run_checks(paths=[p]))) is flagged


class TestSelfRun:
    def test_repo_clean_against_committed_baseline(self):
        new, stale = apply_baseline(run_checks(), load_baseline())
        assert new == [], "\n".join(f.format() for f in new)
        assert stale == [], "\n".join(e.format() for e in stale)

    def test_all_four_checkers_registered(self):
        run_checks(paths=[FIXTURES / "good_retrace.py"])  # force registration
        assert {"retrace", "hostdevice", "donation", "pallas"} <= set(REGISTRY)


class TestBaseline:
    def test_stale_entry_detected(self):
        # a suppression for a finding that no longer fires must surface
        entry = BaselineEntry(
            code="PL404", path="src/repro/kernels/gone.py",
            message="ancient finding", reason="fixed long ago",
        )
        new, stale = apply_baseline([], [entry])
        assert new == [] and stale == [entry]

    def test_matching_entry_suppresses(self):
        f = Finding("PL404", "src/x.py", 3, "msg")
        entry = BaselineEntry(code="PL404", path="src/x.py", message="msg", reason="known")
        new, stale = apply_baseline([f], [entry])
        assert new == [] and stale == []

    def test_reason_is_mandatory(self, tmp_path):
        p = tmp_path / "baseline.json"
        p.write_text('{"suppressions": [{"code": "X", "path": "p", "message": "m"}]}')
        with pytest.raises(ValueError, match="reason"):
            load_baseline(p)

    def test_save_load_roundtrip(self, tmp_path):
        f = Finding("RT101", "src/a.py", 1, "knob")
        p = save_baseline([f], tmp_path / "b.json")
        (entry,) = load_baseline(p)
        assert entry.key == f.key


class TestCLI:
    def test_strict_clean_on_repo_static(self):
        assert main(["--no-harness", "--strict"]) == 0

    def test_strict_fails_on_each_violation_class(self):
        for bad in sorted(FIXTURES.rglob("bad_*.py")):
            assert main(["--strict", "--paths", str(bad)]) == 1, bad.name

    def test_nonstrict_reports_without_failing(self):
        assert main(["--paths", str(FIXTURES / "bad_pallas.py")]) == 0

    def test_report_artifact(self, tmp_path):
        import json

        report = tmp_path / "findings.json"
        main(["--paths", str(FIXTURES / "bad_donation.py"), "--report", str(report)])
        data = json.loads(report.read_text())
        assert data["clean"] is False
        assert {f["code"] for f in data["findings"]} == {"DN301", "DN302"}

    def test_stale_baseline_fails_strict(self, tmp_path):
        stale = tmp_path / "stale.json"
        save_baseline([Finding("ZZ999", "src/never.py", 1, "gone")], stale)
        rc = main(["--strict", "--no-harness", "--baseline", str(stale),
                   "--paths", str(FIXTURES / "good_retrace.py")])
        assert rc == 1


class TestHarness:
    def test_taus_are_jaxpr_invars(self):
        # the fast jaxpr-level proof; the serve/train cache-reuse checks run
        # in the lint-invariants CI lane (they build a real engine)
        from repro.analysis.harness import _check_taus_are_jaxpr_invars

        res = _check_taus_are_jaxpr_invars()
        assert res.ok, res.detail
