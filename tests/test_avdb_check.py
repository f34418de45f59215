"""Analyzer unit tests: every rule family pinned by checked-in fixture
files with expected (code, line) pairs, plus suppression semantics, the
project-audit codes (driven through synthetic registries), the --json
schema, and a self-hosting smoke test.

Fixture convention (``tests/data/analysis_fixtures/``): a violation line
carries a trailing ``# EXPECT: <CODE>[, <CODE>...]`` marker; the test
asserts the analyzer reports EXACTLY those (line, code) pairs for the
file — so a rule that stops firing (or starts over-firing) fails here
before it silently stops guarding the tree.  A new rule family lands with
a fixture file the same way a new fault point lands with a matrix case.
"""

import json
import os
import re
import subprocess
import sys

import pytest

from annotatedvdb_tpu.analysis import run_paths
from annotatedvdb_tpu.analysis.core import (
    FileContext,
    Project,
    ProjectFacts,
    find_repo_root,
)

REPO = find_repo_root(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "data", "analysis_fixtures")
_EXPECT_RE = re.compile(r"#\s*EXPECT:\s*([A-Z0-9,\s]+)")


def expected_pairs(path):
    """{(line, code)} parsed from the fixture's EXPECT markers."""
    out = set()
    with open(path) as f:
        for i, line in enumerate(f, start=1):
            m = _EXPECT_RE.search(line)
            if not m:
                continue
            for code in m.group(1).split(","):
                code = code.strip()
                if code:
                    out.add((i, code))
    return out


def found_pairs(path, **kwargs):
    findings, n_files = run_paths([path], **kwargs)
    assert n_files == 1
    return {(f.line, f.code) for f in findings}, findings


FIXTURE_FILES = [
    "trace_safety_viol.py",
    "lock_viol.py",
    "registry_viol.py",
    "env_viol.py",
    "hygiene_viol.py",
    "async_viol.py",
]


@pytest.mark.parametrize("name", FIXTURE_FILES)
def test_fixture_findings_match_markers_exactly(name):
    path = os.path.join(FIXTURES, name)
    want = expected_pairs(path)
    assert want, f"{name}: fixture has no EXPECT markers"
    got, findings = found_pairs(path)
    assert got == want, (
        f"{name}: findings != markers\n  extra: {sorted(got - want)}\n"
        f"  missing: {sorted(want - got)}\n  raw: "
        + "\n  ".join(f.render() for f in findings)
    )


def test_cli_contract_fixture():
    """AVDB501/502 need the loader-CLI list pointed at the fixture."""
    path = os.path.join(FIXTURES, "cli_viol.py")
    want = expected_pairs(path)
    got, findings = found_pairs(
        path, loader_clis=("tests/data/analysis_fixtures/cli_viol.py",)
    )
    assert got == want, (got, want)


def test_fixtures_fail_via_cli_entrypoint():
    """Acceptance: the CLI exits non-zero on each checked-in fixture."""
    for name in FIXTURE_FILES + ["cli_viol.py"]:
        cmd = [sys.executable, os.path.join(REPO, "tools", "avdb_check.py"),
               os.path.join(FIXTURES, name)]
        if name == "cli_viol.py":
            cmd += ["--loaderCli", "tests/data/analysis_fixtures/cli_viol.py"]
        p = subprocess.run(cmd, capture_output=True, text=True, cwd=REPO)
        assert p.returncode == 1, (name, p.returncode, p.stdout, p.stderr)


def test_every_rule_family_covered_by_fixtures():
    """One fixture-backed assertion per family, by construction."""
    families = set()
    tree_fixtures = [
        os.path.join("twins_tree", "annotatedvdb_tpu", "ops",
                     "__init__.py"),
        os.path.join("twins_tree", "annotatedvdb_tpu", "ops",
                     "kernels.py"),
        os.path.join("durability_tree", "store", "bad_writer.py"),
        os.path.join("durability_tree", "serve", "http.py"),
        os.path.join("noqa_tree", "pipeline.py"),
    ]
    for name in FIXTURE_FILES + ["cli_viol.py"] + tree_fixtures:
        for _line, code in expected_pairs(os.path.join(FIXTURES, name)):
            families.add(code[:-2])  # AVDB101 -> AVDB1, AVDB1001 -> AVDB10
    # no AVDB8: the cross-front-end parity family went with the second
    # front end it policed
    assert families == {"AVDB1", "AVDB2", "AVDB3", "AVDB4", "AVDB5",
                        "AVDB6", "AVDB7", "AVDB9", "AVDB10"}
    from annotatedvdb_tpu import analysis

    assert not hasattr(analysis, "rules_parity")
    assert not os.path.exists(os.path.join(FIXTURES, "parity_tree"))


# ---------------------------------------------------------------------------
# tree fixtures: the twins registry (AVDB9xx) is a cross-file rule, so its
# fixture is a little tree, scanned whole


def _tree_pairs(tree, files):
    want = {}
    for rel in files:
        path = os.path.join(tree, rel)
        for line, code in expected_pairs(path):
            want.setdefault(rel.replace(os.sep, "/"), set()).add(
                (line, code)
            )
    return want


def test_twins_tree_fixture():
    tree = os.path.join(FIXTURES, "twins_tree")
    findings, n = run_paths([tree], root=tree)
    assert n == 3
    got = {}
    for f in findings:
        rel = f.path.replace("\\", "/").split("twins_tree/")[-1]
        got.setdefault(rel, set()).add((f.line, f.code))
    want = _tree_pairs(tree, [
        os.path.join("annotatedvdb_tpu", "ops", "__init__.py"),
        os.path.join("annotatedvdb_tpu", "ops", "kernels.py"),
    ])
    assert got == want, (got, want)


def test_twins_silent_without_registry_scan():
    """Scanning one ops module alone (the registry not in the scan) must
    not fire the twin audits — AVDB9xx needs ops/__init__.py."""
    tree = os.path.join(FIXTURES, "twins_tree")
    findings, _n = run_paths(
        [os.path.join(tree, "annotatedvdb_tpu", "ops", "kernels.py")],
        root=tree,
    )
    assert [f for f in findings if f.code.startswith("AVDB9")] == []


# ---------------------------------------------------------------------------
# durability tree (AVDB10xx) and the stale-noqa tree (AVDB604)


def test_durability_tree_fixture():
    tree = os.path.join(FIXTURES, "durability_tree")
    findings, n = run_paths([tree], root=tree)
    assert n == 3
    got = {}
    for f in findings:
        rel = f.path.replace("\\", "/").split("durability_tree/")[-1]
        got.setdefault(rel, set()).add((f.line, f.code))
    want = _tree_pairs(tree, [
        os.path.join("store", "bad_writer.py"),
        os.path.join("serve", "http.py"),
    ])
    assert got == want, (got, want)


def test_durability_fsck_xref_silent_without_fsck_scan():
    """AVDB1002/1003 cross-reference fsck's attribution codes; a scan
    that does not include store/fsck.py cannot decide them."""
    tree = os.path.join(FIXTURES, "durability_tree")
    findings, _n = run_paths(
        [os.path.join(tree, "store", "bad_writer.py")], root=tree
    )
    codes = {f.code for f in findings}
    assert "AVDB1002" not in codes and "AVDB1003" not in codes
    # the per-function durability codes stay live on the partial scan
    assert {"AVDB1001", "AVDB1004", "AVDB1005"} <= codes


def test_durability_fsck_xref_silent_in_diff_mode():
    """audit=False (--diff) force-disables the fsck cross-reference even
    when store/fsck.py happens to be in the scan set."""
    tree = os.path.join(FIXTURES, "durability_tree")
    findings, _n = run_paths([tree], root=tree, audit=False)
    codes = {f.code for f in findings}
    assert "AVDB1002" not in codes and "AVDB1003" not in codes
    assert "AVDB1001" in codes


def test_noqa_tree_fixture():
    """The stale and blanket suppressions are flagged AVDB604; the live
    AVDB602 suppression is honored (no AVDB602 in the output)."""
    tree = os.path.join(FIXTURES, "noqa_tree")
    findings, n = run_paths([tree], root=tree)
    assert n == 3
    got = {}
    for f in findings:
        rel = f.path.replace("\\", "/").split("noqa_tree/")[-1]
        got.setdefault(rel, set()).add((f.line, f.code))
    want = _tree_pairs(tree, ["pipeline.py"])
    assert got == want, (got, want)


def test_noqa_audit_gated_to_tree_scans():
    """A partial scan (no config.py / no tests/) must not judge
    staleness — the suppressed code might fire only on a full scan."""
    tree = os.path.join(FIXTURES, "noqa_tree")
    findings, _n = run_paths(
        [os.path.join(tree, "pipeline.py")], root=tree
    )
    assert [f for f in findings if f.code == "AVDB604"] == []


def test_blanket_noqa_cannot_self_suppress_avdb604(tmp_path):
    """A blanket noqa covers every code EXCEPT AVDB604 — a suppression
    must not certify itself; silencing the audit takes an explicit
    [AVDB604] list."""
    ctx = FileContext(
        str(tmp_path / "f.py"),
        "x = 1  # avdb: noqa\n"
        "y = 2  # avdb: noqa[AVDB604] -- deliberate fixture\n",
    )
    assert not ctx.suppressed(1, "AVDB604")
    assert ctx.suppressed(1, "AVDB999")
    assert ctx.suppressed(2, "AVDB604")


# ---------------------------------------------------------------------------
# suppression semantics


def test_noqa_parsing_forms(tmp_path):
    src = (
        "x = 1  # avdb: noqa[AVDB601]\n"
        "y = 2  # avdb: noqa[AVDB101, AVDB102] -- reason here\n"
        "z = 3  # avdb: noqa\n"
        "w = 4\n"
    )
    ctx = FileContext(str(tmp_path / "f.py"), src)
    assert ctx.suppressed(1, "AVDB601")
    assert not ctx.suppressed(1, "AVDB602")
    assert ctx.suppressed(2, "AVDB101") and ctx.suppressed(2, "AVDB102")
    assert ctx.suppressed(3, "AVDB999")  # blanket
    assert not ctx.suppressed(4, "AVDB601")


def test_noqa_honored_identically_for_relative_and_absolute_scans(tmp_path,
                                                                  monkeypatch):
    """Suppression is keyed by absolute path on both sides: a noqa must
    work the same under `avdb_check .` and `avdb_check /abs/tree` (it was
    once silently ignored for absolute scans of project-level findings)."""
    bad = tmp_path / "bad.py"
    bad.write_text(
        "def f(x=[]):  # avdb: noqa[AVDB603] -- fixture\n    return x\n"
    )
    abs_findings, _ = run_paths([str(bad)])
    monkeypatch.chdir(tmp_path)
    rel_findings, _ = run_paths(["bad.py"])
    assert abs_findings == [] and rel_findings == []


def test_fixture_data_skipped_only_under_tests(tmp_path):
    """Only tests/data is exempt from scanning — a package dir that merely
    happens to be NAMED `data` must still be analyzed."""
    from annotatedvdb_tpu.analysis import iter_python_files

    (tmp_path / "tests" / "data").mkdir(parents=True)
    (tmp_path / "tests" / "data" / "fixture.py").write_text("x = 1\n")
    (tmp_path / "pkg" / "data").mkdir(parents=True)
    (tmp_path / "pkg" / "data" / "module.py").write_text("x = 1\n")
    files = [os.path.relpath(f, tmp_path)
             for f in iter_python_files([str(tmp_path)])]
    assert os.path.join("pkg", "data", "module.py") in files
    assert os.path.join("tests", "data", "fixture.py") not in files


def test_noqa_suppresses_finding_end_to_end(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "try:\n    pass\n"
        "except Exception:  # avdb: noqa[AVDB602] -- fixture\n    pass\n"
    )
    findings, _ = run_paths([str(bad)])
    assert findings == []


# ---------------------------------------------------------------------------
# project-audit codes (AVDB302/305/402/403) — driven through synthetic
# registries so the shipped tree (which is clean) still proves they fire


def _project(**over):
    base = dict(
        root=REPO, readme="", fault_points=frozenset(),
        fault_matrix_src="", env_declared={}, loader_clis=(),
        flag_registrars={},
    )
    base.update(over)
    return Project(**base)


def _audit_facts():
    facts = ProjectFacts()
    facts.full_registry_scan = True
    facts.tree_scan = True
    return facts


def test_avdb302_uncovered_fault_point():
    from annotatedvdb_tpu.analysis import rules_registry

    project = _project(
        fault_points=frozenset({"a.b", "c.d"}),
        fault_matrix_src="only a.b is exercised here",
    )
    findings = rules_registry.finalize(_audit_facts(), project)
    assert [f.code for f in findings] == ["AVDB302"]
    assert "c.d" in findings[0].message


def test_avdb305_readme_metric_reference():
    from annotatedvdb_tpu.analysis import rules_registry
    from annotatedvdb_tpu.analysis.rules_registry import MetricReg

    facts = _audit_facts()
    facts.metric_regs = {
        "avdb_real_rows_total": [MetricReg(
            "avdb_real_rows_total", False, "counter", (), "m.py", 1
        )],
    }
    project = _project(
        readme="`avdb_real_rows_total` exists; `avdb_ghost_total` not; "
               "`avdb_check` is a tool, not a metric",
    )
    findings = rules_registry.finalize(facts, project)
    assert [f.code for f in findings] == ["AVDB305"]
    assert "avdb_ghost_total" in findings[0].message


def test_avdb402_403_env_audit():
    from annotatedvdb_tpu.analysis import rules_env

    facts = _audit_facts()
    facts.env_reads = [("x.py", 1, "AVDB_USED")]
    project = _project(
        env_declared={
            "AVDB_USED": "doc", "AVDB_UNDOCUMENTED": "doc",
            "AVDB_STALE": "doc",
        },
        readme="AVDB_USED and AVDB_STALE are in the readme",
    )
    findings = rules_env.finalize(facts, project)
    by_code = {}
    for f in findings:
        by_code.setdefault(f.code, []).append(f.message)
    assert sorted(by_code) == ["AVDB402", "AVDB403"]
    assert any("AVDB_UNDOCUMENTED" in m for m in by_code["AVDB402"])
    # AVDB_STALE: documented but never read; AVDB_UNDOCUMENTED is also
    # unread (bench.py supplements reads, neither appears there)
    assert any("AVDB_STALE" in m for m in by_code["AVDB403"])


def test_audit_codes_gated_off_on_partial_scans():
    """Scanning a fixture subtree must not audit the whole project."""
    from annotatedvdb_tpu.analysis import rules_env, rules_registry

    facts = ProjectFacts()  # full_registry_scan stays False
    project = _project(
        fault_points=frozenset({"never.tested"}),
        fault_matrix_src="no coverage here",
        env_declared={"AVDB_NEVER_READ": "doc"},
        readme="nothing",
    )
    codes = [f.code for f in rules_registry.finalize(facts, project)]
    codes += [f.code for f in rules_env.finalize(facts, project)]
    assert "AVDB302" not in codes
    assert "AVDB402" not in codes and "AVDB403" not in codes


# ---------------------------------------------------------------------------
# --diff mode: the fast pre-commit scan


def test_diff_mode_is_clean_and_audit_free():
    """``--diff HEAD`` analyzes only changed files and must stay clean on
    a tree the full gate accepts: the whole-project audit codes
    (AVDB302/305/402/403/9xx) gate OFF — a partial scan that happens to
    include config.py must not judge the files it did not scan."""
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "avdb_check.py"),
         "--diff", "HEAD", "--json"],
        capture_output=True, text=True, cwd=REPO,
    )
    assert p.returncode == 0, p.stdout + p.stderr
    report = json.loads(p.stdout)
    assert report["findings"] == []


def test_diff_mode_rejects_bad_rev_and_path_mix():
    tool = os.path.join(REPO, "tools", "avdb_check.py")
    p = subprocess.run(
        [sys.executable, tool, "--diff", "no-such-rev-zzz"],
        capture_output=True, text=True, cwd=REPO,
    )
    assert p.returncode == 2
    assert "failed" in p.stderr
    p = subprocess.run(
        [sys.executable, tool, "--diff", "HEAD", "somepath"],
        capture_output=True, text=True, cwd=REPO,
    )
    assert p.returncode == 2
    assert "exclusive" in p.stderr


def test_diff_mode_audit_gating_via_api():
    """audit=False keeps call-site codes firing but silences the
    project audits even when config.py is in the scan set."""
    config = os.path.join(REPO, "annotatedvdb_tpu", "config.py")
    bad = os.path.join(FIXTURES, "hygiene_viol.py")
    findings, _n = run_paths([config, bad], audit=False)
    codes = {f.code for f in findings}
    assert any(c.startswith("AVDB6") for c in codes)  # per-file still on
    assert not any(
        c in {"AVDB302", "AVDB305", "AVDB402", "AVDB403"} or
        c.startswith("AVDB9") for c in codes
    ), codes


# ---------------------------------------------------------------------------
# --json schema (alongside tools/check_bench_schema.py conventions)


def test_json_output_schema():
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "avdb_check.py"),
         "--json", os.path.join(FIXTURES, "hygiene_viol.py")],
        capture_output=True, text=True, cwd=REPO,
    )
    assert p.returncode == 1
    report = json.loads(p.stdout)
    assert report["version"] == 1
    assert report["exit_code"] == 1
    assert isinstance(report["files_scanned"], int)
    assert report["files_scanned"] == 1
    assert isinstance(report["findings"], list) and report["findings"]
    for f in report["findings"]:
        assert set(f) == {"code", "path", "line", "message", "hint"}
        assert re.fullmatch(r"AVDB\d{3,4}", f["code"])
        assert isinstance(f["line"], int) and f["line"] >= 1
        assert f["message"] and f["hint"]


def test_json_clean_tree_shape():
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "avdb_check.py"),
         "--json", os.path.join(REPO, "annotatedvdb_tpu", "analysis")],
        capture_output=True, text=True, cwd=REPO,
    )
    assert p.returncode == 0, p.stdout + p.stderr
    report = json.loads(p.stdout)
    assert report["findings"] == [] and report["exit_code"] == 0


# ---------------------------------------------------------------------------
# self-hosting smoke: the analyzer over the package is clean via the API
# (the full-tree CLI gate lives in tests/test_static_checks.py)


def test_self_hosting_package_clean():
    findings, n_files = run_paths([os.path.join(REPO, "annotatedvdb_tpu")])
    assert n_files > 50
    assert findings == [], "\n".join(f.render() for f in findings)


def test_syntax_error_is_a_finding_not_a_crash(tmp_path):
    bad = tmp_path / "broken.py"
    bad.write_text("def f(:\n")
    findings, _ = run_paths([str(bad)])
    assert [f.code for f in findings] == ["AVDB001"]
