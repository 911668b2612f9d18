"""tpulint seeded-violation corpus: every rule must fire at the exact
file:line of each deliberate violation (fixtures under
tests/tpulint_fixtures/, expectations parsed from their `# EXPECT:`
markers), suppressions with a reason must silence findings while
reason-less ones are themselves flagged, the baseline machinery must
grandfather without hiding new findings — and the real tree must lint
clean."""
import json
import os
import re
import subprocess
import sys

import pytest

from spark_rapids_tpu.analysis import (run_lint, rule_ids,
                                       summary_line, write_baseline)
from spark_rapids_tpu.analysis.core import (collect_conf_keys,
                                            parse_suppressions)

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
FIXTURES = os.path.join(HERE, "tpulint_fixtures")

#: fixture file -> the rule it seeds (fx_suppress is machinery-only)
RULE_FIXTURES = {
    "host-sync": os.path.join(FIXTURES, "exec", "fx_host_sync.py"),
    "sem-blocking": os.path.join(FIXTURES, "exec",
                                 "fx_sem_blocking.py"),
    "unbounded-wait": os.path.join(FIXTURES, "shuffle",
                                   "fx_unbounded_wait.py"),
    "conf-discipline": os.path.join(FIXTURES, "plan", "fx_conf.py"),
    "conf-unread": os.path.join(FIXTURES, "conf_unread", "config.py"),
    "compile-under-lock": os.path.join(FIXTURES, "exec",
                                       "fx_compile_lock.py"),
    "collective-discipline": os.path.join(FIXTURES, "parallel",
                                          "fx_collective.py"),
}

_EXPECT_RE = re.compile(r"#\s*EXPECT:\s*([a-z\-, ]+)$")


def expected_findings(path):
    """{(rule, line), ...} parsed from the fixture's EXPECT markers."""
    out = set()
    with open(path) as f:
        for i, line in enumerate(f, start=1):
            m = _EXPECT_RE.search(line)
            if m:
                for rule in m.group(1).split(","):
                    out.add((rule.strip(), i))
    return out


def lint_one(path, **kw):
    kw.setdefault("baseline_path", None)
    return run_lint([path], **kw)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("rule", sorted(RULE_FIXTURES))
def test_rule_fires_at_expected_lines(rule):
    path = RULE_FIXTURES[rule]
    expected = expected_findings(path)
    assert expected, f"fixture {path} has no EXPECT markers"
    got = {(f.rule, f.line) for f in lint_one(path).findings}
    assert got == expected, (
        f"rule {rule}: expected {sorted(expected)} got {sorted(got)}")


@pytest.mark.parametrize("rule", sorted(RULE_FIXTURES))
def test_rule_is_load_bearing_when_disabled(rule):
    """Disabling a rule must remove exactly its findings — proving the
    findings come from THAT rule pass being live, not a lucky overlap."""
    path = RULE_FIXTURES[rule]
    enabled = lint_one(path)
    assert any(f.rule == rule for f in enabled.findings), \
        f"rule {rule} found nothing in its own fixture"
    disabled = lint_one(path, disable=[rule])
    assert not any(f.rule == rule for f in disabled.findings)
    # other rules' findings in the same file are untouched
    others = {(f.rule, f.line) for f in enabled.findings
              if f.rule != rule}
    assert {(f.rule, f.line) for f in disabled.findings} == others


def test_suppression_with_reason_silences():
    res = lint_one(RULE_FIXTURES["host-sync"])
    sup = [f for f in res.suppressed if f.rule == "host-sync"]
    assert len(sup) == 1
    assert "host-resident" in sup[0].reason
    assert not any(f.line == sup[0].line for f in res.findings)


def test_reasonless_suppression_is_flagged_and_ignored():
    path = os.path.join(FIXTURES, "exec", "fx_suppress.py")
    res = lint_one(path)
    bad = [f for f in res.findings if f.rule == "bad-suppress"]
    assert len(bad) == 1
    # the un-reasoned disable did NOT suppress: the host-sync finding
    # on the same line stays active
    assert any(f.rule == "host-sync" and f.line == bad[0].line
               for f in res.findings)
    # the reasoned one did suppress
    assert len(res.suppressed) == 1
    assert res.suppressed[0].reason.startswith("fixture:")


def test_standalone_comment_suppresses_next_code_line():
    src = [
        "# tpulint: disable=unbounded-wait -- reason one",
        "# continuation of the reason",
        "ev.wait()",
    ]
    sups, bad = parse_suppressions(src)
    assert not bad
    assert sups[0].line == 3 and sups[0].covers("unbounded-wait")


def test_baseline_grandfathers_but_new_findings_stay(tmp_path):
    path = RULE_FIXTURES["unbounded-wait"]
    first = lint_one(path)
    assert first.findings and first.exit_code == 1
    bl = tmp_path / "baseline.json"
    write_baseline(str(bl), first.findings)
    second = lint_one(path, baseline_path=str(bl))
    assert not second.findings and second.exit_code == 0
    assert {(f.rule, f.line) for f in second.baselined} == \
        {(f.rule, f.line) for f in first.findings}
    # a NEW violation is not covered by the baseline
    extra = tmp_path / "shuffle"
    extra.mkdir()
    extra_file = extra / "fresh.py"
    extra_file.write_text("def f(ev):\n    ev.wait()\n")
    third = run_lint([path, str(extra_file)], baseline_path=str(bl))
    assert len(third.findings) == 1
    assert third.findings[0].rule == "unbounded-wait"


def test_real_tree_lints_clean():
    res = run_lint()
    assert res.files_scanned > 100
    assert res.findings == [], "\n".join(
        f"{f.location()}: [{f.rule}] {f.message}"
        for f in res.findings)
    # every suppression in the tree carries a reason by construction;
    # the baseline stays empty (repo policy: fix, don't grandfather)
    assert all(f.reason for f in res.suppressed)
    assert not res.baselined
    assert len(res.rules) == 7
    assert "rules=7" in summary_line(res)


def test_conf_registry_parse_matches_runtime():
    """Rule 4a's parsed key set must agree with the live registry —
    a registry refactor that broke the AST parse would silently turn
    the rule off."""
    from spark_rapids_tpu import config as C
    parsed = collect_conf_keys(
        os.path.join(REPO, "spark_rapids_tpu", "config.py"))
    runtime = {k for k in C._REGISTRY if k.startswith("spark.rapids.")}
    assert runtime <= parsed


def test_conf_unread_sees_every_registered_entry():
    """`conf-unread` judges the entries it can see: module-level
    `NAME = conf(...)` bindings.  An entry registered any other way
    would escape it, so the bindings it parses must be the whole live
    registry."""
    import ast
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.analysis.rules import ConfUnreadRule
    with open(os.path.join(REPO, "spark_rapids_tpu", "config.py")) as f:
        entries = ConfUnreadRule.entries(ast.parse(f.read()))
    assert {key for _, key, _ in entries} == set(C._REGISTRY)
    assert all(getattr(C, name).key == key for name, key, _ in entries)


# ---------------------------------------------------------------------------
def _run(args, **kw):
    return subprocess.run([sys.executable] + args, cwd=REPO,
                          capture_output=True, text=True, **kw)


def test_cli_json_format_and_exit_codes():
    r = _run(["scripts/lint.py", "--format", "json",
              RULE_FIXTURES["conf-discipline"], "--no-baseline"])
    assert r.returncode == 1
    payload = json.loads(r.stdout)
    assert payload["rules"] == rule_ids()
    assert all({"rule", "path", "line", "message", "fingerprint"}
               <= set(f) for f in payload["findings"])
    assert "tpulint summary:" in r.stderr
    clean = _run(["scripts/lint.py"])
    assert clean.returncode == 0, clean.stdout + clean.stderr


def test_configs_doc_drift_gate(tmp_path):
    ok = _run(["scripts/gen_configs_doc.py", "--check"])
    assert ok.returncode == 0, ok.stdout + ok.stderr
    assert "ok" in ok.stdout
    stale = tmp_path / "configs.md"
    with open(os.path.join(REPO, "docs", "configs.md")) as f:
        content = f.read()
    stale.write_text(content.replace(
        "spark.rapids.sql.enabled", "spark.rapids.sql.enabledX", 1))
    drifted = _run(["scripts/gen_configs_doc.py", "--check",
                    str(stale)])
    assert drifted.returncode == 1
    assert "stale" in drifted.stdout
