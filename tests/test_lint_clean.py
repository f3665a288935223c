"""The tier-1 lint gate: the FULL package is kgct-lint clean, no allowlist.

This is the enforcement half of the static-analysis subsystem: every rule
in analysis/rules runs over every package module (plus the repo-root
script, chip_smoke.py) and the
baseline is EMPTY. A hot-path host sync, a trace-unsafe branch, a donated
buffer read, an unbounded metric label — any regression fails here, in
tests, instead of shipping as a silent perf/correctness cliff. There is
deliberately no suppression mechanism: a finding is fixed or the rule is
wrong (and fixed).
"""

from pathlib import Path

from kubernetes_gpu_cluster_tpu.analysis import ALL_RULES, run_lint
from kubernetes_gpu_cluster_tpu.analysis.cli import main as lint_main

REPO = Path(__file__).resolve().parent.parent
PACKAGE = REPO / "kubernetes_gpu_cluster_tpu"
SCRIPTS = [REPO / "chip_smoke.py"]


def test_package_is_lint_clean_empty_baseline():
    findings = run_lint([PACKAGE, *SCRIPTS], root=REPO)
    assert findings == [], (
        "kgct-lint must stay clean (fix the finding, don't allowlist):\n"
        + "\n".join(f.format() for f in findings))


def test_all_rules_actually_ran_against_real_structures():
    """Guard against a vacuous pass: the shared analyses must resolve the
    engine's real jitted programs, hot path and donation map — if a
    refactor renames the patterns the rules key on, this fails before the
    empty baseline becomes meaningless."""
    from kubernetes_gpu_cluster_tpu.analysis.core import LintModule
    mod = LintModule(PACKAGE / "engine" / "engine.py", root=REPO)
    jitted = {getattr(j.node, "name", "<lambda>")
              for j in mod.jitted_functions}
    assert {"prefill_step", "spec_step", "mixed_step"} <= jitted
    hot = {f.name for f in mod.hot_path_functions}
    assert {"step", "_step", "_step_spec", "_dispatch_window",
            "_process_window"} <= hot
    donated = mod.donated_attr_map
    assert donated.get("_prefill_fn") == (1,)
    assert donated.get("_decode_fn") == (1, 6)


def test_cli_clean_run_exits_zero(capsys):
    rc = lint_main([str(PACKAGE / "analysis")])
    assert rc == 0
    assert "0 finding(s)" in capsys.readouterr().err


def test_cli_findings_exit_one(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("import time\n\nasync def h():\n    time.sleep(1)\n")
    rc = lint_main([str(bad)])
    out = capsys.readouterr()
    assert rc == 1
    assert "KGCT006" in out.out


def test_cli_list_rules_shows_all_eight(capsys):
    assert lint_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in ALL_RULES:
        assert rule.code in out
    assert len(ALL_RULES) >= 8


def test_cli_json_format(tmp_path, capsys):
    import json
    bad = tmp_path / "bad.py"
    bad.write_text("def f(logger, a):\n    logger.info(f'{a}')\n")
    rc = lint_main([str(bad), "--format", "json"])
    findings = json.loads(capsys.readouterr().out)
    assert rc == 1 and findings[0]["rule"] == "KGCT008"


def test_cli_console_script_is_declared():
    pyproject = (REPO / "pyproject.toml").read_text()
    assert ('kgct-lint = "kubernetes_gpu_cluster_tpu.analysis.cli:main"'
            in pyproject)


def test_concurrency_graph_resolves_real_seam_not_vacuous():
    """Guard against a vacuous pass for the interprocedural layer: the
    PackageModel over the real package must resolve the worker-op seam
    and at least one known async->engine path. An empty graph would make
    KGCT019-021's zero baseline meaningless — fail loudly here first."""
    from kubernetes_gpu_cluster_tpu.analysis.core import (
        CTX_LOOP, CTX_WORKER, PackageModel, get_module, iter_py_files)
    mods = [get_module(p, root=REPO) for p in iter_py_files([PACKAGE])]
    pm = PackageModel(mods)
    # The run_in_worker/post_to_worker seam resolves to real call sites.
    assert pm.seam_sites, "no worker-op seam sites resolved"
    assert any("serving/api_server.py" in rel
               for rel, _, _ in pm.seam_sites)
    # The seam's engine-method targets include the KV export/import ops.
    assert {"export_held", "import_request"} & set(pm.worker_op_targets)
    # At least one async def provably reaches engine state THROUGH the
    # seam (the sanctioned crossing the rules treat as legal).
    assert pm.async_engine_paths, "no async->engine path resolved"
    assert any("api_server" in caller
               for caller, _ in pm.async_engine_paths)
    # Context classification: the worker loop and the submit coroutine.
    ae = next(m for m in mods
              if m.relpath.replace("\\", "/").endswith(
                  "serving/async_engine.py"))
    assert CTX_WORKER in pm.contexts_of(ae, "AsyncLLMEngine._worker")
    assert CTX_LOOP in pm.contexts_of(ae, "AsyncLLMEngine.generate")
    # The engine's ONE sanctioned cross-boundary lock is seen as such.
    assert {CTX_LOOP, CTX_WORKER} <= pm.lock_contexts_of(ae, "_cv")
    # And an actually-empty graph is distinguishable (the loud-failure
    # property this test relies on).
    empty = PackageModel([])
    assert not empty.seam_sites and not empty.async_engine_paths


def test_module_cache_warm_run_parses_nothing():
    """The module-model cache: a warm re-run over unchanged files adds
    ZERO parses (pinned by parse count, not wall clock), and an edited
    file re-parses exactly once."""
    from kubernetes_gpu_cluster_tpu.analysis import core
    target = PACKAGE / "analysis"
    run_lint([target], root=REPO)           # prime (may hit prior cache)
    before = core.PARSE_COUNT
    warm = run_lint([target], root=REPO)
    assert core.PARSE_COUNT == before, (
        f"warm lint run re-parsed {core.PARSE_COUNT - before} file(s); "
        "the (path, content-hash) cache must make re-runs parse-free")
    assert warm == []


def test_module_cache_invalidates_on_content_change(tmp_path):
    from kubernetes_gpu_cluster_tpu.analysis import core
    f = tmp_path / "m.py"
    f.write_text("x = 1\n")
    m1 = core.get_module(f)
    assert core.get_module(f) is m1         # warm hit: same object
    f.write_text("x = 2\n")
    m2 = core.get_module(f)
    assert m2 is not m1                     # content hash changed
    assert core.get_module(f) is m2


def test_sarif_output_has_required_2_1_0_keys(tmp_path, capsys):
    """kgct-lint --format sarif validates against the SARIF 2.1.0
    required keys (what GitHub code-scanning ingestion checks)."""
    import json
    bad = tmp_path / "bad.py"
    bad.write_text("import time\n\nasync def h():\n    time.sleep(1)\n")
    artifact = tmp_path / "out.sarif"
    rc = lint_main([str(bad), "--format", "sarif",
                    "--sarif", str(artifact)])
    assert rc == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["version"] == "2.1.0"
    assert "$schema" in doc
    run = doc["runs"][0]
    driver = run["tool"]["driver"]
    assert driver["name"] == "kgct-lint"
    assert {r["id"] for r in driver["rules"]} == {
        r.code for r in ALL_RULES}
    result = run["results"][0]
    assert result["ruleId"] == "KGCT006"
    assert result["message"]["text"]
    loc = result["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith("bad.py")
    assert loc["region"]["startLine"] == 4
    # The --sarif artifact is the same document.
    assert json.loads(artifact.read_text()) == doc


def test_cli_changed_mode_lints_only_touched_files(capsys):
    """--changed HEAD in a clean tree lints nothing (and exits 0); the
    scope filter and git plumbing are exercised either way."""
    rc = lint_main([str(PACKAGE), "--changed", "HEAD"])
    assert rc in (0, 1)
    err = capsys.readouterr().err
    assert "finding(s)" in err
