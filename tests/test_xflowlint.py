"""xflowlint (xflow_tpu/analysis, tools/xflowlint.py,
tools/smoke_lint.sh): the fixture corpus proves every rule fires on
known-bad code — including the resurrected pre-PR 8 unlocked-appender
bug — and stays silent on the fixed shapes; suppression, baseline, and
CLI exit-code semantics are pinned; seeding a violation of each rule
class into a scratch copy of a REAL module is caught with the correct
rule id and file:line (the ISSUE 10 acceptance drill)."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from xflow_tpu.analysis.core import (  # noqa: E402
    Baseline, BaselineEntry, Finding, Module, Project, run_passes,
)

FIXTURES = os.path.join(REPO_ROOT, "tests", "fixtures", "xflowlint")


def lint(*paths, root=REPO_ROOT, rules=None):
    project = Project.load(root, [os.path.join(FIXTURES, p) if not
                                  os.path.isabs(p) else p for p in paths])
    only = set(rules) if rules else None
    return run_passes(project, only_rules=only)


def rules_of(findings):
    return sorted({f.rule for f in findings})


def marker_lines(fixture, rule):
    """Lines in a fixture carrying a `# XFnnn:` expectation marker."""
    out = set()
    with open(os.path.join(FIXTURES, fixture)) as f:
        for i, line in enumerate(f, 1):
            if f"# {rule}:" in line:
                out.add(i)
    return out


# ------------------------------------------------------------ rule firing


def test_jit_purity_fixture_fires_on_every_marker():
    findings = lint("bad_jit_purity.py")
    assert rules_of(findings) == ["XF101"]
    assert {f.line for f in findings} == marker_lines(
        "bad_jit_purity.py", "XF101")
    # the PR 2 rule by name: perf_counter inside a jit body
    assert any("time.perf_counter" in f.message for f in findings)
    # RNG, print, global, scan-body, and traced-lambda variants all land
    blob = " ".join(f.message for f in findings)
    for needle in ("random.random", "print", "global mutation",
                   "numpy.random.seed", "time.time"):
        assert needle in blob, needle


def test_recompile_fixture_fires_all_three_rules():
    findings = lint("bad_recompile.py")
    by_rule = {r: [f for f in findings if f.rule == r]
               for r in rules_of(findings)}
    assert set(by_rule) == {"XF201", "XF202", "XF203"}
    assert {f.line for f in by_rule["XF201"]} == marker_lines(
        "bad_recompile.py", "XF201")
    assert {f.line for f in by_rule["XF202"]} == marker_lines(
        "bad_recompile.py", "XF202")
    assert {f.line for f in by_rule["XF203"]} == marker_lines(
        "bad_recompile.py", "XF203")


def test_lockset_fixture_retro_detects_pre_pr8_appender():
    """The resurrected pre-PR 8 JsonlAppender (no append lock, health
    thread + handler threads) must fire on every unlocked mutation of
    the shared file-handle state."""
    findings = lint("bad_lockset.py")
    assert rules_of(findings) == ["XF301"]
    attrs = {re.search(r"`self\.(\w+)`", f.message).group(1)
             for f in findings}
    # the lazy-open handle and its byte counter are the bug
    assert "_f" in attrs and "_size" in attrs
    # every finding names both regions that collide
    for f in findings:
        assert "thread:_health_loop" in f.message
        assert "external" in f.message


def test_lockset_silent_on_fixed_appender():
    assert lint("good_lockset.py") == []


def test_config_fixture_fires_on_every_marker():
    findings = lint("bad_config.py")
    assert rules_of(findings) == ["XF401"]
    assert {f.line for f in findings} == marker_lines(
        "bad_config.py", "XF401")
    blob = " ".join(f.message for f in findings)
    for needle in ("train.lag_every", "sreve", "windw_ms", "train.epocs",
                   "serve.max_bach"):
        assert needle in blob, needle


def test_schema_fixture_fires_drift_and_unknown_kind():
    findings = lint("bad_schema.py")
    assert rules_of(findings) == ["XF501", "XF502"]
    msgs = " ".join(f.message for f in findings)
    assert "queue_wait_p50ms" in msgs  # drifted serve window key
    assert "stepp" in msgs  # drift against a stamp-declared kind
    assert '"shadow"' in msgs  # unknown kind


def test_shell_fixture_fires_strict_mode_and_bad_key():
    findings = lint("bad_shell.sh")
    assert rules_of(findings) == ["XF401", "XF601"]
    (f601,) = [f for f in findings if f.rule == "XF601"]
    assert "-o pipefail" in f601.message
    (f401,) = [f for f in findings if f.rule == "XF401"]
    assert "train.log_evry" in f401.message


def test_hostsync_fixture_fires_on_every_marker():
    findings = lint("bad_hostsync.py")
    by_rule = {r: [f for f in findings if f.rule == r]
               for r in rules_of(findings)}
    assert set(by_rule) == {"XF110", "XF111"}
    assert {f.line for f in by_rule["XF110"]} == marker_lines(
        "bad_hostsync.py", "XF110")
    assert {f.line for f in by_rule["XF111"]} == marker_lines(
        "bad_hostsync.py", "XF111")
    blob = " ".join(f.message for f in findings)
    # explicit conversions, formatting, and the implicit branch all land
    for needle in ("float", "print", "f-string", "bool", "int",
                   "branch condition"):
        assert needle in blob, needle


def test_hostsync_one_behind_staged_read_is_exempt_by_construction():
    """The fixture's `staged` reads model the StepTimer discipline: the
    value was staged LAST iteration and a newer dispatch aged it — no
    suppression comment involved, the engine proves it stale. The
    post-run epilogue loop (dispatches nothing, only reads) is the
    other by-construction exemption: its syncs are mandatory one-time
    reads, not pipeline bubbles."""
    src = open(os.path.join(FIXTURES, "bad_hostsync.py")).read()
    exempt = {i for i, ln in enumerate(src.splitlines(), 1)
              if 'float(staged["loss"])' in ln or 'float(m[key])' in ln}
    assert len(exempt) == 2
    findings = lint("bad_hostsync.py")
    assert not (exempt & {f.line for f in findings})


def test_sharding_contract_fixture_fires_on_every_marker():
    findings = lint("bad_sharding_contract.py")
    by_rule = {r: [f for f in findings if f.rule == r]
               for r in rules_of(findings)}
    assert set(by_rule) == {"XF701", "XF702", "XF703"}
    for rule in by_rule:
        assert {f.line for f in by_rule[rule]} == marker_lines(
            "bad_sharding_contract.py", rule), rule
    (f701,) = by_rule["XF701"]
    assert "'tabel'" in f701.message and "data, table" in f701.message


def test_unrecorded_jit_fires_only_in_recorder_scoped_paths(tmp_path):
    """XF204 is scoped to the engine/serve modules where PR 7's
    CompileRecorder contract holds."""
    src = (
        "import jax\n"
        "def build(model):\n"
        "    def step(s, b):\n"
        "        return s\n"
        "    return jax.jit(step)\n"
    )
    scoped = tmp_path / "xflow_tpu" / "serve"
    scoped.mkdir(parents=True)
    (scoped / "newmod.py").write_text(src)
    unscoped = tmp_path / "xflow_tpu" / "data"
    unscoped.mkdir(parents=True)
    (unscoped / "newmod.py").write_text(src)
    findings = lint(str(scoped / "newmod.py"),
                    str(unscoped / "newmod.py"), root=str(tmp_path))
    assert rules_of(findings) == ["XF204"]
    assert [f.path for f in findings] == ["xflow_tpu/serve/newmod.py"]
    assert findings[0].line == 5


# ------------------------------------------------- precision (no false fire)


def test_loop_var_static_check_is_scope_local(tmp_path):
    """A parameter named like an unrelated loop variable in another
    function is NOT a loop variable (XF202 stays quiet)."""
    mod = tmp_path / "m.py"
    mod.write_text(
        "import jax\n\n\ndef f(x, n):\n    return x * n\n\n\n"
        "def other(xs):\n    for k in xs:\n        print(k)\n\n\n"
        "g = jax.jit(f, static_argnums=(1,))\n\n\n"
        "def call(k):\n    return g(1.0, k)\n"
    )
    assert lint(str(mod), rules=["XF202"]) == []


def test_loop_var_after_loop_is_single_valued(tmp_path):
    """XF202 retrofit regression pin: a loop variable read AFTER its
    loop is one value per outer execution — the old name-set heuristic
    flagged it (the documented scope-locality caveat); the dataflow
    engine must not."""
    mod = tmp_path / "m.py"
    mod.write_text(
        "import jax\n\n\ndef f(x, n):\n    return x * n\n\n\n"
        "g = jax.jit(f, static_argnums=(1,))\n\n\n"
        "def post_loop(x, xs):\n"
        "    for k in xs:\n        x = x + k\n"
        "    return g(x, k)\n"
    )
    assert lint(str(mod), rules=["XF202"]) == []


def test_loop_var_copied_through_alias_is_caught(tmp_path):
    """XF202 retrofit gain: `n = k; g(x, n)` inside the loop varies per
    iteration exactly like passing `k` directly — the name heuristic
    missed it, the dataflow engine follows the assignment."""
    mod = tmp_path / "m.py"
    mod.write_text(
        "import jax\n\n\ndef f(x, n):\n    return x * n\n\n\n"
        "g = jax.jit(f, static_argnums=(1,))\n\n\n"
        "def aliased(x, xs):\n"
        "    for k in xs:\n"
        "        n = k\n"
        "        x = g(x, n)\n"
        "    return x\n"
    )
    findings = lint(str(mod), rules=["XF202"])
    assert [f.rule for f in findings] == ["XF202"]
    assert findings[0].line == 14  # the call site, not the alias line


def test_loop_var_rebound_to_constant_is_clean(tmp_path):
    """XF202 retrofit: rebinding the name to a constant inside the loop
    kills the loop-variance fact (flow-sensitivity, not name matching)."""
    mod = tmp_path / "m.py"
    mod.write_text(
        "import jax\n\n\ndef f(x, n):\n    return x * n\n\n\n"
        "g = jax.jit(f, static_argnums=(1,))\n\n\n"
        "def rebound(x, xs):\n"
        "    for k in xs:\n"
        "        k = 3\n"
        "        x = g(x, k)\n"
        "    return x\n"
    )
    assert lint(str(mod), rules=["XF202"]) == []


def test_donated_read_in_loop_without_rebind_is_caught(tmp_path):
    """XF702: the donate-then-reuse loop (forgot `state = step(state)`)
    — the second iteration passes an invalidated buffer."""
    mod = tmp_path / "m.py"
    mod.write_text(
        "import jax\n\n\ndef run(step, state, batches):\n"
        "    jitted = jax.jit(step, donate_argnums=(0,))\n"
        "    outs = []\n"
        "    for b in batches:\n"
        "        outs.append(jitted(state, b))\n"
        "    return outs\n"
    )
    findings = lint(str(mod), rules=["XF702"])
    assert findings and {f.rule for f in findings} == {"XF702"}
    # the rebound form is the fix and must be clean
    mod.write_text(
        "import jax\n\n\ndef run(step, state, batches):\n"
        "    jitted = jax.jit(step, donate_argnums=(0,))\n"
        "    for b in batches:\n"
        "        state, m = jitted(state, b)\n"
        "    return state\n"
    )
    assert lint(str(mod), rules=["XF702"]) == []


def test_undonated_eval_step_is_not_flagged(tmp_path):
    """XF703 keys on the TrainState parameter: eval/predict jits take
    read-only `tables` and must NOT be asked to donate them."""
    mod = tmp_path / "m.py"
    mod.write_text(
        "import jax\n\n\ndef make_eval():\n"
        "    def eval_step(tables, batch):\n"
        "        return tables\n"
        "    return jax.jit(eval_step)\n"
    )
    assert lint(str(mod), rules=["XF703"]) == []


def test_lockset_private_thread_only_helper_not_external(tmp_path):
    """A private helper only the spawned thread calls is single-
    threaded — no finding; the same helper called from a PUBLIC method
    still fires."""
    base = (
        "import threading\n\n\nclass W:\n"
        "    def __init__(self):\n"
        "        self._buf = []\n"
        "        threading.Thread(target=self._run, daemon=True).start()\n\n"
        "    def _run(self):\n        self._flush()\n\n"
        "    def _flush(self):\n        self._buf = []\n"
    )
    mod = tmp_path / "w.py"
    mod.write_text(base)
    assert lint(str(mod), rules=["XF301"]) == []
    mod.write_text(base + "\n    def drain(self):\n        self._flush()\n")
    assert [f.rule for f in lint(str(mod), rules=["XF301"])] == ["XF301"]


def test_shell_strict_mode_must_precede_commands(tmp_path):
    """`set -euo pipefail` AFTER fallible commands protects nothing."""
    sh = tmp_path / "late.sh"
    sh.write_text("#!/usr/bin/env bash\nrm -rf \"$1\"\nset -euo pipefail\n")
    assert [f.rule for f in lint(str(sh))] == ["XF601"]


def test_shell_comment_mentions_of_keys_ignored(tmp_path):
    sh = tmp_path / "c.sh"
    sh.write_text("#!/usr/bin/env bash\nset -euo pipefail\n"
                  "# historical note: serve.windw_ms=3 was renamed\n"
                  "true\n")
    assert lint(str(sh)) == []


# ------------------------------------------------- suppression / negatives


def test_inline_and_file_suppressions():
    assert lint("suppress_line.py") == []
    assert lint("suppress_file.py") == []
    # the same code without the directive DOES fire (the suppression is
    # what silences it, not a pass gap)
    mod = Module("x.py", "x.py",
                 open(os.path.join(FIXTURES, "suppress_line.py")).read()
                 .replace("# xflowlint: disable=XF101", ""))
    assert not mod.line_suppress


def test_clean_fixture_is_clean():
    assert lint("good_clean.py") == []


# -------------------------------------------------------- baseline model


def _finding(rule="XF101", path="a.py", line=3, message="m"):
    return Finding(rule=rule, path=path, line=line, message=message)


def test_baseline_split_new_known_stale():
    base = Baseline([BaselineEntry("XF101", "a.py", "m", reason="legacy")])
    new, known, stale = base.split([_finding(), _finding(line=9)])
    # line numbers are NOT part of the fingerprint: both match
    assert not new and len(known) == 2 and not stale
    new, known, stale = base.split([_finding(message="other")])
    assert len(new) == 1 and not known and len(stale) == 1


def test_baseline_staleness_scoped_to_selected_rules():
    """`--rules XF301` skips the config pass — an XF401 baseline entry
    must not read as stale just because its pass never ran."""
    base = Baseline([BaselineEntry("XF401", "a.py", "m", reason="legacy")])
    _new, _known, stale = base.split([], only_rules={"XF301"})
    assert stale == []
    _new, _known, stale = base.split([], only_rules={"XF401"})
    assert len(stale) == 1
    _new, _known, stale = base.split([])  # full run: stale for real
    assert len(stale) == 1


def test_syntax_error_respects_rules_filter_and_suppression(tmp_path):
    bad = tmp_path / "broken.py"
    bad.write_text("def f(:\n")
    findings = lint(str(bad))
    assert rules_of(findings) == ["XF001"]
    # --rules excluding XF001 filters it
    assert lint(str(bad), rules=["XF301"]) == []
    # disable-file works even though the file never parsed
    bad.write_text("# xflowlint: disable-file=XF001 — generated junk\n"
                   "def f(:\n")
    assert lint(str(bad)) == []


def test_shell_all_wildcard_suppression(tmp_path):
    from xflow_tpu.analysis.core import ShellScript

    sh = ShellScript("x.sh", "x.sh",
                     "# xflowlint: disable-file=all\necho hi\n")
    assert sh.suppressed("XF601", 2)  # Module and ShellScript agree


def test_write_baseline_refuses_partial_scan_and_keeps_reasons(tmp_path):
    bad = os.path.join(FIXTURES, "bad_jit_purity.py")
    # partial path set + no explicit --baseline: refuse (3), never
    # clobber the repo-wide baseline with a partial scan
    r = run_cli(bad, "--write-baseline", "--reason", "r")
    assert r.returncode == 3 and "PARTIAL" in r.stderr
    # an audited reason survives regeneration of the same target, even
    # when a different --reason is supplied for genuinely-new entries
    bl = str(tmp_path / "bl.json")
    assert run_cli(bad, "--write-baseline", "--baseline", bl,
                   "--reason", "first write").returncode == 0
    base = Baseline.load(bl)
    assert base.entries
    assert all(e.reason == "first write" for e in base.entries)
    base.entries[0].reason = "audited: fixture keeps this on purpose"
    base.save(bl)
    assert run_cli(bad, "--write-baseline", "--baseline", bl,
                   "--reason", "regen").returncode == 0
    kept = Baseline.load(bl)
    assert any(e.reason == "audited: fixture keeps this on purpose"
               for e in kept.entries)


def test_write_baseline_requires_reason_for_new_entries(tmp_path):
    """The ISSUE 15 placeholder-leak fix: NEW entries without --reason
    are refused (exit 3) instead of landing as 'TODO: justify or fix'."""
    bad = os.path.join(FIXTURES, "bad_jit_purity.py")
    bl = str(tmp_path / "bl.json")
    r = run_cli(bad, "--write-baseline", "--baseline", bl)
    assert r.returncode == 3 and "--reason" in r.stderr
    assert not os.path.exists(bl)  # refused writes leave no file


def test_baseline_placeholder_reason_fails_audit(tmp_path):
    """A checked-in baseline entry still carrying the placeholder
    reason fails the gate with exit 3 (usage error, not a lint
    verdict) and names the entry."""
    bad = os.path.join(FIXTURES, "bad_jit_purity.py")
    bl = tmp_path / "bl.json"
    base = Baseline([BaselineEntry("XF101", "a.py", "m",
                                   reason="TODO: justify or fix")])
    base.save(str(bl))
    r = run_cli(bad, "--baseline", str(bl))
    assert r.returncode == 3
    assert "placeholder" in r.stderr and "a.py" in r.stderr


def test_write_baseline_refuses_rule_scoped_scan():
    """--rules + --write-baseline would drop every other rule's audited
    entries — refused like the partial-path case."""
    r = run_cli("--rules", "XF301", "--write-baseline")
    assert r.returncode == 3 and "--rules" in r.stderr


def test_unrecorded_jit_catches_decorator_form(tmp_path):
    """`@jax.jit` (and `@partial(jax.jit, ...)`) in a recorder-scoped
    module bypasses compile accounting exactly like the call form."""
    scoped = tmp_path / "xflow_tpu" / "serve"
    scoped.mkdir(parents=True)
    (scoped / "m.py").write_text(
        "import jax\nfrom functools import partial\n\n\n"
        "@jax.jit\ndef step(s):\n    return s\n\n\n"
        "@partial(jax.jit, donate_argnums=(0,))\ndef step2(s):\n"
        "    return s\n"
    )
    findings = lint(str(scoped / "m.py"), root=str(tmp_path))
    assert [f.rule for f in findings] == ["XF204", "XF204"]
    # lineno of a decorated FunctionDef is the `def` line
    assert {f.line for f in findings} == {6, 11}
    # a shard_map body is traced inside some jit but compiles nothing
    # itself: the engines' `@partial(shard_map, ...)` is not an XF204
    (scoped / "m.py").write_text(
        "from functools import partial\nfrom jax import shard_map\n\n\n"
        "@partial(shard_map, mesh=None, in_specs=(), out_specs=())\n"
        "def body(x):\n    return x\n"
    )
    assert lint(str(scoped / "m.py"), root=str(tmp_path)) == []


def test_schema_doc_parser_ignores_fenced_blocks(tmp_path):
    from xflow_tpu.analysis.passes.schema_drift import parse_schema_doc

    doc = tmp_path / "d.md"
    doc.write_text(
        '## Records (`kind="thing"`)\n\n'
        "```bash\n"
        "# this comment must not read as a heading\n"
        "| `not_a_key` | fenced tables are examples |\n"
        "```\n\n"
        "| field | meaning |\n"
        "|---|---|\n"
        "| `real_key` | documented |\n"
    )
    kinds, _stamp = parse_schema_doc(str(doc))
    assert kinds["thing"] == {"real_key", "kind"}


def test_baseline_round_trip(tmp_path):
    p = str(tmp_path / "b.json")
    base = Baseline([BaselineEntry("XF301", "x.py", "msg", reason="why")])
    base.save(p)
    loaded = Baseline.load(p)
    assert [(e.rule, e.path, e.message, e.reason) for e in loaded.entries] \
        == [("XF301", "x.py", "msg", "why")]
    # a missing file is an empty baseline, not an error
    assert Baseline.load(str(tmp_path / "nope.json")).entries == []


# ------------------------------------------------------------ CLI contract


def run_cli(*args, cwd=REPO_ROOT):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools", "xflowlint.py"),
         *args],
        capture_output=True, text=True, timeout=180, env=env, cwd=cwd)


def test_cli_exit_codes(tmp_path):
    bad = os.path.join(FIXTURES, "bad_jit_purity.py")
    # new findings -> 1
    r = run_cli(bad, "--no-baseline")
    assert r.returncode == 1 and "XF101" in r.stdout
    # everything baselined -> 0
    bl = str(tmp_path / "bl.json")
    r = run_cli(bad, "--write-baseline", "--baseline", bl,
                "--reason", "exit-code drill")
    assert r.returncode == 0
    r = run_cli(bad, "--baseline", bl)
    assert r.returncode == 0 and "suppressed by baseline" in r.stdout
    # a fixed finding must leave the baseline -> 2 (baseline-shrink gate)
    clean = os.path.join(FIXTURES, "good_clean.py")
    r = run_cli(clean, "--baseline", bl)
    assert r.returncode == 2 and "STALE baseline entry" in r.stdout
    # --json carries the same verdicts
    r = run_cli(bad, "--no-baseline", "--json")
    data = json.loads(r.stdout)
    assert data["new"] and data["stale_baseline"] == []


def test_cli_full_repo_is_clean():
    """The whole tree lints green against the checked-in baseline —
    the same gate tools/smoke_lint.sh runs in CI."""
    r = run_cli()
    assert r.returncode == 0, (r.stdout, r.stderr)


def test_cli_unknown_rule_is_usage_error():
    assert run_cli("--rules", "XF999").returncode == 3


# ------------------------------------------------- engine-contract matrix


def test_contract_artifact_checked_in_and_byte_stable():
    """tools/engine_contracts.json: covers every engine builder,
    the AST sections match a fresh extraction, two consecutive
    extractions render byte-identically (ISSUE 14 acceptance), and the
    v2 jaxpr section (ISSUE 15) is present and program-complete."""
    from xflow_tpu.analysis.ir import PROGRAMS
    from xflow_tpu.analysis.passes.sharding_contract import (
        ENGINE_MODULES, extract_contracts, render_artifact,
    )

    project = Project.load(REPO_ROOT)
    r1 = render_artifact(extract_contracts(project))
    r2 = render_artifact(extract_contracts(Project.load(REPO_ROOT)))
    assert r1 == r2, "extraction is not deterministic"
    on_disk = json.loads(open(os.path.join(
        REPO_ROOT, "tools", "engine_contracts.json")).read())
    # contracts v2: the jaxpr section rides the same artifact — every
    # IR program with its op histogram / gather-scatter counts / dtype
    # census / cost estimates
    ir = on_disk.pop("ir_programs")
    assert set(ir["programs"]) == {p[0] for p in PROGRAMS}
    for key, prog in ir["programs"].items():
        assert prog["op_histogram"], key
        assert prog["dtype_census"], key
        assert prog["cost"] and prog["cost"]["flops"] > 0, key
        if key.startswith("train_step"):
            assert prog["scatters"] >= 1, key
        # a program that takes the state donates it; the fullshard
        # step's gradient program takes the table alone and writes none
        writes_state = key.startswith(("train_step", "update_step")) \
            and key != "train_step.fullshard.fm[fm]"
        assert prog["donated_args"] == ([0] if writes_state else []), key
    assert render_artifact(on_disk) == r1, (
        "checked-in engine_contracts.json AST sections are stale — "
        "regenerate with tools/xflowlint.py --write-contracts and "
        "review the diff")
    data = json.loads(r1)
    assert set(data["engines"]) == set(ENGINE_MODULES)
    assert data["declared_mesh_axes"] == ["data", "table"]


def test_contract_matrix_covers_known_invariants():
    """Spot-check the matrix against facts the builders guarantee
    today: every program that takes the state donates it, every engine
    covers the core trace scopes, the fullshard builder names both mesh
    axes."""
    data = json.load(open(os.path.join(REPO_ROOT, "tools",
                                       "engine_contracts.json")))
    train_programs = 0
    for rel, eng in data["engines"].items():
        for name, prog in eng["programs"].items():
            if prog["function"] == "grad_part":
                # the fullshard step's first program reads the table
                # and writes no state: nothing to donate
                assert prog["donate_argnums"] == [], (rel, name)
            elif name.startswith(("train_step", "update_step")):
                train_programs += 1
                assert prog["donate_argnums"] == [0], (rel, name)
    # one program per builder that writes the state (the fullshard
    # step's is its update)
    assert train_programs == 3
    fs = data["engines"]["xflow_tpu/parallel/sorted_fullshard.py"]
    assert fs["axes_referenced"] == ["data", "table"]
    for rel, eng in data["engines"].items():
        if rel == "xflow_tpu/parallel/train_step.py":
            continue  # inherits the shared step's scopes by delegation
        assert {"gather", "rows", "update", "health"} <= set(eng["scopes"]), rel


def test_cli_check_contracts_green_then_drift_exits_4(tmp_path):
    """--check-contracts: 0 on a faithful tree, 4 (distinct from
    finding growth) when a builder's contract changed without
    regenerating the artifact."""
    root = tmp_path / "tree"
    for rel in ("xflow_tpu/train/step.py", "xflow_tpu/parallel/mesh.py",
                "xflow_tpu/parallel/train_step.py",
                "xflow_tpu/parallel/sorted_fullshard.py",
                "tools/engine_contracts.json"):
        dst = root / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(os.path.join(REPO_ROOT, rel), dst)
    r = run_cli("--root", str(root), "--check-contracts")
    assert r.returncode == 0, (r.stdout, r.stderr)
    # drop the donation from one builder: contract drift, exit 4
    sf = root / "xflow_tpu/parallel/sorted_fullshard.py"
    sf.write_text(sf.read_text().replace("donate_argnums=(0,),", ""))
    r = run_cli("--root", str(root), "--check-contracts")
    assert r.returncode == 4 and "CONTRACT DRIFT" in r.stderr


def test_xf704_scope_drift_across_builders(tmp_path):
    """Renaming one builder's 'update' scope (present in every other
    builder) fires XF704 on that builder only."""
    root = tmp_path / "tree"
    for rel in ("xflow_tpu/train/step.py", "xflow_tpu/parallel/mesh.py",
                "xflow_tpu/parallel/train_step.py",
                "xflow_tpu/parallel/sorted_fullshard.py"):
        dst = root / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(os.path.join(REPO_ROOT, rel), dst)
    project = Project.load(str(root))
    assert [f for f in run_passes(project) if f.rule == "XF704"] == []
    sf = root / "xflow_tpu/parallel/sorted_fullshard.py"
    sf.write_text(sf.read_text().replace(
        'named_scope("update")', 'named_scope("updat")'))
    findings = [f for f in run_passes(Project.load(str(root)))
                if f.rule == "XF704"]
    assert len(findings) == 1
    assert findings[0].path == "xflow_tpu/parallel/sorted_fullshard.py"
    assert "'update'" in findings[0].message


def test_xf704_silent_on_partial_scan_without_shared_step():
    """A partial scan holding the parallel builders but NOT the shared
    single-device step must not false-fire XF704 on the delegating
    GSPMD builder: the shared step's scopes load from disk (like the
    mesh axes do)."""
    findings = lint(os.path.join(REPO_ROOT, "xflow_tpu", "parallel"))
    assert [f for f in findings if f.rule == "XF704"] == []


def test_xf704_partial_scan_matches_full_tree_verdict():
    """The comparison roster is always the full builder set (missing
    builders load from disk), so the exact --changed file set that used
    to false-fire — the shared step plus ONE parallel builder, where
    'every other builder' collapsed to the step's scope superset —
    stays clean, matching the full-tree verdict."""
    findings = lint(
        os.path.join(REPO_ROOT, "xflow_tpu", "train", "step.py"),
        os.path.join(REPO_ROOT, "xflow_tpu", "parallel",
                     "sorted_fullshard.py"))
    assert [f for f in findings if f.rule == "XF704"] == []


def test_hostsync_jit_construction_does_not_age(tmp_path):
    """Constructing a jit callable dispatches nothing: it must not age
    a same-iteration device value into exemption (XF110 stays live)."""
    mod = tmp_path / "m.py"
    mod.write_text(
        "import jax\n\n\nclass T:\n"
        "    def _fit(self, bs):\n"
        "        for b in bs:\n"
        "            s, m = self.train_step(None, b)\n"
        "            fn = jax.jit(lambda v: v)\n"
        "            x = float(m['loss'])\n"
    )
    findings = lint(str(mod), rules=["XF110"])
    assert [f.rule for f in findings] == ["XF110"]
    assert findings[0].line == 9


def test_xf704_intra_builder_leaf_spec_disagreement(tmp_path):
    """One builder declaring two different shardings for the same table
    leaf is contract drift between its own programs."""
    root = tmp_path / "tree"
    for rel in ("xflow_tpu/train/step.py", "xflow_tpu/parallel/mesh.py",
                "xflow_tpu/parallel/train_step.py",
                "xflow_tpu/parallel/sorted_fullshard.py"):
        dst = root / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(os.path.join(REPO_ROOT, rel), dst)
    sf = root / "xflow_tpu/parallel/sorted_fullshard.py"
    sf.write_text(
        sf.read_text()
        + "\n\n_declared = {\"wv\": NamedSharding(None, P(\"table\", None))}\n"
        + "_drifted = {\"wv\": NamedSharding(None, P(None, None))}\n"
    )
    findings = [f for f in run_passes(Project.load(str(root)))
                if f.rule == "XF704"]
    assert len(findings) == 1
    assert "'wv'" in findings[0].message


# ------------------------------------------------------ CLI: jobs/changed


def test_jobs_fanout_output_identical():
    """-j N must produce byte-identical findings to the serial sweep
    (the pre-commit speed path cannot change verdicts)."""
    bad = os.path.join(FIXTURES, "bad_hostsync.py")
    bad2 = os.path.join(FIXTURES, "bad_sharding_contract.py")
    serial = run_cli(bad, bad2, "--no-baseline", "--json")
    fanned = run_cli(bad, bad2, "--no-baseline", "--json", "--jobs", "2")
    assert serial.returncode == fanned.returncode == 1
    assert json.loads(serial.stdout)["new"] == json.loads(fanned.stdout)["new"]


def test_changed_lints_only_git_changed_files(tmp_path):
    """--changed in a scratch git repo: clean tree -> nothing to lint;
    a modified module -> linted and gated."""
    import subprocess as sp

    root = tmp_path / "repo"
    (root / "xflow_tpu").mkdir(parents=True)
    (root / "xflow_tpu" / "mod.py").write_text("x = 1\n")
    env = dict(os.environ,
               GIT_AUTHOR_NAME="t", GIT_AUTHOR_EMAIL="t@t",
               GIT_COMMITTER_NAME="t", GIT_COMMITTER_EMAIL="t@t")
    for cmd in (["git", "init", "-q"], ["git", "add", "-A"],
                ["git", "commit", "-qm", "seed"]):
        sp.run(cmd, cwd=root, env=env, check=True, capture_output=True)
    r = run_cli("--root", str(root), "--changed")
    assert r.returncode == 0 and "no lintable changed files" in r.stderr
    # introduce a finding in a tracked file -> --changed catches it
    (root / "xflow_tpu" / "mod.py").write_text(
        "import jax, time\n\n\n@jax.jit\ndef f(x):\n"
        "    return x + time.time()\n")
    r = run_cli("--root", str(root), "--changed")
    assert r.returncode == 1 and "XF101" in r.stdout


def test_partial_scan_never_stales_full_tree_only_rules(tmp_path):
    """XF402 (dead-key) only runs on full-tree scans: a partial scan
    that covers the entry's file must still not call it stale (it
    would block the --changed pre-commit path with a bogus exit 2)."""
    bl = tmp_path / "bl.json"
    base = Baseline([BaselineEntry(
        "XF402", "xflow_tpu/config.py", "m", reason="accepted dead key")])
    base.save(str(bl))
    r = run_cli(os.path.join(REPO_ROOT, "xflow_tpu", "config.py"),
                "--baseline", str(bl))
    assert r.returncode == 0, (r.stdout, r.stderr)


def test_baseline_staleness_scoped_to_scanned_paths():
    """A --changed-style partial scan must not call entries in
    untouched files stale (Baseline.split only_paths)."""
    base = Baseline([BaselineEntry("XF101", "a.py", "m", reason="legacy")])
    _new, _known, stale = base.split([], only_paths={"b.py"})
    assert stale == []
    _new, _known, stale = base.split([], only_paths={"a.py"})
    assert len(stale) == 1


# ----------------------------------------- seeded violations (acceptance)

SEEDS = [
    # (rule, module to copy, seed snippet appended, marker)
    ("XF101",
     "xflow_tpu/models/predict.py",
     "\nimport jax as _jax, time as _time\n\n\n"
     "@_jax.jit\ndef _seeded(x):\n"
     "    return x + _time.perf_counter()  # SEED\n",
     "SEED"),
    ("XF201",
     "xflow_tpu/models/predict.py",
     "\nimport jax as _jax\n\n\ndef _seeded(xs):\n"
     "    for _x in xs:\n"
     "        _jax.jit(lambda v: v)(_x)  # SEED\n",
     "SEED"),
    ("XF301",
     "xflow_tpu/serve/metrics.py",
     "\nimport threading as _th\n\n\nclass _Seeded:\n"
     "    def __init__(self):\n"
     "        self.n = 0\n"
     "        _th.Thread(target=self._loop, daemon=True).start()\n"
     "    def _loop(self):\n"
     "        self.n += 1  # SEED\n"
     "    def bump(self):\n"
     "        self.n += 1\n",
     "SEED"),
    ("XF401",
     "xflow_tpu/serve/metrics.py",
     "\ndef _seeded(cfg: 'Config'):\n"
     "    return cfg.serve.windw_ms  # SEED\n",
     "SEED"),
    ("XF501",
     "xflow_tpu/serve/metrics.py",
     "\ndef _seeded(app):\n"
     "    app.append({'kind': 'serve', 'qqps': 1})  # SEED\n",
     "{'kind': 'serve'"),
    ("XF110",
     "xflow_tpu/train/trainer.py",
     "\n\nclass _SeededSync:\n"
     "    def _fit(self, batches):\n"
     "        state = None\n"
     "        for b in batches:\n"
     "            state, m = self.train_step(state, b)\n"
     "            print(float(m['loss']))  # SEED\n",
     "SEED"),
    ("XF111",
     "xflow_tpu/train/trainer.py",
     "\n\nclass _SeededBranch:\n"
     "    def _fit(self, batches):\n"
     "        state = None\n"
     "        for b in batches:\n"
     "            state, m = self.train_step(state, b)\n"
     "            if m['update_ok']:  # SEED\n"
     "                break\n",
     "SEED"),
    ("XF701",
     "xflow_tpu/parallel/mesh.py",
     "\n\ndef _seeded_axis(mesh):\n"
     "    return NamedSharding(mesh, P('tabel', None))  # SEED\n",
     "SEED"),
    ("XF702",
     "xflow_tpu/parallel/mesh.py",
     "\n\ndef _seeded_donated(step, state, b):\n"
     "    jitted = jax.jit(step, donate_argnums=(0,))\n"
     "    out = jitted(state, b)\n"
     "    return out, state  # SEED\n",
     "SEED"),
    ("XF703",
     "xflow_tpu/parallel/mesh.py",
     "\n\ndef _seeded_nodonate():\n"
     "    def train_step(state, batch):\n"
     "        return state\n\n"
     "    return jax.jit(train_step)  # SEED\n",
     "SEED"),
]


@pytest.mark.parametrize("rule,module,snippet,marker",
                         SEEDS, ids=[s[0] for s in SEEDS])
def test_seeded_violation_in_real_module_caught(tmp_path, rule, module,
                                                snippet, marker):
    """ISSUE 10 acceptance: seed one violation of each rule class into a
    scratch copy of a REAL module; xflowlint reports the correct rule id
    at the correct file:line."""
    scratch = tmp_path / module
    scratch.parent.mkdir(parents=True, exist_ok=True)
    src = open(os.path.join(REPO_ROOT, module)).read()
    shutil.copy(os.path.join(REPO_ROOT, module), scratch)
    # the scratch copy must be CLEAN before seeding (real modules are)
    assert lint(str(scratch)) == [], "unseeded copy must lint clean"
    seeded_src = src + snippet
    scratch.write_text(seeded_src)
    want_line = next(i for i, ln in enumerate(seeded_src.splitlines(), 1)
                     if marker in ln)
    findings = lint(str(scratch))
    assert findings and {f.rule for f in findings} == {rule}, findings
    assert want_line in {f.line for f in findings}
    assert findings[0].path.endswith(os.path.basename(module))


# ----------------------------------------------------- schema/config seams


def test_schema_doc_parser_covers_every_shipped_kind():
    from xflow_tpu.analysis.passes.schema_drift import parse_schema_doc

    kinds, stamp = parse_schema_doc(
        os.path.join(REPO_ROOT, "docs", "OBSERVABILITY.md"))
    for kind in ("compile", "serve", "span", "heartbeat", "watchdog"):
        assert kind in kinds, f"doc lost its {kind} schema table"
    assert {"ts", "rank", "run_id", "gen", "world"} <= stamp
    assert "qps" in kinds["serve"] and "flagged_rank" in kinds["watchdog"]
    assert "dur_ms" in kinds["span"] and "op_scopes" in kinds["compile"]


def test_config_tree_parser_matches_dataclasses():
    from xflow_tpu.analysis.passes.config_keys import ConfigTree

    tree = ConfigTree.parse(os.path.join(REPO_ROOT, "xflow_tpu",
                                         "config.py"))
    assert set(tree.sections) == {"model", "optim", "data", "mesh",
                                  "train", "serve", "sync"}
    assert tree.resolve(("train", "log_every"))[0] == "ok"
    assert tree.resolve(("optim", "ftrl", "alpha"))[0] == "ok"
    assert tree.resolve(("num_slots",))[0] == "ok"  # Config property
    assert tree.resolve(("train", "nope"))[0] == "bad"
    assert tree.class_to_path["ServeConfig"] == ("serve",)


def test_dead_key_reported_only_on_full_tree(tmp_path):
    """XF402 needs the whole tree: partial lints must not scream."""
    findings = lint("good_clean.py", rules=["XF402"])
    assert findings == []


# ---------------------------------------------- IR tier (XF801-XF804)


def _toy_facts(**program_overrides):
    """Synthetic IR facts with one program, for rule-function tests."""
    prog = {
        "engine": "xflow_tpu/train/step.py",
        "recorder_name": "train_step",
        "op_histogram": {"gather": 1},
        "dtype_census": {"float32": 3},
        "gathers": 1,
        "scatters": 1,
        "chains": [],
        "converts": [],
        "scans": [],
        "donated_args": [0],
        "has_sharding_annotations": False,
        "cost": {"flops": 1.0, "bytes_accessed": 1.0},
        "config": {}, "batch": "rowmajor",
    }
    prog.update(program_overrides)
    return {"ok": True, "programs": {"train_step[lr]": prog}}


def _toy_chain(**overrides):
    chain = {
        "table": "w", "table_shape": [1 << 22], "table_dtype": "float32",
        "table_bytes": 4 << 22, "occurrences": 32768, "gathers": 1,
        "scatters": 1, "elementwise_table_ops": 31,
        "est_bytes_per_step": 123456,
        "gather_at": ["xflow_tpu/train/step.py", 61],
        "scatter_at": ["xflow_tpu/train/step.py", 61],
    }
    chain.update(overrides)
    return chain


def test_ir_analyze_jaxpr_finds_gather_scatter_chain():
    """XF801's detector on a toy program: big-table gather ->
    elementwise update -> scatter-add is one chain with the table's
    shape/dtype and the op counts."""
    import jax
    import jax.numpy as jnp

    from xflow_tpu.analysis.ir import analyze_jaxpr

    def step(table, idx, g):
        rows = table[idx]             # gather
        upd = rows * 0.5 - g          # elementwise on occurrence side
        table = table * 0.99          # table-wide elementwise sweep
        return table.at[idx].add(upd)  # scatter-add

    sds = jax.ShapeDtypeStruct
    tr = jax.jit(step).trace(
        sds((1 << 20,), jnp.float32), sds((4096,), jnp.int32),
        sds((4096,), jnp.float32))
    facts = analyze_jaxpr(tr.jaxpr.jaxpr, REPO_ROOT,
                          "xflow_tpu/train/step.py",
                          {(1 << 20,): "w"})
    assert facts["gathers"] == 1 and facts["scatters"] == 1
    (chain,) = facts["chains"]
    assert chain["table"] == "w"
    assert chain["table_shape"] == [1 << 20]
    assert chain["occurrences"] == 4096
    assert chain["elementwise_table_ops"] >= 1
    assert chain["est_bytes_per_step"] > 0


def test_ir_analyze_jaxpr_forward_only_gather_is_not_a_chain():
    """predict-style programs gather without scattering: no chain (the
    worklist records UPDATE paths, not forwards)."""
    import jax
    import jax.numpy as jnp

    from xflow_tpu.analysis.ir import analyze_jaxpr

    def fwd(table, idx):
        return table[idx].sum()

    sds = jax.ShapeDtypeStruct
    tr = jax.jit(fwd).trace(sds((1 << 20,), jnp.float32),
                            sds((4096,), jnp.int32))
    facts = analyze_jaxpr(tr.jaxpr.jaxpr, REPO_ROOT,
                          "xflow_tpu/train/step.py", {})
    assert facts["gathers"] == 1 and facts["scatters"] == 0
    assert facts["chains"] == []


def test_ir_analyze_jaxpr_detects_widening_convert():
    """XF802's detector: a big bf16 -> f32 convert is reported with
    shape and element count; small converts are ignored."""
    import jax
    import jax.numpy as jnp

    from xflow_tpu.analysis.ir import analyze_jaxpr

    def f(big, small):
        return (big.astype(jnp.float32).sum()
                + small.astype(jnp.float32).sum())

    sds = jax.ShapeDtypeStruct
    tr = jax.jit(f).trace(sds((1 << 20,), jnp.bfloat16),
                          sds((8,), jnp.bfloat16))
    facts = analyze_jaxpr(tr.jaxpr.jaxpr, REPO_ROOT,
                          "xflow_tpu/train/step.py", {})
    (cv,) = facts["converts"]
    assert cv["from"] == "bfloat16" and cv["to"] == "float32"
    assert cv["elems"] == 1 << 20


def test_ir_analyze_jaxpr_detects_scan_waste_and_clean_scan():
    """XF803's detector: a dead stacked output is reported; a scan
    whose outputs are consumed is clean. The carry leaf the body
    returns unchanged is forwarded out of the loop by lax.scan itself
    while tracing, so the jaxpr holds one carry and nothing to report
    about the other."""
    import jax
    import jax.numpy as jnp

    from xflow_tpu.analysis.ir import analyze_jaxpr

    sds = jax.ShapeDtypeStruct

    def wasteful(x, y):
        # carry leaf y rides unchanged; stacked ys are never read
        (x, y), _ys = jax.lax.scan(
            lambda c, _: ((c[0] + 1.0, c[1]), c[0]), (x, y), None,
            length=4)
        return x + y

    tr = jax.jit(wasteful).trace(sds((8,), jnp.float32),
                                 sds((8,), jnp.float32))
    facts = analyze_jaxpr(tr.jaxpr.jaxpr, REPO_ROOT,
                          "xflow_tpu/train/step.py", {})
    (sc,) = facts["scans"]
    assert sc["dead_outputs"] == [0]
    (eqn,) = [e for e in tr.jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert eqn.params["num_carry"] == 1 and eqn.params["num_consts"] == 1

    def clean(x):
        c, ys = jax.lax.scan(lambda c, _: (c + 1.0, c * 2.0), x, None,
                             length=4)
        return c + ys.sum()

    tr = jax.jit(clean).trace(sds((8,), jnp.float32))
    facts = analyze_jaxpr(tr.jaxpr.jaxpr, REPO_ROOT,
                          "xflow_tpu/train/step.py", {})
    assert facts["scans"] == []


def test_xf801_fires_only_for_unworklisted_chains(tmp_path):
    """A chain recorded in the checked-in worklist is silent; the same
    chain with a changed identity (op count) fires at the scatter's
    anchor."""
    from xflow_tpu.analysis.passes.ir_rules import (
        build_worklist, render_worklist, _xf801,
    )

    facts = _toy_facts(chains=[_toy_chain()])
    root = str(tmp_path)
    (tmp_path / "tools").mkdir()
    (tmp_path / "tools" / "fusion_worklist.json").write_text(
        render_worklist(build_worklist(facts)))
    assert _xf801(facts, root) == []
    # identity change (second scatter appears): XF801 fires
    drifted = _toy_facts(chains=[_toy_chain(scatters=2)])
    (f,) = _xf801(drifted, root)
    assert f.rule == "XF801"
    assert f.path == "xflow_tpu/train/step.py" and f.line == 61
    assert "train_step[lr]" in f.message and "'w'" in f.message


def test_xf801_everything_fires_without_a_worklist(tmp_path):
    from xflow_tpu.analysis.passes.ir_rules import _xf801

    facts = _toy_facts(chains=[_toy_chain()])
    (f,) = _xf801(facts, str(tmp_path))
    assert f.rule == "XF801"


def test_xf802_and_xf803_findings_carry_source_anchors():
    from xflow_tpu.analysis.passes.ir_rules import _xf802, _xf803

    facts = _toy_facts(
        converts=[{"from": "bfloat16", "to": "float32",
                   "shape": [1 << 20], "elems": 1 << 20,
                   "src": ["xflow_tpu/models/fm.py", 42]}],
        scans=[{"dead_outputs": [0], "length": 32,
                "src": ["xflow_tpu/train/step.py", 99]}])
    (f2,) = _xf802(facts)
    assert (f2.rule, f2.path, f2.line) == ("XF802",
                                           "xflow_tpu/models/fm.py", 42)
    assert "bfloat16 -> float32" in f2.message
    (f3,) = _xf803(facts)
    assert (f3.rule, f3.path, f3.line) == ("XF803",
                                           "xflow_tpu/train/step.py", 99)
    assert "no consumer" in f3.message


def test_xf804_donation_mismatch_against_real_ast_records(tmp_path):
    """XF804 compares the AST tier's extracted jit records against the
    lowered signature: a donation the AST cannot see (kwargs splat)
    fires at the jit's line; a matching contract is silent."""
    from xflow_tpu.analysis.passes.ir_rules import _xf804

    root = tmp_path / "tree"
    eng = root / "xflow_tpu" / "train"
    eng.mkdir(parents=True)
    src_literal = (
        "import jax\n\n\ndef build(recorder):\n"
        "    def train_step(state, batch):\n"
        "        return state\n"
        "    jitted = jax.jit(train_step, donate_argnums=(0,))\n"
        "    return recorder.wrap(\"train_step\", jitted)\n"
    )
    (eng / "step.py").write_text(src_literal)
    project = Project.load(str(root))
    facts = _toy_facts()  # lowered donation [0] — matches the literal
    assert _xf804(facts, project) == []
    # hide the donation from the AST tier: mismatch at the jit line
    (eng / "step.py").write_text(src_literal.replace(
        "donate_argnums=(0,)", "**{\"donate_argnums\": (0,)}"))
    findings = _xf804(facts, Project.load(str(root)))
    assert [f.rule for f in findings] == ["XF804"]
    assert findings[0].path == "xflow_tpu/train/step.py"
    assert findings[0].line == 7
    assert "donation" in findings[0].message


def test_xf804_name_matching_handles_fstring_holes():
    from xflow_tpu.analysis.passes.ir_rules import _name_matches

    assert _name_matches("train_step", "train_step")
    assert _name_matches("train_step.fullshard.{mode}",
                         "train_step.fullshard.fm")
    assert not _name_matches("train_step", "predict")
    assert not _name_matches("predict.fullshard.{mode}",
                             "train_step.fullshard.fm")


def test_checked_in_worklist_names_lr_and_fm_chains():
    """ISSUE 15 acceptance: tools/fusion_worklist.json names at least
    the LR and FM gather -> update -> scatter chains, each annotated
    with shape/dtype/bytes."""
    data = json.load(open(os.path.join(REPO_ROOT, "tools",
                                       "fusion_worklist.json")))
    by_table = {}
    for e in data["entries"]:
        by_table.setdefault(e["table"].split("/")[0], []).append(e)
    assert "w" in by_table, "LR chain missing from the worklist"
    assert "wv" in by_table, "FM chain missing from the worklist"
    lr = [e for e in by_table["w"]
          if e["program"].startswith("train_step[lr]")]
    assert lr and lr[0]["table_shape"] == [1 << 22]
    fm = [e for e in by_table["wv"]
          if e["program"] == "train_step[fm.sorted]"]
    assert fm, "the sorted fused-FM chain (the kernel arc's marquee " \
               "target) is missing"
    for e in data["entries"]:
        assert e["table_dtype"] in ("float32", "bfloat16"), e
        assert e["est_bytes_per_step"] > 0, e
        assert e["gathers"] >= 1 and e["scatters"] >= 1, e
        for loc in (e["gather_at"], e["scatter_at"]):
            path, _, line = loc.rpartition(":")
            assert os.path.exists(os.path.join(REPO_ROOT, path)), loc
            assert int(line) >= 1, loc
    # every sorted engine contributes a chain (the per-shard kernel
    # targets the mesh programs lower)
    programs = {e["program"] for e in data["entries"]}
    assert "train_step.fullshard.fm[fm]" in programs
    assert "train_step.gspmd[lr]" in programs


def test_worklist_identity_excludes_source_lines():
    """An unrelated edit that only moves a chain's anchor line must not
    fire XF801 (line drift is --check-worklist's job)."""
    from xflow_tpu.analysis.passes.ir_rules import chain_identity

    a = chain_identity("p", _toy_chain())
    b = chain_identity("p", _toy_chain(
        gather_at=["xflow_tpu/train/step.py", 999],
        scatter_at=["xflow_tpu/train/step.py", 999],
        est_bytes_per_step=1))
    assert a == b


def test_run_passes_default_tiers_exclude_ir(tmp_path):
    """Direct run_passes callers (and partial scans) stay AST-only:
    the IR tier runs only when the caller opts in."""
    from xflow_tpu.analysis.core import PASS_REGISTRY

    assert PASS_REGISTRY["ir-tier"][2] == "ir"
    mod = tmp_path / "m.py"
    mod.write_text("x = 1\n")
    import xflow_tpu.analysis.passes.ir_rules as ir_rules

    calls = []
    orig = ir_rules.ir_facts
    ir_rules.ir_facts = lambda root: calls.append(root) or (None, "test")
    try:
        project = Project.load(str(tmp_path), [str(mod)])
        run_passes(project)
        assert calls == []
        run_passes(project, tiers=("ast", "ir"))
        assert calls, "tiers=('ast','ir') must invoke the IR tier"
    finally:
        ir_rules.ir_facts = orig


def test_cli_ir_skip_notice_on_unimportable_tree(tmp_path):
    """A full-tree run over a tree the IR tier cannot import still runs
    every AST rule and prints the skip notice (graceful degradation)."""
    root = tmp_path / "tree"
    (root / "xflow_tpu").mkdir(parents=True)
    (root / "xflow_tpu" / "m.py").write_text(
        "import jax, time\n\n\n@jax.jit\ndef f(x):\n"
        "    return x + time.time()\n")
    r = run_cli("--root", str(root), "--no-baseline")
    assert r.returncode == 1
    assert "XF101" in r.stdout  # AST tier ran
    assert "IR tier skipped" in r.stderr


def test_xf202_fires_in_comprehension_and_not_after(tmp_path):
    """The dataflow comprehension retrofit: a comprehension target in a
    static slot varies per iteration (fires); the same name read after
    the comprehension is the outer binding (quiet)."""
    mod = tmp_path / "m.py"
    mod.write_text(
        "import jax\n\n\ndef f(x, n):\n    return x * n\n\n\n"
        "g = jax.jit(f, static_argnums=(1,))\n\n\n"
        "def comp(x, xs):\n"
        "    return [g(x, k) for k in xs]\n"
    )
    findings = lint(str(mod), rules=["XF202"])
    assert [f.rule for f in findings] == ["XF202"]
    assert findings[0].line == 12
    mod.write_text(
        "import jax\n\n\ndef f(x, n):\n    return x * n\n\n\n"
        "g = jax.jit(f, static_argnums=(1,))\n\n\n"
        "def after(x, xs, k):\n"
        "    ys = [y for y in xs]\n"
        "    return g(x, k)\n"
    )
    assert lint(str(mod), rules=["XF202"]) == []


def test_cli_artifact_gates_green_on_live_tree():
    """--check-contracts and --check-worklist both pass on the
    checked-in artifacts (ISSUE 15 acceptance; the same gates
    tools/smoke_lint.sh runs in CI)."""
    r = run_cli("--check-contracts", "--check-worklist")
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "matches" in r.stdout


# --------------------------------------------------------------- smoke gate


def test_smoke_lint_script(tmp_path):
    """tools/smoke_lint.sh: repo lint green, fixture corpus fires,
    baseline growth/shrink mechanics, seeded-violation drill, ruff
    layer when available — runnable standalone and from CI."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        ["bash", os.path.join(REPO_ROOT, "tools", "smoke_lint.sh"),
         str(tmp_path / "work")],
        capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "smoke_lint: OK" in r.stdout
