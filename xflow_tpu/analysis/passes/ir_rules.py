"""XF8xx — the IR tier's rule families (analysis/ir.py).

The AST tier answers "what does the source say"; these rules answer
"what does the LOWERED PROGRAM say", over jaxprs extracted in a pinned
subprocess (``python -m xflow_tpu.analysis.ir``; CPU, trace-only, no
execution). Each rule exists for a ROADMAP contract:

- **XF801 unworklisted-fusion-opportunity**: a gather → elementwise →
  scatter subgraph over a table-sized operand that is NOT recorded in
  the checked-in ``tools/fusion_worklist.json``. The worklist is the
  Pallas kernel arc's machine-checked target list (ROADMAP "[speed]
  fused Pallas sparse-update kernel"): every chain in the live tree is
  recorded there with shapes/dtypes/byte estimates, so the kernel PR
  starts from a gated oracle instead of re-deriving the hot path. A
  new chain (or a chain whose shape/dtype/op-count identity changed)
  must be reviewed into the worklist — regenerate with
  ``xflowlint --write-worklist``.
- **XF802 silent-dtype-promotion**: a ``convert_element_type``
  widening bf16/f16 to f32 over a large operand. FM's measured lever
  is FEWER BYTES (bf16 tables, docs/PERF.md); a hidden upcast silently
  pays the f32 bytes the config opted out of.
- **XF803 scan-carry-waste**: a ``lax.scan`` whose stacked outputs no
  consumer reads (length× memory for nothing — usually a refactor
  leftover).
- **XF804 ast-ir-contract-mismatch**: donation or in/out-sharding
  contracts declared at the AST tier (the XF7xx extraction feeding
  ``tools/engine_contracts.json``) that are absent or different in the
  lowered signature — the cross-check that keeps both tiers honest.
  A donation the AST cannot see (built through ``**kwargs``) or an
  in_shardings the lowering dropped would silently rot the contract
  matrix the unified-builder refactor diffs against.

Static-arg hazards stay with the AST tier (XF202/XF203): the captured
jit objects do not expose their static spec, and the lowered program
has already specialized on it.

Availability: the tier needs jax AND an importable tree under the lint
root. When either is missing the pass returns no findings and records
why in ``LAST_STATUS`` — the CLI prints the notice and the AST tier's
verdicts stand alone (scratch-copy AST-only linting keeps working).
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

from xflow_tpu.analysis.core import Finding, Project, register_pass

RULES = ("XF801", "XF802", "XF803", "XF804")

WORKLIST_REL = "tools/fusion_worklist.json"
SUBPROCESS_TIMEOUT_S = 600

# (state, detail): "ok" | "skipped"; the CLI reads this after run_passes
# to print the graceful-degradation notice
LAST_STATUS: tuple = ("ok", "")

# one extraction per root per process: the lint pass, the worklist gate,
# and the contracts-v2 gate all reuse it
_IR_CACHE: dict = {}


def ir_facts(root: str):
    """-> (facts dict, None) or (None, reason). Cached per root."""
    root = os.path.abspath(root)
    if root in _IR_CACHE:
        return _IR_CACHE[root]
    env = dict(os.environ)
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    try:
        r = subprocess.run(
            [sys.executable, "-m", "xflow_tpu.analysis.ir", "--root", root],
            capture_output=True, text=True, timeout=SUBPROCESS_TIMEOUT_S,
            env=env, cwd=root)
    except Exception as e:
        out = (None, f"IR subprocess failed: {type(e).__name__}")
        _IR_CACHE[root] = out
        return out
    if r.returncode != 0:
        reason = "jax or the tree is unavailable"
        try:
            reason = json.loads(r.stdout.strip().splitlines()[-1])["reason"]
        except Exception:
            if r.returncode != 5:
                reason = (f"IR subprocess exited {r.returncode}: "
                          f"{(r.stderr or '').strip()[-200:]}")
        out = (None, reason)
        _IR_CACHE[root] = out
        return out
    try:
        facts = json.loads(r.stdout.strip().splitlines()[-1])
    except Exception:
        out = (None, "IR subprocess produced unparseable output")
        _IR_CACHE[root] = out
        return out
    _IR_CACHE[root] = (facts, None)
    return facts, None


def reset_cache() -> None:
    _IR_CACHE.clear()


# ------------------------------------------------------------- worklist


def chain_identity(program: str, chain: dict) -> tuple:
    """What makes a chain "the same" across edits: its program, table,
    shape/dtype, and gather/scatter op counts. Source lines are
    excluded (an unrelated edit above the chain must not fire XF801 —
    line drift is --check-worklist's job, exit 4)."""
    return (program, chain["table"], tuple(chain["table_shape"]),
            chain["table_dtype"], chain["gathers"], chain["scatters"])


def build_worklist(facts: dict) -> dict:
    """The fusion worklist artifact from extracted IR facts."""
    entries = []
    for key in sorted(facts.get("programs", {})):
        prog = facts["programs"][key]
        for chain in prog.get("chains", []):
            entries.append({
                "program": key,
                "engine": prog["engine"],
                "table": chain["table"],
                "table_shape": chain["table_shape"],
                "table_dtype": chain["table_dtype"],
                "table_bytes": chain["table_bytes"],
                "occurrences": chain["occurrences"],
                "gathers": chain["gathers"],
                "scatters": chain["scatters"],
                "elementwise_table_ops": chain["elementwise_table_ops"],
                "est_bytes_per_step": chain["est_bytes_per_step"],
                "gather_at": _loc(chain["gather_at"]),
                "scatter_at": _loc(chain["scatter_at"]),
            })
    entries.sort(key=lambda e: (e["program"], e["table"],
                                tuple(e["table_shape"])))
    return {
        "_comment": (
            "Fusion worklist: every gather -> elementwise -> scatter "
            "chain in the lowered engine programs, extracted by "
            "xflowlint's IR tier (analysis/ir.py) — the Pallas "
            "sparse-update kernel arc's machine-checked target list "
            "(ROADMAP '[speed]', docs/PERF.md). Regenerate with "
            "`python tools/xflowlint.py --write-worklist`; CI fails "
            "with exit 4 on drift (--check-worklist) and XF801 fires "
            "on chains missing from this list."
        ),
        "jax_version": facts.get("jax_version"),
        "mesh": facts.get("mesh"),
        "entries": entries,
    }


def render_worklist(worklist: dict) -> str:
    return json.dumps(worklist, indent=2, sort_keys=True) + "\n"


def load_worklist(root: str):
    path = os.path.join(root, *WORKLIST_REL.split("/"))
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            return json.load(f)
    except Exception:
        return None


def _loc(src) -> str:
    if not src:
        return ""
    return f"{src[0]}:{src[1]}"


def _split_loc(src, fallback_path: str):
    if src:
        return src[0], int(src[1])
    return fallback_path, 1


# ------------------------------------------------------- contracts v2


def ir_contract_section(facts: dict) -> dict:
    """The per-program jaxpr section of contracts v2: op histogram,
    gather/scatter counts, dtype census, flop/byte estimates."""
    programs = {}
    for key in sorted(facts.get("programs", {})):
        p = facts["programs"][key]
        programs[key] = {
            "engine": p["engine"],
            "recorder_name": p["recorder_name"],
            "op_histogram": p["op_histogram"],
            "gathers": p["gathers"],
            "scatters": p["scatters"],
            "dtype_census": p["dtype_census"],
            "donated_args": p["donated_args"],
            "has_sharding_annotations": p["has_sharding_annotations"],
            "cost": p["cost"],
        }
    return {
        "jax_version": facts.get("jax_version"),
        "device_count": facts.get("device_count"),
        "mesh": facts.get("mesh"),
        "programs": programs,
    }


# ------------------------------------------------------------ the rules


def _xf801(facts: dict, root: str) -> list:
    worklist = load_worklist(root) or {"entries": []}
    # worklist entries carry exactly the keys chain_identity reads, so
    # the suppression set and the identity definition cannot drift
    known = {chain_identity(e["program"], e)
             for e in worklist.get("entries", [])}
    findings = []
    for key in sorted(facts.get("programs", {})):
        prog = facts["programs"][key]
        for chain in prog.get("chains", []):
            if chain_identity(key, chain) in known:
                continue
            path, line = _split_loc(chain["scatter_at"] or
                                    chain["gather_at"], prog["engine"])
            mb = chain["est_bytes_per_step"] / 1e6
            findings.append(Finding(
                rule="XF801", path=path, line=line,
                message=(
                    f"fusion opportunity not in {WORKLIST_REL}: program "
                    f"{key} streams table {chain['table']!r} "
                    f"{chain['table_shape']}/{chain['table_dtype']} "
                    f"through {chain['gathers']} gather(s) + "
                    f"{chain['scatters']} scatter(s) + "
                    f"{chain['elementwise_table_ops']} table-wide "
                    f"elementwise op(s) (~{mb:.0f} MB/step unfused) — "
                    "the Pallas kernel arc's target shape"
                ),
                hint="review the chain into the worklist: `python "
                     "tools/xflowlint.py --write-worklist` and commit "
                     "the diff (it is the kernel arc's acceptance "
                     "oracle)",
            ))
    return findings


def _xf802(facts: dict) -> list:
    findings = []
    for key in sorted(facts.get("programs", {})):
        prog = facts["programs"][key]
        for cv in prog.get("converts", []):
            path, line = _split_loc(cv["src"], prog["engine"])
            findings.append(Finding(
                rule="XF802", path=path, line=line,
                message=(
                    f"silent dtype promotion in program {key}: "
                    f"{cv['from']} -> {cv['to']} over shape "
                    f"{cv['shape']} ({cv['elems']} elements) — pays "
                    f"the {cv['to']} bytes the {cv['from']} config "
                    "opted out of (FM's bytes lever, docs/PERF.md)"
                ),
                hint="keep the compute in the narrow dtype or make "
                     "the upcast explicit at a documented site",
            ))
    return findings


def _xf803(facts: dict) -> list:
    findings = []
    for key in sorted(facts.get("programs", {})):
        prog = facts["programs"][key]
        for sc in prog.get("scans", []):
            path, line = _split_loc(sc["src"], prog["engine"])
            findings.append(Finding(
                rule="XF803", path=path, line=line,
                message=f"scan-carry waste in program {key}: stacked "
                        f"output(s) {sc['dead_outputs']} have no "
                        f"consumer (length={sc['length']}: the whole "
                        "stack is materialized for nothing)",
                hint="drop the dead output (return None from the body)",
            ))
    return findings


def _ast_jit_records(project: Project) -> list:
    """(engine rel, rec) for every recorder-named jit the AST tier
    extracted from the engine builders (rec carries donate/static/
    shardings/line — sharding_contract's raw per-jit records)."""
    from xflow_tpu.analysis.passes.sharding_contract import _analyze

    _findings, engines = _analyze(project)
    out = []
    for rel, mc in sorted(engines.items()):
        for rec in mc.jits:
            if rec.get("name"):
                out.append((rel, rec))
    return out


def _name_matches(ast_name: str, ir_name: str) -> bool:
    """AST names may carry f-string holes ('train_step.fullshard.'
    '{mode}') — match them as wildcards against the concrete lowered
    name."""
    if ast_name == ir_name:
        return True
    if "{" not in ast_name:
        return False
    pat = re.escape(ast_name)
    pat = re.sub(r"\\\{[^}]*\\\}", r"[^\\s]+", pat)
    return re.fullmatch(pat, ir_name) is not None


def _xf804(facts: dict, project: Project) -> list:
    records = _ast_jit_records(project)
    findings = []
    for key in sorted(facts.get("programs", {})):
        prog = facts["programs"][key]
        matches = [(rel, rec) for rel, rec in records
                   if rel == prog["engine"]
                   and _name_matches(rec["name"], prog["recorder_name"])]
        if not matches:
            continue  # program jitted outside the engine modules
        # several jits may share one recorder name (contract() dedups
        # them with a '#n' suffix): the lowered program came from ONE
        # of them, so fire only when NO matching record agrees — a
        # duplicate that does agree must not false-fire, and a real
        # mismatch shared by all of them must not hide
        ir_donate = set(prog["donated_args"])

        def ast_donate(rec):
            out = {x for x in rec["donate_argnums"]
                   if isinstance(x, int)}
            if "state" in rec["donate_argnums"]:
                out.add(0)
            return out

        rel, rec = matches[0]
        if all(ast_donate(r) != ir_donate for _rel, r in matches):
            findings.append(Finding(
                rule="XF804", path=rel, line=rec["line"],
                message=(
                    f"AST/IR contract mismatch for program "
                    f"{rec['name']!r}: AST-tier donation "
                    f"{sorted(ast_donate(rec))} != lowered donation "
                    f"{sorted(ir_donate)} — the contract matrix "
                    "(tools/engine_contracts.json) no longer reflects "
                    "the program that actually runs"
                ),
                hint="declare donation where the AST tier can see it "
                     "(a literal donate_argnums=(...) on the jit call) "
                     "or fix the lowering",
            ))
        ast_sharded = lambda r: r["in_shardings"] is not None \
            or r["out_shardings"] is not None
        if all(ast_sharded(r) for _rel, r in matches) \
                and not prog["has_sharding_annotations"]:
            findings.append(Finding(
                rule="XF804", path=rel, line=rec["line"],
                message=(
                    f"AST/IR contract mismatch for program "
                    f"{rec['name']!r}: in/out shardings declared at the "
                    "AST tier but the lowered module carries no "
                    "sharding annotations — the program would run "
                    "replicated"
                ),
                hint="check the in_shardings/out_shardings actually "
                     "reach jax.jit",
            ))
    return findings


@register_pass("ir-tier", RULES, scope="ir")
def run(project: Project) -> list:
    """The IR tier. Runs only when the CLI enables the 'ir' tier
    (full-tree runs with jax importable; `--ir` forces, `--no-ir`
    disables)."""
    global LAST_STATUS
    facts, reason = ir_facts(project.root)
    if facts is None:
        LAST_STATUS = ("skipped", reason or "unavailable")
        return []
    detail = ""
    if facts.get("errors"):
        broken = ", ".join(e["program"] for e in facts["errors"])
        detail = f"programs failed to lower: {broken}"
    LAST_STATUS = ("ok", detail)
    findings = []
    findings.extend(_xf801(facts, project.root))
    findings.extend(_xf802(facts))
    findings.extend(_xf803(facts))
    findings.extend(_xf804(facts, project))
    return findings
