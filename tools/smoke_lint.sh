#!/usr/bin/env bash
# Static-analysis smoke gate (docs/STATIC_ANALYSIS.md):
#
# 1. Repo-wide xflowlint against the checked-in baseline must be GREEN
#    (zero unbaselined findings, zero stale baseline entries) —
#    includes the IR tier where jax is importable.
# 2. The fixture corpus must behave: every bad_* fixture fires exactly
#    its rule family (incl. the resurrected pre-PR 8 unlocked-appender
#    bug), every good_*/suppress_* fixture stays silent.
# 3. Baseline mechanics: a NEW finding exits 1; a baseline entry whose
#    finding was fixed exits 2 (the baseline-shrink check — fixing a
#    finding must also remove its entry); writing NEW entries without
#    --reason is refused (3) and a checked-in placeholder reason fails
#    the audit (3).
# 4. Seeded-violation drill: one violation of each rule class seeded
#    into a scratch copy of a REAL module is caught with the correct
#    rule id and file:line (4b: XF704 cross-engine drift via a
#    four-builder scratch tree with one trace scope renamed).
# 5. Engine-contract matrix: checked-in tools/engine_contracts.json is
#    current and byte-stable; un-regenerated builder edits exit 4
#    (distinct from finding growth). Builders-only scratch trees
#    compare the AST sections (the IR tier needs an importable tree).
# 6. IR tier (jaxpr rules + fusion worklist, docs/STATIC_ANALYSIS.md
#    "The IR tier"): the checked-in tools/fusion_worklist.json is
#    current and byte-stable, un-regenerated drift exits 4, and a
#    seeded violation of each XF801-XF804 rule in a FULL scratch tree
#    is caught at the exact file:line. SKIPPED with a notice where jax
#    is unimportable — AST-only linting keeps working.
# 7. ruff (the pinned generic-Python layer, pyproject.toml) runs clean
#    when installed; skipped with a notice where the container lacks it.
#
# Standalone:    bash tools/smoke_lint.sh [workdir]
# From pytest:   tests/test_xflowlint.py::test_smoke_lint_script
set -euo pipefail
cd "$(dirname "$0")/.."
ROOT="$(pwd)"

WORK="${1:-}"
[ -n "$WORK" ] || WORK="$(mktemp -d /tmp/xflow_lint.XXXXXX)"
mkdir -p "$WORK"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
export PYTHONPATH="$ROOT${PYTHONPATH:+:$PYTHONPATH}"

HAVE_IR=0
python -c "import jax" >/dev/null 2>&1 && HAVE_IR=1

echo "smoke_lint: workdir $WORK (IR tier available: $HAVE_IR)"

# ---- 1. repo-wide lint, baselined (full-tree runs include the IR
#         tier; --jobs 0 fans the per-module passes over a worker pool)
python tools/xflowlint.py --jobs 0
echo "smoke_lint: repo-wide lint green"

# ---- 2. fixture corpus ----------------------------------------------------
FIX="tests/fixtures/xflowlint"
expect_rules() { # expect_rules <fixture> <rule...>: exact rule-id set
    local fixture="$1"; shift
    local got want
    # xflowlint exits 1 on findings BY DESIGN — that's what we assert
    # on, so the substitution must not trip set -e/pipefail
    got=$({ python tools/xflowlint.py "$FIX/$fixture" --no-baseline \
        2>/dev/null || true; } | { grep -oE 'XF[0-9]+' || true; } \
        | sort -u | tr '\n' ' ')
    want=$(printf '%s\n' "$@" | sort -u | tr '\n' ' ')
    [ "$got" = "$want" ] || {
        echo "smoke_lint: $fixture: expected rules [$want] got [$got]"
        exit 1; }
}
expect_silent() {
    python tools/xflowlint.py "$FIX/$1" --no-baseline >/dev/null 2>&1 || {
        echo "smoke_lint: $1 must lint clean"; exit 1; }
}
expect_rules bad_jit_purity.py XF101
expect_rules bad_recompile.py XF201 XF202 XF203
expect_rules bad_lockset.py XF301     # the pre-PR 8 appender, forever
expect_rules bad_config.py XF401
expect_rules bad_schema.py XF501 XF502
expect_rules bad_shell.sh XF401 XF601
expect_rules bad_hostsync.py XF110 XF111
expect_rules bad_sharding_contract.py XF701 XF702 XF703
expect_silent good_lockset.py
expect_silent good_clean.py
expect_silent suppress_line.py
expect_silent suppress_file.py
echo "smoke_lint: fixture corpus behaves (8 bad fire, 4 good silent)"

# ---- 3. baseline growth + shrink mechanics --------------------------------
BL="$WORK/baseline.json"
rc=0; python tools/xflowlint.py "$FIX/bad_lockset.py" --no-baseline \
    >/dev/null 2>&1 || rc=$?
[ "$rc" -eq 1 ] || { echo "smoke_lint: new finding must exit 1, got $rc"; exit 1; }
# NEW entries need a justification: without --reason the write refuses
rc=0; python tools/xflowlint.py "$FIX/bad_lockset.py" --write-baseline \
    --baseline "$BL" >/dev/null 2>&1 || rc=$?
[ "$rc" -eq 3 ] || {
    echo "smoke_lint: reasonless --write-baseline must exit 3, got $rc"
    exit 1; }
python tools/xflowlint.py "$FIX/bad_lockset.py" --write-baseline \
    --baseline "$BL" --reason "smoke drill: fixture stays bad" >/dev/null
python tools/xflowlint.py "$FIX/bad_lockset.py" --baseline "$BL" >/dev/null \
    || { echo "smoke_lint: baselined lint must exit 0"; exit 1; }
# a checked-in placeholder reason fails the audit (the pre-fix
# --write-baseline default could land verbatim in the baseline)
sed 's/smoke drill: fixture stays bad/TODO: justify or fix/' "$BL" \
    > "$WORK/baseline_todo.json"
rc=0; python tools/xflowlint.py "$FIX/bad_lockset.py" \
    --baseline "$WORK/baseline_todo.json" >/dev/null 2>&1 || rc=$?
[ "$rc" -eq 3 ] || {
    echo "smoke_lint: placeholder baseline reason must fail the audit" \
         "(exit 3), got $rc"; exit 1; }
# "fix" the finding by linting the fixed fixture against the same
# baseline: every entry is now stale -> the gate demands the baseline
# shrink (exit 2)
rc=0; python tools/xflowlint.py "$FIX/good_lockset.py" --baseline "$BL" \
    >/dev/null 2>&1 || rc=$?
[ "$rc" -eq 2 ] || {
    echo "smoke_lint: stale baseline must exit 2 (shrink check), got $rc"
    exit 1; }
echo "smoke_lint: baseline growth/shrink/reason mechanics OK (1 / 3 / 0 / 3 / 2)"

# ---- 4. seeded violations in scratch copies of real modules ---------------
SCRATCH="$WORK/scratch"
seed() { # seed <rule> <module> <<< snippet-on-stdin
    local rule="$1" module="$2"
    local dst="$SCRATCH/$module"
    mkdir -p "$(dirname "$dst")"
    cp "$module" "$dst"
    cat >>"$dst"
    local line
    line=$(awk '/SEED$/{print NR; exit}' "$dst")
    local out
    out=$(python tools/xflowlint.py "$dst" --no-baseline 2>/dev/null || true)
    # herestrings, not `echo | grep -q`: pipefail + grep's early exit
    # can SIGPIPE the producer and fail a passing check
    grep -q "$rule" <<<"$out" || {
        echo "smoke_lint: seeded $rule in $module not caught"; echo "$out"
        exit 1; }
    grep -qE "${module##*/}:$line: $rule" <<<"$out" || {
        echo "smoke_lint: seeded $rule wanted ${module##*/}:$line"
        echo "$out"; exit 1; }
}
seed XF101 xflow_tpu/models/predict.py <<'EOF'


import time


@jax.jit
def _lint_seeded_purity(x):
    return x + time.perf_counter()  # SEED
EOF
seed XF201 xflow_tpu/models/predict.py <<'EOF'


def _lint_seeded_loop(xs):
    for _x in xs:
        jax.jit(lambda v: v)(_x)  # SEED
EOF
seed XF301 xflow_tpu/serve/metrics.py <<'EOF'


class _LintSeededRace:
    def __init__(self):
        self.n = 0
        threading.Thread(target=self._loop, daemon=True).start()

    def _loop(self):
        self.n += 1  # SEED

    def bump(self):
        self.n += 1
EOF
seed XF401 xflow_tpu/serve/metrics.py <<'EOF'


def _lint_seeded_key(cfg: "Config"):
    return cfg.serve.windw_ms  # SEED
EOF
seed XF501 xflow_tpu/serve/metrics.py <<'EOF'


def _lint_seeded_drift(app):
    app.append({"kind": "serve", "qqps": 1})  # SEED
EOF
seed XF110 xflow_tpu/train/trainer.py <<'EOF'


class _LintSeededSync:
    def _fit(self, batches):
        state = None
        for b in batches:
            state, m = self.train_step(state, b)
            print(float(m["loss"]))  # SEED
EOF
seed XF111 xflow_tpu/train/trainer.py <<'EOF'


class _LintSeededBranch:
    def _fit(self, batches):
        state = None
        for b in batches:
            state, m = self.train_step(state, b)
            if m["update_ok"]:  # SEED
                break
EOF
seed XF701 xflow_tpu/parallel/sorted_fullshard.py <<'EOF'


def _lint_seeded_axis(mesh):
    return NamedSharding(mesh, P("tabel", None))  # SEED
EOF
seed XF702 xflow_tpu/train/step.py <<'EOF'


def _lint_seeded_donated(step_fn, state, batch):
    jitted = jax.jit(step_fn, donate_argnums=(0,))
    out = jitted(state, batch)
    return out, state  # SEED
EOF
seed XF703 xflow_tpu/parallel/train_step.py <<'EOF'


def _lint_seeded_nodonate():
    def train_step(state, batch):
        return state

    return jax.jit(train_step)  # SEED
EOF
echo "smoke_lint: seeded-violation drill OK (10 rule classes, exact file:line)"

# ---- 4b. XF704 cross-engine drift needs every builder in one root ---------
DRIFT="$WORK/drift_tree"
mkdir -p "$DRIFT/xflow_tpu/train" "$DRIFT/xflow_tpu/parallel"
cp xflow_tpu/train/step.py "$DRIFT/xflow_tpu/train/"
cp xflow_tpu/parallel/train_step.py \
   xflow_tpu/parallel/sorted_fullshard.py xflow_tpu/parallel/mesh.py \
   "$DRIFT/xflow_tpu/parallel/"
python tools/xflowlint.py --root "$DRIFT" --no-baseline >/dev/null 2>&1 \
    || { echo "smoke_lint: faithful builder copies must lint clean"; exit 1; }
# rename one builder's "update" scope: every OTHER builder covers it
sed -i 's/named_scope("update")/named_scope("updat")/' \
    "$DRIFT/xflow_tpu/parallel/sorted_fullshard.py"
line=$(grep -n 'jax.named_scope' "$DRIFT/xflow_tpu/parallel/sorted_fullshard.py" \
    | head -1 | cut -d: -f1)
out=$(python tools/xflowlint.py --root "$DRIFT" --no-baseline 2>/dev/null || true)
grep -qE "sorted_fullshard.py:$line: XF704" <<<"$out" || {
    echo "smoke_lint: seeded XF704 scope drift not caught at" \
         "sorted_fullshard.py:$line"; echo "$out"; exit 1; }
echo "smoke_lint: XF704 cross-engine scope-drift drill OK"

# ---- 5. engine-contract matrix: checked in, byte-stable, drift-gated ------
# (docs/DISTRIBUTED.md "Engine contract matrix"; exit 4 is DISTINCT
# from finding growth so CI can tell "new bug" from "stale oracle")
python tools/xflowlint.py --check-contracts >/dev/null
CONTRACT="$WORK/contract_tree"
mkdir -p "$CONTRACT/xflow_tpu/train" "$CONTRACT/xflow_tpu/parallel" \
         "$CONTRACT/tools"
cp xflow_tpu/train/step.py "$CONTRACT/xflow_tpu/train/"
cp xflow_tpu/parallel/train_step.py \
   xflow_tpu/parallel/sorted_fullshard.py xflow_tpu/parallel/mesh.py \
   "$CONTRACT/xflow_tpu/parallel/"
cp tools/engine_contracts.json "$CONTRACT/tools/"
python tools/xflowlint.py --root "$CONTRACT" --check-contracts >/dev/null \
    || { echo "smoke_lint: contract check must pass on faithful copies"; exit 1; }
# byte stability: two consecutive regenerations are identical, and both
# match the checked-in artifact
python tools/xflowlint.py --root "$CONTRACT" --write-contracts >/dev/null
cp "$CONTRACT/tools/engine_contracts.json" "$WORK/contracts_r1.json"
python tools/xflowlint.py --root "$CONTRACT" --write-contracts >/dev/null
cmp -s "$WORK/contracts_r1.json" "$CONTRACT/tools/engine_contracts.json" || {
    echo "smoke_lint: contract artifact not byte-stable across two runs"
    exit 1; }
cmp -s "$WORK/contracts_r1.json" tools/engine_contracts.json || {
    echo "smoke_lint: checked-in engine_contracts.json is stale —" \
         "regenerate with tools/xflowlint.py --write-contracts"
    exit 1; }
# drift gate: change a builder's contract (drop the donation) without
# regenerating -> exit 4, distinct from finding growth (1) / stale (2)
sed -i 's/donate_argnums=(0,),//' \
    "$CONTRACT/xflow_tpu/parallel/sorted_fullshard.py"
rc=0; python tools/xflowlint.py --root "$CONTRACT" --check-contracts \
    >/dev/null 2>&1 || rc=$?
[ "$rc" -eq 4 ] || {
    echo "smoke_lint: contract drift must exit 4, got $rc"; exit 1; }
echo "smoke_lint: engine-contract matrix OK (stable, covered, drift=4)"

# ---- 6. IR tier: fusion worklist + XF801-XF804 seeded drills --------------
# (docs/STATIC_ANALYSIS.md "The IR tier"; mirrors the ruff pattern:
# jax unimportable => SKIP with a notice, AST-only linting keeps
# working — which section 1 already proved by running without it)
if [ "$HAVE_IR" -eq 1 ]; then
    # checked-in worklist is current (exit 4 on drift, like contracts)
    python tools/xflowlint.py --check-worklist >/dev/null
    # a FULL scratch tree (the IR tier imports and lowers it; the
    # import guard rejects partial trees, so builders-only copies
    # degrade to AST-only above)
    IRS="$WORK/ir_tree"
    mkdir -p "$IRS"
    cp -r xflow_tpu tools bench.py conftest.py "$IRS/"
    rm -rf "$IRS"/xflow_tpu/__pycache__
    # byte stability: two consecutive regenerations identical, both
    # matching the checked-in artifact
    python tools/xflowlint.py --root "$IRS" --write-worklist >/dev/null
    cp "$IRS/tools/fusion_worklist.json" "$WORK/worklist_r1.json"
    python tools/xflowlint.py --root "$IRS" --write-worklist >/dev/null
    cmp -s "$WORK/worklist_r1.json" "$IRS/tools/fusion_worklist.json" || {
        echo "smoke_lint: fusion worklist not byte-stable across two runs"
        exit 1; }
    cmp -s "$WORK/worklist_r1.json" tools/fusion_worklist.json || {
        echo "smoke_lint: checked-in fusion_worklist.json is stale —" \
             "regenerate with tools/xflowlint.py --write-worklist"
        exit 1; }
    # drift gate: a worklist that no longer matches the lowered
    # programs exits 4 (distinct from finding growth)
    sed -i 's/"gathers": 1/"gathers": 7/' "$IRS/tools/fusion_worklist.json"
    rc=0; python tools/xflowlint.py --root "$IRS" --check-worklist \
        >/dev/null 2>&1 || rc=$?
    [ "$rc" -eq 4 ] || {
        echo "smoke_lint: worklist drift must exit 4, got $rc"; exit 1; }
    # XF801: a chain missing from the worklist fires at the chain's
    # engine-module anchor (the LR two-pass chain anchors at the
    # loss_fn forward line in train/step.py)
    echo '{"entries": []}' > "$IRS/tools/fusion_worklist.json"
    line=$(grep -n 'logits = model.forward(tables, batch, cfg)' \
        "$IRS/xflow_tpu/train/step.py" | head -1 | cut -d: -f1)
    out=$(python tools/xflowlint.py --root "$IRS" --no-baseline \
        --rules XF801 2>/dev/null || true)
    grep -qE "step.py:$line: XF801" <<<"$out" || {
        echo "smoke_lint: seeded XF801 (empty worklist) not caught at" \
             "step.py:$line"; echo "$out"; exit 1; }
    cp tools/fusion_worklist.json "$IRS/tools/"
    # XF802: hidden bf16 -> f32 widening of the state tables
    sed -i 's|loss, grads = jax.value_and_grad(loss_fn)(state.tables, batch, model, cfg)|loss, grads = jax.value_and_grad(loss_fn)({k: v.astype(jnp.bfloat16).astype(jnp.float32) for k, v in state.tables.items()}, batch, model, cfg)  # IR-SEED-802|' \
        "$IRS/xflow_tpu/train/step.py"
    # XF803: a scan with a dead stacked output riding the step
    sed -i 's|^        metrics = {"loss": loss, "rows": batch\["row_mask"\].sum()}|        _c, _ys = jax.lax.scan(lambda c, _: (c, c * 2.0), loss, None, length=4)  # IR-SEED-803\n        metrics = {"loss": loss, "rows": batch["row_mask"].sum()}|' \
        "$IRS/xflow_tpu/train/step.py"
    # XF804: donation the AST tier cannot see (AST says undonated, the
    # lowered signature donates) — the contract matrix would rot
    sed -i 's|train_step = jax.jit(train_step, donate_argnums=(0,), \*\*pinned)|train_step = jax.jit(train_step, **{"donate_argnums": (0,)}, **pinned)  # IR-SEED-804|' \
        "$IRS/xflow_tpu/train/step.py"
    out=$(python tools/xflowlint.py --root "$IRS" --no-baseline \
        --rules XF802,XF803,XF804 2>/dev/null || true)
    for rule in XF802 XF803 XF804; do
        line=$(grep -n "IR-SEED-${rule#XF}" "$IRS/xflow_tpu/train/step.py" \
            | head -1 | cut -d: -f1)
        grep -qE "step.py:$line: $rule" <<<"$out" || {
            echo "smoke_lint: seeded $rule not caught at step.py:$line"
            echo "$out"; exit 1; }
    done
    echo "smoke_lint: IR tier OK (worklist stable+current, drift=4," \
         "XF801-XF804 seeded drills exact file:line)"
else
    echo "smoke_lint: jax not importable — IR tier drills SKIPPED" \
         "(AST-only linting verified above; the IR tier needs jax)"
fi

# ---- 7. ruff: the pinned generic-Python layer -----------------------------
if command -v ruff >/dev/null 2>&1; then
    ruff check .
    echo "smoke_lint: ruff layer green ($(ruff --version))"
else
    echo "smoke_lint: ruff not installed — generic layer SKIPPED" \
         "(pip install -e '.[lint]' to enable; pinned in pyproject.toml)"
fi

echo "smoke_lint: OK"
