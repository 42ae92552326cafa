#!/usr/bin/env python3
"""The unified perf ledger: one trajectory report over every bench
series at the repo root (docs/PERF.md "Bench trajectory").

The trajectory grew organically into four hand-named JSON families —
`BENCH_rNN.json` (per-round bench/smoke datapoints, some wrapped in a
driver envelope with the record under "parsed"), `BENCH_SCALE.json`
(the 10M-row end-to-end scale run), `MULTICHIP_rNN.json` (the
multichip dryrun verdicts), and `BENCH_SERVE.json` (the serving
loadgen) — which nothing consolidated or gated. This tool is the one
reader:

    python tools/perf_ledger.py                      # markdown to stdout
    python tools/perf_ledger.py --json ledger.json   # machine-readable
    python tools/perf_ledger.py --regress            # gate: exit 3 on
                                                     # cross-round regression

- **Consolidation**: every file normalizes into ledger entries
  `{series, round, metric, value, unit, ...}`; the markdown report
  renders the bench trajectory per metric, the multichip verdict
  trail, the scale run, and the serving datapoint in one place.
- **Regression gating** (`--regress`): within each (series, metric)
  group the NEWEST round's value must not fall more than
  `--regress-tol` (default 0.2 — the same tolerance
  metrics_report --regress uses) below the best previous round
  (latency-shaped `*_ms` metrics gate in the opposite direction); a
  multichip round flipping ok -> failed is a regression outright.
  Exit 3 with one line per failure. Rounds measured on different
  machines (the CPU smoke datapoints) are gated within their OWN
  metric name (`telemetry_examples_per_sec`), never against
  chip-scale numbers — metric names partition the comparison.
- **Roofline extrapolation**: the newest device-bench record
  extrapolates ×64 chips against the SNIPPETS.md Criteo-1TB v5e-64
  target (>=50M examples/sec => ~781k ex/s/chip), and when the record
  carries the CompileRecorder's cost stamps (`bytes_per_example`,
  bench.py), the per-chip target converts into "% of HBM bandwidth"
  (docs/PERF.md "Measured roofline").
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

POD_TARGET = 50_000_000  # SNIPPETS.md Criteo-1TB v5e-64 examples/sec
POD_CHIPS = 64
PER_CHIP_TARGET = POD_TARGET / POD_CHIPS

_ROUND_RE = re.compile(r"_r(\d+)\.json$")


def _finite(x) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


def _load(path: str):
    with open(path) as f:
        text = f.read()
    try:
        return json.loads(text)
    except json.JSONDecodeError:
        # JSONL (tools/step_decompose.py --json emits one record per
        # slice): a list of records, each normalized on its own
        return [json.loads(line) for line in text.splitlines() if line.strip()]


def _round_of(path: str):
    m = _ROUND_RE.search(os.path.basename(path))
    return int(m.group(1)) if m else None


def _lower_is_better(metric, unit) -> bool:
    """Latency-shaped metrics (step_decompose's ms/step slices, serve
    p50/p99, the lab's ns/element cells) improve DOWNWARD — 'best' and
    the regression direction flip relative to throughput. `_ratio`
    metrics (the pipeline host-gap ratio) are gap-shaped: a round that
    climbs back toward text-path ratios is the regression the
    packed-shard-cache gate exists to catch. `fresh_*` metrics
    (BENCH_FRESH.json, tools/freshness_report.py) are delay-shaped —
    seconds from ingested row to served prediction — and gate downward
    too: a round where data gets STALER is the regression."""
    return (
        str(metric).endswith(("_ms", "_ns", "_ns_per_element", "_ratio"))
        or str(metric).startswith("fresh_")
        or str(unit).startswith(("ms", "ns"))
    )


def normalize_bench(path: str, data) -> list[dict]:
    """One BENCH_rNN.json -> ledger entries. Two on-disk shapes: the
    driver envelope ({"parsed": {record}}) and the bare record."""
    rec = data.get("parsed") if isinstance(data, dict) and "parsed" in data else data
    if not isinstance(rec, dict) or "metric" not in rec:
        return []
    rnd = _round_of(path)
    if rnd is None and _finite(rec.get("round")):
        # records without a round-numbered filename (BENCH_PIPELINE.json,
        # pipeline_attrib --round) may stamp the round themselves
        rnd = int(rec["round"])
    entry = {
        "series": "bench",
        "round": rnd,
        "path": os.path.basename(path),
        "metric": rec["metric"],
        "value": rec.get("value"),
        "unit": rec.get("unit", ""),
        "vs_baseline": rec.get("vs_baseline"),
        "headline": True,  # the record's own metric field
    }
    for key in (
        "auc", "steps", "examples", "elapsed_s", "compile_time_s",
        "flops_per_example", "bytes_per_example", "ranks",
    ):
        if _finite(rec.get(key)):
            entry[key] = rec[key]
    out = [entry]
    # companion metrics ride in the same record (fm_examples_per_sec,
    # zipf_*, *_s24_*, e2e_*..., and the pipeline record's text-path
    # comparison leg) — each becomes its own gated group
    for key, v in rec.items():
        if key.endswith("_examples_per_sec") and key != rec["metric"] and _finite(v):
            out.append({
                "series": "bench",
                "round": rnd,
                "path": os.path.basename(path),
                "metric": key,
                "value": v,
                "unit": "examples/sec",
                "vs_baseline": rec.get(key.replace("_examples_per_sec", "_vs_baseline")),
            })
    if str(rec["metric"]).startswith("pipeline_"):
        # the host-gap record's own companion groups (BENCH_PIPELINE*,
        # tools/pipeline_attrib.py): the gap ratio gates DOWNWARD (a
        # round regressing back toward text-path ratios exits 3 —
        # `_lower_is_better` keys on the `_ratio` suffix), and the
        # cache-vs-text speedup gates upward like any throughput group
        if _finite(rec.get("host_gap_ratio")):
            out.append({
                "series": "bench",
                "round": rnd,
                "path": os.path.basename(path),
                "metric": "pipeline_host_gap_ratio",
                "value": rec["host_gap_ratio"],
                "unit": "x",
            })
        for key, v in rec.items():
            if key.startswith("speedup_vs_") and _finite(v):
                out.append({
                    "series": "bench",
                    "round": rnd,
                    "path": os.path.basename(path),
                    "metric": f"pipeline_{key}",
                    "value": v,
                    "unit": "x",
                })
    return out


def normalize_multichip(path: str, data) -> list[dict]:
    out = [{
        "series": "multichip",
        "round": _round_of(path),
        "path": os.path.basename(path),
        "metric": "multichip_ok",
        "value": 1.0 if data.get("ok") else 0.0,
        "unit": "bool",
        "n_devices": data.get("n_devices"),
        "skipped": bool(data.get("skipped")),
    }] if isinstance(data, dict) else []
    # multi-slice records (MULTICHIP_r06.json and later)
    # also carry the measured aggregate: the N-slice throughput and its
    # speedup over one slice. The `ok` flag above already folds the
    # >= 1.8x acceptance gate (the script computes it); these entries
    # ride the generic higher-is-better tolerance gate across rounds.
    if isinstance(data, dict) and not data.get("skipped"):
        for key, unit in (
            ("speedup", "x"),
            ("agg_examples_per_sec", "examples/sec"),
        ):
            if _finite(data.get(key)):
                out.append({
                    "series": "multichip",
                    "round": _round_of(path),
                    "path": os.path.basename(path),
                    "metric": f"multislice_{key}",
                    "value": float(data[key]),
                    "unit": unit,
                    "slices": data.get("slices"),
                    "skipped": False,
                })
    return out


def normalize_scale(path: str, data) -> list[dict]:
    if not isinstance(data, dict) or "models" not in data:
        return []
    out = []
    for model, rec in sorted(data["models"].items()):
        if not isinstance(rec, dict):
            continue
        entry = {
            "series": "scale",
            "round": None,
            "path": os.path.basename(path),
            "metric": f"e2e_{model}_examples_per_sec_scale",
            "value": rec.get("examples_per_sec_e2e"),
            "unit": "examples/sec",
        }
        for key in ("test_auc", "steps", "examples", "batch_size"):
            if _finite(rec.get(key)):
                entry[key] = rec[key]
        out.append(entry)
    return out


def normalize_lab(path: str, data) -> list[dict]:
    """One BENCH_LAB*.json (xflow_tpu/tools/bench_lab.py --suite core,
    docs/OBSERVABILITY.md "Sparse-primitive lab") -> ledger entries:
    the headline gather-latency cell plus one per-cell group
    (`lab_<op>_s<table_log2>_n<nnz_log2>_<dtype>`, ns/element — the
    latency direction, gated downward). The round comes from the
    record's own `round` stamp (operator-chosen) or the filename."""
    if not isinstance(data, dict) or not isinstance(data.get("cells"), list):
        return []
    rnd = data.get("round") if _finite(data.get("round")) else _round_of(path)
    rnd = int(rnd) if rnd is not None else None
    out: list[dict] = []
    if data.get("metric") and _finite(data.get("value")):
        entry = {
            "series": "lab",
            "round": rnd,
            "path": os.path.basename(path),
            "metric": data["metric"],
            "value": data["value"],
            "unit": data.get("unit", "ns/element"),
            "headline": True,
        }
        if isinstance(data.get("device"), str):
            entry["device"] = data["device"]
        if isinstance(data.get("headline_cell"), str):
            entry["cell"] = data["headline_cell"]
        out.append(entry)
    for c in data["cells"]:
        if not isinstance(c, dict) or not _finite(c.get("ns_per_element")):
            continue
        entry = {
            "series": "lab",
            "round": rnd,
            "path": os.path.basename(path),
            "metric": (
                f"lab_{c.get('op')}_s{c.get('table_log2')}"
                f"_n{c.get('nnz_log2')}_{c.get('dtype')}"
            ),
            "value": c["ns_per_element"],
            "unit": "ns/element",
        }
        if isinstance(data.get("device"), str):
            # cells inherit the record's device stamp: the roofline
            # citation's CPU-vs-chip preference needs it on every entry
            entry["device"] = data["device"]
        for key in ("time_ms", "flops", "bytes_accessed", "achieved_gbps",
                    "compile_time_s", "row_width"):
            if _finite(c.get(key)):
                entry[key] = c[key]
        out.append(entry)
    return out


def normalize_serve(path: str, data) -> list[dict]:
    if not isinstance(data, dict) or "metric" not in data:
        return []
    rnd = _round_of(path)
    if rnd is None and _finite(data.get("round")):
        # serve records without a round-numbered filename (the original
        # BENCH_SERVE.json) may stamp the round themselves (serve_bench
        # --round), joining the cross-round gate like any _rNN file
        rnd = int(data["round"])
    entry = {
        "series": "serve",
        "round": rnd,
        "path": os.path.basename(path),
        "metric": data["metric"],
        "value": data.get("value"),
        "unit": data.get("unit", ""),
    }
    for key in ("p50_ms", "p99_ms", "requests", "rows", "errors", "gen_flips",
                "trace_sample_rate", "trace_overhead_pct", "qps_untraced",
                "qps_traced", "slo_ms", "slo_attainment_pct"):
        if _finite(data.get(key)):
            entry[key] = data[key]
    if isinstance(data.get("traced"), bool):
        entry["traced"] = data["traced"]
    out = [entry]
    # the latency leg gates as its OWN group, downward (the _ms suffix
    # flips `_lower_is_better`): a round that doubles QPS by letting the
    # tail blow out is a regression, not a win — p99-at-SLO and QPS gate
    # together. Named off the record's metric so BENCH_SERVE /
    # BENCH_SERVE_FLEET / BENCH_TRACE rounds never cross-gate.
    if _finite(data.get("p99_ms")):
        out.append({
            "series": "serve",
            "round": rnd,
            "path": os.path.basename(path),
            "metric": f"{data['metric']}_p99_ms",
            "value": data["p99_ms"],
            "unit": "ms",
        })
    if _finite(data.get("slo_attainment_pct")):
        out.append({
            "series": "serve",
            "round": rnd,
            "path": os.path.basename(path),
            "metric": f"{data['metric']}_slo_attainment_pct",
            "value": data["slo_attainment_pct"],
            "unit": "%",
        })
    return out


def normalize_fresh(path: str, data) -> list[dict]:
    """One BENCH_FRESH*.json (tools/freshness_report.py --bench-json,
    docs/SERVING.md "Freshness") -> ledger entries: the headline
    end-to-end `fresh_delta_s` (ingested row -> first served
    prediction, fleet max) plus one group per Δ-decomposition leg
    (`fresh_<leg>_s`). Every group is delay-shaped: `_lower_is_better`
    keys on the `fresh_` prefix, so a round where data gets staler
    exits 3 under --regress."""
    if not isinstance(data, dict) or "metric" not in data:
        return []
    rnd = _round_of(path)
    if rnd is None and _finite(data.get("round")):
        rnd = int(data["round"])
    entry = {
        "series": "fresh",
        "round": rnd,
        "path": os.path.basename(path),
        "metric": data["metric"],
        "value": data.get("value"),
        "unit": data.get("unit", "s"),
        "headline": True,
    }
    for key in ("publications", "replicas", "traces", "segments"):
        if _finite(data.get(key)):
            entry[key] = data[key]
    out = [entry]
    for key, v in data.items():
        if key == data["metric"]:
            continue
        if key.startswith("fresh_") and key.endswith("_s") and _finite(v):
            out.append({
                "series": "fresh",
                "round": rnd,
                "path": os.path.basename(path),
                "metric": key,
                "value": v,
                "unit": "s",
            })
    return out


def collect(root: str, extra: list[str]) -> list[dict]:
    """Every ledger entry under `root` (+ explicit extra files), sorted
    by (series, metric, round)."""
    entries: list[dict] = []
    seen = set()

    def add(path: str):
        ap = os.path.abspath(path)
        if ap in seen or not os.path.exists(ap):
            return
        seen.add(ap)
        name = os.path.basename(path)
        try:
            data = _load(path)
        except (OSError, json.JSONDecodeError) as e:
            print(f"perf_ledger: warning: skipping {path!r}: {e}", file=sys.stderr)
            return
        if isinstance(data, list):
            for item in data:
                entries.extend(normalize_bench(path, item))
        elif name.startswith("MULTICHIP"):
            entries.extend(normalize_multichip(path, data))
        elif name == "BENCH_SCALE.json" or "SCALE" in name:
            entries.extend(normalize_scale(path, data))
        elif name.startswith("BENCH_LAB"):
            # the sparse-primitive lab matrix (bench_lab --suite core):
            # per-cell ns/element groups, gated downward
            entries.extend(normalize_lab(path, data))
        elif name.startswith("BENCH_FRESH"):
            # the streaming-freshness Δ record (freshness_report): the
            # end-to-end delta and its decomposition legs, gated downward
            entries.extend(normalize_fresh(path, data))
        elif name.startswith(("BENCH_SERVE", "BENCH_TRACE")):
            # BENCH_TRACE.json is the serve_bench record measured with
            # request tracing on (tools/smoke_trace.sh): same serve_qps
            # shape, plus the traced/trace_sample_rate/overhead stamps
            entries.extend(normalize_serve(path, data))
        else:
            entries.extend(normalize_bench(path, data))

    for pattern in ("BENCH_r*.json", "BENCH_SCALE*.json", "MULTICHIP_r*.json",
                    "BENCH_SERVE*.json", "BENCH_TRACE*.json",
                    "BENCH_LAB*.json", "BENCH_PIPELINE*.json",
                    "BENCH_FRESH*.json", "BENCH_CKPT*.json"):
        for path in sorted(glob.glob(os.path.join(root, pattern))):
            add(path)
    for path in extra:
        add(path)
    entries.sort(key=lambda e: (e["series"], str(e["metric"]),
                                e["round"] if e["round"] is not None else -1))
    return entries


def groups_of(entries: list[dict]) -> dict:
    """{(series, metric): [entries in round order]}."""
    out: dict = {}
    for e in entries:
        out.setdefault((e["series"], e["metric"]), []).append(e)
    return out


# ------------------------------------------------------------------ gating


def check_regressions(
    entries: list[dict], tol: float, metrics_re: str = ""
) -> list[str]:
    """Failures ([] = pass): within each (series, metric) group holding
    >= 2 rounds, the newest round's value must be >= (1 - tol) x the
    best previous round; a multichip ok -> failed flip (not skipped)
    fails outright. `metrics_re` scopes the gate to matching metric
    names (the CPU smoke datapoints are machine-local — an operator
    gates the series measured on ONE rig, not apples against oranges)."""
    problems: list[str] = []
    pat = re.compile(metrics_re) if metrics_re else None
    for (series, metric), group in sorted(groups_of(entries).items(), key=str):
        if pat is not None and not pat.search(str(metric)):
            continue
        rounds = [e for e in group if e["round"] is not None and _finite(e["value"])]
        if len(rounds) < 2:
            continue
        newest = rounds[-1]
        prev = rounds[:-1]
        if series == "multichip" and metric == "multichip_ok":
            if newest.get("skipped"):
                continue
            if newest["value"] < 1.0 and any(e["value"] >= 1.0 for e in prev):
                problems.append(
                    f"multichip round {newest['round']} failed "
                    f"({newest['path']}) after passing rounds "
                    f"{[e['round'] for e in prev if e['value'] >= 1.0]}"
                )
            continue
        if _lower_is_better(metric, newest.get("unit", "")):
            best_prev = min(e["value"] for e in prev)
            if best_prev > 0 and newest["value"] > (1.0 + tol) * best_prev:
                problems.append(
                    f"{metric}: round {newest['round']} = {newest['value']:.1f} "
                    f"> (1+{tol}) x best previous {best_prev:.1f} "
                    f"({newest['path']})"
                )
        else:
            best_prev = max(e["value"] for e in prev)
            if best_prev > 0 and newest["value"] < (1.0 - tol) * best_prev:
                problems.append(
                    f"{metric}: round {newest['round']} = {newest['value']:.1f} "
                    f"< (1-{tol}) x best previous {best_prev:.1f} "
                    f"({newest['path']})"
                )
    return problems


# ---------------------------------------------------------------- roofline


def roofline(entries: list[dict], hbm_gbps: float) -> dict:
    """The extrapolation block: newest device-bench headline x 64 chips
    vs the pod target, plus the HBM-bandwidth conversion when the
    record carries bytes_per_example (bench.py's CompileRecorder
    stamp)."""
    # device-bench headline records (the record's own metric field),
    # newest round; telemetry_* smoke datapoints are CPU numbers with
    # no roofline meaning and stay out — and so do the pipeline_*
    # host-gap records (BENCH_PIPELINE.json): their e2e rate is the
    # HOST-limited number, extrapolating it x64 chips would silently
    # replace the device headline with the gap it measures
    heads = [
        e for e in entries
        if e["series"] == "bench" and e["round"] is not None
        and e.get("headline") and _finite(e["value"])
        and str(e["metric"]).endswith("_examples_per_sec")
        and not str(e["metric"]).startswith(("telemetry", "pipeline"))
    ]
    if not heads:
        return {}
    newest = max(heads, key=lambda e: e["round"])
    out = {
        "metric": newest["metric"],
        "round": newest["round"],
        "per_chip_examples_per_sec": newest["value"],
        "pod_extrapolated_examples_per_sec": newest["value"] * POD_CHIPS,
        "pod_target_examples_per_sec": POD_TARGET,
        "pct_of_pod_target": round(
            100.0 * newest["value"] * POD_CHIPS / POD_TARGET, 1
        ),
        "per_chip_target_examples_per_sec": PER_CHIP_TARGET,
        "vs_per_chip_target": newest.get("vs_baseline"),
    }
    bpe = newest.get("bytes_per_example")
    if _finite(bpe) and hbm_gbps > 0:
        # the measured-roofline conversion (docs/PERF.md): examples/sec
        # x modeled bytes/example = HBM bytes/sec the program must move
        out["bytes_per_example"] = bpe
        out["hbm_gbps_assumed"] = hbm_gbps
        out["target_pct_of_hbm_bw"] = round(
            100.0 * PER_CHIP_TARGET * bpe / (hbm_gbps * 1e9), 1
        )
        out["achieved_pct_of_hbm_bw"] = round(
            100.0 * newest["value"] * bpe / (hbm_gbps * 1e9), 1
        )
    # the latency citation: the extrapolation's "why the gap" line now
    # cites the lab's MEASURED gather cell (BENCH_LAB.json) instead of
    # docs/PERF.md's hand-derived ~11 ns/element figure
    gathers = [
        e for e in entries
        if e["series"] == "lab" and _finite(e["value"])
        and "gather" in str(e["metric"])
        and str(e.get("unit", "")).startswith("ns")
    ]
    if gathers:
        # prefer a chip-measured cell over a CPU smoke datapoint: the
        # citation replaces docs/PERF.md's hand-derived TPU figure, and
        # a machine-local CPU number must never outrank a chip number
        # just because it stamped a round
        pick = max(
            gathers,
            key=lambda e: (
                "cpu" not in str(e.get("device", "")).lower(),
                bool(e.get("headline")),
                e["round"] if e["round"] is not None else -1,
            ),
        )
        out["measured_gather_ns_per_element"] = pick["value"]
        out["gather_cell"] = str(pick.get("cell") or pick["metric"])
        if isinstance(pick.get("device"), str):
            out["gather_device"] = pick["device"]
            out["gather_is_cpu"] = "cpu" in pick["device"].lower()
    return out


# ---------------------------------------------------------------- rendering


def _fmt(v) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        if not math.isfinite(v):
            return "-"
        if abs(v) >= 10000:
            return f"{v:,.0f}"
        return f"{v:.4g}"
    return str(v)


def render_markdown(entries: list[dict], hbm_gbps: float) -> str:
    lines = ["# Perf ledger", ""]
    bench = groups_of([e for e in entries if e["series"] == "bench"])
    if bench:
        lines += ["## Bench trajectory (`BENCH_r*.json`)", "",
                  "| metric | rounds | first | best | newest | vs target/chip |",
                  "|---|---|---|---|---|---|"]
        for (_, metric), group in sorted(bench.items(), key=str):
            vals = [e for e in group if _finite(e["value"])]
            if not vals:
                continue
            rounds = [e["round"] for e in vals if e["round"] is not None]
            pick = min if _lower_is_better(metric, vals[-1].get("unit", "")) else max
            best = pick(vals, key=lambda e: e["value"])
            newest = vals[-1]
            lines.append(
                f"| {metric} | {_fmt(min(rounds)) if rounds else '-'}→"
                f"{_fmt(max(rounds)) if rounds else '-'} | {_fmt(vals[0]['value'])} "
                f"| {_fmt(best['value'])} (r{_fmt(best['round'])}) "
                f"| {_fmt(newest['value'])} | {_fmt(newest.get('vs_baseline'))} |"
            )
        lines.append("")
    multi = [e for e in entries if e["series"] == "multichip"
             and e["metric"] == "multichip_ok"]
    if multi:
        lines += ["## Multichip dryrun (`MULTICHIP_r*.json`)", "",
                  "| round | devices | verdict |", "|---|---|---|"]
        for e in sorted(multi, key=lambda e: e["round"] or -1):
            verdict = ("skipped" if e.get("skipped")
                       else "ok" if e["value"] else "FAILED")
            lines.append(f"| r{_fmt(e['round'])} | {_fmt(e.get('n_devices'))} "
                         f"| {verdict} |")
        lines.append("")
        # multi-slice rounds publish measured numbers too — print the
        # speedup trail under the verdict table
        speed = [e for e in entries if e["series"] == "multichip"
                 and e["metric"] == "multislice_speedup"
                 and _finite(e["value"])]
        for e in sorted(speed, key=lambda e: e["round"] or -1):
            agg = next(
                (a["value"] for a in entries
                 if a["series"] == "multichip"
                 and a["metric"] == "multislice_agg_examples_per_sec"
                 and a["round"] == e["round"] and _finite(a["value"])),
                None,
            )
            agg_txt = f", aggregate {agg:.0f} examples/sec" if agg else ""
            lines.append(
                f"multi-slice r{_fmt(e['round'])}: "
                f"{_fmt(e.get('slices'))} slice(s) at {e['value']:.2f}x "
                f"one slice{agg_txt}"
            )
        if speed:
            lines.append("")
    lab = groups_of([e for e in entries if e["series"] == "lab"])
    if lab:
        lines += ["## Sparse-primitive lab (`BENCH_LAB*.json`)", "",
                  "| cell | rounds | first | best | newest | GB/s |",
                  "|---|---|---|---|---|---|"]
        for (_, metric), group in sorted(lab.items(), key=str):
            vals = [e for e in group if _finite(e["value"])]
            if not vals:
                continue
            rounds = [e["round"] for e in vals if e["round"] is not None]
            best = min(vals, key=lambda e: e["value"])  # ns: lower is better
            newest = vals[-1]
            lines.append(
                f"| {metric} | {_fmt(min(rounds)) if rounds else '-'}→"
                f"{_fmt(max(rounds)) if rounds else '-'} "
                f"| {_fmt(vals[0]['value'])} "
                f"| {_fmt(best['value'])} (r{_fmt(best['round'])}) "
                f"| {_fmt(newest['value'])} "
                f"| {_fmt(newest.get('achieved_gbps'))} |"
            )
        lines.append("")
    pipe = groups_of([
        e for e in entries
        if e["series"] == "bench" and (
            str(e["metric"]).startswith("pipeline_")
            # the comparison legs pipeline_attrib --compare folds in,
            # whatever --compare-label named them (text_e2e_..., native_
            # e2e_..., the device-bound companion)
            or str(e["metric"]).endswith("_e2e_examples_per_sec")
            or str(e["metric"]) == "device_bound_examples_per_sec"
        )
    ])
    if pipe:
        # the host-gap trajectory in one place (BENCH_PIPELINE*,
        # docs/PERF.md "Host data plane"): e2e vs device-bound vs the
        # text-path comparison leg, ratio/speedup groups included —
        # the bench table above already gates these, this section is
        # the text-vs-cache story read top to bottom
        lines += ["## Input pipeline (`BENCH_PIPELINE*.json`, host gap)", "",
                  "| metric | rounds | first | newest |", "|---|---|---|---|"]
        for (_, metric), group in sorted(pipe.items(), key=str):
            vals = [e for e in group if _finite(e["value"])]
            if not vals:
                continue
            rounds = [e["round"] for e in vals if e["round"] is not None]
            lines.append(
                f"| {metric} | {_fmt(min(rounds)) if rounds else '-'}→"
                f"{_fmt(max(rounds)) if rounds else '-'} "
                f"| {_fmt(vals[0]['value'])} | {_fmt(vals[-1]['value'])} |"
            )
        lines.append("")
    scale = [e for e in entries if e["series"] == "scale"]
    if scale:
        lines += ["## Scale run (`BENCH_SCALE.json`, end-to-end)", "",
                  "| model | e2e ex/s | test AUC |", "|---|---|---|"]
        for e in scale:
            model = str(e["metric"]).replace("e2e_", "").replace(
                "_examples_per_sec_scale", "")
            lines.append(f"| {model} | {_fmt(e['value'])} "
                         f"| {_fmt(e.get('test_auc'))} |")
        lines.append("")
    serve = [e for e in entries if e["series"] == "serve"]
    if serve:
        # the source column keys the rows apart: BENCH_SERVE (solo),
        # BENCH_SERVE_FLEET (router), BENCH_TRACE (tracing on — its
        # overhead column is the request-tracing cost trajectory,
        # tools/smoke_trace.sh)
        lines += ["## Serving (`BENCH_SERVE*.json` / `BENCH_TRACE*.json`)", "",
                  "| source | metric | value | p50 ms | p99 ms | trace overhead |",
                  "|---|---|---|---|---|---|"]
        for e in serve:
            over = e.get("trace_overhead_pct")
            lines.append(f"| {e['path']} | {e['metric']} | {_fmt(e['value'])} "
                         f"| {_fmt(e.get('p50_ms'))} | {_fmt(e.get('p99_ms'))} "
                         f"| {_fmt(over) + '%' if over is not None else '-'} |")
        lines.append("")
    fresh = groups_of([e for e in entries if e["series"] == "fresh"])
    if fresh:
        # the Δ decomposition read top to bottom: the headline
        # end-to-end delta, then each leg of the stream -> train ->
        # publish -> serve loop. Lower is fresher; the bench gate above
        # already enforces the direction.
        lines += ["## Freshness (`BENCH_FRESH*.json`, ingested row → "
                  "served prediction)", "",
                  "| metric | rounds | first | best | newest |",
                  "|---|---|---|---|---|"]
        for (_, metric), group in sorted(fresh.items(), key=str):
            vals = [e for e in group if _finite(e["value"])]
            if not vals:
                continue
            rounds = [e["round"] for e in vals if e["round"] is not None]
            best = min(vals, key=lambda e: e["value"])  # s: lower = fresher
            lines.append(
                f"| {metric} | {_fmt(min(rounds)) if rounds else '-'}→"
                f"{_fmt(max(rounds)) if rounds else '-'} "
                f"| {_fmt(vals[0]['value'])} "
                f"| {_fmt(best['value'])} (r{_fmt(best['round'])}) "
                f"| {_fmt(vals[-1]['value'])} |"
            )
        lines.append("")
    roof = roofline(entries, hbm_gbps)
    if roof:
        lines += ["## Roofline extrapolation", ""]
        lines.append(
            f"- newest device headline: `{roof['metric']}` r{roof['round']} = "
            f"{_fmt(roof['per_chip_examples_per_sec'])} ex/s/chip "
            f"({_fmt(roof.get('vs_per_chip_target'))}x the "
            f"{_fmt(PER_CHIP_TARGET)} ex/s/chip pod share)"
        )
        lines.append(
            f"- x{POD_CHIPS} chips => "
            f"{_fmt(roof['pod_extrapolated_examples_per_sec'])} ex/s = "
            f"{roof['pct_of_pod_target']}% of the {_fmt(POD_TARGET)} ex/s "
            "pod target (assumes perfect scale-out; the multichip table "
            "above is the composition evidence, not this line)"
        )
        if "target_pct_of_hbm_bw" in roof:
            lines.append(
                f"- measured roofline: {_fmt(roof['bytes_per_example'])} "
                f"modeled bytes/example => the per-chip target is "
                f"{roof['target_pct_of_hbm_bw']}% of {_fmt(hbm_gbps)} GB/s "
                f"HBM; this chip achieves {roof['achieved_pct_of_hbm_bw']}%"
            )
        if "measured_gather_ns_per_element" in roof:
            # the trailing claim is honest about WHERE the cell was
            # measured: a CPU smoke cell tracks the lab's health, only
            # a chip cell is the latency wall the kernel arc must beat
            tail = (
                " — machine-local CPU datapoint; rerun the lab on a "
                "chip to refresh the latency wall"
                if roof.get("gather_is_cpu")
                else " — the latency wall the fused-kernel arc must beat"
            )
            lines.append(
                f"- measured gather random-access latency: "
                f"{_fmt(roof['measured_gather_ns_per_element'])} ns/element "
                f"(`{roof['gather_cell']}`, BENCH_LAB"
                + (f", {roof['gather_device']}" if "gather_device" in roof
                   else "")
                + ")" + tail
            )
        lines.append("")
    if len(lines) <= 2:
        lines.append("_no ledger entries found_")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="consolidate + gate the BENCH_*/MULTICHIP_*/BENCH_SERVE "
        "perf trajectory"
    )
    ap.add_argument("files", nargs="*", help="extra record files to fold in")
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="directory holding the series files "
        "(default: the repo root)")
    ap.add_argument("--json", default="", metavar="OUT",
                    help="write the normalized ledger JSON ('-' = stdout)")
    ap.add_argument("--markdown", default="-", metavar="OUT",
                    help="write the markdown report (default stdout; '' = off)")
    ap.add_argument("--regress", action="store_true",
                    help="gate: exit 3 when any metric's newest round "
                         "regressed beyond --regress-tol")
    ap.add_argument("--regress-tol", type=float, default=0.2,
                    help="allowed fractional drop vs the best previous round "
                         "(default 0.2, matching metrics_report --regress)")
    ap.add_argument("--metrics", default="", metavar="REGEX",
                    help="scope --regress to metric names matching this "
                         "regex (default: every group)")
    ap.add_argument("--hbm-gbps", type=float, default=819.0,
                    help="HBM bandwidth for the roofline conversion "
                         "(default 819 = v5e spec)")
    args = ap.parse_args(argv)

    entries = collect(args.root, args.files)
    if not entries:
        print("perf_ledger: no series files found", file=sys.stderr)
        return 2
    if args.markdown:
        md = render_markdown(entries, args.hbm_gbps)
        if args.markdown == "-":
            print(md)
        else:
            with open(args.markdown, "w") as f:
                f.write(md + "\n")
    if args.json:
        payload = json.dumps({
            "entries": entries,
            "roofline": roofline(entries, args.hbm_gbps),
        }, indent=1)
        if args.json == "-":
            print(payload)
        else:
            with open(args.json, "w") as f:
                f.write(payload + "\n")
    if args.regress:
        problems = check_regressions(entries, args.regress_tol, args.metrics)
        if problems:
            for p in problems:
                print(f"perf_ledger: REGRESSION: {p}", file=sys.stderr)
            return 3
        print(f"perf_ledger: no regression across "
              f"{len(groups_of(entries))} metric group(s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
