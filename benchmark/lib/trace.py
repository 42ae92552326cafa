"""From the profiler's trace to numbers: device busy time, idle share,
a kernel's time, collectives not hidden behind compute, the operations
that took most time and the longest idle gaps by what the host was doing.

A trace here is plain data: {"planes": [{"name", "lines": [{"name",
"events": [[name, start_ns, duration_ns], ...]}]}]}. `load` reads the
profiler's `.xplane.pb` into it; the tests use small ones written by
hand and one cut from a real trace of the chip. Which planes are
devices and which line holds the operations is said by
`trace_names.json`, not here.
"""

from __future__ import annotations

import glob
import json
import os
import re


def load_names(root: str) -> dict:
    with open(os.path.join(root, "trace_names.json")) as f:
        return json.load(f)


def short_name(text: str) -> str:
    """A device event is named by its whole HLO line (`%gather.5 =
    f32[...] custom-call(...), custom_call_target="tpu_custom_call"`):
    keep the instruction's name, and mark a Pallas kernel."""
    name = text.split(" = ", 1)[0].lstrip("%")
    return name + "[pallas]" if 'custom_call_target="tpu_custom_call"' in text else name


def load(profile_dir: str) -> dict:
    """The newest `.xplane.pb` under `profile_dir` as plain data."""
    from jax.profiler import ProfileData

    hits = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"), recursive=True)
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir!r}")
    data = ProfileData.from_file(max(hits, key=os.path.getmtime))
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[short_name(e.name), float(e.start_ns), float(e.duration_ns)] for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_ops(trace: dict, names: dict, chips: int | None = None) -> list:
    """One list of [name, start, duration] per device, in plane order,
    from each device plane's operations line; control-flow wrappers
    listed under `skip_ops` (they cover their children) are dropped."""
    skip = re.compile(names["skip_ops"]) if names.get("skip_ops") else None
    out = []
    for plane in trace["planes"]:
        if not re.match(names["device_plane"], plane["name"]):
            continue
        for line in plane["lines"]:
            if line["name"] == names["op_line"]:
                ev = [e for e in line["events"] if e[2] > 0 and not (skip and skip.search(e[0]))]
                out.append(sorted(ev, key=lambda e: e[1]))
    return out[:chips] if chips else out


def host_spans(trace: dict, names: dict) -> list:
    """[name, start, duration] of every host event whose name matches
    `host_spans`: the harness's annotations and the runtime's calls."""
    pat = re.compile(names["host_spans"])
    out = []
    for plane in trace["planes"]:
        if not re.match(names["host_plane"], plane["name"]):
            continue
        for line in plane["lines"]:
            out.extend(e for e in line["events"] if e[2] > 0 and pat.search(e[0]))
    return sorted(out, key=lambda e: e[1])


def union(intervals: list) -> list:
    """Merged [start, end] of (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(merged: list) -> float:
    return sum(e - s for s, e in merged)


def window_of(per_device: list) -> tuple[float, float]:
    """The traced window: first operation's start to the last's end,
    over all devices."""
    starts = [ops[0][1] for ops in per_device if ops]
    ends = [max(e[1] + e[2] for e in ops) for ops in per_device if ops]
    return min(starts), max(ends)


def busy_seconds(per_device: list) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    busy = [_length(union([(e[1], e[1] + e[2]) for e in ops])) for ops in per_device]
    return sum(busy) / len(busy) / 1e9 if busy else 0.0


def op_seconds(per_device: list, pattern: str) -> float:
    """Summed duration of the operations matching `pattern`, averaged
    over the devices."""
    pat = re.compile(pattern)
    tot = [sum(e[2] for e in ops if pat.search(e[0])) for ops in per_device]
    return sum(tot) / len(tot) / 1e9 if tot else 0.0


def exposed_seconds(per_device: list, pattern: str) -> float:
    """Seconds in which an operation matching `pattern` (a collective)
    runs on a device and no other operation does, averaged over devices."""
    pat = re.compile(pattern)
    out = []
    for ops in per_device:
        coll = union([(e[1], e[1] + e[2]) for e in ops if pat.search(e[0])])
        rest = union([(e[1], e[1] + e[2]) for e in ops if not pat.search(e[0])])
        hidden, j = 0.0, 0
        for s, e in coll:
            while j < len(rest) and rest[j][1] <= s:
                j += 1
            k = j
            while k < len(rest) and rest[k][0] < e:
                hidden += min(e, rest[k][1]) - max(s, rest[k][0])
                k += 1
        out.append(_length(coll) - hidden)
    return sum(out) / len(out) / 1e9 if out else 0.0


def top_ops(per_device: list, n: int = 10) -> list:
    """[[name, seconds], ...] on the first device, numbered suffixes
    folded (`fusion.12` -> `fusion`)."""
    tot: dict = {}
    for name, _, dur in per_device[0] if per_device else []:
        key = re.sub(r"[.\d]+(?=(\.remat\d*)?(\[pallas\])?$)", "", name) or name
        tot[key] = tot.get(key, 0.0) + dur
    return [[k, v / 1e9] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(per_device: list, spans: list, n: int = 10) -> list:
    """The first device's idle time by what the host was doing:
    [[name, seconds], ...], each gap between operations given to the
    shortest host span that covers its middle ("none" where no span does)."""
    tot: dict = {}
    if not per_device or not per_device[0]:
        return []
    merged = union([(e[1], e[1] + e[2]) for e in per_device[0]])
    for (_, end), (start, _) in zip(merged, merged[1:]):
        mid = (end + start) / 2
        cover = [s for s in spans if s[1] <= mid <= s[1] + s[2]]
        name = min(cover, key=lambda s: s[2])[0] if cover else "none"
        tot[name] = tot.get(name, 0.0) + (start - end)
    return [[k, v / 1e9] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def module_runs(trace: dict, names: dict) -> dict:
    """{module name: [executions, seconds]} on the first device plane's
    line of whole program executions (`module_line`), the run id in
    brackets dropped (`jit_step(123)` -> `jit_step`)."""
    out: dict = {}
    for plane in trace["planes"]:
        if not re.match(names["device_plane"], plane["name"]):
            continue
        for line in plane["lines"]:
            if line["name"] == names.get("module_line", "XLA Modules"):
                for name, _, dur in line["events"]:
                    c = out.setdefault(re.sub(r"\(\d+\)$", "", name), [0, 0.0])
                    c[0] += 1
                    c[1] += dur / 1e9
        break
    return out


def summarize(trace: dict, names: dict, chips: int) -> dict:
    """Everything the harness keeps of a trace."""
    per_device = device_ops(trace, names, chips)
    if not per_device or not any(per_device):
        return {"devices": 0}
    t0, t1 = window_of(per_device)
    return {
        "devices": len(per_device),
        "window_s": (t1 - t0) / 1e9,
        "busy_s": busy_seconds(per_device),
        "ops": per_device,
        "device_ops": top_ops(per_device),
        "idle_gaps": idle_gaps(per_device, host_spans(trace, names)),
    }


def keep(trace: dict, out_dir: str, tag: str, cut_events: int = 4000) -> None:
    """For reading by hand: every plane and line with its names by time,
    and a cut of the first device plane and the host's spans."""
    import gzip

    os.makedirs(out_dir, exist_ok=True)
    outline = []
    for plane in trace["planes"]:
        lines = []
        for line in plane["lines"]:
            tot: dict = {}
            for name, _, dur in line["events"]:
                c = tot.setdefault(name, [0, 0.0])
                c[0] += 1
                c[1] += dur
            top = sorted(tot.items(), key=lambda kv: -kv[1][1])[:25]
            lines.append({"name": line["name"], "events": len(line["events"]),
                          "top": [[k, v[0], v[1] / 1e9] for k, v in top]})
        outline.append({"name": plane["name"], "lines": lines})
    with open(os.path.join(out_dir, tag + ".outline.json"), "w") as f:
        json.dump(outline, f, indent=1)
    cut = {"planes": []}
    for plane in trace["planes"]:
        if plane["name"].startswith("/device:") and not cut["planes"]:
            cut["planes"].append({"name": plane["name"], "lines": [
                {"name": ln["name"], "events": sorted(ln["events"], key=lambda e: e[1])[:cut_events]}
                for ln in plane["lines"]]})
    t_end = max((e[1] + e[2] for p in cut["planes"] for ln in p["lines"] for e in ln["events"]), default=0)
    for plane in trace["planes"]:
        if plane["name"].startswith("/host:CPU"):
            cut["planes"].append({"name": plane["name"], "lines": [
                {"name": ln["name"], "events": [e for e in ln["events"] if e[1] <= t_end][:cut_events]}
                for ln in plane["lines"]]})
    with gzip.open(os.path.join(out_dir, tag + ".cut.json.gz"), "wt") as f:
        json.dump(cut, f)
