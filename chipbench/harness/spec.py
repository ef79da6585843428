"""The benchmark's data files, found by name.

Everything that belongs to one configuration, one traffic mix, one cell
or one metric sits in a file of its own under ``chipbench/``; a later PR
adds files and edits none. ``BENCHMARK.json`` at the root of the repo is
:func:`benchmark_json` of these files (a test holds the two together).
"""

from __future__ import annotations

import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
COMMAND = ["python3", "-m", "chipbench.run"]
RUN_SECONDS_FILE = "run_seconds.json"


def _check_name(name: str, what: str):
    if not NAME.match(name):
        raise ValueError(f"{what} {name!r}: not a name (letters, digits, "
                         f"'_', '.', '-', at most 64)")


def _line(text: str, what: str):
    if not (1 <= len(text) <= 200) or "\n" in text or "\t" in text:
        raise ValueError(f"{what}: 1 to 200 characters on one line")


def load(kind: str, name: str, root: str = ROOT) -> dict:
    """``<root>/<kind>/<name>.json`` with its ``name`` filled in."""
    _check_name(name, kind)
    with open(os.path.join(root, kind, name + ".json")) as f:
        out = json.load(f)
    out["name"] = name
    return out


def names(kind: str, root: str = ROOT):
    return sorted(f[:-5] for f in os.listdir(os.path.join(root, kind))
                  if f.endswith(".json"))


def metric(kind: str, name: str, root: str = ROOT) -> dict:
    m = load(kind, name, root)
    if not UNIT.match(m["unit"]):
        raise ValueError(f"{kind}/{name}: unit {m['unit']!r}")
    if m["better"] not in ("lower", "higher"):
        raise ValueError(f"{kind}/{name}: better {m['better']!r}")
    if m["source"] not in SOURCES:
        raise ValueError(f"{kind}/{name}: source {m['source']!r}")
    return m


def cell(name: str, root: str = ROOT) -> dict:
    """One cell with its configuration, traffic mix and metrics resolved,
    and every cross-reference checked."""
    c = load("cells", name, root)
    _line(c["why"], f"cells/{name} why")
    if c["chips"] not in (1, 4):
        raise ValueError(f"cells/{name}: chips {c['chips']}")
    c["config_spec"] = load("configs", c["config"], root)
    c["traffic_spec"] = load("traffic", c["traffic"], root)
    c["end_to_end_specs"] = [metric("end_to_end", m, root)
                             for m in c["end_to_end"]]
    c["per_layer_specs"] = [metric("layer_metrics", m, root)
                            for m in c["per_layer"]]
    # readings kept beside the result with no claim to move a metric
    # under a bound (``recorded/<name>.json``, the readers' own schema)
    c["recorded_specs"] = [metric("recorded", m, root)
                           for m in c.get("recorded", [])]
    if "setup_s" not in c["end_to_end"] or len(c["end_to_end"]) < 2:
        raise ValueError(f"cells/{name}: setup_s and one more end-to-end "
                         f"metric")
    if not c["per_layer"]:
        raise ValueError(f"cells/{name}: no per-layer metric")
    for m in c["per_layer_specs"]:
        _line(m["layer"], f"layer_metrics/{m['name']} layer")
        if m["moves"] not in c["end_to_end"]:
            raise ValueError(
                f"layer_metrics/{m['name']} moves {m['moves']!r}, which "
                f"cell {name} does not report")
    return c


def run_seconds(root: str = ROOT) -> int:
    with open(os.path.join(root, RUN_SECONDS_FILE)) as f:
        return int(json.load(f)["run_seconds"])


def benchmark_json(root: str = ROOT) -> dict:
    """What ``BENCHMARK.json`` has to hold, from the files alone."""
    rel = os.path.basename(root)
    cells = [cell(n, root) for n in names("cells", root)]
    cells.sort(key=lambda c: (c.get("order", 1 << 30), c["name"]))
    configs, seen = [], set()
    for c in cells:
        spec = c["config_spec"]
        if spec["name"] in seen:
            continue
        seen.add(spec["name"])
        _line(spec["why"], f"configs/{spec['name']} why")
        for key in spec["reduced"]:
            _check_name(key, "reduced key")
        configs.append({
            "name": spec["name"], "source": spec["source"],
            "file": f"{rel}/configs/{spec['name']}.json",
            "reduced": spec["reduced"], "why": spec["why"]})

    def reporting(key):
        out = {}
        for c in cells:
            for m in c[key]:
                out.setdefault(m, []).append(c["name"])
        return out

    e2e_cells = reporting("end_to_end")
    layer_cells = reporting("per_layer")
    end_to_end = []
    for n in sorted(e2e_cells, key=lambda n: (n == "setup_s", n)):
        m = metric("end_to_end", n, root)
        row = {"name": n, "unit": m["unit"], "better": m["better"],
               "bound": m["bound"], "source": m["source"]}
        if len(e2e_cells[n]) < len(cells):
            row["workloads"] = e2e_cells[n]
        end_to_end.append(row)
    per_layer = []
    for n in sorted(layer_cells):
        m = metric("layer_metrics", n, root)
        per_layer.append({
            "name": n, "unit": m["unit"], "better": m["better"],
            "source": m["source"], "layer": m["layer"],
            "moves": m["moves"], "workloads": layer_cells[n]})
    return {
        "command": COMMAND, "paths": [rel],
        "run_seconds": run_seconds(root), "configs": configs,
        "workloads": [{"name": c["name"], "config": c["config"],
                       "traffic": c["traffic"], "chips": c["chips"],
                       "why": c["why"]} for c in cells],
        "end_to_end": end_to_end, "per_layer": per_layer,
    }


if __name__ == "__main__":
    print(json.dumps(benchmark_json(), indent=1))
