"""The benchmark's data files, found by name.

Everything that belongs to one configuration, one traffic mix, one cell
or one metric sits in a file of its own under ``chipbench/``; a later PR
adds files and edits none. ``BENCHMARK.json`` at the root of the repo is
:func:`benchmark_json` of these files (a test holds the two together).

What a later PR brings, and the key that finds it (a test adds one of
each in a temporary root, with no file edited):

- a configuration: ``configs/<name>.json``. ``"model_name"`` names the
  architecture in ``distkeras_tpu.models``' registry (absent:
  ``transformer_lm``), built with ``**"model"``; ``"reference"`` names
  the module of its plain reference (absent:
  ``chipbench.harness.reference``; see :func:`reference` for what such
  a module holds).
- a traffic mix: ``traffic/<name>.json``, parameters of a ``"kind"`` the
  one generator reads (``chipbench.run.runner_for`` goes by it). The
  warm-up request follows from the engine (its prefill chunk and six
  tokens more: two chunks, then decode), not from the mix.
- a cell: ``cells/<name>.json`` over a configuration and a mix.
- a per-layer metric: ``layer_metrics/<name>.json`` with its reader. For
  a cell that exists it names the cell under ``"cells"`` and joins that
  cell's ``per_layer`` after the cell's own list; a new cell may also
  list it itself; a ``"cells"`` entry that names no cell is refused.
  ``"since"`` is the number of the PR that brought the file:
  ``BENCHMARK.json`` lists per-layer metrics by (``since``, name), so
  every accepted entry stays where it is and a new one comes after. A
  later PR's metric file carries its own number; only the eight files
  PR 24 was accepted with say none (``FIRST``), and any other file
  that says none is refused.
"""

from __future__ import annotations

import importlib
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
COMMAND = ["python3", "-m", "chipbench.run"]
RUN_SECONDS_FILE = "run_seconds.json"
FIRST_PR = 24  # "since" of the eight metric files that do not say
FIRST = frozenset((
    "causal_attention_roofline.train", "device_idle_pct.knee",
    "device_idle_pct.sat", "device_idle_pct.train", "mfu_pct.train",
    "occupancy_pct.sat", "tick_device_ms.knee", "tick_host_pct.sat"))


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


def named(path: str):
    """The object ``"<module>:<attribute>"`` names."""
    module, attribute = path.split(":")
    return getattr(importlib.import_module(module), attribute)


def model_name(config: dict) -> str:
    """The architecture's name in ``distkeras_tpu.models``' registry."""
    return config.get("model_name", "transformer_lm")


def reference(config: dict):
    """The module that holds a configuration's plain reference. It
    imports nothing of the program, and every function takes the whole
    configuration first: a reference may need what no weight's shape
    says (rope's base, the experts held, top-k, group limits).

    Serving (``serve_runner``):

    - ``PRECISIONS``: ``"f32"`` and the names of the lower precisions
      the control can be computed in; ``config["precision"]["control"]``
      is one of them.
    - ``make_params(config, seed) -> variables``: the weights, on the
      device in one jitted call from the seed, in the dtypes
      ``config["precision"]["parameters"]`` states, laid out as the
      program's model reads them. The program is handed these.
    - ``forward_logits(config, variables, tokens, at, precision,
      pad_to) -> [len(at), V]``: float32 logits of the one sequence
      ``tokens`` at the positions ``at``, computed in ``precision``,
      the sequence padded to ``pad_to`` so that few lengths compile.

    Training (``train_runner``; a serving-only configuration's
    reference need not have these):

    - ``row_losses(config, variables, batch, precision) -> [B]``: each
      row's mean next-token loss, forward only.
    - ``train_losses(config, variables, batches, precision) -> (losses,
      first_grad_norms)``: the losses of the first ``len(batches)``
      optimizer steps under ``config["trainer"]``, each before its
      update, and the norm of every leaf's first gradient;
      ``variables`` is consumed.
    """
    return importlib.import_module(
        config.get("reference", "chipbench.harness.reference"))


def _age(m: dict):
    """Accepted entries stay where they are, a later PR's come after."""
    if "since" not in m and m["name"] not in FIRST:
        raise ValueError(f"layer_metrics/{m['name']}: no \"since\" (the "
                         f"number of the PR that brings the file)")
    return m.get("since", FIRST_PR), m["name"]


def cell(name: str, root: str = ROOT) -> dict:
    """One cell with its configuration, traffic mix and metrics resolved,
    and every cross-reference checked. After the cell's own ``per_layer``
    come the metric files that name the cell under ``"cells"``."""
    c = load("cells", name, root)
    _line(c["why"], f"cells/{name} why")
    if c["chips"] not in (1, 4):
        raise ValueError(f"cells/{name}: chips {c['chips']}")
    c["config_spec"] = load("configs", c["config"], root)
    c["traffic_spec"] = load("traffic", c["traffic"], root)
    c["end_to_end_specs"] = [metric("end_to_end", m, root)
                             for m in c["end_to_end"]]
    waiting = [m for m in (load("layer_metrics", n, root)
                           for n in names("layer_metrics", root))
               if name in m.get("cells", ())
               and m["name"] not in c["per_layer"]]
    c["per_layer"] += [m["name"] for m in sorted(waiting, key=_age)]
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
        _age(m)
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
    known = names("cells", root)
    for n in names("layer_metrics", root):
        for c in load("layer_metrics", n, root).get("cells", ()):
            if c not in known:
                raise ValueError(f"layer_metrics/{n}: \"cells\" names "
                                 f"{c!r}, which is no cell")
    cells = [cell(n, root) for n in known]
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
    layer_specs = sorted((metric("layer_metrics", n, root)
                          for n in layer_cells), key=_age)
    per_layer = [{
        "name": m["name"], "unit": m["unit"], "better": m["better"],
        "source": m["source"], "layer": m["layer"], "moves": m["moves"],
        "workloads": layer_cells[m["name"]]} for m in layer_specs]
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
