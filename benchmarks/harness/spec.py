"""Finds a cell's files by the names ``BENCHMARK.json`` gives.

A later PR adds a configuration, a traffic mix or a per-layer metric as
files of its own and an entry in ``BENCHMARK.json``; nothing here knows
any of them by name.

    configs/<config>.json      the sizes as run, the source, what was
                               assumed, and the two files beside it:
      "reference": <path>      the plain reference with its counts: the
                               equations, ``param_table``, ``vocab``,
                               ``longest_row``, ``weight_std``, and the
                               operations and bytes the readers divide by
      "program": <path>        ``model_config(models, sizes)``: the
                               program's own configuration object from
                               the configuration's own keys
    workloads/<traffic>.json   the generator's kind and its parameters
    metrics/<metric>.py        ``read(ctx)``: the number, or None
"""

import dataclasses
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


def load_module(path):
    """Import one file by its path (names here carry dots and dashes)."""
    name = "bench_" + "".join(c if c.isalnum() else "_" for c in
                              os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _json(path):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict            # the configuration's file
    traffic_name: str
    traffic: dict           # the traffic mix's file
    end_to_end: list        # the metric entries this cell reports
    per_layer: list
    root: str

    def reference(self):
        return load_module(os.path.join(self.root, self.config["reference"]))

    def model_config(self, models):
        """The program's own configuration object, as the
        configuration's ``"program"`` file makes it."""
        return load_module(os.path.join(
            self.root, self.config["program"])).model_config(
                models, self.config)

    def reader(self, metric):
        return load_module(os.path.join(
            self.root, "benchmarks", "metrics", metric + ".py")).read


def load_cell(name, root=ROOT):
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has "
                         f"{sorted(cells)}")
    w = cells[name]
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])

    def mine(metrics, default):
        return [m for m in metrics if name in m.get("workloads", default)]

    e2e = mine(bench["end_to_end"], [name])
    reported = {m["name"] for m in e2e}
    # a per-layer metric without a list belongs to every cell that
    # reports the end-to-end metric it moves
    per = [m for m in bench["per_layer"]
           if (name in m["workloads"] if "workloads" in m
               else m["moves"] in reported)]
    return Cell(
        name=name, chips=w["chips"], config_name=w["config"],
        config=_json(os.path.join(root, cfg["file"])),
        traffic_name=w["traffic"],
        traffic=_json(os.path.join(root, "benchmarks", "workloads",
                                   w["traffic"] + ".json")),
        end_to_end=e2e, per_layer=per, root=root)
