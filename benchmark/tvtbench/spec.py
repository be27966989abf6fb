"""What a run is made of, found by name: BENCHMARK.json's entry for the
cell, then `configs/<config>.json`, `traffic/<mix>.json`,
`generators/<name>.py` and `layer_metrics/<metric>.py`. Nothing is
registered anywhere: a later PR adds a file and an entry."""

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(path):
    with open(path, encoding="utf-8") as fp:
        return json.load(fp)


def load_module(kind, name):
    """The module `benchmark/<kind>/<name>.py` (names may hold `-` and
    `.`, so they are loaded by path, not imported by name)."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"no {kind} named {name!r}: {path} is missing (have "
            f"{sorted(available(kind, '.py'))})")
    spec = importlib.util.spec_from_file_location(
        f"tvtbench_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def available(kind, suffix):
    try:
        names = os.listdir(os.path.join(BENCH_DIR, kind))
    except FileNotFoundError:
        return []
    return [n[:-len(suffix)] for n in names
            if n.endswith(suffix) and not n.startswith("_")]


def _applies(metric, cell_name):
    return cell_name in metric.get("workloads", [cell_name])


class Cell:
    """One entry of BENCHMARK.json's `workloads` with everything it
    names resolved."""

    def __init__(self, name, root):
        self.bench = load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(have {sorted(cells)})")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        config_entry = next(c for c in self.bench["configs"]
                            if c["name"] == self.entry["config"])
        self.config = load_json(os.path.join(root, config_entry["file"]))
        self.traffic = load_json(os.path.join(
            BENCH_DIR, "traffic", self.entry["traffic"] + ".json"))
        self.generator = load_module("generators",
                                     self.traffic["generator"])
        self.end_to_end = [m for m in self.bench["end_to_end"]
                           if _applies(m, name)]
        e2e_here = {m["name"] for m in self.end_to_end}
        # a per-layer metric is reported only where the metric it
        # moves is
        self.per_layer = [m for m in self.bench["per_layer"]
                          if _applies(m, name) and m["moves"] in e2e_here]
