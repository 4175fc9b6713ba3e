"""Reads `BENCHMARK.json` and the files it names. A cell, a configuration
and a per-layer metric are each a file found by its name, so a later PR
adds files and entries and edits nothing that is here.

`bench/planned.json` holds, in `BENCHMARK.json`'s own form, the entries of
cells whose files are here but which the benchmark does not run yet
(PERF.md, Open questions, says why): `run.py` and the tests find them too,
and the PR that admits one moves its entries over."""

from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(Exception):
    pass


def _load_json(path: str) -> dict:
    try:
        with open(path) as fp:
            return json.load(fp)
    except (OSError, ValueError) as e:
        raise SpecError(f"cannot read {path}: {e}") from e


def load_module(path: str, name: str):
    """Import one file under `bench/` by path. It is registered in
    `sys.modules`, so `inspect.getsource` finds the UDFs' source, which the
    program's reflection needs."""
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise SpecError(f"cannot import {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def _modname(*parts: str) -> str:
    return "benchfile_" + "_".join(
        p.replace("-", "_").replace(".", "_") for p in parts)


def config_module(config_dir: str, stem: str):
    """`<config_dir>/<stem>.py`: a configuration's generator or one of its
    pipelines (with its reference and comparison)."""
    return load_module(os.path.join(config_dir, stem + ".py"),
                       _modname(os.path.basename(config_dir), stem))


def entries(root: str = ROOT) -> dict:
    """`BENCHMARK.json`, with the planned cells' configurations, workloads
    and per-layer metrics after its own."""
    bm = _load_json(os.path.join(root, "BENCHMARK.json"))
    planned_path = os.path.join(root, "bench", "planned.json")
    if os.path.isfile(planned_path):
        planned = _load_json(planned_path)
        for key in ("configs", "workloads", "per_layer"):
            bm[key] = bm[key] + planned.get(key, [])
    return bm


class Cell:
    """One entry of `workloads`, with its configuration, its traffic file
    and the metrics it reports."""

    def __init__(self, name: str, root: str = ROOT):
        self.root = root
        self.bench_dir = os.path.join(root, "bench")
        bm = entries(root)
        entry = next((w for w in bm["workloads"] if w["name"] == name), None)
        if entry is None:
            raise SpecError(
                f"BENCHMARK.json and bench/planned.json have no workload "
                f"{name!r}; they have "
                f"{[w['name'] for w in bm['workloads']]}")
        self.name = name
        self.chips = int(entry["chips"])
        self.config_name = entry["config"]
        cfg_entry = next(c for c in bm["configs"]
                         if c["name"] == self.config_name)
        self.config = _load_json(os.path.join(root, cfg_entry["file"]))
        self.config_dir = os.path.join(self.bench_dir, "configs",
                                       self.config_name)
        self.traffic = _load_json(os.path.join(
            self.bench_dir, "workloads", name + ".json"))
        if self.traffic.get("config") != self.config_name:
            raise SpecError(f"{name}: its traffic file names the "
                            f"configuration {self.traffic.get('config')!r}")
        self.pipeline_name = self.traffic["pipeline"]
        # generator parameters: the configuration's, then the cell's own
        self.params = dict(self.config.get("params", {}))
        self.params.update(self.traffic.get("params", {}))
        self.context_options = dict(self.config.get("context_options", {}))
        self.context_options.update(self.traffic.get("context_options", {}))
        self.tables = {t: dict(v) for t, v in self.config["tables"].items()}
        self.scale_rows(None)
        self.limits = self.config.get("limits", {})
        self.end_to_end = [m for m in bm["end_to_end"]
                           if name in m.get("workloads", [name])]
        e2e = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bm["per_layer"]
                          if name in m.get("workloads", [name])
                          and m["moves"] in e2e]

    def scale_rows(self, fact_rows) -> None:
        """Rehearsals and tests only: the pipeline's fact table at
        `fact_rows` rows and the other tables in proportion. The generator
        reads every table's size under `params["_rows"]`."""
        if fact_rows:
            full = self.config["tables"]
            k = fact_rows / int(full[self.pipeline().FACT]["rows"])
            for t, v in full.items():
                self.tables[t]["rows"] = max(200, round(int(v["rows"]) * k))
                self.tables[t]["chunk_rows"] = max(
                    100, round(int(v["chunk_rows"]) * k))
        self.params["_rows"] = {t: int(v["rows"])
                                for t, v in self.tables.items()}

    def generator(self):
        return config_module(self.config_dir, "generate")

    def pipeline(self):
        return config_module(self.config_dir, self.pipeline_name)

    def reader(self, metric: str):
        return load_module(
            os.path.join(self.bench_dir, "layer_metrics", metric + ".py"),
            _modname("layer_metric", metric))

    def job_tables(self) -> tuple:
        pipe = self.pipeline()
        return (pipe.FACT,) + tuple(pipe.SIDE)
