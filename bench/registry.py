"""Find a cell's pieces by the names that ``BENCHMARK.json`` gives them.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own:

* ``bench/configs/<config>.json``: the model (or deployment) as it is run;
* ``bench/traffic/<traffic>.json``: the parameters of one traffic mix,
  read by the one general generator in ``loadgen.py``;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric, a
  function ``read(run) -> float | None``;
* ``bench/arch/<arch>.py``: what the benchmark knows of one architecture
  (named by the configuration file's ``"arch"``): the program's config,
  the weight schema, the plain reference and the counts.

A new cell, mix, metric or architecture therefore needs only new files
and new entries in ``BENCHMARK.json``; nothing here names a cell.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def _json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json; have "
                     f"{[w['name'] for w in bench['workloads']]}")


def config(bench: dict, name: str, root: str = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return _json(os.path.join(root, c["file"]))
    raise SystemExit(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str, bench_dir: str = BENCH) -> dict:
    return _json(os.path.join(bench_dir, "traffic", name + ".json"))


def metric_reader(name: str, bench_dir: str = BENCH):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


_ARCHS: dict = {}


def arch(c: dict, bench_dir: str = BENCH):
    """The module ``bench/arch/<c["arch"]>.py``, loaded once a process."""
    name = c.get("arch")
    path = os.path.join(bench_dir, "arch", f"{name}.py")
    if path not in _ARCHS:
        have = sorted(f.removesuffix(".py") for f in os.listdir(
            os.path.join(bench_dir, "arch")) if f.endswith(".py"))
        if name not in have:
            raise SystemExit(f"config {c.get('name')!r} names arch "
                             f"{name!r}; bench/arch/ has {have}")
        spec = importlib.util.spec_from_file_location(
            "bench_arch_" + name.replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _ARCHS[path] = mod
    return _ARCHS[path]


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def end_to_end(bench: dict, cell: str) -> list[dict]:
    """The end-to-end metrics a cell reports (``--trace 0``)."""
    return [m for m in bench["end_to_end"] if _reports(m, cell)]


def per_layer(bench: dict, cell: str) -> list[dict]:
    """The per-layer metrics a cell reports (``--trace 1``): those that
    list the cell, and those without a list whose end-to-end metric the
    cell reports."""
    e2e = {m["name"] for m in end_to_end(bench, cell)}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]
