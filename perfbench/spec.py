"""Finding a cell's files by the names BENCHMARK.json gives them.

A cell names a configuration and a traffic mix; a metric names itself.
Everything else is looked up here: `<path>/traffic/<traffic>.json`,
the configuration's `file`, `<path>/metrics/<metric>.py`, and the
`<path>/families/<family>.py` a configuration's `"family"` key names (what
the harness knows of a model: families/mistral.py says what that is), for
each directory in `paths`. A later PR adds files and entries and edits none.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAFFIC_SUFFIXES = (".json",)
FAMILY_STATES = ("model_kwargs", "build_model", "weight_rule",
                 "teacher_forced_gaps", "batch_loss", "stored_param_bytes",
                 "decode_step_bytes", "train_step_flops",
                 "causal_attention_flops")


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or inconsistent."""


def load_benchmark(root: str = ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"no BENCHMARK.json in {root}") from None


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"BENCHMARK.json has no {what} named {name!r}; it has "
                    f"{[e['name'] for e in entries]}")


def workload(bench: dict, name: str) -> dict:
    return _by_name(bench["workloads"], name, "workload")


def load_config(bench: dict, name: str, root: str = ROOT) -> dict:
    """The configuration's file, with its entry and the file of its
    family: what `family_of` needs, in this process or in a worker."""
    entry = _by_name(bench["configs"], name, "config")
    path = os.path.join(root, entry["file"])
    with open(path) as f:
        cfg = json.load(f)
    if "family" not in cfg:
        raise SpecError(f'{path} has no "family": the name of a '
                        f'<path>/families/<name>.py')
    cfg["_entry"] = entry
    cfg["_family_file"] = _find(bench, root, "families", cfg["family"],
                                (".py",))
    family_of(cfg)                       # a faulty file is refused here
    return cfg


def _find(bench: dict, root: str, sub: str, name: str, suffixes) -> str:
    tried = []
    for p in bench["paths"]:
        for suffix in suffixes:
            path = os.path.join(root, p, sub, name + suffix)
            if os.path.isfile(path):
                return path
            tried.append(path)
    raise SpecError(f"none of {tried} exists")


def load_traffic(bench: dict, name: str, root: str = ROOT) -> dict:
    with open(_find(bench, root, "traffic", name, TRAFFIC_SUFFIXES)) as f:
        return json.load(f)


def metrics_of(bench: dict, cell: str, group: str) -> list:
    """The `end_to_end` or `per_layer` metrics this cell reports: those
    with no `workloads` key and those that list the cell."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def load_reader(bench: dict, metric: str, root: str = ROOT):
    """A metric's reader: `read(run) -> number | None` in
    `<path>/metrics/<metric>.py`. A quantity split by cells because its
    cells report different end-to-end metrics (`hbm_peak_gb.train`) may
    share the reader of the name before the dot (`hbm_peak_gb.py`)."""
    try:
        path = _find(bench, root, "metrics", metric, (".py",))
    except SpecError:
        if "." not in metric:
            raise
        path = _find(bench, root, "metrics", metric.split(".")[0], (".py",))
    mod = _load_module("perfbench_metric_" + metric, path)
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"{path} defines no read(run)")
    return mod.read


def family_of(cfg: dict):
    """The module of a loaded configuration's family, loaded once a
    process."""
    return _family_at(cfg["_family_file"], cfg["family"])


@functools.lru_cache(maxsize=None)
def _family_at(path: str, name: str):
    mod = _load_module("perfbench_family_" + name, path)
    missing = [n for n in FAMILY_STATES
               if not callable(getattr(mod, n, None))]
    if missing:
        raise SpecError(f"{path} defines no {', '.join(missing)}")
    return mod


def _load_module(name: str, path: str):
    name = name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod       # a dataclass (a flax module) looks here
    spec.loader.exec_module(mod)
    return mod
