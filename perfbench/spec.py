"""Finding a cell's files by the names BENCHMARK.json gives them.

A cell names a configuration and a traffic mix; a metric names itself.
Everything else is looked up here: `<path>/traffic/<traffic>.json`,
the configuration's `file`, `<path>/metrics/<metric>.py`, for each
directory in `paths`. A later PR adds files and entries and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAFFIC_SUFFIXES = (".json",)


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
    entry = _by_name(bench["configs"], name, "config")
    with open(os.path.join(root, entry["file"])) as f:
        cfg = json.load(f)
    cfg["_entry"] = entry
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
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + metric.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"{path} defines no read(run)")
    return mod.read


# ------------------------------------------------ configuration -> program
# published key -> TransformerConfig field (models/transformer.py)
_MODEL_KEYS = {
    "vocab_size": "vocab_size", "hidden_size": "d_model",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "intermediate_size": "d_ff",
    "max_position_embeddings": "max_seq_len", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
    "num_local_experts": "n_experts", "num_experts_per_tok": "expert_top_k",
}


def transformer_kwargs(cfg: dict) -> dict:
    """The configuration file as keyword arguments of TransformerConfig
    (dtypes as strings; the process that owns JAX turns them into dtypes).
    Refuses what the program cannot state: another activation, a head size
    that is not hidden/heads, a sliding window shorter than the engine's
    slots (the program has no window, so it must be inert)."""
    if cfg.get("hidden_act", "silu") != "silu":
        raise SpecError(f"hidden_act {cfg['hidden_act']!r}: the program's "
                        f"MLP is SwiGLU")
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    if cfg.get("head_dim", hd) != hd:
        raise SpecError("head_dim is not hidden_size / num_attention_heads")
    kw = {dst: cfg[src] for src, dst in _MODEL_KEYS.items() if src in cfg}
    longest = max((cfg.get("engine") or {}).get("max_len", 0),
                  (cfg.get("train") or {}).get("seq_len", 0))
    window = cfg.get("sliding_window")
    if window is not None and longest > window:
        raise SpecError(f"sequences of {longest} pass the sliding window "
                        f"{window}, which the program does not implement")
    if longest > cfg["max_position_embeddings"]:
        raise SpecError(f"sequences of {longest} pass "
                        f"max_position_embeddings")
    kw["dtype"] = "bfloat16"
    kw["param_dtype"] = cfg.get("param_dtype", cfg.get("torch_dtype",
                                                       "bfloat16"))
    for key in ("capacity_factor", "remat_policy", "attention_impl"):
        if key in (cfg.get("program") or {}):
            kw[key] = cfg["program"][key]
    return kw


def build_transformer_config(kw: dict):
    """In a process that may import JAX: kwargs -> TransformerConfig."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig
    kw = dict(kw)
    for key in ("dtype", "param_dtype"):
        kw[key] = jnp.dtype(kw[key])
    return TransformerConfig(**kw)
