"""What a later PR brings, written into a copy of the fixture root: new
files in a directory of its own and new entries in BENCHMARK.json, and no
edit to a file that was there. It adds a model family the harness has
never seen (`bench2/families/other.py`: a subclass of the program's model
with one more leaf, `logit_bias`, which the `mistral` family's rule
refuses; a configuration under other key names; its own reference beside
it), a mix that states its `reference_cases`, a metric and two cells."""
import json
import os
import shutil

FIXTURE = os.path.join(os.path.dirname(__file__), "fixture_root")

FAMILY = '''\
"""A family of the later PR's own: the program's model with a bias on the
logits, configured under other key names."""
import importlib.util
import math
import os

_KEYS = {"vocab_size": "vocab_size", "width": "d_model", "depth": "n_layers",
         "heads": "n_heads", "kv_heads": "n_kv_heads", "ffn": "d_ff",
         "positions": "max_seq_len", "theta": "rope_theta", "eps": "norm_eps"}


def _reference():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "reference_other.py")
    spec = importlib.util.spec_from_file_location("reference_other", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_kwargs(cfg):
    kw = {dst: cfg[src] for src, dst in _KEYS.items()}
    kw.update(dtype="bfloat16", param_dtype=cfg["param_dtype"],
              tie_embeddings=False)
    return kw


def build_model(kw):
    import jax.numpy as jnp
    from flax import linen as nn

    from ray_tpu.models import TransformerLM
    from ray_tpu.models.transformer import TransformerConfig

    class BiasedLM(TransformerLM):
        def _logits(self, x, embed, unembed):
            bias = self.param("logit_bias", nn.initializers.zeros,
                              (self.cfg.vocab_size,), jnp.float32)
            return super()._logits(x, embed, unembed) + bias

    kw = dict(kw)
    for key in ("dtype", "param_dtype"):
        kw[key] = jnp.dtype(kw[key])
    return BiasedLM(TransformerConfig(**kw))


def weight_rule(names, shape):
    leaf = names[-1] if names[-1] != "kernel" else names[-2]
    if leaf == "scale":
        return None
    if leaf == "logit_bias":
        return 1.0, False
    if leaf in ("embed", "unembed"):
        return 0.02, False
    fan_in = {"q": shape[1], "k": shape[1], "v": shape[1],
              "o": shape[1] * shape[2], "gate": shape[-2],
              "up": shape[-2], "down": shape[-2]}[leaf]
    return 1.0 / math.sqrt(fan_in), True


def teacher_forced_gaps(params, cfg, prompt, generated, pad_to=None,
                        with_spread=False):
    return _reference().teacher_forced_gaps(params, cfg, prompt, generated,
                                            pad_to, with_spread)


def batch_loss(params, cfg, batch):
    raise NotImplementedError("the later PR brings no training cell")


def stored_param_bytes(cfg, param_bytes):
    d, hd = cfg["width"], cfg["width"] // cfg["heads"]
    layer = 2 * d * (cfg["heads"] + cfg["kv_heads"]) * hd + 3 * d * cfg["ffn"]
    return (cfg["depth"] * layer + 2 * cfg["vocab_size"] * d) * param_bytes


def decode_step_bytes(cfg, live_lens, param_bytes, kv_bytes):
    hd = cfg["width"] // cfg["heads"]
    return stored_param_bytes(cfg, param_bytes) \\
        - cfg["vocab_size"] * cfg["width"] * param_bytes \\
        + sum(2.0 * cfg["depth"] * n * cfg["kv_heads"] * hd * kv_bytes
              for n in live_lens)


def causal_attention_flops(cfg, batch, length, backward):
    return cfg["depth"] * 2.0 * batch * length * length * cfg["width"] \\
        * (3.0 if backward else 1.0)


def train_step_flops(cfg, batch, length):
    raise NotImplementedError("the later PR brings no training cell")
'''

REFERENCE = '''\
"""The later PR's plain reference: the benchmark's float32 forward pass
under this family's key names, plus the bias on the logits."""
from perfbench import reference

_KEYS = {"width": "hidden_size", "depth": "num_hidden_layers",
         "heads": "num_attention_heads", "kv_heads": "num_key_value_heads",
         "theta": "rope_theta", "eps": "rms_norm_eps"}


def logits(params, cfg, tokens):
    m = {dst: cfg[src] for src, dst in _KEYS.items()}
    plain = {k: v for k, v in params.items() if k != "logit_bias"}
    return reference.logits(plain, m, tokens) + params["logit_bias"]


def teacher_forced_gaps(params, cfg, prompt, generated, pad_to, with_spread):
    import jax.numpy as jnp
    import numpy as np
    seq = (list(prompt) + list(generated))[:-1]
    n = len(seq)
    rows = logits(params, cfg, seq + [0] * max(0, (pad_to or 0) - n))[
        len(prompt) - 1:n]
    chosen = jnp.take_along_axis(
        rows, jnp.asarray(generated)[:, None], axis=-1)[:, 0]
    gaps = np.asarray(rows.max(-1) - chosen, np.float64).tolist()
    return (gaps, float(jnp.std(rows, axis=-1).mean())) if with_spread \\
        else gaps
'''

CONFIG = {
    "name": "tiny-other", "family": "other", "width": 64, "ffn": 128,
    "heads": 4, "kv_heads": 2, "depth": 2, "vocab_size": 256,
    "positions": 512, "theta": 10000.0, "eps": 1e-05,
    "param_dtype": "bfloat16",
    "engine": {"n_slots": 4, "max_len": 256, "prefill_chunk": 16,
               "prefill_budget": 32, "prefix_cache_slots": 2,
               "temperature": 0.0, "eos_id": -1, "max_ongoing_requests": 32},
    "reference_tolerance": {"logit_gap": 0.25, "share_within": 0.9,
                            "why": "fixture"}}

QUEUE_DEPTH = ("def read(run):\n"
               "    c = run.get('counters') or {}\n"
               "    return c['t1']['queue_depth'] if 't1' in c else None\n")


def snapshot(root: str) -> dict:
    out = {}
    for d, _, files in os.walk(root):
        if os.path.basename(d) == "__pycache__":
            continue
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                out[os.path.join(d, f)] = fh.read()
    return out


def _write(path: str, text: str) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


def add_later_pr(root: str) -> dict:
    """Copies the fixture root to `root`, adds the later PR, and returns
    the files as they were before it."""
    shutil.copytree(FIXTURE, root)
    before = snapshot(root)
    new = os.path.join(root, "bench2")
    with open(os.path.join(root, "bench", "configs",
                           "tiny-mistral.json")) as f:
        wide = dict(json.load(f), name="tiny-wide", intermediate_size=256)
    with open(os.path.join(root, "bench", "traffic", "open.json")) as f:
        mix = json.load(f)
    _write(os.path.join(new, "configs", "tiny-wide.json"), json.dumps(wide))
    _write(os.path.join(new, "configs", "tiny-other.json"),
           json.dumps(CONFIG))
    _write(os.path.join(new, "traffic", "burst.json"), json.dumps(dict(
        mix, arrival={"process": "gamma", "cv": 2.5, "rate_per_s": 6.0},
        reference_cases=[[40, 8], [100, 6]])))
    _write(os.path.join(new, "metrics", "queue_depth_end.py"), QUEUE_DEPTH)
    _write(os.path.join(new, "families", "other.py"), FAMILY)
    _write(os.path.join(new, "reference_other.py"), REFERENCE)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["paths"].append("bench2")
    for name in ("tiny-wide", "tiny-other"):
        bench["configs"].append({
            "name": name, "source": "test", "reduced": [], "why": "t",
            "file": f"bench2/configs/{name}.json"})
        bench["workloads"].append({
            "name": name + ".burst", "config": name, "traffic": "burst",
            "chips": 1, "why": "t"})
    bench["per_layer"].append({
        "name": "queue_depth_end", "unit": "requests", "better": "lower",
        "source": "program_counter", "layer": "Engine", "moves": "setup_s",
        "workloads": ["tiny-wide.burst", "tiny-other.burst"]})
    _write(os.path.join(root, "BENCHMARK.json"), json.dumps(bench))
    return before
