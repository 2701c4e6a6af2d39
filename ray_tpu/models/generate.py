"""Autoregressive generation over the sharded KV-cache decode path (the
serving counterpart of parallel/train_step.make_train_fns; reference
framework ships no model code — this is the TPU-native inference engine
its Serve story would orchestrate).

Shape: ONE jitted function runs prefill (full-prompt forward seeding the
cache) and then `lax.scan`s single-token decode steps — token selection
(greedy or temperature sampling) happens inside the scan, so the whole
generation is a single XLA program with no host round trips. Params
shard per the megatron rule table; the KV cache shards batch over the
data axes and KV heads over `tensor`, so decode attention reads are
local to each tensor shard and the only cross-device traffic is the
activation all-reduce the matmul shardings already imply.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models.sampling import sample_logits
from ray_tpu.models.transformer import (cache_shapes, init_cache,
                                        kv_cache_sharding)
from ray_tpu.parallel import sharding as sharding_lib
from ray_tpu.parallel.mesh import use_mesh
from ray_tpu.parallel.train_step import (_prune_indivisible,
                                         logical_pspec_to_mesh,
                                         state_shardings)


def make_generate_fn(model: nn.Module, mesh: Mesh, rules=None,
                     batch: int = 8, prompt_len: int = 128,
                     max_new_tokens: int = 128,
                     temperature: float = 0.0,
                     ) -> Tuple[Callable, Callable, Any]:
    """Returns (init_fn(rng) -> params, generate_fn(params, tokens, rng)
    -> [B, max_new_tokens] token ids, param_sharding_tree).

    temperature 0.0 = greedy argmax; >0 = softmax sampling inside the
    decode scan. max_len = prompt_len + max_new_tokens bounds the KV
    cache (static shapes: XLA compiles one prefill + one decode body)."""
    cfg = model.cfg
    rules = rules or sharding_lib.DEFAULT_RULES
    max_len = prompt_len + max_new_tokens
    tokens0 = jnp.zeros((batch, prompt_len), jnp.int32)

    def init_params(rng):
        return model.init(rng, tokens0)["params"]

    abstract = jax.eval_shape(init_params, jax.random.PRNGKey(0))
    param_sh = state_shardings(abstract, mesh, rules)
    init_fn = jax.jit(init_params, out_shardings=param_sh)

    cache_sh = {name: kv_cache_sharding(shape, mesh, rules, name)
                for name, shape in cache_shapes(cfg, batch, max_len).items()}
    cache_sh["idx"] = NamedSharding(mesh, P())

    def _pick(logits, rng):
        # shared with the inference engine (models/sampling.py); static
        # temperature=0 compiles to the same bare argmax as before
        return sample_logits(logits, rng, temperature=temperature)

    def generate(params, tokens, rng):
        cache = init_cache(cfg, batch, max_len)
        cache = jax.lax.with_sharding_constraint(cache, cache_sh)
        # prefill: one full-prompt forward seeds every layer's cache
        logits, cache = model.apply({"params": params}, tokens,
                                    cache=cache)
        rng, k0 = jax.random.split(rng)
        first = _pick(logits[:, -1, :], k0).astype(jnp.int32)

        def step(carry, _):
            cache, tok, rng = carry
            logits, cache = model.apply({"params": params}, tok[:, None],
                                        cache=cache)
            rng, k = jax.random.split(rng)
            nxt = _pick(logits[:, -1, :], k).astype(jnp.int32)
            cache = jax.lax.with_sharding_constraint(cache, cache_sh)
            return (cache, nxt, rng), nxt

        (_, _, _), rest = jax.lax.scan(
            step, (cache, first, rng), None, length=max_new_tokens - 1)
        return jnp.concatenate([first[:, None], rest.T], axis=1)

    batch_sh = NamedSharding(
        mesh, _prune_indivisible(
            logical_pspec_to_mesh(P("batch", None), rules),
            (batch, prompt_len), mesh))
    jit_gen = jax.jit(generate,
                      in_shardings=(param_sh, batch_sh, None),
                      out_shardings=NamedSharding(mesh, P()))

    def generate_with_mesh(params, tokens, rng):
        with use_mesh(mesh):
            return jit_gen(params, tokens, rng)

    def init_with_mesh(rng):
        with use_mesh(mesh):
            return init_fn(rng)

    return init_with_mesh, generate_with_mesh, param_sh
