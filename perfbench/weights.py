"""Seeded weights, made on the device in one jitted call.

The tree has the shapes and dtypes of the program's own
`TransformerLM.init` (taken from `jax.eval_shape`, so nothing is
computed), and each leaf is drawn in the type it is served in: a stacked
leaf one layer at a time (`lax.map` over the layers axis), so that set-up
never holds a whole-tree float32 intermediate. Scales follow the
program's initialisers (normal 0.02 for the tables, 1/sqrt(fan_in) for the
matmuls, ones for the norms), so activations stay O(1) through the depth
and the reference check has logits of a realistic spread.
"""

from __future__ import annotations

import math


def _fan_in(names, shape) -> int:
    """Fan-in of a matmul leaf by its name in the program's tree; an
    unknown name raises, so a change of the tree is noticed here."""
    leaf = names[-1] if names[-1] != "kernel" else names[-2]
    stacked = shape[1:]                      # without the layers axis
    if leaf in ("q", "k", "v", "router"):
        return stacked[0]                    # [d_model, ...]
    if leaf == "o":
        return stacked[0] * stacked[1]       # [heads, head_dim, d_model]
    if leaf in ("gate", "up", "down"):
        return stacked[-2]                   # [(experts,) in, out]
    raise KeyError(f"weights.py knows no initialiser for leaf "
                   f"{'/'.join(names)} of shape {shape}")


def seeded_params(model, seed: int):
    """The model's parameter tree, seeded, on the default device."""
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    tokens0 = jnp.zeros((1, 8), jnp.int32)
    abstract = meta.unbox(jax.eval_shape(
        lambda k: model.init(k, tokens0)["params"], jax.random.PRNGKey(0)))
    paths, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    def draw(key, shape, dtype, std):
        return (jax.random.normal(key, shape, jnp.float32)
                * std).astype(dtype)

    def make(key):
        leaves = []
        for i, (path, a) in enumerate(paths):
            names = [p.key for p in path]
            k = jax.random.fold_in(key, i)
            if names[-1] == "scale":
                leaves.append(jnp.ones(a.shape, a.dtype))
            elif names[-1] in ("embed", "unembed"):
                leaves.append(draw(k, a.shape, a.dtype, 0.02))
            else:
                std = 1.0 / math.sqrt(_fan_in(names, a.shape))
                leaves.append(jax.lax.map(
                    lambda kk, a=a, std=std: draw(kk, a.shape[1:], a.dtype,
                                                  std),
                    jax.random.split(k, a.shape[0])))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    # the hardware generator where there is one: threefry would spend
    # seconds of the chip on 9e9 normals
    impl = "rbg" if jax.default_backend() == "tpu" else "threefry2x32"
    return jax.jit(make)(jax.random.key(int(seed) % (2 ** 31), impl=impl))
