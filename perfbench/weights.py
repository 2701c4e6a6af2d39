"""Seeded weights, made on the device in one jitted call.

The tree has the shapes and dtypes of the model's own `init` (taken from
`jax.eval_shape`, so nothing is computed), and each leaf is drawn in the
type it is served in: a stacked leaf one layer at a time (`lax.map` over
the layers axis), so that set-up never holds a whole-tree float32
intermediate. The scales are the family's rule (`weight_rule(names,
shape)`: None for ones, or a standard deviation and whether the first axis
is a stack), which follows the program's initialisers, so activations stay
O(1) through the depth and the reference check has logits of a realistic
spread.
"""

from __future__ import annotations

import inspect


def seeded_params(model, seed: int, rule):
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
            try:
                how = rule(names, a.shape)
            except KeyError as e:
                raise KeyError(
                    f"{inspect.getsourcefile(rule)} states no initialiser "
                    f"for leaf {'/'.join(names)} of shape {a.shape}") from e
            if how is None:
                leaves.append(jnp.ones(a.shape, a.dtype))
                continue
            std, stacked = how
            if not stacked:
                leaves.append(draw(k, a.shape, a.dtype, std))
            else:
                leaves.append(jax.lax.map(
                    lambda kk, a=a, std=std: draw(kk, a.shape[1:], a.dtype,
                                                  std),
                    jax.random.split(k, a.shape[0])))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    # the hardware generator where there is one: threefry would spend
    # seconds of the chip on 9e9 normals
    impl = "rbg" if jax.default_backend() == "tpu" else "threefry2x32"
    return jax.jit(make)(jax.random.key(int(seed) % (2 ** 31), impl=impl))
