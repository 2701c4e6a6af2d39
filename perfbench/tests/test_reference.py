"""The plain reference against the program's TransformerLM at a tiny
Mistral-shaped and Mixtral-shaped size: logits, loss and gradients."""
import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from perfbench import reference, spec, weights
from ray_tpu.parallel.train_step import cross_entropy_loss

FIXTURE = os.path.join(os.path.dirname(__file__), "fixture_root")
FIXBENCH = spec.load_benchmark(FIXTURE)


def _build(name, capacity_factor=None, dtype="float32", seed=3):
    cfg = spec.load_config(FIXBENCH, name, FIXTURE)
    family = spec.family_of(cfg)
    if dtype is not None:              # the parity is of the mathematics
        cfg["param_dtype"] = dtype
    if capacity_factor is not None:
        cfg["program"] = {"capacity_factor": capacity_factor}
    kw = family.model_kwargs(cfg)
    if dtype is not None:
        kw["dtype"] = dtype
    model = family.build_model(kw)
    return cfg, model, weights.seeded_params(model, seed, family.weight_rule)


def _tokens(n, vocab=256, seed=0):
    return np.random.default_rng(seed).integers(0, vocab, size=n)


@pytest.mark.parametrize("name,cf", [("tiny-mistral", None),
                                     ("tiny-mixtral", 2.0)])
def test_logits_match_the_program(name, cf):
    cfg, model, params = _build(name, cf)
    toks = _tokens(48)
    with jax.default_matmul_precision("highest"):
        want = model.apply({"params": params}, jnp.asarray(toks)[None])[0]
    got = reference.logits(params, cfg, toks)
    assert float(jnp.abs(want - got).max()) < 1e-5
    assert float(jnp.abs(want).max()) > 0.1


def test_a_dropped_token_is_caught():
    """capacity_factor 1.25 drops tokens in the program; the dropless
    reference then disagrees by the order of the logits themselves."""
    cfg, model, params = _build("tiny-mixtral", 1.25)
    toks = _tokens(48)
    want = model.apply({"params": params}, jnp.asarray(toks)[None])[0]
    got = reference.logits(params, cfg, toks)
    assert float(jnp.abs(want - got).max()) > 0.05


@pytest.mark.parametrize("name,cf", [("tiny-mistral", None),
                                     ("tiny-mixtral", 2.0)])
def test_loss_and_gradients_match_the_program(name, cf):
    cfg, model, params = _build(name, cf)
    batch = np.stack([_tokens(33, seed=1), _tokens(33, seed=2)])

    def program_loss(p):
        logits = model.apply({"params": p}, jnp.asarray(batch[:, :-1]))
        return cross_entropy_loss(logits, jnp.asarray(batch[:, 1:]))[0]

    def reference_loss(p):
        return (reference.sequence_loss(p, cfg, batch[0])
                + reference.sequence_loss(p, cfg, batch[1])) / 2

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(program_loss)(params)
    lr, gr = jax.value_and_grad(reference_loss)(params)
    assert float(lr) == pytest.approx(float(lp), abs=1e-5)
    assert reference.batch_loss(params, cfg, batch) == pytest.approx(
        float(lp), abs=1e-5)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        scale = float(jnp.abs(a).max()) + 1e-9
        assert float(jnp.abs(a - b).max()) / scale < 1e-3


def test_teacher_forced_gaps_and_padding():
    cfg, model, params = _build("tiny-mistral")
    prompt = _tokens(20).tolist()
    seq = list(prompt)
    for _ in range(6):                       # greedy, by the program
        lg = model.apply({"params": params}, jnp.asarray(seq)[None])[0, -1]
        seq.append(int(jnp.argmax(lg)))
    gen = seq[len(prompt):]
    gaps = reference.teacher_forced_gaps(params, cfg, prompt, gen)
    padded = reference.teacher_forced_gaps(params, cfg, prompt, gen,
                                           pad_to=64)
    assert len(gaps) == 6 and max(gaps) < 1e-4
    assert np.allclose(gaps, padded, atol=1e-5)
    wrong = list(gen)
    wrong[2] = (wrong[2] + 1) % 256          # not the argmax any more
    assert reference.teacher_forced_gaps(params, cfg, prompt, wrong)[2] > 0


def test_seeded_weights_are_seeded_and_typed():
    cfg, model, params = _build("tiny-mixtral", 2.0)
    rule = spec.family_of(cfg).weight_rule
    again = weights.seeded_params(model, 3, rule)
    other = weights.seeded_params(model, 4, rule)
    init = meta.unbox(jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0)))
    assert jax.tree.structure(params) == jax.tree.structure(init)
    for a, b, c, s in zip(*map(jax.tree.leaves, (params, again, other, init))):
        assert a.shape == s.shape and a.dtype == s.dtype
        assert bool((a == b).all())
    assert not bool((params["embed"] == other["embed"]).all())
    assert float(params["final_norm"]["scale"].min()) == 1.0


# sha256 over the leaves (in tree order, as float32 bytes) of the tree that
# the parent's weights.py drew on the CPU, before the rule moved into the
# family's file (written down from commit 22e994d, PR 31)
PARENT_TREES = {
    ("tiny-mistral", 3):
        "bb3096a8216a8d0ecaaa86f3ee32908898b4d01ec9a9f6bc9c0cc0d1aabcb2a2",
    ("tiny-mistral", 3000000001):
        "95018e262ff019d1569cdbf6227a1fc08f6ceb3146f014f3df0c890d05cb9c3d",
    ("tiny-mixtral", 3):
        "80a380b77e00a49eae8312fac26e3c606e6b4952347a5241a209b7f4d82373eb",
    ("tiny-mixtral", 3000000001):
        "4dbc1cf5140ee939922e2a779f73071ae1f18d940ddda75140f5336b636d2734",
}


@pytest.mark.parametrize("name,seed", sorted(PARENT_TREES))
def test_seeded_weights_are_the_parents_bit_for_bit(name, seed):
    _, _, params = _build(name, dtype=None, seed=seed)
    h = hashlib.sha256()
    for a in jax.tree.leaves(params):
        h.update(np.asarray(a.astype(jnp.float32)).tobytes())
    assert h.hexdigest() == PARENT_TREES[(name, seed)]


def test_an_unknown_leaf_raises_and_names_the_familys_file():
    cfg, model, _ = _build("tiny-mistral")
    rule = spec.family_of(cfg).weight_rule

    def narrower(names, shape):              # a family that knows no MLP
        if "mlp" in names:
            raise KeyError(names[-1])
        return rule(names, shape)

    with pytest.raises(KeyError) as e:
        weights.seeded_params(model, 3, narrower)
    assert os.path.basename(__file__) in str(e.value)
    assert "mlp" in str(e.value)
