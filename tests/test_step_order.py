"""The order of an engine step (inference/engine.py): read, decide, plan,
dispatch, deliver. The slots' carry (lengths, last tokens, temperatures,
the key) lives on the device and the step programs advance it; a step's
programs are issued back to back and read once; tokens reach their
consumers behind the next step's dispatch. CPU-only, no cluster."""
import functools

import numpy as np
import pytest

from tests.test_fused_step import model_of

KINDS = ["dense", "all-experts", "indexer", "two-kinds",
         "side-by-side"]


@functools.lru_cache(maxsize=None)
def _model(kind):
    if kind == "two-kinds":
        from tests import test_hybrid_mixer_model as hybrid
        model = hybrid.build(hybrid.config())
        return model, hybrid.seeded(model)
    if kind == "side-by-side":      # attention heads and a state-space mixer
        from tests import test_falcon_h1_model as falcon
        model = falcon.build(falcon.config())
        return model, falcon.seeded(model)
    return model_of({"all-experts": "moe"}.get(kind, kind))


def _engine(kind, **kw):
    from ray_tpu.inference import EngineConfig, InferenceEngine
    model, params = _model(kind)
    cfg = dict(n_slots=4, max_len=64, prefill_chunk=4, prefill_budget=8)
    if kind in ("dense", "all-experts"):    # K and V only: prefix blocks
        cfg["prefix_cache_slots"] = 2
    cfg.update(kw)
    return InferenceEngine(model, params, EngineConfig(**cfg))


def _traffic(eng, eos, after_step=lambda: None):
    """Mixed traffic, by steps (so the same on any tree): prompts that span
    several tiles, a prompt that ends while rows decode, two spans in one
    step, a cancel, an EOS finish (`eos`: the token that ends request
    `eos`), a `max_len` eviction and (an engine with prefix blocks) a prefix
    hit -> {name: (tokens, finish_reason)}."""
    rng = np.random.RandomState(7)
    vocab = eng.model.cfg.vocab_size

    def prompt(n):
        return rng.randint(1, vocab, n)

    def steps(n):
        for _ in range(n):
            eng.step()
            after_step()

    hs = {}
    shared = prompt(21)
    hs["tiles"] = eng.submit(shared, max_new_tokens=12)
    steps(4)                                    # three tiles, then a row
    hs["ends_beside_rows"] = eng.submit(prompt(13), max_new_tokens=6)
    steps(1)
    hs["span_one"] = eng.submit(prompt(3), max_new_tokens=5)
    hs["eos"] = eng.submit(prompt(4), max_new_tokens=9, eos_id=eos)
    steps(3)
    hs["cancel"] = eng.submit(prompt(9), max_new_tokens=30)
    hs["max_len"] = eng.submit(prompt(40), max_new_tokens=40)
    steps(6)
    hs["cancel"].cancel()
    steps(2)
    hs["prefix_hit"] = eng.submit(
        np.concatenate([shared[:16], prompt(5)]), max_new_tokens=4)
    for _ in range(400):
        if not eng.sched.has_work():
            break
        steps(1)
    assert not eng.sched.has_work()
    return {name: (list(h), h.finish_reason) for name, h in hs.items()}


COUNTED = ("steps", "fused_steps", "tokens_generated", "prefill_dispatches",
           "prefill_tokens", "admitted", "first_tokens")


PINNED = {
    "dense": (63, [42, 8, 52, 15, 95, 7, 7], {'tiles': ([36, 55, 99, 55, 105, 99, 55, 99, 1, 99, 55, 117], 'length'), 'ends_beside_rows': ([11, 105, 25, 115, 127, 127], 'length'), 'span_one': ([32, 90, 85, 100, 67], 'length'), 'eos': ([113, 5, 113, 63], 'eos'), 'cancel': ([99, 115, 115], 'cancelled'), 'max_len': ([85, 85, 37, 85, 115, 85, 85, 85, 115, 85, 125, 115, 85, 115, 115, 115, 115, 115, 115, 115, 115, 115, 21, 115, 115], 'length'), 'prefix_hit': ([99, 115, 41, 25], 'length')}),
    "all-experts": (9, [42, 8, 54, 15, 95, 7, 7], {'tiles': ([66, 40, 40, 40, 23, 23, 23, 23, 23, 23, 23, 23], 'length'), 'ends_beside_rows': ([92, 92, 92, 92, 92, 92], 'length'), 'span_one': ([105, 53, 34, 78, 100], 'length'), 'eos': ([40, 40, 40, 40, 40, 9], 'eos'), 'cancel': ([102, 76, 28], 'cancelled'), 'max_len': ([122, 110, 76, 76, 76, 76, 76, 76, 76, 76, 76, 76, 76, 76, 76, 76, 76, 76, 107, 107, 107, 107, 107, 112, 22], 'length'), 'prefix_hit': ([10, 38, 17, 66], 'length')}),
    "indexer": (60, [39, 0, 54, 17, 111, 7, 7], {'tiles': ([127, 69, 76, 114, 124, 23, 67, 114, 60, 124, 68, 75], 'length'), 'ends_beside_rows': ([45, 75, 80, 80, 28, 58], 'length'), 'span_one': ([25, 103, 103, 5, 103], 'length'), 'eos': ([80, 66, 60], 'eos'), 'cancel': ([119, 48, 96, 48, 119, 96], 'cancelled'), 'max_len': ([52, 124, 5, 4, 124, 8, 99, 60, 79, 93, 79, 60, 109, 109, 103, 105, 60, 60, 109, 29, 99, 91, 104, 105, 100], 'length'), 'prefix_hit': ([124, 81, 69, 89], 'length')}),
    "two-kinds": (94, [41, 11, 52, 17, 111, 7, 7], {'tiles': ([143, 41, 151, 88, 11, 14, 247, 64, 59, 232, 216, 93], 'length'), 'ends_beside_rows': ([57, 23, 10, 221, 254, 90], 'length'), 'span_one': ([105, 187, 42, 90, 87], 'length'), 'eos': ([47, 15, 94], 'eos'), 'cancel': ([112, 41, 2, 95], 'cancelled'), 'max_len': ([60, 99, 8, 185, 98, 216, 146, 147, 194, 73, 131, 85, 206, 71, 38, 201, 13, 64, 23, 10, 98, 64, 52, 30, 114], 'length'), 'prefix_hit': ([122, 65, 219, 16], 'length')}),
    "side-by-side": (207, [41, 11, 52, 17, 111, 7, 7], {'tiles': ([184, 190, 65, 100, 153, 224, 18, 155, 72, 190, 237, 73], 'length'), 'ends_beside_rows': ([119, 72, 4, 162, 46, 67], 'length'), 'span_one': ([224, 191, 217, 9, 20], 'length'), 'eos': ([116, 236, 207], 'eos'), 'cancel': ([144, 69, 44, 70], 'cancelled'), 'max_len': ([219, 153, 5, 174, 199, 247, 21, 122, 73, 87, 225, 234, 32, 96, 122, 130, 135, 188, 134, 61, 93, 215, 15, 178, 59], 'length'), 'prefix_hit': ([115, 188, 145, 237], 'length')}),
}


@functools.lru_cache(maxsize=None)
def _served(kind):
    """The traffic served once a kind -> (what it gave, the counters,
    where the carry left the host's mirror)."""
    eos, _, _ = PINNED[kind]
    eng = _engine(kind)
    off = []

    def carry_is_the_mirror():
        S = eng.config.n_slots
        lengths, toks, temps = np.asarray(eng._carry)[:3 * S].reshape(3, S)
        temps = temps.view(np.float32)
        if not np.array_equal(lengths, eng._lengths):
            off.append((eng.steps, "lengths", lengths, eng._lengths.copy()))
        for st in eng.sched.active_states():
            if (toks[st.slot], temps[st.slot]) != (
                    st.last_token, np.float32(st.temperature)):
                off.append((eng.steps, st.slot, toks, temps))

    got = _traffic(eng, eos, carry_is_the_mirror)
    return got, eng.stats(), off


@pytest.mark.parametrize("kind", KINDS)
def test_greedy_tokens_are_the_parents(kind):
    """Pinned on the commit before the carry moved to the device (PR 42's
    tree, by `python tests/test_step_order.py`; "side-by-side" on the tree
    that brought its model, PR 44's, where every served token is also held
    to the reference's argmax, tests/test_falcon_h1_model.py): every
    program computes what it computed. The counters are the plans' since
    PR 57, one dispatch fewer than before it: `max_len`'s 40 tokens are
    five tiles cut from its own start, where the seven tokens `cancel`'s
    end left of a step's budget had been a sixth span ahead of them."""
    _, counted, want = PINNED[kind]
    got, stats, _ = _served(kind)
    assert got == want
    assert [stats[k] for k in COUNTED] == counted
    assert stats["prefill_deferred"] >= 1
    if "prefix_hits" in stats:
        assert stats["prefix_hits"] == 1


@pytest.mark.parametrize("kind", KINDS)
def test_the_carry_is_the_hosts_mirror_after_every_step(kind):
    _, stats, off = _served(kind)
    assert stats["steps"] > 30 and not off, off[:3]


def _host_leaves(args):
    import jax
    return [a for a in jax.tree.leaves(args)
            if not isinstance(a, jax.Array)]


def _spy_programs(eng, seen):
    """Note every call of a step program as (name, the arguments that
    are not on the device); each such argument is put there explicitly,
    so the call itself transfers nothing."""
    import jax

    def spied(name, fn):
        def call(*args):
            seen.append((name, _host_leaves(args)))
            return fn(*jax.tree.map(
                lambda a: a if isinstance(a, jax.Array)
                else jax.device_put(a), args))
        return call
    eng._prefill_fn = spied("tile", eng._prefill_fn)
    eng._decode_fn = spied("decode", eng._decode_fn)
    eng._slots._insert_fn = spied("insert", eng._slots._insert_fn)


@pytest.mark.parametrize("kind", ["dense", "indexer"])
def test_a_step_sends_the_device_one_array(kind):
    import jax
    eng = _engine(kind, prefix_cache_slots=0)
    seen = []
    _spy_programs(eng, seen)
    decoding = eng.submit(np.arange(1, 6), max_new_tokens=40)
    eng.step()
    prompt = eng.submit(np.arange(1, 20), max_new_tokens=4)
    eng.step()                      # its first span: a new scratch
    del seen[:]
    with jax.transfer_guard_host_to_device("disallow"):
        eng.step()                  # a tile step
        eng.step()                  # the tile that ends the prompt
        eng.step()                  # a decode-only step
    names = [name for name, _ in seen]
    assert names == (["tile", "tile", "insert", "decode"] if eng._ride else
                     ["tile", "decode", "tile", "insert", "decode", "decode"])
    for name, host in seen:
        assert len(host) == (0 if name == "insert" else 1), (name, host)
        assert all(a.dtype == np.int32 and a.ndim == 1 for a in host)
    decoding.cancel(), prompt.cancel()
    eng.step()


def test_tokens_are_delivered_behind_the_next_dispatch():
    """A consumer receives step N's token only after step N + 1's program
    was issued when work follows, and at once when none does; `ttft_s` and
    `finish_reason` as before; `issued_ahead` counts the steps whose
    program went out over undelivered tokens."""
    import time
    eng = _engine("dense")
    log = []
    _spy_programs(eng, log)
    h = eng.submit(np.arange(1, 6), max_new_tokens=3)
    put = h._q.put
    h._q.put = lambda item: (log.append(("token", item)), put(item))
    eng.step()                      # the prompt ends: a first token
    t1 = time.monotonic()
    assert [n for n, _ in log] == ["tile", "insert"]
    assert h._q.empty() and h.first_token_t is None
    assert eng.first_tokens == 1 and eng.sched.holding()
    eng.step()
    assert [n for n, _ in log[2:]] == ["decode", "token"]
    assert h._q.qsize() == 1 and eng.sched.holding()
    # the stamp is the read's, not the delivery's
    assert h.submitted_t < h.first_token_t < t1
    assert h.ttft_s == h.first_token_t - h.submitted_t
    eng.step()                      # the last token: nothing follows
    assert [n for n, _ in log[4:]] == ["decode", "token", "token", "token"]
    assert not eng.sched.holding() and not eng.sched.has_work()
    assert h.finish_reason == "length" and len(h.tokens()) == 3
    st = eng.stats()
    assert (st["steps"], st["issued_ahead"]) == (3, 2)
    # an idle engine's next step has nothing to go out over
    again = eng.submit(np.arange(1, 6), max_new_tokens=1)
    eng.step()
    assert again.tokens() and again.finish_reason == "length"
    st = eng.stats()
    assert (st["steps"], st["issued_ahead"]) == (4, 2)


def test_a_finished_request_leaves_its_row_and_slot_to_the_next_plan():
    eng = _engine("dense", n_slots=2, prefix_cache_slots=0)
    seen = []
    _spy_programs(eng, seen)
    short = eng.submit(np.arange(1, 4), max_new_tokens=2)
    long = eng.submit(np.arange(4, 8), max_new_tokens=30)
    eng.step()                      # both prompts end (two spans)
    slot = next(s for s, st in eng.sched._active.items()
                if st.handle is short)
    queued = eng.submit(np.arange(1, 10), max_new_tokens=2)
    del seen[:]
    eng.step()                      # step N: `short` has its two tokens
    assert eng.stats()["slots_free"] == 1 and eng.stats()["queue_depth"] == 1
    assert short.finish_reason is None          # decided, not delivered
    ((name, (host,)),) = seen
    assert name == "decode" and list(host) == [1, 1]
    del seen[:]
    eng.step()                      # step N + 1
    (name, (host,)), *_ = seen
    S = eng.config.n_slots
    # its plan gave the queue the slot; the rows behind the tile are the
    # other request's alone
    assert name == "tile" and host[S + 2] == slot
    assert list(host[:S]) == [int(s != slot) for s in range(S)]
    assert short.finish_reason == "length" and len(short.tokens()) == 2
    while eng.step():
        pass
    assert len(long.tokens()) == 30 and len(queued.tokens()) == 2


def test_a_first_token_that_ends_a_request_leaves_its_decode_row_unread():
    """An engine whose rows do not ride issues the decode program behind
    the tile that ended a prompt, the new slot's row in it; where the
    first token was the request's last, the row's token is nobody's."""
    prompt = np.arange(3, 12)
    beside = np.arange(20, 27)

    def served(**kw):
        eng = _engine("indexer")
        other = eng.submit(beside, max_new_tokens=8)
        eng.step()
        h = eng.submit(prompt, **kw)
        while eng.step():
            pass
        return h, other.tokens(), eng
    h1, alone, eng = served(max_new_tokens=1)
    (eos,) = h1.tokens()
    h, toks, eng = served(max_new_tokens=5, eos_id=eos)
    assert (h.tokens(), h.finish_reason) == ([eos], "eos")
    assert toks == alone and eng.stats()["slots_free"] == 4


if __name__ == "__main__":      # the pins: run on the parent commit
    import sys
    for kind in KINDS:
        free = _traffic(_engine(kind), -1)
        toks = free["eos"][0]
        i = next(i for i in range(2, 9) if toks[i] not in toks[:i])
        eos = toks[i]
        eng = _engine(kind)
        got = _traffic(eng, eos)
        assert got["eos"] == (toks[:i + 1], "eos"), got["eos"]
        counted = [eng.stats()[k] for k in COUNTED]
        print(f'    "{kind}": ({eos}, {counted}, {got!r}),', file=sys.stdout)
