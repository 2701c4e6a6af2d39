import numpy as np
import pytest

from perfbench import spec, traffic

BENCH = spec.load_benchmark()


def _mix(name):
    return spec.load_traffic(BENCH, name)


def test_open_loop_same_seed_same_schedule():
    mix = _mix("chat-steady")
    a = traffic.schedule(mix, 3_000_000_001, 45, 32000, 2048)
    b = traffic.schedule(mix, 3_000_000_001, 45, 32000, 2048)
    assert [(r.due_s, r.prompt, r.max_new_tokens) for r in a] == \
        [(r.due_s, r.prompt, r.max_new_tokens) for r in b]


def test_open_loop_lengths_dues_and_counts():
    mix = _mix("chat-steady")
    rate, ramp = mix["arrival"]["rate_per_s"], mix["ramp_s"]
    reqs = traffic.schedule(mix, 7, 45, 32000, 2048)
    window = [r for r in reqs if r.due_s >= 0]
    assert len(window) == round(rate * 45)
    assert len(reqs) - len(window) == round(rate * ramp)
    assert all(-ramp <= r.due_s < 45 for r in reqs)
    assert [r.due_s for r in reqs] == sorted(r.due_s for r in reqs)
    assert all(32 <= len(r.prompt) <= 1536 for r in reqs)
    assert all(8 <= r.max_new_tokens <= 384 for r in reqs)
    assert all(1 <= t < 32000 for r in reqs for t in r.prompt)
    med = np.median([len(r.prompt) for r in window])
    assert 180 < med < 360                       # log-normal around 256


def test_every_seed_runs_the_same_schedule_with_other_tokens():
    mix = _mix("chat-steady")
    a = traffic.schedule(mix, 1, 45, 32000, 2048)
    b = traffic.schedule(mix, 2, 45, 32000, 2048)
    shape = lambda rs: [(r.due_s, len(r.prompt), r.max_new_tokens)
                        for r in rs]
    assert shape(a) == shape(b)
    assert all(x.prompt != y.prompt for x, y in zip(a, b))


def test_gamma_arrivals_are_burstier_than_poisson():
    rng = np.random.default_rng(0)
    p = traffic.draw_gaps({"process": "poisson"}, 4000, 1000.0, rng)
    g = traffic.draw_gaps({"process": "gamma", "cv": 3.0}, 4000, 1000.0, rng)
    assert p.sum() == pytest.approx(1000.0) and g.sum() == pytest.approx(1000.0)
    cv = lambda x: x.std() / x.mean()
    assert 0.9 < cv(p) < 1.1 and 2.5 < cv(g) < 3.5
    with pytest.raises(ValueError):
        traffic.draw_gaps({"process": "weibull"}, 4, 1.0, rng)


def test_closed_loop_clients_and_clips():
    mix = _mix("batch-longprompt")
    reqs = traffic.schedule(mix, 11, 45, 32000, 2048)
    assert {r.client for r in reqs} == set(range(16))
    assert all(r.due_s is None for r in reqs)
    assert all(1024 <= len(r.prompt) <= 1792 for r in reqs)
    assert all(16 <= r.max_new_tokens <= 64 for r in reqs)
    again = traffic.schedule(mix, 11, 45, 32000, 2048)
    assert [r.prompt for r in reqs[:20]] == [r.prompt for r in again[:20]]
    other = traffic.schedule(mix, 12, 45, 32000, 2048)
    assert [(len(r.prompt), r.max_new_tokens) for r in reqs] == \
        [(len(r.prompt), r.max_new_tokens) for r in other]
    assert reqs[0].prompt != other[0].prompt


def test_shared_prefix_sessions():
    mix = dict(_mix("chat-steady"), shared_prefix={
        "groups": 3, "think_s": 2.0,
        "prefix_len": {"dist": "uniform", "min": 400, "max": 600},
        "turns": {"dist": "uniform", "min": 2, "max": 4}})
    reqs = traffic.schedule(mix, 5, 20, 32000, 2048)
    sessions = {}
    for r in reqs:
        sessions.setdefault(r.session, []).append(r)
    assert len(sessions) > 10
    prefixes = set()
    for turns in sessions.values():
        turns.sort(key=lambda r: r.turn)
        assert [t.turn for t in turns] == list(range(len(turns)))
        assert 1 <= len(turns) <= 4
        for a, b in zip(turns, turns[1:]):
            assert b.prompt[:len(a.prompt)] == a.prompt   # history grows
            assert b.due_s == pytest.approx(a.due_s + 2.0)
        assert all(len(t.prompt) + t.max_new_tokens <= 2048 for t in turns)
        prefixes.add(tuple(turns[0].prompt[:400]))
    assert len(prefixes) == 3                    # three shared system prompts


def test_train_batches_are_fresh_and_seeded():
    mix = _mix("pretrain-4k")
    a = traffic.train_batch(mix, 9, 0, 32000)
    assert a.shape == (2, 4097) and a.dtype == np.int32
    assert (a == traffic.train_batch(mix, 9, 0, 32000)).all()
    assert (a != traffic.train_batch(mix, 9, 1, 32000)).any()
    assert a.min() >= 0 and a.max() < 32000


def test_unknown_distribution_raises():
    with pytest.raises(ValueError):
        traffic.draw_lengths({"dist": "zipf", "min": 1, "max": 2}, 3,
                             np.random.default_rng(0))
