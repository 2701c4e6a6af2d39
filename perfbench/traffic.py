"""One general traffic generator: a mix is a data file, never code.

A mix (`traffic/<name>.json`) gives a driver kind and its parameters:

  driver      "open" (requests are sent when they are due, whatever the
              system does), "closed" (`clients` callers, each sends its next
              request when its last one ended) or "train" (no requests: a
              batch shape for the training loop)
  arrival     open loop: {"process": "poisson" | "gamma", "rate_per_s": r,
              "cv": c}; gamma with a coefficient of variation c > 1 makes
              bursts, c = 1 is Poisson
  clients     closed loop: the number of callers
  ramp_s      seconds of the same traffic before the window, so that the
              window opens in steady state (counts as set-up)
  prompt_len, output_len
              {"dist": "lognormal", "median": m, "sigma": s} |
              {"dist": "uniform"} | {"dist": "fixed", "value": v}, each with
              "min" and "max" (clips; the bounds of a uniform)
  shared_prefix
              null, or {"groups": g, "prefix_len": <dist>, "turns": <dist>,
              "think_s": t}: sessions of several turns over one of g shared
              system prompts; turn k's prompt is the prefix, then the
              earlier turns (user text and a stand-in for the reply), then
              this turn's user text; turns of a session are due `think_s`
              apart
  population_seed
              the sizes, the gaps and their order are drawn from THIS seed,
              so every `--seed` runs the same schedule of work: `--seed`
              chooses the token ids (and the weights). An order drawn from
              `--seed` would move a tail over a hundred requests by tens of
              per cent from one seed to the next, which is the seed
              changing the work, not the system changing its speed

Times are seconds relative to the window's first instant; a request of the
ramp has a negative due time.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Request:
    idx: int
    due_s: Optional[float]      # open loop; None in a closed loop
    client: Optional[int]       # closed loop; None in an open loop
    prompt: List[int]
    max_new_tokens: int
    session: Optional[int] = None
    turn: int = 0


def draw_lengths(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    lo, hi = int(dist["min"]), int(dist["max"])
    kind = dist["dist"]
    if kind == "lognormal":
        x = rng.lognormal(np.log(dist["median"]), dist["sigma"], size=n)
    elif kind == "uniform":
        x = rng.integers(lo, hi + 1, size=n)
    elif kind == "fixed":
        x = np.full(n, dist["value"])
    else:
        raise ValueError(f"length distribution {kind!r}: expected "
                         f"lognormal, uniform or fixed")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def draw_gaps(arrival: dict, n: int, span_s: float,
              rng: np.random.Generator) -> np.ndarray:
    """n inter-arrival gaps whose sum is span_s: exponential (Poisson
    arrivals, given their count) or gamma with the coefficient of
    variation `cv`, scaled to the span."""
    process = arrival.get("process", "poisson")
    if process == "poisson":
        gaps = rng.exponential(1.0, size=n)
    elif process == "gamma":
        shape = 1.0 / float(arrival["cv"]) ** 2
        gaps = rng.gamma(shape, 1.0 / shape, size=n)
    else:
        raise ValueError(f"arrival process {process!r}: expected poisson "
                         f"or gamma")
    return gaps * (span_s / gaps.sum())


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> List[int]:
    return rng.integers(1, vocab, size=int(n)).tolist()


def _population(mix: dict, n: int, salt: int):
    """n (prompt_len, output_len) pairs, the same for every --seed."""
    rng = np.random.default_rng([int(mix.get("population_seed", 0)), salt])
    return (draw_lengths(mix["prompt_len"], n, rng),
            draw_lengths(mix["output_len"], n, rng), rng)


def _open_span(mix, seed_rng, vocab, span_s, t0, salt, idx0, limit):
    """Requests due in [t0, t0 + span_s): sizes and gaps from
    population_seed, token ids from --seed."""
    n = int(round(mix["arrival"]["rate_per_s"] * span_s))
    if n == 0:
        return []
    plen, olen, pop_rng = _population(mix, n, salt)
    gaps = draw_gaps(mix["arrival"], n, span_s, pop_rng)
    # an arrival sits in the middle of its gap, so the span is filled
    # evenly and no request is due at the window's very first instant
    due = t0 + np.cumsum(gaps) - gaps / 2.0
    out = []
    for i in range(n):
        room = limit - int(olen[i])
        out.append(Request(idx0 + i, float(due[i]), None,
                           _tokens(seed_rng, min(int(plen[i]), room), vocab),
                           int(olen[i])))
    return out


def _with_sessions(mix, reqs, seed_rng, vocab, limit):
    """Rewrite an open schedule into sessions over shared prefixes: each
    scheduled request opens a session, whose further turns follow it."""
    sp = mix["shared_prefix"]
    pop = np.random.default_rng([int(mix.get("population_seed", 0)), 7])
    prefix_len = draw_lengths(sp["prefix_len"], sp["groups"], pop)
    prefixes = [_tokens(np.random.default_rng([int(mix.get(
        "population_seed", 0)), 8, g]), prefix_len[g], vocab)
        for g in range(sp["groups"])]
    turns = draw_lengths(sp["turns"], len(reqs), pop)
    out = []
    for s, first in enumerate(reqs):
        history = list(prefixes[int(seed_rng.integers(sp["groups"]))])
        for k in range(int(turns[s])):
            user = first.prompt if k == 0 else _tokens(
                seed_rng, len(first.prompt), vocab)
            prompt = history + user
            if len(prompt) + first.max_new_tokens > limit:
                break
            out.append(Request(0, first.due_s + k * sp["think_s"], None,
                               prompt, first.max_new_tokens, session=s,
                               turn=k))
            # the stand-in for the reply: seeded tokens of its length
            history = prompt + _tokens(seed_rng, first.max_new_tokens,
                                       vocab)
    out.sort(key=lambda r: r.due_s)
    for i, r in enumerate(out):
        r.idx = i
    return out


def schedule(mix: dict, seed: int, seconds: float, vocab: int,
             max_len: int) -> List[Request]:
    """Every request of one run: the ramp's, then the window's. `max_len`
    is the engine's slot length: a prompt is cut so that prompt + output
    fits (the mixes of this repo never reach it)."""
    seed_rng = np.random.default_rng(int(seed))
    ramp_s = float(mix.get("ramp_s", 0.0))
    driver = mix["driver"]
    if driver == "open":
        ramp = _open_span(mix, seed_rng, vocab, ramp_s, -ramp_s, 1, 0,
                          max_len)
        reqs = ramp + _open_span(mix, seed_rng, vocab, float(seconds), 0.0,
                                 2, len(ramp), max_len)
        if mix.get("shared_prefix"):
            reqs = _with_sessions(mix, reqs, seed_rng, vocab, max_len)
        return reqs
    if driver == "closed":
        # more than any window can finish; a client that runs out stops
        n = int(mix.get("population", 4096))
        plen, olen, _ = _population(mix, n, 3)
        clients = int(mix["clients"])
        return [Request(i, None, i % clients,
                        _tokens(seed_rng,
                                min(int(plen[i]), max_len - int(olen[i])),
                                vocab),
                        int(olen[i]))
                for i in range(n)]
    raise ValueError(f"driver {driver!r} sends no requests")


def train_batch(mix: dict, seed: int, step: int, vocab: int) -> np.ndarray:
    """The training loop's batch for one step: fresh seeded tokens,
    [batch, seq_len + 1] (inputs and shifted targets)."""
    rng = np.random.default_rng([int(seed), int(step)])
    return rng.integers(0, vocab, size=(mix["batch"], mix["seq_len"] + 1),
                        dtype=np.int32)
