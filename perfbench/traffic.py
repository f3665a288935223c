"""The one traffic generator: a traffic file's parameters -> requests.

A traffic mix is DATA (``traffic/<name>.json``); a cell adds its offered
load (``cells/<cell>.json``: ``rate_rps`` for an open loop, ``clients`` for
a closed one). The generator is a pure function of (traffic, load, vocab,
seconds, seed).

Every seed offers the SAME WORK: the multiset of prompt lengths, output
lengths and inter-arrival gaps is fixed by the parameters alone (stratified
quantiles of each distribution), and the seed only permutes them and draws
the token ids and per-request sampling seeds. So two runs with different
seeds differ in order, not in load.
"""

from __future__ import annotations

import math
import random
from dataclasses import dataclass
from statistics import NormalDist
from typing import Optional

_MASK31 = 0x7FFFFFFF


@dataclass
class Request:
    index: int
    due_s: Optional[float]      # offset from the phase start; None = closed
    client: Optional[int]       # closed loop: which client sends it
    prompt: list                # token ids
    body: dict                  # sampling fields of the OpenAI request
    max_tokens: int


def _rng(seed: int, *stream) -> random.Random:
    # A str seed goes through sha512: the same in every process and build.
    return random.Random(":".join(str(int(s)) for s in (seed,) + stream))


def quantile_points(n: int) -> list:
    return [(i + 0.5) / n for i in range(n)]


def length_at(dist: dict, q: float) -> int:
    """Inverse CDF of a length distribution at quantile q, clipped."""
    kind = dist["dist"]
    if kind == "fixed":
        x = dist["value"]
    elif kind == "uniform":
        x = dist["min"] + q * (dist["max"] - dist["min"])
    elif kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(q))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return int(min(max(round(x), dist.get("min", 1)), dist.get("max", x)))


def lengths(dist: dict, n: int) -> list:
    return [length_at(dist, q) for q in quantile_points(n)]


def gaps(arrivals: dict, n: int) -> list:
    """n inter-arrival gaps with mean 1 (the caller scales them)."""
    process = arrivals.get("process", "poisson")
    if process == "poisson":
        g = [-math.log(1.0 - q) for q in quantile_points(n)]
    elif process == "uniform":
        g = [1.0] * n
    else:
        raise ValueError(f"unknown arrival process {process!r}")
    mean = sum(g) / n
    return [x / mean for x in g]


def _prompt_ids(r: random.Random, n: int, vocab: int) -> list:
    # ids 0..2 are pad/bos/eos of the byte tokenizer; stay clear of them
    # whatever tokenizer the server holds.
    return [r.randrange(3, vocab) for _ in range(n)]


def sampling_body(traffic: dict, seed: int, index: int) -> dict:
    """The sampling fields of one request of this traffic mix."""
    s = traffic.get("sampling", {})
    body = {"temperature": s.get("temperature", 0.0)}
    for key in ("top_p", "top_k"):
        if key in s:
            body[key] = s[key]
    if s.get("seeded"):
        body["seed"] = _rng(seed, 3, index).getrandbits(31) & _MASK31
    return body


def make_requests(traffic: dict, load: dict, vocab: int, seconds: float,
                  seed: int, max_len: int) -> list:
    """The requests of one phase of ``seconds`` seconds.

    Open loop: round(rate * seconds) requests with due times inside the
    phase. Closed loop: ``clients * per_client`` requests, dealt round-robin
    to the clients, who send them one after the other."""
    closed = traffic["loop"] == "closed"
    if closed:
        clients = int(load["clients"])
        n = clients * int(traffic.get("per_client", 32))
    else:
        n = max(1, round(float(load["rate_rps"]) * seconds))
    order = _rng(seed, 1)
    p_lens = lengths(traffic["prompt_len"], n)
    o_lens = lengths(traffic["output_len"], n)
    order.shuffle(p_lens)
    order.shuffle(o_lens)
    dues = [None] * n
    if not closed:
        g = gaps(traffic.get("arrivals", {}), n)
        order.shuffle(g)
        # First request at 0; the gaps sum to the phase, so the last one is
        # due one gap before its end.
        t, dues = 0.0, []
        for x in g:
            dues.append(t)
            t += x * seconds / n
    out = []
    for i in range(n):
        r = _rng(seed, 2, i)
        p_len = min(p_lens[i], max_len - 1)
        o_len = max(1, min(o_lens[i], max_len - p_len))
        prompt = _prompt_ids(r, p_len, vocab)
        out.append(Request(
            index=i, due_s=dues[i],
            client=(i % int(load["clients"])) if closed else None,
            prompt=prompt, body=sampling_body(traffic, seed, i),
            max_tokens=o_len))
    return out
