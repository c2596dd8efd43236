"""One generator for every traffic mix.  A mix is a JSON file of
parameters (``traffic/<mix>.json``); nothing here names a mix.

``kind: "graph"`` is a closed loop of one caller over the captured forward:
``batch`` x ``seq`` token ids, ``inputs`` distinct arrays drawn from the
seed and cycled.

``kind: "serve"`` is a list of requests.  Prompt lengths come from a fixed
corpus (``prompt_corpus``, drawn once from ``corpus_seed``), so that every
length compiles once and every seed runs the same set of sizes.  The set
of requests (prompt length, output length), their order and the arrival
gaps are drawn from ``template_seed``; ``--seed`` draws only the token ids,
so every seed runs the same work in the same order.  ``load: "backlog"`` queues ``requests`` at once;
``load: "poisson"`` spreads ``round(rate_per_s * seconds)`` arrivals over
the window with exponential gaps.
"""
from __future__ import annotations

import dataclasses
import json
import os

import numpy as np


def load_traffic(path: str) -> dict:
    """A mix's parameters; its ``kind`` names the module of the harness
    that runs it (``harness/<kind>.py``)."""
    with open(path) as f:
        t = json.load(f)
    here = os.path.dirname(os.path.abspath(__file__))
    kind = t.get("kind")
    if not (isinstance(kind, str) and kind.isidentifier()
            and os.path.exists(os.path.join(here, kind + ".py"))):
        raise ValueError(f"{path}: no harness module for kind {kind!r}")
    return t


def draw(spec: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    """``n`` whole numbers from a length distribution."""
    dist = spec["dist"]
    if dist == "uniform":
        v = rng.integers(spec["low"], spec["high"] + 1, n)
    elif dist == "lognormal":
        v = np.rint(spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n)))
    elif dist == "fixed":
        v = np.full(n, spec["value"])
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    return np.clip(v, spec.get("low", 1), spec.get("high", None)).astype(int)


def corpus(t: dict) -> list[int]:
    """The fixed, sorted, distinct prompt lengths of a serve mix."""
    spec = t["prompt_corpus"]
    rng = np.random.default_rng(spec["corpus_seed"])
    lengths: set[int] = set()
    while len(lengths) < spec["n"]:
        lengths.add(int(draw(spec, rng, 1)[0]))
    return sorted(lengths)


@dataclasses.dataclass
class Req:
    rid: int
    prompt: list[int]
    max_tokens: int
    arrival_s: float        # due time, seconds after the window opens


def n_requests(t: dict, seconds: float) -> int:
    if t["load"] == "backlog":
        return int(t["requests"])
    if t["load"] == "poisson":
        return max(1, int(round(t["rate_per_s"] * seconds)))
    raise ValueError(f"unknown load {t['load']!r}")


def requests(t: dict, seed: int, seconds: float, vocab: int) -> list[Req]:
    """The run's requests in arrival order."""
    n = n_requests(t, seconds)
    tmpl = np.random.default_rng(t["template_seed"])
    lengths = corpus(t)
    prompt_len = np.asarray(lengths)[np.arange(n) % len(lengths)]
    tmpl.shuffle(prompt_len)
    out_len = draw(t["output_tokens"], tmpl, n)
    gaps = tmpl.exponential(1.0, n + 1)
    if t["load"] == "poisson":
        arrivals = seconds * np.cumsum(gaps)[:n] / gaps.sum()
    else:
        arrivals = np.zeros(n)
    rng = np.random.default_rng(seed)
    return [Req(rid=i, prompt=rng.integers(1, vocab, int(prompt_len[i])).tolist(),
                max_tokens=int(out_len[i]), arrival_s=float(arrivals[i]))
            for i in range(n)]
