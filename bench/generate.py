"""The one traffic generator: reads a mix's parameters
(``bench/traffic/<mix>.json``) and yields requests from ``--seed``.

Every seed gets the same set of sizes and gaps, drawn once from the
mix's own ``size_seed``; the run's seed only orders them, and draws the
token ids, the sampling seeds and the weights. So two seeds do the same
work in another order, and a seed reproduces its run exactly.

Keys of a language-model mix:

- ``loop``: ``closed`` (``clients`` callers that each wait for their
  reply before sending again) or ``poisson`` (independent arrivals at
  ``rate_per_s``, timed from when each request was due);
- ``prompt`` and ``output``: ``{"median", "sigma", "min", "max"}`` of a
  lognormal, clipped, in tokens;
- ``greedy_share``: the share of requests decoded greedily, the rest
  sampled at ``sampling`` (temperature, top-k, a per-request seed);
- ``pool``: how many sizes (and gaps) are drawn before they repeat;
- ``fixed_head`` (optional): the first this many requests of every run
  take the pool's first sizes and arrival gaps in the pool's own order,
  so that every seed's window holds the same work and the seed changes
  only what the requests say (the token ids, sampling seeds, weights);
- ``lead_in_s``: traffic that runs before the window opens, so the
  window opens in steady state.

A document mix (``kind: cv_docs``) draws ``pool`` documents from the
corpus generator in ``bench/cvcorpus.py`` and sends them in the seed's
order.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass

import numpy as np


@dataclass
class RequestSpec:
    index: int
    prompt: list
    max_new: int
    greedy: bool
    temperature: float
    top_k: int
    sample_seed: int


def _lognormal(rng, spec: dict, n: int) -> np.ndarray:
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def sizes(mix: dict) -> tuple:
    """(prompt lengths, output lengths, arrival gaps in s) of the mix's
    fixed pool: the same for every seed."""
    rng = np.random.default_rng(mix["size_seed"])
    n = mix["pool"]
    prompts = _lognormal(rng, mix["prompt"], n)
    outputs = _lognormal(rng, mix["output"], n)
    gaps = (rng.exponential(1.0 / mix["rate_per_s"], n)
            if mix["loop"] == "poisson" else np.zeros(n))
    return prompts, outputs, gaps


def lm_stream(mix: dict, seed: int, vocab: int):
    """Endless (RequestSpec, gap_s) pairs for one run. The pool is walked
    in a seed-drawn order, and again in a fresh order once used up."""
    prompts, outputs, gaps = sizes(mix)
    rng = np.random.default_rng(seed)
    samp = mix.get("sampling", {})
    n = len(prompts)
    # greedy requests spread evenly over the pool
    every = 1.0 / mix["greedy_share"] if mix["greedy_share"] else np.inf
    greedy = np.zeros(n, bool)
    greedy[np.floor(np.arange(0, n, every)).astype(int)] = True
    head = min(mix.get("fixed_head", 0), n)
    for i in itertools.count():
        if i % n == 0:
            order = rng.permutation(n)
            gap_order = rng.permutation(n)
            if i == 0 and head:
                order[:head] = gap_order[:head] = np.arange(head)
                order[head:] = head + rng.permutation(n - head)
                gap_order[head:] = head + rng.permutation(n - head)
        j = order[i % n]
        g = bool(greedy[j])
        spec = RequestSpec(
            index=i,
            prompt=rng.integers(0, vocab, int(prompts[j])).tolist(),
            max_new=int(outputs[j]), greedy=g,
            temperature=0.0 if g else float(samp.get("temperature", 0.8)),
            top_k=0 if g else int(samp.get("top_k", 0)),
            sample_seed=int(rng.integers(0, 2**31 - 1)))
        yield spec, float(gaps[gap_order[i % n]])


def doc_stream(mix: dict, seed: int):
    """Endless documents of the mix's fixed corpus, in the seed's order."""
    from bench import cvcorpus
    docs = cvcorpus.make_corpus(mix["pool"], seed=mix["size_seed"])
    rng = np.random.default_rng(seed)
    while True:
        for j in rng.permutation(len(docs)):
            yield docs[j]
