"""The one traffic generator: reads a mix's parameters, returns requests.

Every seed gets the same requests in the same order: each block of
``block`` requests holds one fixed list of (prompt length, answer
length) pairs, in one fixed order, and the seed draws only the prompt
tokens. So runs with different seeds do the same work.

Mix keys:
  clients         requests per call: each call sends this many, the next
                  as soon as the previous one returned (a closed loop)
  prompt_len      [lo, hi], uniform, inclusive
  answer_len      [lo, hi], uniform, inclusive
  block           requests per block of fixed sizes
  warm_slots      calls whose decode shapes set-up warms ahead (at least
                  the calls a window makes)
  check_requests  how many finished requests the correctness check reads
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Req:
    index: int
    prompt: np.ndarray        # int32 token ids
    answer_len: int           # tokens to generate


def _uniform_grid(lo: int, hi: int, n: int) -> np.ndarray:
    """n evenly spread integers over [lo, hi]: uniform without sampling."""
    q = (np.arange(n) + 0.5) / n
    return np.floor(lo + q * (hi - lo + 1)).astype(np.int64)


def block_sizes(mix: dict):
    """The fixed block: (prompt lengths, answer lengths), in the order
    they are sent. Lengths are paired, and the pairs ordered, by fixed
    permutations, the same for every seed."""
    n = int(mix["block"])
    prompts = _uniform_grid(*mix["prompt_len"], n)
    answers = _uniform_grid(*mix["answer_len"], n)
    answers = answers[np.random.default_rng(0).permutation(n)]
    order = np.random.default_rng(1).permutation(n)
    return prompts[order], answers[order]


def sizes(mix: dict):
    """(prompt length, answer length) of every request, without end."""
    prompts, answers = block_sizes(mix)
    while True:
        yield from zip(prompts.tolist(), answers.tolist())


def stream(mix: dict, seed: int, vocab: int):
    """Requests for ``seed``, block after block, without end."""
    rng = np.random.default_rng(int(seed))
    for i, (p, a) in enumerate(sizes(mix)):
        toks = rng.integers(0, vocab, size=p, dtype=np.int64)
        yield Req(index=i, prompt=toks.astype(np.int32), answer_len=a)
