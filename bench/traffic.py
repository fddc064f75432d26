"""The one traffic generator: a traffic file's parameters and a seed in,
the prompt stream of a closed training loop out.

A traffic file (``bench/traffic/<name>.json``) gives the batch (prompts per
step and the group each prompt is sampled for), the denoising steps, the
latent geometry, and the prompt pool: its size, the word count range of a
prompt and the words to draw from.  Every seed draws the same number of
distinct prompts; only which words they hold differs.
"""
from __future__ import annotations

from typing import Dict, Iterator, List

import numpy as np


def make_prompts(traffic: Dict, seed: int) -> List[str]:
    """``prompt_pool`` distinct prompts drawn from the seed."""
    rng = np.random.default_rng(seed)
    words = traffic["words"]
    lo, hi = traffic["prompt_words"]
    out, seen = [], set()
    while len(out) < traffic["prompt_pool"]:
        k = int(rng.integers(lo, hi + 1))
        p = " ".join(rng.choice(words, size=k, replace=False))
        if p not in seen:
            seen.add(p)
            out.append(p)
    return out


class PromptCycle:
    """The system's prompt dataset: batches of ``batch_size`` prompts in
    pool order, cycling through the pool."""

    def __init__(self, prompts: List[str], batch_size: int):
        self.prompts = list(prompts)
        self.batch_size = batch_size

    def batch(self, i: int) -> List[str]:
        n, b = len(self.prompts), self.batch_size
        return [self.prompts[(i * b + j) % n] for j in range(b)]

    def infinite(self, skip: int = 0) -> Iterator[List[str]]:
        i = skip
        while True:
            yield self.batch(i)
            i += 1


def prompt_cycle(n_prompts: int, batch_prompts: int, seed: int,
                 prompts: List[str]) -> PromptCycle:
    """Dataset factory in the shape the system's dataset registry calls."""
    if len(prompts) != n_prompts:
        raise ValueError(f"{len(prompts)} prompts for a pool of {n_prompts}")
    return PromptCycle(prompts, batch_prompts)
