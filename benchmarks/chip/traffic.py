"""The task stream of a traffic file, generated from a seed.

A task is one batch of ``batch`` prompts of one length with ``new_tokens``
to generate. Prompt lengths come from ``buckets`` in fixed proportions
(``weight`` tasks of each bucket in every block of ``sum(weight)`` tasks);
the seed permutes the order within each block, so every window of the same
length holds the same mix. Prompt token ids are drawn uniformly below the
configuration's ``token_ids_below``.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one named stream of one seed; any size of seed."""
    return np.random.default_rng([seed % 2**64, *stream])


# stream ids under one seed
ORDER, PROMPTS, WARM, SAMPLE = 1, 2, 3, 4


@dataclass(frozen=True)
class Task:
    index: int
    prompt_len: int
    prompts: np.ndarray          # (batch, prompt_len) int32


class TaskStream:
    def __init__(self, mix: dict, seed: int, token_ids_below: int):
        self.batch = int(mix["batch"])
        self.new_tokens = int(mix["new_tokens"])
        self.block = [int(b["prompt_len"]) for b in mix["buckets"]
                      for _ in range(int(b["weight"]))]
        self.prompt_lens = sorted(set(self.block))
        self.seed = seed
        self.vocab = int(token_ids_below)
        self._orders: dict[int, np.ndarray] = {}

    def prompt_len(self, i: int) -> int:
        blk, pos = divmod(i, len(self.block))
        if blk not in self._orders:
            self._orders[blk] = rng(self.seed, ORDER, blk).permutation(
                self.block)
        return int(self._orders[blk][pos])

    def _prompts(self, S: int, *stream: int) -> np.ndarray:
        return rng(self.seed, *stream).integers(
            0, self.vocab, (self.batch, S), dtype=np.int32)

    def task(self, i: int) -> Task:
        S = self.prompt_len(i)
        return Task(i, S, self._prompts(S, PROMPTS, i))

    def warm_tasks(self) -> list[Task]:
        """One task of each prompt length, with prompts of their own."""
        return [Task(-1, S, self._prompts(S, WARM, S))
                for S in self.prompt_lens]
