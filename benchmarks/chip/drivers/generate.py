"""Closed loop, one client: each task is one ``ServeSession.generate`` call.

This is how Murakkab's executor issues an LLM task: one ``generate`` over
the task's fan-out, waited for, then the next task. The next task is sent
when the previous one's tokens are on the host.
"""
from __future__ import annotations

import sys
import time
import traceback
from dataclasses import dataclass

import numpy as np

from repro.runtime.serve import ServeSession


@dataclass
class Done:
    task: object         # traffic.Task
    start: float         # seconds from the window's start
    end: float
    tokens: np.ndarray   # (batch, new_tokens) int32


class Driver:
    def __init__(self, model, params, stream):
        self.session = ServeSession(model, params)
        self.stream = stream

    def _serve(self, task) -> np.ndarray:
        out = self.session.generate(task.prompts, self.stream.new_tokens)
        return np.asarray(out)   # waits for the device, as the executor does

    def warm(self) -> None:
        """Compile every program the window will run: one task per length."""
        for task in self.stream.warm_tasks():
            self._serve(task)

    def window(self, seconds: float, span):
        """Serve tasks back to back until the first completion at or after
        ``seconds`` that ends a whole block of the mix, so that every window
        holds the same tasks in another order (a window cut inside a block
        would count one more or one fewer long task, by chance).
        ``span(name)`` is a context manager around each task. Returns
        (finished tasks, attempted, failed, elapsed seconds)."""
        done, failed, i = [], 0, 0
        t0 = time.perf_counter()
        while True:
            task = self.stream.task(i)
            i += 1
            start = time.perf_counter() - t0
            try:
                with span("bench.task"):
                    tokens = self._serve(task)
            except Exception:  # a task that fails is counted, not fatal
                if not failed:
                    traceback.print_exc(file=sys.stderr)
                failed += 1
                tokens = None
            end = time.perf_counter() - t0
            if tokens is not None:
                done.append(Done(task, start, end, tokens))
            if end >= seconds and i % len(self.stream.block) == 0:
                return done, i, failed, end

    def close(self) -> None:
        """Drop the session; the weights stay with the caller."""
        self.session = None
