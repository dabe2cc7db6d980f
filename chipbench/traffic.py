"""The benchmark's own inputs, drawn from ``--seed``.

``lm_batch`` gives training rows: per-row arithmetic progressions of token
ids with a random start and a stride from 1 to 7 (every row differs, and
the next token is learnable), targets the tokens shifted left with the
last masked. ``TokenStream`` feeds them to a hosted trainer in place of
its own pipeline (same protocol: ``next``, ``state_dict``,
``load_state_dict``, ``step``) and stamps the host clock at every call,
which marks the end of the previous step.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np


def lm_batch(seed: int, step: int, batch: int, seq_len: int,
             vocab: int) -> Dict[str, np.ndarray]:
    rng = np.random.Generator(np.random.PCG64([seed, step]))
    start = rng.integers(0, vocab, size=(batch, 1), dtype=np.int64)
    stride = rng.integers(1, 8, size=(batch, 1), dtype=np.int64)
    tokens = ((start + stride * np.arange(seq_len, dtype=np.int64))
              % vocab).astype(np.int32)
    targets = np.roll(tokens, -1, axis=1)
    targets[:, -1] = -1
    return {"tokens": tokens, "targets": targets}


def prompts(seed: int, batch: int, prompt_len: int,
            vocab: int) -> np.ndarray:
    """The serving prompts a ``ServeApp`` of this seed draws: uniform ids
    from PCG64(seed)."""
    rng = np.random.Generator(np.random.PCG64(seed))
    return rng.integers(0, vocab, (batch, prompt_len)).astype(np.int32)


class TokenStream:
    """Checkpointable batch stream for a hosted trainer.

    ``on_batch(k)`` is called before batch ``k`` is handed out, when the
    trainer's state holds the result of ``k`` steps.
    """

    def __init__(self, seed: int, batch: int, seq_len: int, vocab: int,
                 on_batch: Optional[Callable[[int], None]] = None):
        self.seed, self.batch, self.seq_len, self.vocab = \
            seed, batch, seq_len, vocab
        self.step = 0
        self.on_batch = on_batch
        self.stamps: List[float] = []       # host clock at each next()
        self._lock = threading.Lock()

    def state_dict(self) -> Dict[str, Any]:
        return {"seed": self.seed, "step": self.step,
                "global_batch": self.batch, "seq_len": self.seq_len}

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        self.seed = int(state["seed"])
        self.step = int(state["step"])

    def next(self) -> Dict[str, np.ndarray]:
        now = time.perf_counter()
        with self._lock:
            self.stamps.append(now)
        if self.on_batch is not None:
            self.on_batch(self.step)
        out = lm_batch(self.seed, self.step, self.batch, self.seq_len,
                       self.vocab)
        self.step += 1
        return out

    def stamps_between(self, t0: float, t1: float) -> List[float]:
        with self._lock:
            return [t for t in self.stamps if t0 <= t <= t1]
