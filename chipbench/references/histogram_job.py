"""Plain NumPy reference of a WordCount histogram.

Semantics: key of a token = ``token mod Q`` (tokens are non-negative int32);
output row q = number of tokens with key q, as float32, shape [Q, 1].  It
shares no code with the engine or its jobs.

Job k = s V + r of a run (V = the token range) counts ``(base + r) mod V``
with the subfiles rotated by s: new content for every k < V N.  With
V == Q every token is its own key, so job k's counts are the base counts
rolled by r: the whole reference is one ``np.bincount`` per run.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np


def _check(cfg: dict) -> None:
    if cfg["token_range"] != cfg["Q"]:
        raise ValueError("histogram_job reference needs token_range == Q")


def job_input(base: np.ndarray, k: int, cfg: dict) -> np.ndarray:
    """A new array: the subfiles rotated by k // V, every word id shifted
    by k mod V within the vocabulary."""
    V = cfg["token_range"]
    s, r = divmod(k, V)
    x = (np.roll(base, s, axis=0) if s else base) + np.int32(r)
    np.subtract(x, V, out=x, where=x >= V)
    return x


def direct(tokens: np.ndarray, cfg: dict) -> np.ndarray:
    """The reference answer of one input array, counted from scratch."""
    Q = cfg["Q"]
    keys = tokens.astype(np.int64).ravel() % Q
    return np.bincount(keys, minlength=Q).astype(np.float32)[:, None]


def prepare(base: np.ndarray, cfg: dict) -> np.ndarray:
    _check(cfg)
    return direct(base, cfg)


def expected(state: np.ndarray, k: int, cfg: dict) -> np.ndarray:
    """Job k's answer: key q holds the base count of word (q - k) mod V."""
    return np.roll(state, k % cfg["token_range"], axis=0)


def control(base: np.ndarray, k: int, cfg: dict) -> np.ndarray:
    """The reference one precision down: per-subfile counts rounded to
    bfloat16 and summed in bfloat16, as a map emitting bf16 values would."""
    tokens = job_input(base, k, cfg)
    acc = np.zeros((cfg["Q"], 1), ml_dtypes.bfloat16)
    for row in tokens:
        acc = acc + direct(row, cfg).astype(ml_dtypes.bfloat16)
    return acc.astype(np.float32)
