"""The plain reference of the ``sdpa-paper`` configuration: the serial
fp64 `attention()` of the source's ``attention.c``, on a sample of query
rows against the whole of K and V.  NumPy only; imports nothing of the
program."""

from __future__ import annotations

import numpy as np


def attention_rows(q_rows, k, v):
    """fp64 softmax(q k^T / sqrt(dk)) v for the given query rows."""
    q_rows = np.asarray(q_rows, np.float64)
    k = np.asarray(k, np.float64)
    v = np.asarray(v, np.float64)
    s = q_rows @ k.T / np.sqrt(k.shape[1])
    s -= s.max(axis=1, keepdims=True)
    p = np.exp(s)
    p /= p.sum(axis=1, keepdims=True)
    return p @ v


def sample_rows(m: int, count: int, seed: int) -> np.ndarray:
    """``count`` distinct query rows drawn from the seed, the first and
    the last row among them (the ends of a sharded sequence)."""
    rng = np.random.default_rng([seed, m])
    count = min(count, m)
    rows = rng.choice(m, size=count, replace=False)
    rows[:2] = (0, m - 1)
    return np.unique(rows)


def fp8_round(x):
    """The control's precision: ``x`` rounded to float8 e4m3 under one
    scale per tensor (the step below bf16 that the configuration
    states), returned in fp64."""
    import ml_dtypes

    x = np.asarray(x, np.float64)
    scale = 448.0 / max(float(np.abs(x).max()), 1e-30)
    return (x * scale).astype(ml_dtypes.float8_e4m3fn).astype(np.float64) / scale


def control_rows(q_rows, k, v):
    """The reference in the program's place, one precision lower: the
    same arithmetic on inputs rounded to fp8."""
    return attention_rows(fp8_round(q_rows), fp8_round(k), fp8_round(v))
