"""The benchmark's own arithmetic: the open-loop schedule, percentiles that
refuse too few samples, and throughput from a series of completion times.
Pure functions of their arguments, so that they can be tested without a
device and give the same numbers on every commit."""
from __future__ import annotations

import math
from typing import Sequence

import numpy as np


def fixed_work_schedule(seed: int, rate_per_s: float, seconds: float,
                        rows_mix: Sequence[dict], pool_rows: int,
                        t_from: float = 0.0):
    """The requests of one open-loop segment, a pure function of its
    arguments: (due_s, rows, offset) arrays of equal length.

    The amount of work is fixed, not drawn.  The segment holds exactly
    round(rate x seconds) requests, due at sorted uniform times in
    [t_from, t_from + seconds) (a Poisson process given its count).  Each
    class of `rows_mix`, {"p": share, "low": n, "high": m}, gets its exact
    share of the requests, with the row counts low..high dealt out evenly;
    the seed shuffles which request gets which, and where in the pool of
    `pool_rows` images its rows [offset, offset + rows) start.  So every
    seed offers the same number of rows, and a rate of completed rows
    moves only when the server does.
    """
    rng = np.random.RandomState(seed)
    n = int(round(rate_per_s * seconds))
    due = t_from + np.sort(rng.random_sample(n)) * seconds
    counts = [int(round(m["p"] * n)) for m in rows_mix]
    counts[0] += n - sum(counts)
    rows = []
    for m, c in zip(rows_mix, counts):
        span = m["high"] - m["low"] + 1
        rows += [m["low"] + k % span for k in range(c)]
    rows = rng.permutation(np.asarray(rows, int))
    if rows.max(initial=0) > pool_rows:
        raise ValueError(f"a request of {rows.max()} rows does not fit a "
                         f"pool of {pool_rows}")
    offset = (rng.random_sample(n) * (pool_rows - rows + 1)).astype(int)
    return due, rows, offset


def percentile(samples: Sequence[float], q: float) -> float:
    """The q-th percentile (nearest rank, no interpolation), refused where
    fewer than ten samples lie beyond it: a p99 over 300 requests is the
    mean of its three worst, not a percentile."""
    n = len(samples)
    beyond = n * (1.0 - q / 100.0) if q >= 50 else n * q / 100.0
    if beyond < 10:
        raise ValueError(
            f"p{q:g} of {n} samples has {beyond:.1f} beyond it; ten are "
            f"needed ({math.ceil(10 / (1 - q / 100)) if q >= 50 else math.ceil(1000 / q)} samples)")
    ordered = np.sort(np.asarray(samples, "f8"))
    return float(ordered[min(n - 1, max(0, math.ceil(q / 100.0 * n) - 1))])


def samples_per_s(t_done: Sequence[float], samples_per_step: int,
                  t0: float, t1: float) -> dict:
    """Throughput over the steps whose completion falls in [t0, t1]:
    samples x (n - 1) / (last completion - first completion), so that a
    partial step at either end of the window does not quantise the result.
    Also the per-step wall times between those completions."""
    t = np.asarray([x for x in t_done if t0 <= x <= t1], "f8")
    if len(t) < 3:
        raise ValueError(f"{len(t)} step completions in a window of "
                         f"{t1 - t0:.1f} s: too few for a rate")
    steps = np.diff(t)
    q25, q50, q75 = np.percentile(steps, [25, 50, 75])
    return {"samples_per_s": float(samples_per_step * (len(t) - 1) / (t[-1] - t[0])),
            "n_steps": int(len(t)), "step_ms_p50": float(q50 * 1e3),
            "step_ms_p25": float(q25 * 1e3), "step_ms_p75": float(q75 * 1e3),
            "step_ms_max": float(steps.max() * 1e3)}
