"""Arithmetic of the end-to-end metrics: window membership, rates,
percentiles, open-loop schedules. No clock is read here."""

from __future__ import annotations

import math

import numpy as np


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (the value at or above q% of the samples),
    so a tail is a latency some request really had."""
    v = np.sort(np.asarray(values, dtype=np.float64))
    if v.size == 0:
        raise ValueError("percentile of no samples")
    rank = max(1, math.ceil(q / 100.0 * v.size))
    return float(v[rank - 1])


def in_window(t_ref, t0: float, seconds: float) -> np.ndarray:
    t = np.asarray(t_ref, dtype=np.float64)
    return (t >= t0) & (t < t0 + seconds)


def closed_loop_window(t_send, t_done, ok, t0: float, seconds: float):
    """Requests of a closed loop that COMPLETED inside the window: their
    mask and latencies (send -> reply, ms)."""
    t_send, t_done = np.asarray(t_send, float), np.asarray(t_done, float)
    mask = in_window(t_done, t0, seconds) & np.asarray(ok, bool)
    return mask, (t_done[mask] - t_send[mask]) * 1e3


def open_loop_window(t_due, t_done, ok, t0: float, seconds: float):
    """Requests of an open loop that were DUE inside the window, timed
    from when they were due (a stall's wait counts against every request
    behind it). A request that failed or never finished has no latency
    and is counted by the caller as failed."""
    t_due, t_done = np.asarray(t_due, float), np.asarray(t_done, float)
    due = in_window(t_due, t0, seconds)
    mask = due & np.asarray(ok, bool)
    return due, mask, (t_done[mask] - t_due[mask]) * 1e3


def rate(work_done: float, seconds: float) -> float:
    """All completed work over the whole window."""
    if seconds <= 0:
        raise ValueError("window has no length")
    return float(work_done) / float(seconds)


def open_loop_schedule(rate_per_s: float, span_s: float, gaps_seed: int,
                       order_seed: int) -> np.ndarray:
    """Due times (s from the schedule's start) of a Poisson process.

    Every run seed gets the SAME set of inter-arrival gaps (drawn from
    `gaps_seed`, a constant of the mix) in another order (`order_seed`),
    so the seed moves bursts around without changing how much work or how
    many bursts a run holds."""
    n = int(round(rate_per_s * span_s))
    gaps = np.random.default_rng(gaps_seed).exponential(1.0 / rate_per_s, n)
    gaps *= span_s / gaps.sum()
    gaps = np.random.default_rng(order_seed).permutation(gaps)
    return np.cumsum(gaps) - gaps[0]


def lateness_ms(t_due, t_send) -> np.ndarray:
    """How late the generator sent each request (ms after it was due)."""
    return np.maximum(0.0, (np.asarray(t_send, float)
                            - np.asarray(t_due, float)) * 1e3)


def finite_mean(values) -> float | None:
    """Mean of the finite readings; None where there is nothing to read."""
    v = np.asarray(values, dtype=np.float64)
    v = v[np.isfinite(v)]
    return float(v.mean()) if v.size else None
