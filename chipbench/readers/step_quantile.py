"""Quantile ``q`` of the window's whole-step times, in ms (host clock)."""

from .. import stats


def read(env, q):
    return 1e3 * stats.quantile(stats.durations(env.steps), q)
