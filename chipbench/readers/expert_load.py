"""The fullest expert over the mean, from the program's own counter
(``res["counters"]`` ``moe_tokens_per_expert``: tokens each expert held
here received, a step and expert layer): for each of the window's steps
the largest count of any expert in any layer over that layer's mean
count, and of those the median.  1.0 is an even load; the grouped
matmul's time follows the sum, its tail tiles the fullest expert.
Returns nothing where the runner handed over no counter."""

from .. import stats


def window_counts(env):
    """``[step][expert layer][expert held]`` of the window's steps, or
    nothing where the runner handed over no counter."""
    return (env.res.get("counters") or {}).get("moe_tokens_per_expert")


def served(counts):
    """All the assignments the counts hold."""
    return sum(sum(sum(layer) for layer in step) for step in counts)


def read(env):
    counts = window_counts(env)
    if not counts:
        return None
    per_step = []
    for step in counts:
        per_step.append(max(max(layer) * len(layer) / max(sum(layer), 1)
                            for layer in step))
    return stats.median(per_step)
