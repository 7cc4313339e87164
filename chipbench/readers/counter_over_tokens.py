"""One of the program's counters over the DATA tokens of a step, from what
the runner handed over (``res["counters"]``: ``{name: [step]...}`` of the
window's steps): for each step the counter's sum over the traffic's ``batch
* seq``, and of those the median.  ``blockdiff_masked_tokens``: the share of
the data tokens that were masked and so are loss terms, about the mean of
the noise levels (0.5 under ``U(0.001, 1)``).  Returns nothing where the
runner handed over no such counter."""

from .. import stats


def read(env, counter):
    steps = (env.res.get("counters") or {}).get(counter)
    if not steps:
        return None
    tokens = env.traffic["batch"] * env.traffic["seq"]
    return stats.median([step / tokens for step in steps])
