"""The mean pass at which a token would leave a looped stack, from the
program's counter of the exit distribution (``res["counters"][counter]``:
``[step][R]``, the mean over a step's tokens of ``p_r``): for each step of
the window ``sum_r r * mass_r`` (1 = every token leaves after the first
pass, ``R`` = none before the last), and of those the median.  It moves
when the gate, its gradient or the entropy term breaks, which no timing
shows: a gate stuck shut reads ``R``, one that collapses to the first pass
1.  Returns nothing where the runner handed over no such counter."""

from .. import stats


def exit_step(mass):
    return sum(r * m for r, m in enumerate(mass, start=1))


def read(env, counter):
    steps = (env.res.get("counters") or {}).get(counter)
    if not steps:
        return None
    return stats.median([exit_step(mass) for mass in steps])
