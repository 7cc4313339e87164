"""One of the program's counters over another, from what the runner handed
over (``res["counters"]``: ``{name: [step][...]}`` of the window's steps):
for each step the sum of ``over`` divided by the sum of ``under``, and of
those the median.  ``eva_pairs_scored`` over ``eva_pairs_needed``: 1.0 is a
grid that scores exactly the pairs the mask holds; the dense fallback reads
11.8 at the cell's shape.  Returns nothing where the runner handed over
neither counter."""

from .. import stats


def _total(x):
    return sum(_total(v) for v in x) if isinstance(x, (list, tuple)) else x


def read(env, over, under):
    counters = env.res.get("counters") or {}
    if not counters.get(over) or not counters.get(under):
        return None
    return stats.median([_total(a) / max(_total(b), 1)
                         for a, b in zip(counters[over], counters[under])])
