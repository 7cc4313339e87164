"""Collective time with no other op running on that device over the
window, in %; mean over the chips.  Nothing on one chip."""

from .. import trace


def read(env):
    if env.chips < 2:
        return None
    lo, hi = env.traced["window"]
    shares = [trace.exposed_collective_seconds(
        ev, env.traced["async"].get(chip, ())) / (hi - lo)
        for chip, ev in env.traced["devices"].items()]
    return 100.0 * sum(shares) / len(shares) if shares else None
