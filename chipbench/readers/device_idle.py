"""1 - union of device-op intervals / window, in %; mean over the chips."""

from .. import trace


def read(env):
    lo, hi = env.traced["window"]
    shares = [1.0 - trace.busy_seconds(ev) / (hi - lo)
              for ev in env.traced["devices"].values()]
    return 100.0 * sum(shares) / len(shares) if shares else None
