"""Operations and bytes of sliding-window flash attention over grouped KV
heads, for the calls that RAN in the traced window: what a window NEEDS and
no more.  A query row ``t`` sees ``min(t + 1, window)`` keys; K and V cross
HBM once a KV head; the calls are counted in the trace (events named
``flash_window<W>_attention_fwd`` / ``_bwd_dq``).  The arithmetic is
``flash_attention_gqa``'s with the window's pairs.
"""

from .flash_attention_gqa import WINDOW, kind_cost


def window_cost(env):
    return kind_cost(env, WINDOW, "flash_window",
                     window=int(env.config["model"]["sliding_window"]))
