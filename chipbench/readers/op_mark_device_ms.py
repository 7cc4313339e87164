"""Device time a step of the ops whose INSTRUCTION name holds a mark, in
ms: the own time (``trace.self_times``: a ``while`` keeps only its
overhead) of the first chip's ops inside the window whose name -- the
compiler's, ``fusion.12.remat``, not the ``tf_op`` path the program gave
(``readers/scope_device_ms.py`` reads that) -- contains ``mark``, over the
window's whole steps.

``mark: ".remat"`` is the suffix XLA's rematerialisation pass gives the
clones it makes when a program would not fit (``convolution_add_fusion
.remat``, ``.remat2`` ...): time the compiler CHOSE to spend again, where
``rematted_computation`` in a ``tf_op`` is the recomputation the program
asked for (``jax.checkpoint``).  It cuts across the parts like ``.backward``.

0.0 is a reading (the compiler cloned nothing); nothing is returned only
where the window holds no whole step or the trace no op.  The names are the
compiler's own, so this reads a program from before PR 34 too.
"""

from .. import trace
from . import scope_device_ms


def instruction(short):
    """``fusion.12.remat bf16[4,8]`` -> ``fusion.12.remat``."""
    return short.split(" ", 1)[0]


def marked_seconds(own, mark):
    """Of ``{(short name, tf_op): own seconds}``, the seconds of the ops
    whose instruction name holds ``mark``."""
    return sum(seconds for (short, _), seconds in own.items()
               if mark in instruction(short))


def read(env, mark):
    if not env.steps:
        return None
    own = scope_device_ms.window_own(trace.find_xplane(env.ctx.trace_dir),
                                     *env.traced["window"], env.chips)
    if not own:
        return None
    return 1e3 * marked_seconds(own, mark) / len(env.steps)
