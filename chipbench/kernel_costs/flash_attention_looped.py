"""Operations and bytes of causal flash attention in a LOOPED stack, for
the training window's steps: ``flash_attention.call_cost`` of one call on
``[B, T, N, D]`` (forward two matmuls over the causal half of T x T,
backward five; the tensors that must cross HBM once), times

    steps * num_hidden_layers * total_ut_steps

calls: every layer's attention runs once forward and once backward in EACH
of the ``R`` passes over the shared weights.  ``kernel_costs/
flash_attention.py`` counts a layer once a step and would read a quarter of
the share here.  With the layers rematerialised and the flash forward's
output and row statistics kept (the cell's ``trainer.remat``) the forward
kernel is not run a second time; were it, the extra calls would count for
nothing, as everywhere.
"""

from .flash_attention import call_cost


def window_cost(env):
    """(FLOP, bytes) of every layer's and pass's fwd + bwd over the traced
    steps, on ONE chip."""
    model = env.config["model"]
    flops, nbytes = call_cost(env.traffic["batch"], env.traffic["seq"],
                              model["num_attention_heads"],
                              model["head_dim"])
    calls = (len(env.steps) * model["num_hidden_layers"]
             * model["total_ut_steps"])
    return calls * flops, calls * nbytes
