"""FLOPs counting (reference python/paddle/hapi/dynamic_flops.py).

TPU redesign: instead of a hand-maintained per-layer FLOPs table, ask the
compiler — ``jit(forward).lower(...).compile().cost_analysis()`` returns
XLA's own flop count for the exact program that will run (fusions and
all).  The reference's table approach both undercounts (unlisted layers)
and overcounts (ops XLA folds away); the compiled number is ground truth.
"""

import numpy as np

import jax
import jax.numpy as jnp


def flops(net, input_size, dtypes=None, print_detail=False):
    """Total forward FLOPs of ``net`` at ``input_size``.

    input_size: shape tuple (one input) or list of shape tuples.
    Returns an int (FLOPs for one forward pass).
    """
    from ..core.tensor import Tensor
    from ..jit import functional_call

    shapes = [input_size] if isinstance(input_size[0], int) else \
        list(input_size)
    dtypes = dtypes or ["float32"] * len(shapes)
    examples = [jnp.zeros(s, jnp.dtype(d)) for s, d in zip(shapes, dtypes)]

    was_training = net.training
    net.eval()
    try:
        state = {k: v._data for k, v in net.state_dict().items()}

        def fn(state, *xs):
            out = functional_call(net, state, *(Tensor(x) for x in xs))
            outs = out if isinstance(out, (tuple, list)) else (out,)
            return tuple(o._data if isinstance(o, Tensor) else o
                         for o in outs)

        compiled = jax.jit(fn).lower(state, *examples).compile()
        total = int(compiled.cost_analysis().get("flops", 0))
    finally:
        if was_training:
            net.train()

    if print_detail:
        n_params = sum(int(np.prod(p.shape)) for p in net.parameters())
        print(f"Total Flops: {total:,}    Total Params: {n_params:,}")
    return total
