"""Operations and bytes of the grouped matmuls of NON-GATED experts that
work in a latent (``relu2`` body: ``W2 relu(W1 l)^2``, ``W1 [L, I]``, ``W2
[I, L]``, ``L`` = ``moe_latent_size``), for the calls that RAN in the
traced window over the rows the program's own counter reported for its
steps (``res["counters"]`` ``moe_tokens_per_expert``), as
``kernel_costs/moe_grouped_matmul.py`` does for the gated body of the
hidden width -- which reckons three matrices ``[H, I]`` and must not be
pointed at this family.

One pass over both matrices costs a row ``2 L I + 2 I L = 4 L I`` FLOP and
is two kernel calls.  The forward is one pass, its backward two (the rows'
gradient: ``gmm``; the weights': ``tgmm``), a rematerialised layer runs
the forward's again.  The calls are COUNTED in the trace (``gmm`` and
``tgmm`` events of the first chip): ``passes = calls / (2 * layer steps)``,
the layer steps being the window's steps times the pattern's ``E`` layers.

Bytes: a ``gmm`` call reads its matrix of every held expert and a ``tgmm``
call writes a gradient of that size (half of W1 + W2 a call, on average); a
pass moves the rows' inputs and outputs once (``L``, ``I`` out; ``I``,
``L`` out).
"""

from ..readers.expert_load import served, window_counts
from .moe_grouped_matmul import calls_in_window


def window_cost(env):
    model = env.config["model"]
    latent, inter = model["moe_latent_size"], model["moe_intermediate_size"]
    held = model["n_routed_experts"]
    counts = window_counts(env)
    if not counts:
        raise RuntimeError("the grouped matmul ran and the runner handed "
                           "over no moe_tokens_per_expert counter")
    rows = served(counts)
    layer_steps = len(env.steps) * model["hybrid_override_pattern"].count("E")
    n_gmm, n_tgmm = calls_in_window(env)
    passes = (n_gmm + n_tgmm) / (2.0 * layer_steps)
    flops = passes * 4.0 * latent * inter * rows
    weights = held * 2 * latent * inter * 2         # bf16, W1 + W2
    row_io = rows * 2 * (latent + inter) * 2
    nbytes = layer_steps * weights * passes + passes * row_io
    env.ctx.note(f"moe_grouped_matmul_relu2: {rows} rows in {layer_steps} "
                 f"layer steps ({rows / max(layer_steps, 1) / held:.1f} an "
                 f"expert), {n_gmm} gmm and {n_tgmm} tgmm calls = "
                 f"{passes:g} passes")
    return flops, nbytes
