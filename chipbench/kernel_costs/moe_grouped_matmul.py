"""Operations and bytes of the experts' grouped matmuls, for the calls
that RAN in the traced window over the rows the program's own counter
reported for its steps (``res["counters"]`` ``moe_tokens_per_expert``: the
tokens each expert held here received, a step and expert layer) -- never
an expectation of the routing or of the step's shape.

An expert is a gated MLP: ``gate_up [H, 2I]`` and ``down [I, H]``, so one
pass over both costs a row ``2 * H * 2I + 2 * I * H = 6 H I`` FLOP.  A
pass is two kernel calls.  The forward is one pass, its backward two (the
gradient of the rows: ``gmm``; of the weights: ``tgmm``), and a layer that
is rematerialised runs the forward's a second time.  The calls are COUNTED
in the trace, as ``flash_attention_mla`` counts its own (``gmm`` and
``tgmm`` events of the first chip), not read from the configuration: a
remat policy that kept the experts' results would run fewer, and a share
of the roofline is of what ran.  ``passes = calls / (2 * layer steps)``.

Bytes: a ``gmm`` call reads its matrix of every held expert and a ``tgmm``
call writes a gradient of that size (half of gate_up + down a call, on
average); a pass moves the rows' inputs and outputs once.
"""


from ..readers.expert_load import served, window_counts


def calls_in_window(env):
    """(gmm calls, tgmm calls) among the first chip's events."""
    events = env.traced["devices"][min(env.traced["devices"])]
    names = [ev[0] for ev in events]
    tgmm = sum(1 for n in names if n.startswith("tgmm"))
    return sum(1 for n in names if n.startswith("gmm")), tgmm


def window_cost(env):
    model = env.config["model"]
    h, inter = model["hidden_size"], model["moe_intermediate_size"]
    held = model["n_routed_experts"]
    counts = window_counts(env)
    if not counts:
        raise RuntimeError("the grouped matmul ran and the runner handed "
                           "over no moe_tokens_per_expert counter")
    rows = served(counts)
    layer_steps = len(env.steps) * (model["num_hidden_layers"]
                                    - model["first_k_dense_replace"])
    n_gmm, n_tgmm = calls_in_window(env)
    passes = (n_gmm + n_tgmm) / (2.0 * layer_steps)
    flops = passes * 6.0 * h * inter * rows
    weights = held * 3 * h * inter * 2          # bf16, gate_up + down
    row_io = rows * (h + 2 * inter + inter + h) * 2
    nbytes = layer_steps * weights * passes + passes * row_io
    env.ctx.note(f"moe_grouped_matmul: {rows} rows in {layer_steps} layer "
                 f"steps ({rows / max(layer_steps, 1) / held:.1f} an expert), "
                 f"{n_gmm} gmm and {n_tgmm} tgmm calls = {passes:g} passes")
    return flops, nbytes
