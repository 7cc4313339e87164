"""Operations and bytes of the ``eva_attention_*`` kernels (attention over
the exact keys of a query's own block-aligned window and the chunk
summaries of every earlier window, ``paddle_tpu/ops/pallas/
eva_attention_kernel.py``), for the calls that RAN in the traced window:
what the mask NEEDS and no more.

A query ``t`` of window ``w = t // W`` sees ``t - wW + 1`` exact keys and
``w W / C`` summaries: over a row of ``T / W`` windows that is ``(T / W) W (W
+ 1) / 2`` exact pairs and ``W (W / C) (T / W)(T / W - 1) / 2`` summary pairs
(16,785,408 + 7,340,032 at 16,384 | 2,048 | 16).  One matmul over the pairs
with inner or outer width ``D`` is ``2 B n pairs D`` FLOP.  A forward call
runs two (scores, P V), a backward call five (scores again, dV, dP, dQ, dK);
what the program's three backward kernels form twice, and the masked part
of the diagonal blocks, count for nothing.  Softmax's exponentials and the
pooling (XLA, outside the kernels) are left out.

Bytes are the tensors that must cross HBM once a call: q, o, dO, dq once a
head; k, v, dk, dv once (every head has its own: no grouping); the
summaries kt, vt and their gradients once a head; the row statistics in
float32 (lse forward; lse and delta backward).

The calls are COUNTED in the trace (``eva_attention_fwd`` events are forward
calls, ``eva_attention_bwd_dq`` events backward calls), so a forward that
rematerialisation runs twice is paid for twice.
"""

MARK = "eva_attention"


def pairs(seq, window, chunk):
    """(exact, summary) pairs one row and head needs."""
    nw = seq // window
    return (nw * window * (window + 1) // 2,
            window * (window // chunk) * (nw * (nw - 1) // 2))


def call_costs(batch, seq, heads, head_dim, window, chunk, itemsize=2):
    """((forward FLOP, bytes), (backward FLOP, bytes)) of one call."""
    unit = 2.0 * batch * heads * sum(pairs(seq, window, chunk)) * head_dim
    per_row = batch * seq * heads * head_dim * itemsize
    per_summary = per_row // chunk
    stats = batch * heads * seq * 4
    fwd = (2 * unit, 4 * per_row + 2 * per_summary + stats)
    # read q, o, dO, k, v, kt, vt, lse, delta; write dq, dk, dv, dkt, dvt
    bwd = (5 * unit, 8 * per_row + 4 * per_summary + 2 * stats)
    return fwd, bwd


def calls_in_window(env):
    """(forward calls, backward calls) among the first chip's events."""
    events = env.traced["devices"][min(env.traced["devices"])]
    names = [ev[0] for ev in events if MARK in ev[0]]
    return (sum(1 for n in names if MARK + "_fwd" in n),
            sum(1 for n in names if MARK + "_bwd_dq" in n))


def window_cost(env):
    model = env.config["model"]
    heads = int(model["num_attention_heads"])
    (f_flop, f_bytes), (b_flop, b_bytes) = call_costs(
        env.traffic["batch"], env.traffic["seq"], heads,
        int(model["hidden_size"]) // heads, int(model["window_size"]),
        int(model["chunk_size"]))
    n_fwd, n_bwd = calls_in_window(env)
    env.ctx.note(f"{MARK}: {n_fwd} forward and {n_bwd} backward calls in "
                 f"the window, {len(env.steps)} steps of "
                 f"{model['num_hidden_layers']} layers")
    return (n_fwd * f_flop + n_bwd * b_flop,
            n_fwd * f_bytes + n_bwd * b_bytes)
