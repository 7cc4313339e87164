"""Operations and bytes of causal flash attention whose q and k are wider
than its v (latent attention's expanded form: q, k ``nope + rope`` wide, v
``v_head_dim``), for the calls that RAN in the traced window.

A causal score matrix is half of T x T; one matmul over it with inner or
outer width ``d`` is ``2 * B*N * T*T/2 * d`` FLOP.  A forward call runs two
(Q K^T over the q/k width, P V over the v width).  A backward call needs
five: S again (q/k), dV (v), dP (v), dQ (q/k), dK (q/k).  So a layer's
forward and backward are seven matmuls, four q/k wide and three v wide.
The program's two backward kernels form S and dP twice; that counts for
nothing.  Softmax's exponentials are left out.

The calls are COUNTED in the trace, not assumed: with the layers
rematerialised the forward kernel runs twice a layer and step, and a
share of the roofline is of the calls that ran (``flash_attention_fwd``
events are forward calls, ``flash_attention_bwd_dq`` events backward
calls: the dq and dkv kernels run once each a backward).

Bytes are the tensors that must cross HBM once a call: forward reads q, k,
v and writes o and the row statistics; backward reads q, k, v, o, do and
the statistics and writes dq, dk, dv.
"""


def call_costs(batch, seq, heads, qk_dim, v_dim, itemsize=2):
    """((forward FLOP, bytes), (backward FLOP, bytes)) of one call."""
    unit = lambda d: 2.0 * batch * heads * (seq * seq / 2.0) * d  # noqa: E731
    wide = batch * seq * heads * qk_dim * itemsize
    narrow = batch * seq * heads * v_dim * itemsize
    stats = batch * heads * seq * 4
    fwd = (unit(qk_dim) + unit(v_dim), 2 * wide + 2 * narrow + stats)
    bwd = (3 * unit(qk_dim) + 2 * unit(v_dim),
           (2 * wide + 3 * narrow + 2 * stats) + (2 * wide + narrow))
    return fwd, bwd


def calls_in_window(env):
    """(forward calls, backward calls) among the first chip's events."""
    events = env.traced["devices"][min(env.traced["devices"])]
    count = lambda mark: sum(1 for ev in events if mark in ev[0])  # noqa: E731
    return count("flash_attention_fwd"), count("flash_attention_bwd_dq")


def window_cost(env):
    model = env.config["model"]
    fwd, bwd = call_costs(
        env.traffic["batch"], env.traffic["seq"],
        model["num_attention_heads"],
        model["qk_nope_head_dim"] + model["qk_rope_head_dim"],
        model["v_head_dim"])
    n_fwd, n_bwd = calls_in_window(env)
    env.ctx.note(f"flash_attention_mla: {n_fwd} forward and {n_bwd} backward "
                 f"calls in the window, {len(env.steps)} steps of "
                 f"{model['num_hidden_layers']} layers")
    return n_fwd * fwd[0] + n_bwd * bwd[0], n_fwd * fwd[1] + n_bwd * bwd[1]
