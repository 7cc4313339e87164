"""Operations and bytes of causal flash attention over grouped KV heads
(the FULL-attention layers of a model whose layers differ: q heads of a
layer over fewer kv heads), and with ``window`` the same under a sliding
window, for the calls that RAN in the traced window.

A query row ``t`` sees ``t + 1`` keys, or ``min(t + 1, window)`` under a
window: ``pairs`` is their sum over the rows, and one matmul over them
with inner or outer width ``d`` is ``2 * B * N * pairs * d`` FLOP (N q
heads).  A forward call runs two (Q K^T, P V), a backward call five (S
again, dV, dP, dQ, dK); what the program's two backward kernels form
twice, and the masked part of the blocks a window's edges cross, count
for nothing: a share of the roofline is of what the window NEEDS.
Softmax's exponentials are left out.

Bytes are the tensors that must cross HBM once a call.  K, V, dK and dV
cross once a KV head, not once a q head; q, o, dO and dq once a q head;
the row statistics once a q head (float32; lse forward, lse and delta
backward).

The calls are COUNTED in the trace, as ``flash_attention_mla`` counts its
own (``..._fwd`` events are forward calls, ``..._bwd_dq`` events backward
calls): with the layers rematerialised and the flash forward's results
kept, each runs once a layer and step.  The layers of one kind need not
have one head count: a pass over all of them is costed layer by layer, and
the passes are the calls over the layers.
"""

FULL, WINDOW = "full_attention", "sliding_attention"


def pairs(seq, window=None):
    """Sum over query rows of the keys a row sees."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def call_costs(batch, seq, q_heads, kv_heads, head_dim, window=None,
               itemsize=2):
    """((forward FLOP, bytes), (backward FLOP, bytes)) of one call."""
    unit = 2.0 * batch * q_heads * pairs(seq, window) * head_dim
    per_q = batch * seq * q_heads * head_dim * itemsize
    per_kv = batch * seq * kv_heads * head_dim * itemsize
    stats = batch * q_heads * seq * 4
    fwd = (2 * unit, 2 * per_q + 2 * per_kv + stats)
    bwd = (5 * unit, (3 * per_q + 2 * per_kv + 2 * stats)
           + (per_q + 2 * per_kv))
    return fwd, bwd


def calls_in_window(env, mark):
    """(forward calls, backward calls) among the first chip's events whose
    name holds ``mark``."""
    events = env.traced["devices"][min(env.traced["devices"])]
    names = [ev[0] for ev in events if mark in ev[0]]
    return (sum(1 for n in names if "_fwd" in n),
            sum(1 for n in names if "_bwd_dq" in n))


def kind_cost(env, kind, mark, window=None):
    """(FLOP, bytes) of the traced window's calls for the layers of one
    ``kind``, whose kernels' names hold ``mark``."""
    model = env.config["model"]
    heads = [int(n) for n, k in zip(model["num_attention_heads_per_layer"],
                                    model["layer_types"]) if k == kind]
    if not heads:
        raise RuntimeError(f"kernels named {mark!r} ran and the model has "
                           f"no {kind} layer")
    costs = [call_costs(env.traffic["batch"], env.traffic["seq"], n,
                        int(model["num_key_value_heads"]),
                        int(model["head_dim"]), window) for n in heads]
    n_fwd, n_bwd = calls_in_window(env, mark)
    fwd_passes, bwd_passes = n_fwd / len(heads), n_bwd / len(heads)
    env.ctx.note(f"{mark}: {n_fwd} forward and {n_bwd} backward calls in "
                 f"the window, {len(env.steps)} steps of {len(heads)} {kind} "
                 f"layers ({heads} q heads over "
                 f"{model['num_key_value_heads']}), window {window}")
    return (sum(fwd_passes * f[0] + bwd_passes * b[0] for f, b in costs),
            sum(fwd_passes * f[1] + bwd_passes * b[1] for f, b in costs))


def window_cost(env):
    return kind_cost(env, FULL, "flash_attention")
