"""Operations and bytes of causal flash attention, forward and backward,
for the training window's steps.

Per call on ``[B, T, N, D]`` bf16 (N heads of D): a causal score matrix
is half of T x T, and one matmul over it is ``2 * B*N * T*T/2 * D`` FLOP.
Forward needs two (Q K^T, P V).  Backward needs five: S is formed again
(the algorithm keeps no T x T matrix), then dV, dP, dQ, dK.  The
program's two backward kernels form S and dP twice; what is recomputed
beyond the five counts for nothing.  Softmax's exponentials are left out.

Bytes are the tensors that must cross HBM once: forward reads q, k, v
and writes o and the row statistics; backward reads q, k, v, o, do and
the statistics and writes dq, dk, dv.
"""


def call_cost(batch, seq, heads, head_dim, itemsize=2):
    unit = 2.0 * batch * heads * (seq * seq / 2.0) * head_dim
    flops = (2 + 5) * unit
    tensor = batch * seq * heads * head_dim * itemsize
    stats = batch * heads * seq * 4
    nbytes = (4 * tensor + stats) + (8 * tensor + 2 * stats)
    return flops, nbytes


def window_cost(env):
    """(FLOP, bytes) of every layer's fwd + bwd over the traced steps, on
    ONE chip."""
    model = env.config["model"]
    heads = model["num_attention_heads"]
    flops, nbytes = call_cost(env.traffic["batch"], env.traffic["seq"],
                              heads, model["hidden_size"] // heads)
    calls = len(env.steps) * model["num_hidden_layers"]
    return calls * flops, calls * nbytes
