"""jaxpr pattern-rewrite passes — the small IR layer SURVEY §7.4 planned.

Reference role: the inference/graph IR pass zoo
(paddle/fluid/framework/ir/*_fuse_pass.cc — e.g.
multihead_matmul_fuse_pass recognizes unfused attention subgraphs and
swaps in the fused kernel).  TPU redesign: XLA already owns generic
fusion, so the only passes worth keeping are the ones XLA can NOT do —
replacing a mathematically-recognized subgraph with a DIFFERENT
algorithm.  The flagship pass rewrites naive user-written attention
(``softmax(q @ k.T / sqrt(d)) @ v``, which materializes the [T, S] score
matrix) into the online-softmax flash kernel.

Mechanics are jax-idiomatic: a pass is a jaxpr analysis that yields
rewrite plans, applied by a replay interpreter (the "custom interpreter"
pattern) — under ``jax.jit`` the replay traces once into the optimized
program, so passes cost nothing at runtime.

    fast = ir.optimize(naive_attention_fn)      # all registered passes
    jax.jit(fast)(q, k, v)                      # flash kernel inside
"""

import functools
from collections import OrderedDict

import numpy as np

import jax
import jax.numpy as jnp
from jax.extend import core as jcore

PASSES = OrderedDict()


def register_pass(name):
    def deco(fn):
        PASSES[name] = fn
        return fn

    return deco


class Rewrite:
    """One planned substitution: consume ``eqn_indices``, bind the values
    of ``in_vars`` to ``apply`` and write its result to ``out_var``."""

    def __init__(self, eqn_indices, in_vars, out_var, apply):
        self.eqn_indices = frozenset(eqn_indices)
        self.in_vars = in_vars
        self.out_var = out_var
        self.apply = apply
        self.anchor = max(eqn_indices)  # fires at the pattern's last eqn


# ------------------------------------------------------------- matching ----

def _producers(jaxpr):
    prod = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.outvars:
            prod[v] = (i, eqn)
    return prod


def _unwrap(var, prod):
    """Walk through shape/type-preserving wrappers back to the math."""
    seen = []
    while not isinstance(var, jcore.Literal) and var in prod:
        i, eqn = prod[var]
        name = eqn.primitive.name
        if name in ("convert_element_type", "stop_gradient", "copy"):
            seen.append(i)
            var = eqn.invars[0]
        elif name == "broadcast_in_dim":
            # only TRIVIAL broadcasts (rank/keepdims plumbing) are
            # transparent; a genuine size change is real math
            src = eqn.invars[0].aval.shape
            dst = eqn.outvars[0].aval.shape
            if int(np.prod(src)) != int(np.prod(dst)):
                break
            seen.append(i)
            var = eqn.invars[0]
        elif name == "max" and isinstance(eqn.invars[0], jcore.Literal):
            # jax.nn.softmax guards the running max with max(-inf, .)
            seen.append(i)
            var = eqn.invars[1]
        else:
            break
    return var, seen


def _eqn_of(var, prod, prim_name):
    if isinstance(var, jcore.Literal) or var not in prod:
        return None
    i, eqn = prod[var]
    return (i, eqn) if eqn.primitive.name == prim_name else None


def _match_softmax(prod, p_var):
    """Match ``p_var = softmax(src, axis=-1)`` (div(exp(sub(src, max)),
    sum)); returns (src_var, consumed_indices) or None.  Shared by
    fuse_attention and decode_attention so the chain-walk has exactly one
    implementation."""
    m = _eqn_of(p_var, prod, "div")
    if m is None:
        return None
    div_i, div_eqn = m
    num_var, skip_b = _unwrap(div_eqn.invars[0], prod)
    den_var, skip_c = _unwrap(div_eqn.invars[1], prod)
    m = _eqn_of(num_var, prod, "exp")
    if m is None:
        return None
    exp_i, exp_eqn = m
    m = _eqn_of(den_var, prod, "reduce_sum")
    if m is None:
        return None
    sum_i, sum_eqn = m
    s_nd = len(sum_eqn.invars[0].aval.shape)
    if tuple(sum_eqn.params.get("axes", ())) != (s_nd - 1,):
        return None
    sum_src, skip_d = _unwrap(sum_eqn.invars[0], prod)
    if sum_src is not num_var:
        return None
    m = _eqn_of(_unwrap(exp_eqn.invars[0], prod)[0], prod, "sub")
    if m is None:
        return None
    sub_i, sub_eqn = m
    src_var, skip_e = _unwrap(sub_eqn.invars[0], prod)
    mx_var, skip_f = _unwrap(sub_eqn.invars[1], prod)
    m = _eqn_of(mx_var, prod, "reduce_max")
    if m is None:
        return None
    max_i, max_eqn = m
    if _unwrap(max_eqn.invars[0], prod)[0] is not src_var:
        return None
    mx_nd = len(max_eqn.invars[0].aval.shape)
    if tuple(max_eqn.params.get("axes", ())) != (mx_nd - 1,):
        return None
    consumed = {div_i, exp_i, sum_i, sub_i, max_i}
    consumed.update(skip_b + skip_c + skip_d + skip_e + skip_f)
    return src_var, consumed


def _neg_fill(var, prod, threshold=-1e8):
    """True if ``var`` is (a broadcast/convert of) a scalar <= threshold —
    an 'effectively -inf' softmax fill (exp underflows to exactly 0.0 in
    f32 for any realistic score magnitude).  The threshold admits the
    bf16 rounding of the common -1e9 spelling (bf16(-1e9) ~= -9.98e8)."""
    for _ in range(8):
        if isinstance(var, jcore.Literal):
            v = np.asarray(var.val)
            return v.ndim == 0 and float(v) <= threshold
        if var not in prod:
            return False
        _, eqn = prod[var]
        if eqn.primitive.name in ("convert_element_type",
                                  "broadcast_in_dim", "stop_gradient",
                                  "copy"):
            var = eqn.invars[0]
        else:
            return False
    return False


def _match_where_mask(prod, var):
    """Match ``var = where(pred, scores, fill)`` with a boolean pred and a
    large-negative scalar fill; returns (pred_var, scores_operand,
    eqn_index) or None.  The where must not upsize the scores operand — a
    broadcast here would change the batch layout downstream dot checks
    were made against."""
    if isinstance(var, jcore.Literal) or var not in prod:
        return None
    i, eqn = prod[var]
    if len(eqn.invars) != 3:
        return None     # multi-case select_n / hoisted-const _where
    if _pjit_name(eqn) == "_where":
        pred, scores, fill = eqn.invars
    elif eqn.primitive.name == "select_n":
        pred, fill, scores = eqn.invars
    else:
        return None
    if not jnp.issubdtype(pred.aval.dtype, jnp.bool_):
        return None
    if not _neg_fill(fill, prod):
        return None
    if tuple(eqn.outvars[0].aval.shape) != tuple(scores.aval.shape):
        return None
    return pred, scores, i


def _try_const_eval(var, jaxpr, consts, prod, max_elems=1 << 26,
                    max_eqns=64):
    """Numerically evaluate ``var`` if it depends only on literals,
    constvars, and eqns — no jaxpr inputs.  Returns a numpy array or
    None.  Used to prove mask structure (e.g. causal tril) at match
    time; evaluation is eager and bounded."""
    if isinstance(var, jcore.Literal):
        return np.asarray(var.val)
    if var.aval.shape and int(np.prod(var.aval.shape)) > max_elems:
        return None
    const_env = dict(zip(jaxpr.constvars, consts))
    needed = set()
    stack, visited = [var], set()
    while stack:
        v = stack.pop()
        if isinstance(v, jcore.Literal) or v in const_env or v in visited:
            continue
        visited.add(v)
        if v not in prod:
            return None          # reaches a jaxpr input: runtime value
        i, eqn = prod[v]
        needed.add(i)
        if len(needed) > max_eqns:
            return None
        # bound every INTERMEDIATE too — a small slice of a huge
        # constant table would otherwise materialize the table eagerly
        # at match time (review finding)
        for ov in eqn.outvars:
            if ov.aval.shape and int(np.prod(ov.aval.shape)) > max_elems:
                return None
        stack.extend(eqn.invars)
    env = dict(const_env)

    def read(v):
        return v.val if isinstance(v, jcore.Literal) else env[v]

    try:
        with jax.ensure_compile_time_eval():
            for i in sorted(needed):
                eqn = jaxpr.eqns[i]
                subfuns, bind_params = \
                    eqn.primitive.get_bind_params(eqn.params)
                ans = eqn.primitive.bind(
                    *subfuns, *[read(x) for x in eqn.invars],
                    **bind_params)
                if eqn.primitive.multiple_results:
                    for ov, a in zip(eqn.outvars, ans):
                        env[ov] = a
                else:
                    env[eqn.outvars[0]] = ans
        return np.asarray(env[var])
    except Exception:
        return None


def _match_scaled_dot(prod, scores_var):
    """Match an optional scalar ``* c`` / ``/ c`` around a dot_general;
    returns (dot_i, dot_eqn, scale_mode, scale_val, consumed) or None."""
    sdot = _eqn_of(scores_var, prod, "dot_general")
    if sdot is not None:
        return sdot[0], sdot[1], None, None, set()
    for op in ("div", "mul"):
        m = _eqn_of(scores_var, prod, op)
        if m is None:
            continue
        op_i, op_eqn = m
        cand, sk = _unwrap(op_eqn.invars[0], prod)
        sdot = _eqn_of(cand, prod, "dot_general")
        # the scale must be a SCALAR (literal or runtime) — a shaped
        # operand here is a mask/bias, not a scale
        if sdot is not None and not op_eqn.invars[1].aval.shape:
            return (sdot[0], sdot[1], op, op_eqn.invars[1],
                    {op_i} | set(sk))
    return None


@register_pass("fuse_attention")
def fuse_attention(jaxpr, consts=()):
    """Find softmax(mask(scale(q @ k^T))) @ v chains; plan flash swaps.

    Matches the 2D single-head layout (q [T, D], k [S, D], v [S, D]) and
    the batched-heads einsum layout (q [B, N, T, D] against k
    [B, N, S, D]).  The score scaling may be ``/ c`` or ``* c`` by a
    scalar, or absent.  An optional mask between the scaled dot and the
    softmax is matched in both spellings real transformer code uses:

    - ``where(pred, scores, -big)``  (boolean mask, fill <= -1e9)
    - ``scores + bias``              (additive mask)

    If the mask is compile-time constant it is evaluated at match time;
    a proven causal tril (T == S) routes to the flash kernel's
    ``is_causal=True`` online-softmax path — the pattern every naive
    causal GPT block writes.  Any other broadcast-compatible mask
    (constant or runtime, e.g. padding masks) is routed through
    ``flash_attention(attn_mask=...)``, whose fused path applies the
    mask with f32 softmax.  Masks that upsize the scores or do not
    right-align under broadcasting decline.
    Reference role: multihead_matmul_fuse_pass +
    python/paddle/nn/functional/flash_attention.py:53 (mask/causal
    arguments of the fused op).
    """
    prod = _producers(jaxpr)
    rewrites = []
    for i, eqn in enumerate(jaxpr.eqns):
        if eqn.primitive.name != "dot_general":
            continue
        # final dot: [.., T, S] @ v — LHS must be a softmax output
        p_var, skip_a = _unwrap(eqn.invars[0], prod)
        v_var = eqn.invars[1]
        sm = _match_softmax(prod, p_var)
        if sm is None:
            continue
        scores_var, sm_consumed = sm
        # optional mask between the softmax and the scaled dot
        mask_var = None
        mask_bool = False
        mask_consumed = set()
        sd = None
        wh = _match_where_mask(prod, scores_var)
        if wh is not None:
            pred_var, inner_raw, wh_i = wh
            inner, sk_m = _unwrap(inner_raw, prod)
            sd = _match_scaled_dot(prod, inner)
            if sd is None:
                continue
            mask_var, mask_bool = pred_var, True
            mask_consumed = {wh_i} | set(sk_m)
        else:
            m = _eqn_of(scores_var, prod, "add")
            if m is not None:
                add_i, add_eqn = m
                for a, b in ((0, 1), (1, 0)):
                    inner, sk_m = _unwrap(add_eqn.invars[a], prod)
                    sd_try = _match_scaled_dot(prod, inner)
                    if sd_try is not None and not isinstance(
                            add_eqn.invars[b], jcore.Literal):
                        sd = sd_try
                        mask_var = add_eqn.invars[b]
                        mask_consumed = {add_i} | set(sk_m)
                        break
                if sd is None:
                    continue
            else:
                sd = _match_scaled_dot(prod, scores_var)
                if sd is None:
                    continue
        dot_i, dot_eqn, scale_mode, scale_val, sd_consumed = sd
        q_var, k_var = dot_eqn.invars
        ((lc, rc), (lb, rb)) = dot_eqn.params["dimension_numbers"]
        q_aval = q_var.aval
        nd = len(q_aval.shape)
        # layouts: 2D q[T,D]·k[S,D] (contract (1,1), or (1,0) through an
        # explicit k.T transpose) or batched q[B,N,T,D]·k[B,N,S,D]
        layout = None
        skip_h = []
        if nd == 2 and tuple(lc) == (1,) and not lb:
            if tuple(rc) == (1,):
                layout = "2d"
            elif tuple(rc) == (0,):
                kt = _eqn_of(k_var, prod, "transpose")
                if kt is not None and tuple(
                        kt[1].params["permutation"]) == (1, 0):
                    layout = "2d"
                    skip_h = [kt[0]]
                    k_var = kt[1].invars[0]
        elif nd == 4 and tuple(lc) == (3,) and tuple(rc) == (3,) \
                and tuple(lb) == (0, 1) and tuple(rb) == (0, 1):
            layout = "bhtd"
        if layout is None:
            continue
        # the final dot must contract the softmax's last axis with v's
        # matching axis, same batching as the scores
        ((flc, frc), (flb, frb)) = eqn.params["dimension_numbers"]
        if layout == "2d" and (tuple(flc), tuple(frc)) != ((1,), (0,)):
            continue
        if layout == "bhtd" and ((tuple(flc), tuple(frc)) != ((3,), (2,))
                                 or tuple(flb) != (0, 1)
                                 or tuple(frb) != (0, 1)):
            continue

        # mask validation: must right-align under numpy broadcasting with
        # the [.., T, S] scores; a compile-time-constant causal tril
        # upgrades to the kernel's is_causal path
        causal = False
        if mask_var is not None:
            t_dim = q_var.aval.shape[-2]
            k_aval = k_var.aval
            s_dim = k_aval.shape[0] if layout == "2d" else k_aval.shape[-2]
            score_shape = (t_dim, s_dim) if layout == "2d" else \
                (q_aval.shape[0], q_aval.shape[1], t_dim, s_dim)
            mshape = mask_var.aval.shape
            if len(mshape) > len(score_shape):
                continue
            if any(md != 1 and md != sd_ for md, sd_ in
                   zip(reversed(mshape), reversed(score_shape))):
                continue
            # mval is only consumed by the causal (square) check — skip
            # the eager evaluation entirely for cross-attention shapes
            mval = _try_const_eval(mask_var, jaxpr, consts, prod) \
                if t_dim == s_dim else None
            if mval is not None:
                tril = np.tril(np.ones((t_dim, s_dim), bool))
                if mask_bool:
                    causal = bool(np.all((mval != 0) == tril))
                else:
                    # additive causal bias: exactly 0 where attended,
                    # effectively -inf where masked (threshold matches
                    # _neg_fill's bf16-rounding allowance)
                    causal = bool(np.all(np.where(tril, mval == 0,
                                                  mval <= -1e8)))

        consumed = {i, dot_i} | sm_consumed | sd_consumed | mask_consumed
        consumed.update(skip_a + skip_h)
        if not _interior_ok(jaxpr, consumed, i):
            continue
        if causal:
            # the mask value is no longer read — consume its whole
            # producer chain too so eager replay doesn't rebuild the
            # tril every call (dead code; XLA would DCE it only under
            # jit).  If the chain is shared with anything outside the
            # pattern, keep the base set.
            chain, stack, cseen = set(), [mask_var], set()
            while stack:
                v = stack.pop()
                if isinstance(v, jcore.Literal) or v in cseen \
                        or v not in prod:
                    continue
                cseen.add(v)
                ci, ceqn = prod[v]
                chain.add(ci)
                stack.extend(ceqn.invars)
            extended = consumed | chain
            if _interior_ok(jaxpr, extended, i):
                consumed = extended

        head_dim = q_aval.shape[-1]
        s_literal = (scale_val.val if isinstance(scale_val, jcore.Literal)
                     else None) if scale_mode else None

        def apply(read, *, _layout=layout, _mode=scale_mode,
                  _sval=scale_val, _slit=s_literal, _d=head_dim,
                  _q=q_var, _k=k_var, _v=v_var, _mask=mask_var,
                  _causal=causal):
            from ..ops import pallas

            q = read(_q)
            k = read(_k)
            v = read(_v)
            # normalize the matched scale onto q so the kernel's own
            # 1/sqrt(d) yields the user's exact scaling
            scale = 1.0
            if _mode == "div":
                s = _slit if _slit is not None else read(_sval)
                scale = 1.0 / s
            elif _mode == "mul":
                scale = _slit if _slit is not None else read(_sval)
            q = q * (scale * jnp.sqrt(jnp.asarray(_d, q.dtype)))
            kw = {}
            if _causal:
                kw["is_causal"] = True
            elif _mask is not None:
                kw["attn_mask"] = read(_mask)
            if _layout == "2d":
                out = pallas.flash_attention(
                    q[None, :, None, :], k[None, :, None, :],
                    v[None, :, None, :], **kw)
                return out[0, :, 0, :]
            # bhtd: [B, N, T, D] -> kernel layout [B, T, N, D]
            out = pallas.flash_attention(
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), **kw)
            return out.transpose(0, 2, 1, 3)

        in_vars = (q_var, k_var, v_var)
        if mask_var is not None and not causal:
            in_vars = in_vars + (mask_var,)
        rewrites.append(Rewrite(consumed, in_vars,
                                eqn.outvars[0], apply))
    return rewrites


def _pjit_name(eqn):
    """Named-subcall eqns (jnp.where / log_softmax / take_along_axis trace
    as `jit` eqns carrying the traced function's name)."""
    if eqn.primitive.name not in ("jit", "pjit"):
        return None
    return eqn.params.get("name")


def _interior_ok(jaxpr, consumed, anchor_idx):
    """True iff no eqn outside ``consumed`` (and no jaxpr output) reads a
    value produced inside the pattern (other than the anchor's output)."""
    interior = set()
    for j in consumed:
        if j != anchor_idx:
            interior.update(jaxpr.eqns[j].outvars)
    for j, other in enumerate(jaxpr.eqns):
        if j in consumed:
            continue
        if any(v in interior for v in other.invars
               if not isinstance(v, jcore.Literal)):
            return False
    return not any(v in interior for v in jaxpr.outvars
                   if not isinstance(v, jcore.Literal))


@register_pass("decode_attention")
def decode_attention(jaxpr, consts=()):
    """Single-token masked decode attention -> ragged GQA decode kernel.

    Matches the canonical KV-cache decode chain (the shape
    FusedMultiTransformer emits at T=1):

        logits = einsum('bqnd,bknd->bnqk', q, cache_k) * scale
        logits = where(iota_S <= pos, logits, -big)      # prefix mask
        att    = softmax(logits, axis=-1)                # f32
        out    = einsum('bnqk,bknd->bqnd', att, cache_v)

    and swaps in ``ragged_decode_attention`` (Pallas on TPU, dense-masked
    XLA elsewhere — same semantics), which reads only ``lengths`` cache
    rows per head instead of S_max.  The prefix mask is PROVEN at match
    time (the predicate must be ``le``/``lt`` of an iota over the score
    axis), then measured at run time (lengths = per-row popcount).
    Reference role: the decode path of
    fused_multi_transformer_op + multihead_matmul_fuse_pass.cc.
    """
    prod = _producers(jaxpr)
    rewrites = []
    for i, eqn in enumerate(jaxpr.eqns):
        # final dot: v-first (einsum puts the cache on the left) with a
        # following transpose, or att-first
        if eqn.primitive.name != "dot_general":
            continue
        ((lc, rc), (lb, rb)) = eqn.params["dimension_numbers"]
        v_first = None
        if (tuple(lc), tuple(rc)) == ((1,), (3,)) and \
                (tuple(lb), tuple(rb)) == ((0, 2), (0, 1)):
            v_first = True          # [B,S,N,D] x [B,N,1,S] -> [B,N,D,1]
        elif (tuple(lc), tuple(rc)) == ((3,), (1,)) and \
                (tuple(lb), tuple(rb)) == ((0, 1), (0, 2)):
            v_first = False         # [B,N,1,S] x [B,S,N,D] -> [B,N,1,D]
        else:
            continue
        att_raw = eqn.invars[1] if v_first else eqn.invars[0]
        v_var = eqn.invars[0] if v_first else eqn.invars[1]
        p_var, skip_a = _unwrap(att_raw, prod)
        sm = _match_softmax(prod, p_var)
        if sm is None:
            continue
        masked_var, sm_consumed = sm
        # the masked logits: where(pred, scaled_scores, -big)
        if isinstance(masked_var, jcore.Literal) or masked_var not in prod:
            continue
        wh_i, wh_eqn = prod[masked_var]
        if _pjit_name(wh_eqn) != "_where":
            continue
        pred_var, scores_raw, fill = wh_eqn.invars
        if not jnp.issubdtype(pred_var.aval.dtype, jnp.bool_):
            continue
        fill_neg = (isinstance(fill, jcore.Literal)
                    and np.ndim(fill.val) == 0 and fill.val <= -1e20)
        if not fill_neg:
            continue
        s_max = wh_eqn.outvars[0].aval.shape[-1]
        # pred must be a PREFIX mask over the score axis, uniform across
        # heads.  Three proofs (review-hardened — an le/lt+iota match
        # alone admits per-head cutoffs and per-position vectors):
        #  (a) pred's last dim is S and every other dim is 1, or only
        #      the leading (batch) dim is >1 — so lengths don't secretly
        #      vary across heads;
        #  (b) the iota side varies ONLY along that last axis (its aval
        #      is [*, S] with all other dims 1);
        #  (c) the comparand is constant along S (its last dim is 1).
        ps = pred_var.aval.shape
        if not ps or ps[-1] != s_max:
            continue
        mid_one = all(d == 1 for d in ps[1:-1])
        if not (all(d == 1 for d in ps[:-1]) or
                (len(ps) == 4 and mid_one)):
            continue
        pm_var, _skg = _unwrap(pred_var, prod)
        cmp = _eqn_of(pm_var, prod, "le") or _eqn_of(pm_var, prod, "lt")
        if cmp is None:
            continue
        cmp_i, cmp_eqn = cmp
        lhs_shape = cmp_eqn.invars[0].aval.shape
        rhs_shape = cmp_eqn.invars[1].aval.shape
        if not lhs_shape or lhs_shape[-1] != s_max or \
                any(d != 1 for d in lhs_shape[:-1]):
            continue
        if rhs_shape and rhs_shape[-1] != 1:
            continue
        iota_var, _skh = _unwrap(cmp_eqn.invars[0], prod)
        if _eqn_of(iota_var, prod, "iota") is None:
            continue
        # the scores: optional scalar mul/div around the q@k dot
        scores_var, skip_i = _unwrap(scores_raw, prod)
        sd = _match_scaled_dot(prod, scores_var)
        if sd is None:
            continue
        dot_i, dot_eqn, scale_mode, scale_val, sd_consumed = sd
        ((qlc, qrc), (qlb, qrb)) = dot_eqn.params["dimension_numbers"]
        if (tuple(qlc), tuple(qrc)) != ((3,), (3,)) or \
                (tuple(qlb), tuple(qrb)) != ((0, 2), (0, 2)):
            continue
        q_var = dot_eqn.invars[0]
        k_var, skip_k = _unwrap(dot_eqn.invars[1], prod)
        if len(q_var.aval.shape) != 4 or q_var.aval.shape[1] != 1:
            continue        # decode only: a single query token
        v_real, skip_l = _unwrap(v_var, prod)

        del cmp_i  # prefix-ness proven; the mask chain stays live in
        # the replay because apply() reads the predicate value
        consumed = {i, wh_i, dot_i} | sm_consumed | sd_consumed
        consumed.update(skip_a + skip_i + skip_k + skip_l)
        # the optional transpose right after a v-first dot belongs to the
        # pattern (it restores [B,1,N,D])
        out_var = eqn.outvars[0]
        tr = None
        for j, other in enumerate(jaxpr.eqns):
            if other.primitive.name == "transpose" and \
                    other.invars[0] is out_var and \
                    tuple(other.params["permutation"]) == (
                        (0, 3, 1, 2) if v_first else (0, 2, 1, 3)):
                tr = (j, other)
                break
        if tr is not None:
            consumed.add(tr[0])
            out_var = tr[1].outvars[0]
        anchor = max(consumed)
        if not _interior_ok(jaxpr, consumed, anchor):
            continue

        head_dim = q_var.aval.shape[-1]
        s_lit = (scale_val.val if isinstance(scale_val, jcore.Literal)
                 else None) if scale_mode else None

        out_dtype = out_var.aval.dtype

        def apply(read, *, _mode=scale_mode, _sval=scale_val, _slit=s_lit,
                  _d=head_dim, _q=q_var, _k=k_var, _v=v_real,
                  _pred=pred_var, _vfirst=v_first, _tr=tr is not None,
                  _dt=out_dtype):
            from ..ops.pallas import _use_pallas
            from ..ops.pallas import decode_attention_kernel as dk

            q = read(_q)            # [B, 1, N, D]
            k = read(_k)            # [B, S, N, D]
            v = read(_v)
            pred = read(_pred)      # prefix mask, proven at match time
            scale = 1.0
            if _mode == "div":
                s = _slit if _slit is not None else read(_sval)
                scale = 1.0 / s
            elif _mode == "mul":
                scale = _slit if _slit is not None else read(_sval)
            q = q * (scale * jnp.sqrt(jnp.asarray(_d, q.dtype)))
            b, s_max = k.shape[0], k.shape[1]
            # pred is proven [1,..,1,S] or [B,1,1,S] at match time
            lsum = pred.sum(-1).astype(jnp.int32)
            if len(pred.shape) == 4 and pred.shape[0] == b:
                lengths = lsum.reshape(b)              # per-batch mask
            else:
                lengths = jnp.broadcast_to(lsum.reshape(-1)[0], (b,))
            if _use_pallas() and \
                    dk.supports(s_max, _d, q.shape[2], k.shape[2]):
                # the XLA composition is a decision (the flag), never
                # a silent substitute for a kernel that cannot compile
                raise NotImplementedError(dk.TPU_REFUSAL)
            out = dk.decode_attention_xla(q[:, 0], k, v, lengths)
            out = out.astype(_dt)           # [B, N, D]
            if _tr:
                return out[:, None]         # [B, 1, N, D]
            if not _vfirst:
                return out[:, :, None]      # att-first raw: [B, N, 1, D]
            return out[..., None]           # v-first raw: [B, N, D, 1]
        rewrites.append(Rewrite(consumed, (q_var, k_var, v_real, pred_var),
                                out_var, apply))
    return rewrites


@register_pass("fuse_layernorm")
def fuse_layernorm(jaxpr, consts=()):
    """Hand-written layernorm -> one fused normalization in f32.

    Matches ``(x - mean(x)) * rsqrt(var(x) + eps) * w + b`` (reduce over
    the last axis) and replaces the 10-eqn chain with a single fused
    computation whose statistics run in float32 — for bf16 activations
    this is a numerics upgrade the unfused bf16 chain doesn't have.
    Reference role: the layer_norm fuse passes
    (paddle/fluid/framework/ir/ layer-norm fuse family).
    """
    prod = _producers(jaxpr)
    rewrites = []

    def _bcast_1d(var):
        """var (through a trivial broadcast) of a 1-D vector; returns the
        source var or None."""
        if isinstance(var, jcore.Literal):
            return None, []
        v, sk = _unwrap(var, prod)
        if isinstance(v, jcore.Literal):
            return None, []
        if len(v.aval.shape) == 1:
            return v, sk
        if var in prod:
            j, e = prod[var]
            if e.primitive.name == "broadcast_in_dim" and \
                    len(e.invars[0].aval.shape) == 1:
                # the vector must map onto the LAST axis — an explicit
                # broadcast_in_dim to another axis of equal size is not
                # last-axis scaling (advisor finding, round 4)
                out_nd = len(e.outvars[0].aval.shape)
                if tuple(e.params.get("broadcast_dimensions", ())) != \
                        (out_nd - 1,):
                    return None, []
                return e.invars[0], [j]
        return None, []

    def _mean_of(var):
        """div(reduce_sum(src), n) behind a trivial broadcast."""
        v, sk = _unwrap(var, prod)
        if isinstance(v, jcore.Literal):
            return None
        m = _eqn_of(v, prod, "div")
        if m is None:
            return None
        div_i, div_eqn = m
        if not isinstance(div_eqn.invars[1], jcore.Literal):
            return None
        divisor = float(np.asarray(div_eqn.invars[1].val))
        s, sk2 = _unwrap(div_eqn.invars[0], prod)
        m2 = _eqn_of(s, prod, "reduce_sum")
        if m2 is None:
            return None
        sum_i, sum_eqn = m2
        nd = len(sum_eqn.invars[0].aval.shape)
        if tuple(sum_eqn.params.get("axes", ())) != (nd - 1,):
            return None
        # a true mean divides by the reduced axis length — anything else
        # (ddof=1 variance, arbitrary scaling) is a different function
        # (review-hardened)
        if divisor != float(sum_eqn.invars[0].aval.shape[-1]):
            return None
        src, sk3 = _unwrap(sum_eqn.invars[0], prod)
        return (src,
                {div_i, sum_i} | set(sk) | set(sk2) | set(sk3))

    for i, eqn in enumerate(jaxpr.eqns):
        if eqn.primitive.name != "add":
            continue
        b_var, skb = _bcast_1d(eqn.invars[1])
        if b_var is None:
            continue
        core_var, sk0 = _unwrap(eqn.invars[0], prod)
        m = _eqn_of(core_var, prod, "mul")
        if m is None:
            continue
        mulw_i, mulw_eqn = m
        w_var, skw = _bcast_1d(mulw_eqn.invars[1])
        if w_var is None:
            continue
        norm_var, sk1 = _unwrap(mulw_eqn.invars[0], prod)
        m = _eqn_of(norm_var, prod, "mul")
        if m is None:
            continue
        muln_i, muln_eqn = m
        sub_var, sk2 = _unwrap(muln_eqn.invars[0], prod)
        rs_var, sk3 = _unwrap(muln_eqn.invars[1], prod)
        m = _eqn_of(sub_var, prod, "sub")
        rs = _eqn_of(rs_var, prod, "rsqrt")
        if m is None or rs is None:
            continue
        sub_i, sub_eqn = m
        rs_i, rs_eqn = rs
        # mean: sub(x, mean(x)) — compare through dtype converts (the
        # bf16 trace upcasts the reduction and converts back)
        x_var, skx = _unwrap(sub_eqn.invars[0], prod)
        mean = _mean_of(sub_eqn.invars[1])
        if mean is None or mean[0] is not x_var:
            continue
        # rsqrt(var + eps)
        va, sk4 = _unwrap(rs_eqn.invars[0], prod)
        m = _eqn_of(va, prod, "add")
        if m is None:
            continue
        eadd_i, eadd_eqn = m
        if not isinstance(eadd_eqn.invars[1], jcore.Literal):
            continue
        eps = float(eadd_eqn.invars[1].val)
        var_mean = _mean_of(eadd_eqn.invars[0])
        if var_mean is None:
            continue
        sq_var, sk5 = _unwrap(var_mean[0], prod)
        sq = _eqn_of(sq_var, prod, "integer_pow")
        if sq is None or sq[1].params.get("y") != 2:
            continue
        sq_i, sq_eqn = sq
        centered, sk6 = _unwrap(sq_eqn.invars[0], prod)
        m = _eqn_of(centered, prod, "sub")
        if m is None:
            continue
        sub2_i, sub2_eqn = m
        x2_var, skx2 = _unwrap(sub2_eqn.invars[0], prod)
        if x2_var is not x_var:
            continue
        mean2 = _mean_of(sub2_eqn.invars[1])
        if mean2 is None or mean2[0] is not x_var:
            continue

        consumed = {i, mulw_i, muln_i, sub_i, rs_i, eadd_i, sq_i, sub2_i}
        consumed |= mean[1] | var_mean[1] | mean2[1]
        consumed.update(skb + sk0 + skw + sk1 + sk2 + sk3 + sk4 + sk5 +
                        sk6 + skx + skx2)
        anchor = max(consumed)
        if not _interior_ok(jaxpr, consumed, anchor):
            continue

        def apply(read, *, _x=x_var, _w=w_var, _b=b_var, _eps=eps):
            x = read(_x)
            xf = x.astype(jnp.float32)
            mu = xf.mean(-1, keepdims=True)
            var = jnp.square(xf - mu).mean(-1, keepdims=True)
            y = (xf - mu) * jax.lax.rsqrt(var + _eps)
            y = y * read(_w).astype(jnp.float32) \
                + read(_b).astype(jnp.float32)
            return y.astype(x.dtype)

        rewrites.append(Rewrite(consumed, (x_var, w_var, b_var),
                                eqn.outvars[0], apply))
    return rewrites


@register_pass("chunk_cross_entropy")
def chunk_cross_entropy(jaxpr, consts=()):
    """log_softmax + take_along_axis -> chunked softmax-xent.

    The naive spelling materializes the full [N, V] log-probability
    matrix; the rewrite swaps in ``_chunked_softmax_xent`` (lax.map over
    row chunks with a custom VJP), keeping only [chunk, V] transient —
    the HBM saver for LLM-scale vocabularies.  Reference role: the
    softmax_with_cross_entropy fused op
    (paddle/phi/kernels/softmax_with_cross_entropy_*).
    """
    prod = _producers(jaxpr)
    rewrites = []
    for i, eqn in enumerate(jaxpr.eqns):
        if _pjit_name(eqn) != "take_along_axis":
            continue
        lp_var, sk0 = _unwrap(eqn.invars[0], prod)
        if lp_var not in prod:
            continue
        ls_i, ls_eqn = prod[lp_var]
        if _pjit_name(ls_eqn) != "log_softmax":
            continue
        logits_var = ls_eqn.invars[0]
        if len(logits_var.aval.shape) != 2:
            continue
        # the softmax must reduce over the class axis
        inner = ls_eqn.params["jaxpr"].jaxpr
        nd = len(logits_var.aval.shape)
        red_ok = any(e.primitive.name == "reduce_max"
                     and tuple(e.params.get("axes", ())) == (nd - 1,)
                     for e in inner.eqns)
        if not red_ok:
            continue
        lbl_raw = eqn.invars[1]
        if not jnp.issubdtype(lbl_raw.aval.dtype, jnp.integer):
            continue
        if tuple(lbl_raw.aval.shape) != (logits_var.aval.shape[0], 1):
            continue
        # the gather must be along the CLASS axis: picking one entry per
        # row yields [N, 1] — an axis=0 gather yields [N, V]
        # (review-hardened)
        if tuple(eqn.outvars[0].aval.shape) != \
                (logits_var.aval.shape[0], 1):
            continue
        lbl_var, sk1 = _unwrap(lbl_raw, prod)
        sk2 = []
        if len(lbl_var.aval.shape) == 2 and lbl_var in prod:
            j, e = prod[lbl_var]
            if e.primitive.name == "broadcast_in_dim" and \
                    len(e.invars[0].aval.shape) == 1:
                lbl_var = e.invars[0]
                sk2 = [j]
        consumed = {i, ls_i}
        consumed.update(sk0 + sk1 + sk2)
        anchor = max(consumed)
        if not _interior_ok(jaxpr, consumed, anchor):
            continue

        out_dtype = eqn.outvars[0].aval.dtype

        def apply(read, *, _logits=logits_var, _lbl=lbl_var,
                  _dt=out_dtype):
            from ..nn.functional import _chunked_softmax_xent

            logits = read(_logits)
            labels = read(_lbl).reshape(-1)
            loss = _chunked_softmax_xent(logits, labels)   # = -picked
            return (-loss).astype(_dt)[:, None]

        rewrites.append(Rewrite(consumed, (logits_var, lbl_var),
                                eqn.outvars[0], apply))
    return rewrites


# -------------------------------------------------------------- replay ----

def _replay(closed, rewrites, args):
    jaxpr = closed.jaxpr
    by_anchor = {}
    consumed = set()
    for rw in rewrites:
        by_anchor[rw.anchor] = rw
        consumed |= rw.eqn_indices
    env = {}

    def read(var):
        return var.val if isinstance(var, jcore.Literal) else env[var]

    def write(var, val):
        env[var] = val

    for v, c in zip(jaxpr.constvars, closed.consts):
        write(v, c)
    flat = jax.tree_util.tree_leaves(args)
    for v, a in zip(jaxpr.invars, flat):
        write(v, a)
    for i, eqn in enumerate(jaxpr.eqns):
        rw = by_anchor.get(i)
        if rw is not None:
            write(rw.out_var, rw.apply(read))
            continue
        if i in consumed:
            # interior eqns still execute if a LATER anchor needs their
            # inputs?  No: consumed eqns feed only the anchor (checked
            # during matching) — skip them entirely.
            continue
        subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
        invals = [read(x) for x in eqn.invars]
        ans = eqn.primitive.bind(*subfuns, *invals, **bind_params)
        if eqn.primitive.multiple_results:
            for v, a in zip(eqn.outvars, ans):
                write(v, a)
        else:
            write(eqn.outvars[0], ans)
    return [read(v) for v in jaxpr.outvars]


def optimize(fn, passes=None, static_argnums=()):
    """Return ``fn`` with the registered jaxpr passes applied.

    The trace + pattern match is cached per input structure
    (shapes/dtypes/treedef + static-arg values), so eager loops pay it
    once; under jit the optimized replay itself is traced once.
    Functions where no pattern matches run unchanged.  The wrapper
    exposes ``last_rewrite_count`` for tests/diagnostics.
    """
    names = list(PASSES) if passes is None else list(passes)
    static = set(static_argnums)
    cache = {}

    @functools.wraps(fn)
    def wrapped(*args):
        dyn = [a for i, a in enumerate(args) if i not in static]
        leaves, in_tree = jax.tree_util.tree_flatten(tuple(dyn))
        try:
            key = (in_tree,
                   tuple((jnp.shape(x), jnp.result_type(x))
                         for x in leaves),
                   tuple(args[i] for i in sorted(static)))
        except TypeError:
            key = None
        entry = cache.get(key) if key is not None else None
        if entry is None:
            closed, out_shape = jax.make_jaxpr(
                fn, static_argnums=tuple(static_argnums),
                return_shape=True)(*args)
            out_tree = jax.tree_util.tree_structure(out_shape)
            rewrites = []
            taken = set()
            for n in names:
                for rw in PASSES[n](closed.jaxpr, tuple(closed.consts)):
                    if not (rw.eqn_indices & taken):
                        rewrites.append(rw)
                        taken |= rw.eqn_indices
            entry = (closed, rewrites, out_tree)
            if key is not None:
                cache[key] = entry
        closed, rewrites, out_tree = entry
        wrapped.last_rewrite_count = len(rewrites)
        if not rewrites:
            return fn(*args)
        # bind only the DYNAMIC leaves — static args never became invars
        outs = _replay(closed, rewrites, dyn)
        return jax.tree_util.tree_unflatten(out_tree, outs)

    wrapped.last_rewrite_count = 0
    return wrapped
