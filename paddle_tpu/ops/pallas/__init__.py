"""Pallas TPU kernels for the hot ops.

Analog of the reference's hand-fused CUDA kernels
(paddle/phi/kernels/fusion/, flash_attn at
paddle/phi/kernels/gpu/flash_attn_kernel.cu).  Every kernel has an XLA
composition of the same function that works everywhere; which of the two
serves a call ONE rule decides from what the call shows (the backend, its
shapes, the mesh): :func:`_refusal`, :func:`_dispatch`.  No flag and no
environment variable enters it.

On a TPU the XLA composition is never taken silently in place of a
kernel: a refusal calls :func:`warn_fallback`, which warns once per
distinct (kernel, shape, reason) under :class:`KernelFallbackWarning`, so a
run can turn it into an error (``chip_smoke.py`` does).

Imports point downward only: kernel files take ``common`` and
``registry``, this file takes the kernel files.
"""

import importlib
import math
import warnings

import jax
import jax.numpy as jnp

from . import common
from .common import pick_block, record_flash_layout  # noqa: F401  (public)


class KernelFallbackWarning(RuntimeWarning):
    """On a TPU, an XLA composition ran where a Pallas kernel exists."""


def _use_pallas():
    """Whether the kernels are the main path here: the platform says."""
    return jax.default_backend() == "tpu"


GSPMD_REASON = ("GSPMD cannot partition a Mosaic kernel; it needs a "
                "shard_map over every mesh axis")


def _partitioned_by_gspmd():
    """True while tracing a computation that GSPMD will partition over
    several devices.  JAX refuses a Mosaic kernel there ("Mosaic kernels
    cannot be automatically partitioned"): one lowers only for a single
    device, or inside a ``shard_map`` that makes EVERY mesh axis manual
    (the serving engine's tensor-parallel step).  What tracing can see:
    the shard_map's abstract mesh, else the mesh the SPMD entry points
    enter (``fleet.spmd.use_mesh``)."""
    am = jax.sharding.get_abstract_mesh()
    if not am.empty:
        return set(am.manual_axes) != set(am.axis_names)
    from ...distributed.fleet.spmd import current_mesh
    mesh = current_mesh()
    return mesh is not None and mesh.size > 1


def warn_fallback(kernel, shape, reason):
    """Record that ``kernel`` gave way to its XLA composition.  Silent
    off the TPU, where the composition is the normal path."""
    if jax.default_backend() == "tpu":
        warnings.warn(
            f"{kernel}: XLA composition taken on TPU for {shape}: {reason}",
            KernelFallbackWarning, stacklevel=3)


NO_TPU = "no TPU backend"
# Below this sequence length the fused XLA attention is taken on TPU; flash
# pays off once the [T, S] score matrix dominates HBM.  The crossover was
# profiled on the v5e when the kernel ran 128 x 128 blocks; since PR 25 it
# runs blocks up to 512 x 512 (2.3x faster at seq 1024, 3.2x at 2048), so
# the crossover may lie lower now: not measured (PERF.md section 7).  That
# is a choice, not a fallback: short sequences take XLA without a word.
FLASH_MIN_SEQ = 1024
BY_CHOICE = "shorter than FLASH_MIN_SEQ: XLA's fused attention, by choice"


def _kernels(module):
    """A kernel file of this package, imported when a call first needs it
    (``import paddle_tpu`` does not pay for Pallas)."""
    return module and importlib.import_module(f"{__name__}.{module}")


def _refusal(module, unfit=None, fits=None, sharded_refusal=None):
    """THE rule that places a call: why it takes its XLA composition, or
    None where it takes the kernels of ``module``.  Off the TPU: ``NO_TPU``.
    On it: the caller's own ``unfit`` (arguments, dtypes), then
    ``module.supports(*fits)``, then the one GSPMD rule: where GSPMD
    partitions the computation a Mosaic kernel needs a sharded launch,
    which only flash has (``sharded_refusal()``: why that cannot serve)."""
    if not _use_pallas():
        return NO_TPU
    if unfit:
        return unfit
    if fits is not None and not _kernels(module).supports(*fits):
        return f"{module}.supports() refuses the shape"
    if _partitioned_by_gspmd():
        return sharded_refusal() if sharded_refusal else \
            GSPMD_REASON + "; these kernels have no sharded launch"
    return None


def _dispatch(kernel, module, shapes, launch, compose, counter=None,
              more=(), **rule):
    """One call placed by :func:`_refusal` (``rule``), recorded and run:
    ``launch(module)``, else ``compose()``, ALOUD (:func:`warn_fallback`)
    unless the reason is no fallback (off the TPU the composition is the
    normal path; ``BY_CHOICE`` is a choice).  The record joins the one log
    with ``more``; ``counter`` names the sums' count of these calls (and
    ``<counter>_composed``)."""
    reason = _refusal(module, **rule)
    common.traced_calls.append({
        "kernel": kernel, "shapes": shapes, "reason": reason,
        "path": "kernel" if reason is None else "composition", **dict(more)})
    if counter:
        common.traced_sums[counter] += 1
        common.traced_sums[counter + "_composed"] += reason is not None
    if reason is None:
        return launch(_kernels(module))
    if reason not in (NO_TPU, BY_CHOICE):
        warn_fallback(kernel, shapes, reason)
    return compose()


def _view(keys, kernel=None):
    """The log's records of ``kernel`` (else: the flash launches', which
    alone say what went in place), oldest first, as ``keys``."""
    return [{k: r[k] for k in keys} for r in common.traced_calls
            if (r["kernel"] == kernel if kernel else "in_place" in r)]


def flash_layout_log():
    """The records of the newest flash calls that reached the kernels,
    oldest first: ``kernel``, ``shapes``, ``in_place``, ``copied``."""
    return _view(("kernel", "shapes", "in_place", "copied"))


def flash_layout_sums():
    """``flash_calls``, ``flash_operands_in_place`` and
    ``flash_operands_copied`` over every flash call traced in this
    process."""
    return {k: n for k, n in common.traced_sums.items()
            if k.startswith("flash_")}


def ssd_scan_log():
    """The records of the newest traced :func:`ssd_scan` calls, oldest
    first: ``shapes`` (x, B), ``chunk``, ``path`` (``kernel`` or
    ``composition``) and ``reason`` (why the composition; None for the
    kernels)."""
    return _view(("shapes", "chunk", "path", "reason"), "ssd_scan")


def mla_expand_log():
    """The same of :func:`mla_expand_qkv`: ``shapes`` (q, kv_b), ``path``
    and ``reason``."""
    return _view(("shapes", "path", "reason"), "mla_expand")


def moe_run_sum_log():
    """The same of :func:`moe_run_sum`: ``shapes`` (the bucket's rows),
    ``max_run``, ``weighted``, ``path`` and ``reason``."""
    return _view(("shapes", "max_run", "weighted", "path", "reason"),
                 "moe_run_sum")


def causal_conv_log():
    """The same of :func:`causal_conv1d`: ``shapes`` (x, weight), ``start``,
    ``path`` and ``reason``."""
    return _view(("shapes", "start", "path", "reason"), "causal_conv")


def gated_norm_log():
    """The same of :func:`gated_rms_norm`: ``shapes`` (y, z), ``groups``,
    ``start``, ``path`` and ``reason``."""
    return _view(("shapes", "groups", "start", "path", "reason"),
                 "gated_norm")


def cca_mix_log():
    """The same of :func:`cca_mix`: ``shapes`` (q, k), ``path`` and
    ``reason``."""
    return _view(("shapes", "path", "reason"), "cca_mix")


def traced_call_sums():
    """What a compiled step's account takes its own share of
    (``profiler.StepTrace.dispatch``): :func:`flash_layout_sums`;
    ``ssd_calls`` / ``ssd_calls_composed``, the :func:`ssd_scan` calls
    traced in this process and those of them the composition served;
    ``mla_expand_calls`` / ``mla_expand_calls_composed``, the same of
    :func:`mla_expand_qkv`; ``moe_run_sum_calls`` /
    ``moe_run_sum_calls_composed``, the same of :func:`moe_run_sum` (an
    expert layer's routed block traces one a branch of each of its two
    switches: combine, and the dispatch's transpose);
    ``causal_conv_calls`` / ``causal_conv_calls_composed``, the same of
    :func:`causal_conv1d` (three a Mamba mixer: x, B and C);
    ``gated_norm_calls`` / ``gated_norm_calls_composed``, the same of
    :func:`gated_rms_norm` (one a Mamba mixer); and ``cca_mix_calls`` /
    ``cca_mix_calls_composed``, the same of :func:`cca_mix` (one a compressed
    convolutional attention)."""
    return dict(common.traced_sums)


def block_diffusion_mask(seq, block):
    """bool ``[seq, seq]``: whether query ``i`` sees key ``j`` of a row
    ``[noised ; clean]`` of two copies of ``L = seq / 2`` positions, with
    ``blk(i) = (i mod L) // block``: noised sees noised of its OWN block,
    noised sees clean of EARLIER blocks, clean never sees noised, clean sees
    clean of its own block and earlier ones (the vectorised training of
    block diffusion, arXiv:2503.09573 section 3)."""
    half = seq // 2
    pos = jnp.arange(seq)
    noised, blk = pos < half, (pos % half) // block
    qn, kn, qb, kb = noised[:, None], noised[None, :], blk[:, None], \
        blk[None, :]
    return jnp.where(qn, jnp.where(kn, qb == kb, kb < qb), ~kn & (kb <= qb))


def _xla_attention(q, k, v, attn_mask=None, is_causal=False, dropout_p=0.0,
                   dropout_key=None, scale=None, window=None,
                   block_diffusion=None):
    """Reference XLA attention on [B, T, N, H] (paddle flash-attn layout).

    Matmuls stay in the input dtype (bf16 on the MXU) with f32 accumulation
    via ``preferred_element_type``; only the softmax runs in f32.  Upcasting
    the operands themselves would push the score/context matmuls onto the
    4x-slower f32 MXU path — measured as the dominant per-step cost on v5e.

    The same function as the flash kernels compute: k and v may have
    fewer heads than q (q head ``n`` reads kv head ``n // group``), and
    ``window`` (causal only) keeps the keys ``0 <= t - j < window``;
    ``block_diffusion`` (not causal) keeps :func:`block_diffusion_mask`'s,
    materialised ``[seq, seq]``.
    """
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], dtype=jnp.float32))
    grouped = q.shape[2] != k.shape[2]
    if grouped:
        b, t, n, h = q.shape
        nkv = k.shape[2]
        logits = jnp.einsum(
            "btkgh,bskh->bkgts", q.reshape(b, t, nkv, n // nkv, h), k,
            preferred_element_type=jnp.float32).reshape(b, n, t, -1) * scale
    else:
        logits = jnp.einsum("btnh,bsnh->bnts", q, k,
                            preferred_element_type=jnp.float32) * scale
    if is_causal:
        t, s = logits.shape[-2], logits.shape[-1]
        causal = jnp.tril(jnp.ones((t, s), dtype=bool), k=s - t)
        if window is not None:
            causal &= ~jnp.tril(jnp.ones((t, s), dtype=bool),
                                k=s - t - window)
        logits = jnp.where(causal, logits, jnp.finfo(jnp.float32).min)
    elif window is not None:
        raise ValueError("a window needs is_causal")
    if block_diffusion is not None:
        if is_causal or q.shape[1] != k.shape[1] or q.shape[1] % 2:
            raise ValueError("a block-diffusion mask is its own mask, over "
                             "a self-attention row of two halves")
        logits = jnp.where(block_diffusion_mask(q.shape[1], block_diffusion),
                           logits, jnp.finfo(jnp.float32).min)
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            logits = jnp.where(attn_mask, logits, jnp.finfo(jnp.float32).min)
        else:
            logits = logits + attn_mask.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    if dropout_p > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    if grouped:
        out = jnp.einsum(
            "bkgts,bskh->btkgh",
            probs.astype(q.dtype).reshape(b, nkv, n // nkv, t, -1), v,
            preferred_element_type=jnp.float32).reshape(b, t, n, -1)
    else:
        out = jnp.einsum("bnts,bsnh->btnh", probs.astype(q.dtype), v,
                         preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _mesh_split(mesh):
    """How attention divides over ``mesh``, by the repo's own convention:
    the batch over the axes ``SpmdTrainStep`` shards its batch by, the
    heads over ``mp`` (the column-parallel q|k|v projection leaves them
    there).  ``(batch_axes, head_axis)``, of the axes larger than 1."""
    from ...distributed.fleet.spmd import data_axes

    head_axis = "mp" if mesh.shape.get("mp", 1) > 1 else None
    return data_axes(mesh), head_axis


def _sharded_refusal(q, mesh, kv_heads=None):
    """Why :func:`flash_attention_sharded` cannot serve ``q [batch, seq,
    heads, head_dim]`` (over ``kv_heads`` heads of k and v, q's own where
    not given) where GSPMD partitions the computation, or None."""
    am = jax.sharding.get_abstract_mesh()
    if not am.empty:
        # spmd_pipeline at pp > 1: a second shard_map over the axes left
        # to GSPMD would have to be nested; no cell runs it
        return (f"{GSPMD_REASON}; already inside a shard_map that is "
                f"manual over {tuple(am.manual_axes)} only")
    if mesh.shape.get("sep", 1) > 1:
        return (f"the mesh has a sep axis of {mesh.shape['sep']}: context "
                f"parallelism has its own path")
    batch_axes, head_axis = _mesh_split(mesh)
    ways = math.prod(mesh.shape[a] for a in batch_axes)
    if q.shape[0] % ways:
        return (f"batch {q.shape[0]} does not divide over mesh axes "
                f"{batch_axes} of {ways}")
    for heads in {q.shape[2], kv_heads or q.shape[2]}:
        if head_axis and heads % mesh.shape[head_axis]:
            return (f"{heads} heads do not divide over {head_axis} of "
                    f"{mesh.shape[head_axis]}")
    return None


def flash_attention_sharded(q, k, v, is_causal, mesh, interpret=False,
                            window=None, block_diffusion=None):
    """The flash kernels where GSPMD partitions the step: a ``shard_map``
    that makes EVERY axis of ``mesh`` manual (what a Mosaic kernel needs),
    each shard running the kernels on its rows and heads.  Attention mixes
    neither batch rows nor heads, so no collective is inside (grouped KV
    heads: a shard holds whole groups, q's heads and kv's being split the
    same number of ways); the backward is the kernels' ``custom_vjp``,
    transposed per shard.  The caller has asked :func:`_sharded_refusal`."""
    from jax.sharding import PartitionSpec as P

    from .attention_kernel import flash_attention_pallas

    batch_axes, head_axis = _mesh_split(mesh)
    spec = P(batch_axes or None, None, head_axis, None)
    return jax.shard_map(
        lambda q, k, v: flash_attention_pallas(
            q, k, v, is_causal, interpret=interpret, window=window,
            block_diffusion=block_diffusion),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)(q, k, v)


def _flash_unfit(seq_q, seq_k, is_causal, masked_or_scaled=False):
    """Why a flash call takes the composition before its shapes are asked
    (:func:`_refusal`'s ``unfit``), or None."""
    if seq_q < FLASH_MIN_SEQ:
        return BY_CHOICE
    if masked_or_scaled:
        return ("the kernel takes no attn_mask, dropout or scale "
                "(a causal sliding window it takes as window=, a "
                "block-diffusion mask as block_diffusion=)")
    if is_causal and seq_q != seq_k:
        # causal masking in the kernel is top-left aligned; for seq_q !=
        # seq_k the paddle/XLA semantics are bottom-right aligned, so only
        # self-attention-shaped causal inputs take the kernel path
        return "causal with seq_q != seq_k"
    return None


def flash_attention(q, k, v, attn_mask=None, is_causal=False, dropout_p=0.0,
                    dropout_key=None, scale=None, window=None,
                    block_diffusion=None):
    """Flash attention on [batch, seq, num_heads, head_dim].

    k and v may have fewer heads than q, a whole divisor (grouped KV
    heads: q head ``n`` reads kv head ``n // group``; nobody expands K and
    V).  ``window`` (with ``is_causal``): a query sees itself and the
    ``window - 1`` keys before it.  Kernel and XLA composition compute the
    same function of both.

    When ``dropout_p > 0`` and no explicit key is given, a key is drawn from
    the global RNG (paddle.seed-controlled) — attention dropout must not be
    silently dropped.  Attention dropout forces the XLA path (the Pallas
    kernel is dropout-free, like most production flash kernels at
    inference/bf16 pretrain settings).

    Under a mesh that GSPMD partitions (``SpmdTrainStep``, ``use_mesh``)
    the kernels run inside :func:`flash_attention_sharded`.

    The masks the kernels take: none, causal (top-left aligned, so
    self-attention-shaped inputs only), causal under a SLIDING window, and
    ``block_diffusion=B`` (not causal: the row is ``[noised ; clean]``,
    :func:`block_diffusion_mask`; no ``[seq, seq]`` array crosses HBM);
    an ``attn_mask``, dropout or a ``scale`` takes the XLA composition,
    aloud.  Segment ids (packed rows) are none of them.  A window that is
    a BLOCK (query ``t`` sees the keys of ``t // W`` up to itself) with
    pooled chunk summaries of the earlier windows under the same softmax is
    not a mask of this function: it has two kinds of key, its own kernels
    and its own dispatcher, :func:`eva_attention`."""
    from ...distributed.fleet.spmd import current_mesh

    if dropout_p > 0.0 and dropout_key is None:
        from ...framework.random import get_rng_key
        dropout_key = get_rng_key()
    unfit = _flash_unfit(
        q.shape[1], k.shape[1], is_causal,
        attn_mask is not None or dropout_p > 0.0 or scale is not None)

    def launch(kernels):
        # a call without a window or a block-diffusion mask is the call it
        # was
        kw = {name: value for name, value in (
            ("window", window), ("block_diffusion", block_diffusion))
            if value is not None}
        if _partitioned_by_gspmd():
            return flash_attention_sharded(q, k, v, is_causal,
                                           current_mesh(), **kw)
        return kernels.flash_attention_pallas(q, k, v, is_causal, **kw)

    return _dispatch(
        "flash_attention", "attention_kernel",
        f"q{tuple(q.shape)} k{tuple(k.shape)}", launch,
        lambda: _xla_attention(q, k, v, attn_mask=attn_mask,
                               is_causal=is_causal, dropout_p=dropout_p,
                               dropout_key=dropout_key, scale=scale,
                               window=window,
                               block_diffusion=block_diffusion),
        unfit=unfit,
        # sequence and head widths are whole in every shard, so this
        # answers for the sharded launch too
        fits=(q.shape[1], k.shape[1], q.shape[3], v.shape[3], q.shape[2],
              k.shape[2], window, is_causal, block_diffusion),
        sharded_refusal=lambda: _sharded_refusal(q, current_mesh(),
                                                 k.shape[2]))


def blockdiff_pairs(seq, head_dim, q_heads, kv_heads, block):
    """``(scored, needed)``: the (query, key) pairs :func:`flash_attention`
    forms scores for over one ``[noised ; clean]`` row of ``seq`` positions
    and one head under ``block_diffusion=block``, by the path it takes
    where this is asked (the same trace, the same rule) -- the kernels'
    grids, or the composition's whole ``seq x seq`` matrix, so that a
    fallback shows in the count -- and the pairs the mask holds."""
    from . import attention_kernel as flash

    half = seq // 2
    on_kernels = _refusal(
        "attention_kernel", unfit=_flash_unfit(seq, seq, False),
        fits=(seq, seq, head_dim, head_dim, q_heads, kv_heads, None, False,
              block)) is None
    return (flash.blockdiff_pairs_scored(half, block) if on_kernels
            else seq * seq), flash.blockdiff_pairs_needed(half, block)


def _xla_eva_attention(q, k, v, kt, vt, window, chunk):
    """The XLA composition of ``eva_attention_kernel``: q, k, v ``[B, T, N,
    D]``, summaries kt, vt ``[B, T / chunk, N, D]``.  ONE softmax over the
    DENSE ``[T, T + T / chunk]`` scores under an explicit mask: the exact
    keys of a query's own block-aligned window up to itself, and the
    summaries of the chunks of every earlier window.  Operands in the
    dtype they come in, float32 accumulation and softmax, as
    :func:`_xla_attention`."""
    t = q.shape[1]
    scale = float(q.shape[-1]) ** -0.5
    exact = jnp.einsum("btnh,bsnh->bnts", q, k,
                       preferred_element_type=jnp.float32) * scale
    pooled = jnp.einsum("btnh,bcnh->bntc", q, kt,
                        preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(t)
    own = (pos[:, None] // window == pos[None, :] // window) \
        & (pos[None, :] <= pos[:, None])
    earlier = (jnp.arange(t // chunk)[None, :] * chunk) // window \
        < pos[:, None] // window
    low = jnp.finfo(jnp.float32).min
    probs = jax.nn.softmax(jnp.concatenate(
        [jnp.where(own, exact, low), jnp.where(earlier, pooled, low)],
        axis=-1), axis=-1).astype(q.dtype)
    out = jnp.einsum("bnts,bsnh->btnh", probs[..., :t], v,
                     preferred_element_type=jnp.float32) \
        + jnp.einsum("bntc,bcnh->btnh", probs[..., t:], vt,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def eva_attention(q, k, v, kt, vt, window, chunk):
    """Attention over the exact keys of a query's own block-aligned window
    (causally) and the chunk summaries kt, vt of every earlier window, under
    one softmax.  q, k, v ``[batch, T, heads, D]``, kt, vt ``[batch, T /
    chunk, heads, D]``; returns ``[batch, T, heads, D]``.  The
    ``eva_attention_*`` kernels (``eva_attention_kernel``), whose work
    follows the mask, or the dense XLA composition, as :func:`_dispatch`
    places it."""
    return _dispatch(
        "eva_attention", "eva_attention_kernel",
        f"q{tuple(q.shape)} window={window} chunk={chunk}",
        lambda eva: eva.eva_attention_pallas(q, k, v, kt, vt, window, chunk),
        lambda: _xla_eva_attention(q, k, v, kt, vt, window, chunk),
        fits=(q.shape[1], q.shape[3], window, chunk))


def eva_pairs_scored(seq, head_dim, window, chunk):
    """The (query, key-or-summary) pairs :func:`eva_attention` forms scores
    for over one row and head, by the path it takes where this is asked
    (the same trace, the same rule) and the block sizes that path runs: the
    kernels' grids, or the composition's whole ``T x (T + T / chunk)``
    matrix, so that a fallback shows in the count."""
    from . import eva_attention_kernel as eva

    if _refusal("eva_attention_kernel",
                fits=(seq, head_dim, window, chunk)) is None:
        return sum(eva.pairs_scored(seq, window, chunk))
    return eva.pairs_dense(seq, chunk)


def ssd_scan(x, dt, A, B, C, D, chunk):
    """``nn/functional.py ssd_scan`` on rows of whole chunks: ``x [batch,
    T, heads, P]``, ``dt [batch, T, heads]``, ``B``, ``C`` ``[batch, T,
    groups, N]``.  The ``ssd_scan_fwd`` / ``ssd_scan_bwd`` kernels
    (``ssd_scan_kernel``) or the XLA composition ``nn/functional.py
    _ssd_scan_rows``, as :func:`_dispatch` places it; every traced call is
    recorded (:func:`ssd_scan_log`)."""
    from ...nn.functional import _ssd_scan_rows

    return _dispatch(
        "ssd_scan", "ssd_scan_kernel", (tuple(x.shape), tuple(B.shape)),
        lambda ssd: ssd.ssd_scan_pallas(x, dt, A, B, C, D, chunk),
        lambda: _ssd_scan_rows(x, dt, A, B, C, D, chunk),
        counter="ssd_calls", more={"chunk": chunk},
        unfit=None if B.dtype == C.dtype == x.dtype else
        f"B {B.dtype}, C {C.dtype} beside x {x.dtype}",
        fits=(x.shape[1], x.shape[2], x.shape[3], B.shape[2], B.shape[3],
              chunk, x.dtype))


def causal_conv1d(x, weight, bias=None, start=0):
    """``nn/functional.py causal_conv1d(..., activation="silu")``: ``x
    [batch, T, W]``, ``weight [C, K]``, ``bias [C]`` or ``None`` ->
    ``silu(conv(x[..., start:start + C]) + bias)`` ``[batch, T, C]``, the
    taps' sum in float32.  The ``causal_conv_fwd`` / ``causal_conv_bwd``
    kernels (``causal_conv_kernel``), which read the channels where they lie
    in ``x``, or the XLA composition ``nn/functional.py
    _causal_conv1d_silu``, as :func:`_dispatch` places it; every traced call
    is recorded (:func:`causal_conv_log`)."""
    from ...nn.functional import _causal_conv1d_silu

    return _dispatch(
        "causal_conv", "causal_conv_kernel",
        (tuple(x.shape), tuple(weight.shape)),
        lambda conv: conv.causal_conv_pallas(x, weight, bias, start=start),
        lambda: _causal_conv1d_silu(x, weight, bias, start),
        counter="causal_conv_calls", more={"start": start},
        fits=(x.shape[1], weight.shape[0], weight.shape[1], x.dtype, start))


def gated_rms_norm(y, z, weight, groups, epsilon, start=0):
    """The Mamba mixer's gated group norm (``models/nemotron_h.py
    _gated_norm``): ``y [..., C]``, ``z [..., W]`` of the same rows,
    ``weight [C]`` -> ``rms_groups(y * silu(z[..., start:start + C])) *
    weight`` ``[..., C]``, the statistics over each of ``groups`` equal
    parts of ``C``, float32 inside.  The ``gated_norm_fwd`` /
    ``gated_norm_bwd`` kernels (``gated_norm_kernel``), which read the gate
    where it lies in ``z``, or the XLA composition ``models/nemotron_h.py
    _gated_norm_composed``, as :func:`_dispatch` places it; every traced
    call is recorded (:func:`gated_norm_log`)."""
    from ...models.nemotron_h import _gated_norm_composed

    return _dispatch(
        "gated_norm", "gated_norm_kernel", (tuple(y.shape), tuple(z.shape)),
        lambda norm: norm.gated_norm_pallas(
            y, z, weight, groups=groups, epsilon=epsilon, start=start),
        lambda: _gated_norm_composed(y, z, weight, groups, epsilon, start),
        counter="gated_norm_calls", more={"groups": groups, "start": start},
        unfit=None if z.dtype == y.dtype else
        f"z {z.dtype} beside y {y.dtype}",
        fits=(math.prod(y.shape[:-1]), y.shape[-1], groups, y.dtype, start))


def cca_mix(q, k, v, q_conv0, q_conv1, k_conv0, k_conv1, tau, cos, sin,
            epsilon):
    """Compressed convolutional attention between its projections and its
    flash call (``models/zaya.py CompressedConvAttention.mix``): ``q [B, T,
    n, D]``, ``k``, ``v`` ``[B, T, kv, D]``, the depthwise taps ``[heads D,
    K0]`` and the per-head taps ``[heads, K1, D, D]`` of q and of k, ``tau
    [kv]``, ``cos`` / ``sin`` ``[T, rot / 2]`` -> q^, k^, v' of the
    operands' shapes.  The ``cca_mix_fwd`` / ``cca_mix_bwd`` kernels
    (``cca_mix_kernel``), which read and write ``[B, T, heads D]`` as the
    projections leave it and the flash kernels take it, or the XLA
    composition ``models/zaya.py _mix_composed``, as :func:`_dispatch`
    places it; every traced call is recorded (:func:`cca_mix_log`)."""
    from ...models.zaya import _mix_composed

    operands = (q, k, v, q_conv0, q_conv1, k_conv0, k_conv1, tau, cos, sin)
    others = {str(x.dtype) for x in (k, v, q_conv1, k_conv1)} \
        - {str(q.dtype)}
    return _dispatch(
        "cca_mix", "cca_mix_kernel", (tuple(q.shape), tuple(k.shape)),
        lambda mix: mix.cca_mix_pallas(*operands, epsilon=epsilon),
        lambda: _mix_composed(*operands, epsilon),
        counter="cca_mix_calls",
        unfit=f"{', '.join(sorted(others))} beside q {q.dtype}"
        if others else None,
        fits=(q.shape[1], q.shape[2], k.shape[2], q.shape[3],
              (q_conv0.shape[1], q_conv1.shape[1]), 2 * cos.shape[1],
              q.dtype))


def mla_rope(x, cos, sin, interleave):
    """``x [..., T, N, D]`` rotated by ``cos``/``sin [T, D/2]``.
    ``interleave``: the published layout keeps a pair in neighbouring
    lanes; it is de-interleaved into halves first, and the result stays in
    halves (q and k are permuted alike, so the scores are those of the
    pairwise rotation)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    if interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(dt)


def _xla_mla_expand_qkv(q, kv_b, k_rope, cos, sin, *, nope, interleave):
    """The XLA composition of ``mla_expand_kernel``: ``q [B, T, N, nope +
    rope]``, ``kv_b [B, T, N, nope + v]``, ``k_rope [B, T, rope]`` -> q, k
    ``[B, T, N, nope + rope]`` and v ``[B, T, N, v]`` with the rotary parts
    rotated (:func:`mla_rope`) and ``k_rope`` given to every head."""
    q_rot = mla_rope(q[..., nope:], cos, sin, interleave)
    k_rot = mla_rope(k_rope[:, :, None, :], cos, sin, interleave)
    q = jnp.concatenate([q[..., :nope], q_rot], axis=-1)
    k = jnp.concatenate(
        [kv_b[..., :nope],
         jnp.broadcast_to(k_rot, q.shape[:3] + k_rot.shape[3:])], axis=-1)
    return q, k, kv_b[..., nope:]


def mla_expand_qkv(q, kv_b, k_rope, cos, sin, *, nope, interleave):
    """Latent attention's q, k, v for the flash call (``models/mla_moe.py``):
    ``q [B, T, N, nope + rope]``, ``kv_b [B, T, N, nope + v]``, ``k_rope
    [B, T, rope]``, ``cos`` / ``sin`` ``[T, rope / 2]``.  The
    ``mla_expand_fwd`` / ``mla_expand_bwd`` kernels (``mla_expand_kernel``),
    whose results are the flash kernels' layout already, or the XLA
    composition :func:`_xla_mla_expand_qkv`, as :func:`_dispatch` places it;
    every traced call is recorded (:func:`mla_expand_log`)."""
    return _dispatch(
        "mla_expand", "mla_expand_kernel",
        (tuple(q.shape), tuple(kv_b.shape)),
        lambda mla: mla.mla_expand_pallas(q, kv_b, k_rope, cos, sin,
                                          nope=nope, interleave=interleave),
        lambda: _xla_mla_expand_qkv(q, kv_b, k_rope, cos, sin, nope=nope,
                                    interleave=interleave),
        counter="mla_expand_calls",
        unfit=None if kv_b.dtype == k_rope.dtype == q.dtype else
        f"kv_b {kv_b.dtype}, k_rope {k_rope.dtype} beside q {q.dtype}",
        fits=(q.shape[1], q.shape[2], nope, q.shape[3] - nope,
              kv_b.shape[3] - nope, q.dtype))


def moe_run_sum(rows, rem, weights=None, *, max_run):
    """The pass that adds up each token's rows on the expert layer's way
    back to its tokens (``incubate/distributed/models/moe/dropless.py
    _sum_by_runs``): ``rows [R, H]`` in token order, ``rem [R]`` int32 (the
    rows of its own run behind each row, a run at most ``max_run`` long),
    ``weights [R]`` float32 or ``None`` -> ``[R, H]``, at the first row of
    every run its rows' float32 sum, weighted, rounded once.  The
    ``moe_run_sum`` kernel (``moe_run_sum_kernel``) or the XLA composition
    ``dropless._run_sums``, as :func:`_dispatch` places it; every traced
    call is recorded (:func:`moe_run_sum_log`)."""
    from ...incubate.distributed.models.moe.dropless import _run_sums

    return _dispatch(
        "moe_run_sum", "moe_run_sum_kernel", tuple(rows.shape),
        lambda runs: runs.moe_run_sum_pallas(rows, rem, weights,
                                             max_run=max_run),
        lambda: _run_sums(rows, rem, weights, max_run=max_run),
        counter="moe_run_sum_calls",
        more={"max_run": max_run, "weighted": weights is not None},
        fits=(rows.shape[0], rows.shape[1], max_run, rows.dtype))


def _grouped_row_tile(shape):
    """The row tile of JAX's grouped-matmul Pallas kernels for ``shape
    [M, K]`` rows where :func:`_dispatch` places the call on them, or
    ``None`` where the XLA forms serve."""
    tm = next((t for t in (512, 256, 128) if shape[0] % t == 0), None)
    return _dispatch(
        "grouped_matmul", None, tuple(shape), lambda _: tm, lambda: None,
        unfit=None if tm else "rows not a multiple of 128")


def grouped_matmul(xs, w, group_sizes, transpose_w=False):
    """``xs [M, K]`` @ ``w [G, K, N]`` by consecutive groups of rows:
    rows ``sum(group_sizes[:g])`` to ``sum(group_sizes[:g + 1])`` meet
    ``w[g]`` (``w [G, N, K]`` with ``transpose_w``: the product's gradient
    for its rows); rows behind the last group meet nothing and are not
    read back by any caller (``incubate/distributed/models/moe/dropless.py``
    masks them).  The work follows the rows that are there, not ``M``.

    On the TPU: JAX's own grouped-matmul Pallas kernel (``megablox``
    ``gmm``, with ``tgmm`` for the weights' gradient), whose tiles follow
    ``group_sizes``.  ``jax.lax.ragged_dot`` runs as fast there (the
    experts' two matmuls over 12,288 real rows of a 98,304-row buffer,
    forward and backward: 9.18 ms against 9.45, PR 26) but the compiler
    names its kernels ``ragged-dot-none`` and drops the ``jax.named_scope``
    path, so a trace could not say whose time they are; the Pallas calls
    keep both (``gmm``, ``tgmm`` under ``.../experts/...``).  Elsewhere,
    and under GSPMD: ``jax.lax.ragged_dot``."""
    tm = _grouped_row_tile(xs.shape)
    if tm is not None:
        from jax.experimental.pallas.ops.tpu.megablox import ops
        return ops.gmm(xs, w, group_sizes.astype(jnp.int32),
                       preferred_element_type=xs.dtype,
                       tiling=(tm, 512, 512), transpose_rhs=transpose_w)
    return jax.lax.ragged_dot(xs, w.swapaxes(1, 2) if transpose_w else w,
                              group_sizes, preferred_element_type=xs.dtype)


def grouped_matmul_dw(xs, g, group_sizes):
    """:func:`grouped_matmul`'s gradient for its matrices: ``xs [M, K]``
    and ``g [M, N]`` -> ``[G, K, N]``, group by group ``xs_g^T @ g_g``
    (zero for an empty group; rows behind the last group are not read).
    The call ``megablox``'s own backward rule makes (``tgmm``), for a
    caller that writes its backward by hand: differentiated inside another
    rule the kernels lose their names (``jvp_jit_gmm__`` in a trace), and
    a reader that counts ``gmm`` / ``tgmm`` calls loses them."""
    tm = _grouped_row_tile(xs.shape)
    if tm is not None:
        from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm
        return tgmm(xs.swapaxes(0, 1), g, group_sizes.astype(jnp.int32),
                    xs.dtype, (tm, 512, 512), None, group_sizes.shape[0])
    return jax.lax.ragged_dot_general(
        xs, g, group_sizes, jax.lax.RaggedDotDimensionNumbers(
            dot_dimension_numbers=(([0], [0]), ([], [])),
            lhs_ragged_dimensions=[0], rhs_group_dimensions=[]),
        preferred_element_type=xs.dtype)
