"""The paged KV cache as ONE pytree — allocation, specs, writes, copies.

A cache is a dict of pools: ``{"k", "v"}``, each
``[L, num_blocks, Nkv, block_size, D]`` (head-major, the layout the
ragged kernel's page block needs), plus — under int8 KV —
``{"k_scale", "v_scale"}``, each ``[L, num_blocks, Nkv, block_size]``
float32: one dequant scale per (layer, page, head, slot).  Which leaves
a cache HAS is the only place "is the pool quantized" lives: a token
write quantizes when the layer view carries scale leaves, attention
(``paged_attention.paged_ragged_attention``) dequantizes when it does,
and every whole-cache operation (allocate, donate, copy-on-write, page
gather/scatter, ``shard_map`` specs) is a ``tree_map`` over whatever
leaves there are.  The engine's target pools and its draft model's
pools are two caches of one :class:`KVCacheSpec`.

Under tensor parallelism every pool shards its HEAD axis (axis 2) over
``'mp'``; block tables and slots ride replicated, so each shard writes
and copies the same pages of its own head slice.

The migration payload and the host tier's entry format name the same
leaves ``k_pages`` / ``v_pages`` / ``k_scales`` / ``v_scales``
(:data:`PAYLOAD_KEYS`): data formats that cross replicas, flattened to
and from the pytree at that edge (``engine.export_request`` /
``import_request``).
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .quant import quantize_kv_rows

# cache leaf -> its name in the migration / host-tier payload
PAYLOAD_KEYS = {"k": "k_pages", "v": "v_pages",
                "k_scale": "k_scales", "v_scale": "v_scales"}


class KVCacheSpec:
    """Static shape of one engine's paged cache: the per-page leaf
    shapes (everything but ``num_blocks``, which the engine derives
    from its memory budget AFTER pricing a page) and the ``'mp'``
    layout.  ``page_shapes[name]`` is ``(L, Nkv, block_size[, D])``."""

    def __init__(self, num_layers, num_heads, block_size, head_dim, dtype,
                 quantized, mesh=None):
        page = (num_layers, num_heads, block_size, head_dim)
        kv_dtype = jnp.dtype(jnp.int8 if quantized else dtype)
        self.page_shapes = {"k": page, "v": page}
        self.dtypes = {"k": kv_dtype, "v": kv_dtype}
        if quantized:
            f32 = jnp.dtype(jnp.float32)
            self.page_shapes.update(k_scale=page[:-1], v_scale=page[:-1])
            self.dtypes.update(k_scale=f32, v_scale=f32)
        # head axis over 'mp' once the page axis is back in at axis 1
        self.specs = {n: P(None, None, "mp", *(None,) * (len(s) - 2))
                      for n, s in self.page_shapes.items()}
        self.shardings = None if mesh is None else {
            n: NamedSharding(mesh, s) for n, s in self.specs.items()}

    def page_bytes(self, tp=1):
        """Per-chip bytes of one page across every leaf (K and V, all
        layers): an int8 slot costs head_dim bytes of values plus one
        f32 scale per (slot, head), full precision head_dim *
        itemsize — the migration cost model's bytes-moved unit is this
        times ``tp``."""
        return sum(int(np.prod(s)) * self.dtypes[n].itemsize
                   for n, s in self.page_shapes.items()) // tp

    def shape(self, name, num_blocks):
        s = self.page_shapes[name]
        return (s[0], num_blocks) + tuple(s[1:])

    def abstract(self, num_blocks):
        """``ShapeDtypeStruct`` stand-ins (lint traces these without
        touching, or donating, cache state)."""
        return {n: jax.ShapeDtypeStruct(self.shape(n, num_blocks),
                                        self.dtypes[n])
                for n in self.page_shapes}

    def zeros(self, num_blocks, xp=jnp):
        """Allocate one cache.  Under a mesh every pool is built
        SHARDED (never materialized on one device — the point of TP
        serving is a pool larger than one chip).  ``xp=np`` gives the
        discrete-event simulator's host pools: 100+ virtual replicas
        cost host RAM (lazily, pages untouched until written) and zero
        device memory."""
        def build():
            return {n: xp.zeros(self.shape(n, num_blocks), self.dtypes[n])
                    for n in self.page_shapes}

        if self.shardings is not None and xp is jnp:
            return jax.jit(build, out_shardings=self.shardings)()
        return build()


# ------------------------------------------------- inside the jitted step --
def token_slots(block_tables, positions, rows, num_blocks, block_size):
    """Absolute pool slot of each packed query token, and its visible
    context length: token at position ``p`` of block-table row ``r``
    writes slot ``table[r, p // bs] * bs + p % bs`` and attends over
    positions ``0..p``.  Padding tokens (position -1) get the
    out-of-range slot ``num_blocks * block_size`` — page ``num_blocks``,
    which every write drops — and context 0 (exact-zero output)."""
    p_safe = jnp.maximum(positions, 0)
    slot = (block_tables[rows, p_safe // block_size] * block_size
            + p_safe % block_size)
    slots = jnp.where(positions >= 0, slot, num_blocks * block_size)
    ctx = p_safe + jnp.where(positions >= 0, 1, 0)
    return slots, ctx


def write_tokens(cache_l, slots, k, v):
    """Write one layer's [N, Nkv, D] token rows at absolute token
    slots of the layer view ``cache_l`` (leaves ``[nb, Nkv, bs, ...]``,
    head-major: token slot s is row ``s % bs`` of every head of page
    ``s // bs``); padded rows carry the out-of-range slot and are
    dropped, not written.  Under TP the view is the LOCAL pool shard
    and ``k``/``v`` this shard's heads — slots are replicated, so
    every shard writes the same pages of its own head slice.

    A view with scale leaves quantizes at append: each written
    [Nkv, D] token row quantizes per head (absmax / 127) and lands as
    int8 values plus one f32 scale per (slot, head)."""
    bs = cache_l["k"].shape[2]
    page, off = slots // bs, slots % bs
    out = {}
    for n, rows in (("k", k), ("v", v)):
        if n + "_scale" in cache_l:
            q, s = quantize_kv_rows(rows)     # int8 [N,Nkv,D], [N,Nkv]
            out[n] = cache_l[n].at[page, :, off].set(q, mode="drop")
            out[n + "_scale"] = cache_l[n + "_scale"].at[
                page, :, off].set(s, mode="drop")
        else:
            out[n] = cache_l[n].at[page, :, off].set(
                rows.astype(cache_l[n].dtype), mode="drop")
    return out


def copy_pages(cache, src, dst):
    """Copy-on-write page payloads for fork siblings diverging off a
    shared partial tail: dst pages get src contents BEFORE this step's
    token writes land (int8 payload and scales move together).
    Padding entries carry dst == num_blocks (out of range) and drop.
    Under TP each shard copies its own head slice — indices ride
    replicated, pools are local.  Numpy pools (the simulator's) copy
    in place, live entries only."""
    if isinstance(cache["k"], np.ndarray):
        live = np.asarray(dst) < cache["k"].shape[1]  # noqa: H001 (the simulator's host pools)
        if live.any():
            s, d = np.asarray(src)[live], np.asarray(dst)[live]  # noqa: H001 (the simulator's host pools)
            for pool in cache.values():
                pool[:, d] = pool[:, s]
        return cache
    return {n: pool.at[:, dst].set(pool[:, src], mode="drop")
            for n, pool in cache.items()}


# ------------------------------------- host-staged page movement (eager) --
# Jitted page-row scatter/gather for the migration and KV-tier paths
# (cached per input shape — the page-bucket padding below bounds the
# shape count).  The scatter DONATES its pool argument, so XLA aliases
# the output buffer onto the input: an in-place row write instead of the
# eager functional whole-pool copy, and one dispatch instead of the
# eager op machinery that dominated tier traffic.  Callers immediately
# reassign the returned array over the donated one, so nothing observes
# the consumed buffer.
_scatter_jit = jax.jit(lambda pool, idx, vals: pool.at[:, idx].set(vals),
                       donate_argnums=(0,))
_gather_jit = jax.jit(lambda pool, idx: jnp.take(pool, idx, axis=1))


def _padded_index(block_ids):
    """The page-index batch padded to its power-of-two bucket by
    repeating the LAST page.  The gather/scatter above compile one
    executable per input SHAPE; the KV tier turns page movement into a
    hot path with a different chain length every call, so unpadded
    indices would recompile per length (a silent compile storm outside
    the watched ragged family).  Buckets bound that at log2(max_pages)
    executables per op.  Returns (padded index, real length)."""
    idx = np.asarray(block_ids, np.int64)  # noqa: H001 (host block-id list, not a tensor)
    n = len(idx)
    b = 1 << max(0, int(n - 1).bit_length())
    if b > n:
        idx = np.concatenate([idx, np.full(b - n, idx[-1], np.int64)])
    return idx, n


def gather_pages(cache, block_ids):
    """Host-staged page gather: select page rows ``[:, block_ids]`` of
    every pool as host numpy arrays ([L, n, Nkv, bs, ...] in
    ``block_ids`` order), slicing ON DEVICE first so the transfer
    carries only the selected pages — O(len(idx)) bytes, not the pool —
    and the GLOBAL view even when the pools are head-sharded (jax
    assembles addressable shards).  The gather compiles outside the
    ragged family (nothing for an armed CompileWatcher to see) and
    leaves the committed pool buffers untouched, so donation is
    unaffected; the padded tail is sliced back off before returning.
    Plain-numpy pools (the simulator's) skip the device round trip."""
    if isinstance(cache["k"], np.ndarray):
        idx = np.asarray(block_ids, np.int64)  # noqa: H001 (host block-id list, not a tensor)
        return {n: pool[:, idx] for n, pool in cache.items()}
    idx, n = _padded_index(block_ids)
    idx = np.asarray(idx, np.int32)  # noqa: H001 (host block-id list, not a tensor)
    return {name: np.asarray(jax.device_get(_gather_jit(pool, idx)))[:, :n]  # noqa: H001 (migration pulls only the selected pages by design)
            for name, pool in cache.items()}


def scatter_pages(cache, block_ids, pages, shardings=None):
    """Host-staged page scatter: upload ``pages`` (one
    [L, n, Nkv, bs, ...] array per cache leaf) and write them into
    their destination pool rows ON DEVICE, re-sharded under TP.
    Transfer cost is the migrated pages, not the pool.  The rebuilt
    arrays are ordinary committed buffers — the next step's jitted
    call donates them exactly like the ones they replace, so migration
    composes with donation and compiles nothing in the watched family.
    Indices and payload are padded to the power-of-two bucket by
    repeating the LAST page — duplicate indices carrying identical
    values make the extra writes idempotent.  Returns the new cache;
    numpy pools are written in place (and stay numpy)."""
    if isinstance(cache["k"], np.ndarray):
        idx = np.asarray(block_ids, np.int64)  # noqa: H001 (host block-id list, not a tensor)
        for name, pool in cache.items():
            pool[:, idx] = pages[name]
        return cache
    idx, n = _padded_index(block_ids)
    pad = len(idx) - n
    idx = np.asarray(idx, np.int32)  # noqa: H001 (host block-id list, not a tensor)
    out = {}
    for name, pool in cache.items():
        vals = np.asarray(pages[name], pool.dtype)  # noqa: H001 (host page payload upload by design)
        if pad:
            vals = np.concatenate(
                [vals, np.repeat(vals[:, -1:], pad, axis=1)], axis=1)
        new = _scatter_jit(pool, idx, vals)
        if shardings is not None:
            new = jax.device_put(new, shardings[name])
        out[name] = new
    return out
