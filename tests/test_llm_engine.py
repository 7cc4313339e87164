"""Continuous-batching LLM serving engine (inference/llm/).

The load-bearing claim: paged continuous-batching decode is TOKEN-EXACT
vs the naive dense-cache FusedMultiTransformer decode — mixed-length
traces, staggered arrivals, and preemption/recompute all reproduce the
reference token stream bit for bit, while the block manager never leaks
a page.  Plus: allocator/scheduler unit coverage, the paged Pallas
kernel vs its XLA gather fallback (interpret mode), and the engine-backed
PredictorServer socket path.
"""

import socket
import struct
import threading
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle


def _make_model(num_layers=2, seed=0):
    from paddle_tpu.models.gpt import gpt_tiny

    paddle.seed(seed)
    m = gpt_tiny(num_layers=num_layers)
    m.eval()
    return m


def _fmt_reference(model, prompts, max_new, max_length=64):
    """Naive dense-cache decode, one request at a time (batch 1)."""
    from paddle_tpu.incubate.nn import FusedMultiTransformer

    fmt = FusedMultiTransformer(model, max_length=max_length)
    return [fmt.generate(np.asarray(p, np.int32)[None],
                         max_new_tokens=max_new)[0] for p in prompts]


# ---------------------------------------------------------------------------
class TestBlockManager:
    def test_alloc_free_roundtrip(self):
        from paddle_tpu.inference.llm import BlockManager

        bm = BlockManager(num_blocks=8, block_size=4)
        t = bm.allocate("a", 10)            # ceil(10/4) = 3 pages
        assert len(t) == 3 and bm.num_free_blocks == 5
        assert bm.block_table("a") == t and bm.num_tokens("a") == 10
        bm.free("a")
        assert bm.num_free_blocks == 8 and not bm.has_seq("a")

    def test_append_slot_and_page_boundary(self):
        from paddle_tpu.inference.llm import BlockManager

        bm = BlockManager(num_blocks=4, block_size=4)
        bm.allocate("a", 3)
        slot, cow = bm.append_slot("a")     # fills the first page
        assert cow is None and slot == bm.block_table("a")[0] * 4 + 3
        slot, cow = bm.append_slot("a")     # crosses into a new page
        assert bm.num_free_blocks == 2
        assert slot == bm.block_table("a")[1] * 4

    def test_oom_raises_and_preserves_state(self):
        from paddle_tpu.inference.llm import BlockManager, NoFreeBlocksError

        bm = BlockManager(num_blocks=2, block_size=4)
        bm.allocate("a", 8)
        with pytest.raises(NoFreeBlocksError):
            bm.allocate("b", 1)
        with pytest.raises(NoFreeBlocksError):
            bm.append_slot("a")
        assert bm.num_tokens("a") == 8      # failed append did not count
        bm.free("a")
        assert bm.num_free_blocks == 2

    def test_append_slots_bulk_matches_repeated_append_slot(self):
        from paddle_tpu.inference.llm import BlockManager

        a = BlockManager(num_blocks=8, block_size=4)
        b = BlockManager(num_blocks=8, block_size=4)
        a.allocate("s", 6)
        b.allocate("s", 6)
        slots, cows = a.append_slots("s", 5)    # crosses two page edges
        ref = [b.append_slot("s")[0] for _ in range(5)]
        assert slots == ref and cows == []
        assert a.num_tokens("s") == 11
        assert a.block_table("s") == b.block_table("s")
        a.check_invariants()

    def test_append_slots_cow_then_rollback_restores_books(self):
        from paddle_tpu.inference.llm import BlockManager

        bm = BlockManager(num_blocks=8, block_size=4)
        bm.allocate("parent", 6)            # 2 pages, last half-full
        bm.fork("parent", "child")
        slots, cows = bm.append_slots("child", 3)   # COW + 1 new page
        assert len(cows) == 1 and len(slots) == 3
        src, dst = cows[0]
        assert dst == bm.block_table("child")[-2] and dst != src
        bm.check_invariants()
        # rollback returns the fresh page but NOT the COW copy — the
        # copied page now holds the child's (shorter) tail and stays
        bm.rollback_slots("child", 3)
        assert bm.num_tokens("child") == 6
        assert bm.block_table("child")[-1] == dst
        bm.check_invariants()
        bm.free("parent")
        bm.free("child")
        assert bm.num_free_blocks == 8

    def test_append_slots_oom_is_atomic(self):
        from paddle_tpu.inference.llm import BlockManager, NoFreeBlocksError

        bm = BlockManager(num_blocks=3, block_size=4)
        bm.allocate("s", 7)                 # 2 pages, 1 free
        table = list(bm.block_table("s"))
        with pytest.raises(NoFreeBlocksError):
            bm.append_slots("s", 6)         # needs 2 new pages, has 1
        # the failed bulk reservation must not have mutated ANYTHING
        assert bm.num_tokens("s") == 7
        assert bm.block_table("s") == table
        assert bm.num_free_blocks == 1
        bm.check_invariants()
        # degenerate and over-rollback arguments are rejected loudly
        with pytest.raises(ValueError):
            bm.append_slots("s", 0)
        with pytest.raises(ValueError):
            bm.rollback_slots("s", -1)
        with pytest.raises(ValueError):
            bm.rollback_slots("s", 8)

    def test_rollback_slots_frees_whole_pages(self):
        from paddle_tpu.inference.llm import BlockManager

        bm = BlockManager(num_blocks=8, block_size=4)
        bm.allocate("s", 3)
        slots, _ = bm.append_slots("s", 6)   # 3 -> 9 tokens, 3 pages
        assert bm.num_free_blocks == 5
        bm.rollback_slots("s", 6)
        assert bm.num_tokens("s") == 3 and bm.num_free_blocks == 7
        bm.rollback_slots("s", 0)            # no-op by contract
        assert bm.num_tokens("s") == 3
        bm.check_invariants()

    def test_fork_refcount_and_copy_on_write(self):
        from paddle_tpu.inference.llm import BlockManager

        bm = BlockManager(num_blocks=8, block_size=4)
        bm.allocate("parent", 6)            # 2 pages, last half-full
        bm.fork("parent", "child")
        assert bm.num_free_blocks == 6      # shared, nothing new
        assert bm.block_table("child") == bm.block_table("parent")
        # child's divergent append copies the shared tail page
        slot, cow = bm.append_slot("child")
        assert cow is not None
        src, dst = cow
        assert src == bm.block_table("parent")[-1]
        assert dst == bm.block_table("child")[-1] and dst != src
        assert slot == dst * 4 + 2
        # parent's next append is in-place (its page is sole-owned again)
        _, cow = bm.append_slot("parent")
        assert cow is None
        bm.free("parent")
        assert bm.num_free_blocks == 6      # child still holds 2 pages
        bm.free("child")
        assert bm.num_free_blocks == 8


# ---------------------------------------------------------------------------
class TestScheduler:
    def _mk(self, num_blocks=8, block_size=4, max_batch=2):
        from paddle_tpu.inference.llm import BlockManager, Scheduler

        bm = BlockManager(num_blocks, block_size)
        return Scheduler(bm, max_batch=max_batch), bm

    def _req(self, rid, n_prompt, max_new=8):
        from paddle_tpu.inference.llm import Request

        return Request(request_id=rid, prompt_ids=tuple(range(n_prompt)),
                       max_new_tokens=max_new)

    @staticmethod
    def _run_chunks(batch):
        """Do the engine's part: mark every scheduled chunk computed."""
        for c in batch.chunks:
            c.request.num_cached = c.start + c.length

    def test_admit_chunks_then_decode(self):
        sched, bm = self._mk()
        sched.add(self._req(0, 5))
        sched.add(self._req(1, 3))
        b = sched.schedule()                # both fit in one budget
        assert b.kind == "mixed" and not b.requests
        assert [(c.request.request_id, c.start, c.length)
                for c in b.chunks] == [(0, 0, 5), (1, 0, 3)]
        assert all(c.is_final for c in b.chunks)
        self._run_chunks(b)
        b = sched.schedule()                # batch full -> decode both
        assert b.kind == "decode" and len(b.requests) == 2
        assert bm.num_tokens(0) == 6 and bm.num_tokens(1) == 4

    def test_long_prompt_chunks_and_mixes_with_decodes(self):
        from paddle_tpu.inference.llm import BlockManager, Scheduler

        bm = BlockManager(16, 4)
        sched = Scheduler(bm, max_batch=2, token_budget=4)
        sched.add(self._req(0, 4))
        b = sched.schedule()
        assert b.kind == "mixed" and b.chunks[0].is_final
        self._run_chunks(b)
        sched.add(self._req(1, 10))
        # the 10-token prompt spreads over several steps, one decode for
        # request 0 riding along in each (no inter-token latency spike)
        expect = [(0, 3), (3, 3), (6, 3), (9, 1)]
        for i, (start, length) in enumerate(expect):
            b = sched.schedule()
            assert b.kind == "mixed"
            assert [r.request_id for r in b.requests] == [0]
            c = b.chunks[0]
            assert (c.start, c.length) == (start, length)
            assert c.is_final == (i == len(expect) - 1)
            self._run_chunks(b)
        b = sched.schedule()
        assert b.kind == "decode" and len(b.requests) == 2

    def test_admission_respects_pool_and_batch(self):
        sched, bm = self._mk(num_blocks=3, max_batch=4)
        sched.add(self._req(0, 8))          # 2 pages
        sched.add(self._req(1, 8))          # needs 2, only 1 free + margin
        b = sched.schedule()
        assert b.kind == "mixed" and len(b.chunks) == 1
        assert b.chunks[0].request.request_id == 0
        self._run_chunks(b)
        b = sched.schedule()                # cannot admit -> decode
        assert b.kind == "decode" and len(b.requests) == 1
        assert sched.waiting[0].request_id == 1

    def test_preempt_on_oom_recycles_and_requeues(self):
        sched, bm = self._mk(num_blocks=5, block_size=4, max_batch=2)
        sched.add(self._req(0, 8))          # 2 pages, page-aligned
        sched.add(self._req(1, 8))          # 2 pages, page-aligned
        b = sched.schedule()
        assert b.kind == "mixed" and len(b.chunks) == 2
        self._run_chunks(b)
        # both need a fresh page for token 9 but only one page is free:
        # the earlier arrival gets it, the later one is preempted
        b = sched.schedule()
        assert b.kind == "decode"
        assert [r.request_id for r in b.requests] == [0]
        assert sched.num_preemptions == 1
        victim = sched.waiting[0]
        assert victim.request_id == 1 and victim.num_cached == 0
        assert victim.num_preemptions == 1
        assert bm.num_free_blocks == 2      # 0 holds 3 of the 5 pages

    def test_bucket_size(self):
        from paddle_tpu.inference.llm.scheduler import bucket_size

        assert bucket_size(1, 8) == 1
        assert bucket_size(3, 8) == 4
        assert bucket_size(9, 8) == 8       # capped
        assert bucket_size(5, 64, floor=8) == 8
        # edges: n far past the cap, n exactly at the floor, and a floor
        # ABOVE the cap (cap must win — the executable grid never holds
        # a bucket larger than the configured maximum)
        assert bucket_size(1000, 8) == 8
        assert bucket_size(8, 64, floor=8) == 8
        assert bucket_size(2, 4, floor=8) == 4
        assert bucket_size(0, 8) == 1       # degenerate n still bucket 1


# ---------------------------------------------------------------------------
class TestPagedAttention:
    def _inputs(self, seed=0, b=3, nq=4, nkv=2, d=16, bs=8, pages=4):
        rng = np.random.RandomState(seed)
        nb = b * pages
        q = rng.randn(b, nq, d).astype(np.float32)
        # drawn token-major, stored head-major [NB, Nkv, bs, D]
        kp = rng.randn(nb, bs, nkv, d).astype(np.float32) \
            .transpose(0, 2, 1, 3)
        vp = rng.randn(nb, bs, nkv, d).astype(np.float32) \
            .transpose(0, 2, 1, 3)
        bt = rng.permutation(nb).reshape(b, pages).astype(np.int32)
        lens = np.array([5, 0, 30], np.int32)[:b]
        return q, kp, vp, bt, lens

    def test_xla_gather_matches_dense_ragged(self):
        import jax.numpy as jnp

        from paddle_tpu.ops.pallas.decode_attention_kernel import (
            decode_attention_xla,
        )
        from ragged_rows import rows_attention

        q, kp, vp, bt, lens = self._inputs()
        out = rows_attention(*map(jnp.asarray, (
            q[:, None], kp, vp, bt, lens[:, None])))[:, 0]
        b, pages = bt.shape
        nkv, bs, d = kp.shape[1:]
        k = kp[bt].transpose(0, 1, 3, 2, 4).reshape(b, pages * bs, nkv, d)
        v = vp[bt].transpose(0, 1, 3, 2, 4).reshape(b, pages * bs, nkv, d)
        ref = decode_attention_xla(jnp.asarray(q), jnp.asarray(k),
                                   jnp.asarray(v), jnp.asarray(lens))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_pallas_kernel_interpret_matches_xla(self):
        import jax.numpy as jnp

        from paddle_tpu.ops.pallas.ragged_attention_kernel import (
            paged_ragged_attention_pallas,
            supports,
        )
        from ragged_rows import rows_attention

        b, pages, bs, nq, nkv, d = 8, 4, 8, 4, 2, 16
        assert supports(bs, d, nq, nkv, b)
        rng = np.random.RandomState(7)
        nb = b * pages
        q = rng.randn(b, nq, d).astype(np.float32)
        kp = rng.randn(nb, bs, nkv, d).astype(np.float32) \
            .transpose(0, 2, 1, 3)
        vp = rng.randn(nb, bs, nkv, d).astype(np.float32) \
            .transpose(0, 2, 1, 3)
        bt = rng.permutation(nb).reshape(b, pages).astype(np.int32)
        lens = np.array([5, 0, 30, 1, 2, 8, 32, 17], np.int32)
        # decode rows as ragged descriptors: one query token per live
        # row, attending over its whole prefix
        out = paged_ragged_attention_pallas(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(bt),
            jnp.arange(b, dtype=jnp.int32),
            jnp.asarray((lens > 0).astype(np.int32)),
            jnp.asarray(np.maximum(lens - 1, 0)),
            interpret=True)
        ref = rows_attention(*map(jnp.asarray, (
            q[:, None], kp, vp, bt, lens[:, None])))[:, 0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_supports_gate(self):
        from paddle_tpu.ops.pallas.ragged_attention_kernel import supports

        assert not supports(8, 256, 4, 2, 8)   # head_dim too wide
        assert not supports(6, 16, 4, 2, 8)    # page not sublane-aligned
        assert not supports(8, 16, 3, 2, 8)    # ragged GQA group
        assert not supports(8, 16, 4, 2, 12)   # off-chunk token count


# ---------------------------------------------------------------------------
class TestEngineTokenExact:
    """LLMEngine.generate vs naive dense-cache FMT decode: bit-equal."""

    def test_mixed_length_trace(self):
        from paddle_tpu.inference.llm import LLMEngine

        m = _make_model()
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, 128, (n,)).astype(np.int32)
                   for n in (3, 7, 12)]
        refs = _fmt_reference(m, prompts, max_new=8)
        eng = LLMEngine(m, block_size=8, max_batch=4, max_model_len=64)
        outs = eng.generate(prompts, max_new_tokens=8)
        for out, ref in zip(outs, refs):
            np.testing.assert_array_equal(out, ref)
        assert eng.block_manager.num_free_blocks == eng.num_blocks
        # one mixed step admits all three prompts as three chunks
        assert eng.stats["chunk_launches"] == 3
        assert eng.stats["prefill_steps"] == 1

    def test_staggered_arrivals_trace(self):
        from paddle_tpu.inference.llm import LLMEngine

        m = _make_model()
        rng = np.random.RandomState(1)
        prompts = [rng.randint(0, 128, (n,)).astype(np.int32)
                   for n in (4, 9, 6)]
        refs = _fmt_reference(m, prompts, max_new=6)
        eng = LLMEngine(m, block_size=8, max_batch=4, max_model_len=64)
        outs = {}

        def drain(n_steps):
            for _ in range(n_steps):
                for fo in eng.step():
                    outs[fo.request_id] = fo.all_ids

        r0 = eng.add_request(prompts[0], max_new_tokens=6)
        drain(2)                            # r0 mid-decode when r1 lands
        r1 = eng.add_request(prompts[1], max_new_tokens=6)
        drain(3)
        r2 = eng.add_request(prompts[2], max_new_tokens=6)
        while eng.has_unfinished():
            drain(1)
        for rid, ref in zip((r0, r1, r2), refs):
            np.testing.assert_array_equal(outs[rid], ref)
        assert eng.block_manager.num_free_blocks == eng.num_blocks

    def test_preemption_trace(self):
        from paddle_tpu.inference.llm import LLMEngine

        m = _make_model()
        rng = np.random.RandomState(2)
        prompts = [rng.randint(0, 128, (4,)).astype(np.int32)
                   for _ in range(3)]
        refs = _fmt_reference(m, prompts, max_new=28)
        # 5 pages of 8 < 3 seqs x 4 pages demanded -> preempt + recompute
        eng = LLMEngine(m, block_size=8, num_blocks=5, max_batch=3,
                        max_model_len=40)
        outs = eng.generate(prompts, max_new_tokens=28)
        for out, ref in zip(outs, refs):
            np.testing.assert_array_equal(out, ref)
        assert eng.scheduler.num_preemptions > 0
        assert eng.block_manager.num_free_blocks == eng.num_blocks

    def test_eos_stops_early_and_frees(self):
        from paddle_tpu.inference.llm import LLMEngine

        m = _make_model()
        prompt = np.array([5, 6, 7], np.int32)
        eng = LLMEngine(m, block_size=8, max_batch=2, max_model_len=64)
        probe = eng.generate([prompt], max_new_tokens=4)[0]
        eos = int(probe[3])                 # first generated token
        eng2 = LLMEngine(m, block_size=8, max_batch=2, max_model_len=64)
        rid = eng2.add_request(prompt, max_new_tokens=8, eos_token_id=eos)
        fo = None
        while eng2.has_unfinished():
            for f in eng2.step():
                fo = f
        assert fo.request_id == rid and fo.finish_reason == "stop"
        assert fo.output_ids.tolist() == [eos]
        assert eng2.block_manager.num_free_blocks == eng2.num_blocks

    def test_warmup_is_a_noop_on_results(self):
        # warmup pre-compiles every bucket via dummy prefill/decode calls
        # whose page writes all land on the dropped OOB slot — generation
        # after warmup must be bit-identical to a cold engine's
        from paddle_tpu.inference.llm import LLMEngine

        m = _make_model()
        rng = np.random.RandomState(5)
        prompts = [rng.randint(0, 128, (n,)).astype(np.int32)
                   for n in (3, 11)]
        cold = LLMEngine(m, block_size=8, max_batch=4, max_model_len=64)
        refs = cold.generate(prompts, max_new_tokens=8)
        warm = LLMEngine(m, block_size=8, max_batch=4, max_model_len=64)
        warm.warmup()
        assert warm.block_manager.num_free_blocks == warm.num_blocks
        outs = warm.generate(prompts, max_new_tokens=8)
        for out, ref in zip(outs, refs):
            np.testing.assert_array_equal(out, ref)

    def test_request_validation(self):
        from paddle_tpu.inference.llm import LLMEngine

        m = _make_model()
        eng = LLMEngine(m, block_size=8, max_batch=2, max_model_len=32)
        with pytest.raises(ValueError, match="exceeds max_model_len"):
            eng.add_request(np.arange(30, dtype=np.int32),
                            max_new_tokens=8)
        with pytest.raises(ValueError, match="empty prompt"):
            eng.add_request([], max_new_tokens=4)
        with pytest.raises(ValueError, match="cannot hold"):
            LLMEngine(m, block_size=8, num_blocks=2, max_model_len=32)


# ---------------------------------------------------------------------------
class TestServingModelSeam:
    """The engine schedules, packs, owns the pools and launches; what
    it launches is the serving model's (inference/llm/gpt2_block.py)
    and what it launches ON is one cache pytree (kv_cache.py)."""

    def test_engine_serves_a_block_that_is_not_gpt2s(self, monkeypatch):
        """A test-local serving model with a PARALLEL-residual block
        (attention and MLP both read the block input through ln_1/ln_2
        and add to the same residual; GPT-2's MLP reads the
        post-attention stream) goes through the unchanged engine —
        chunked prefill, decode, the paged pool — and is token-exact
        against the dense recomputation below.  Fails wherever the
        engine computes the block itself."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.incubate.nn import _layernorm
        from paddle_tpu.inference.llm import LLMEngine
        from paddle_tpu.inference.llm import engine as engine_mod
        from paddle_tpu.inference.llm.gpt2_block import GPT2ServingModel
        from paddle_tpu.inference.llm.kv_cache import write_tokens
        from paddle_tpu.inference.llm.paged_attention import (
            paged_ragged_attention,
        )

        def mlp(p_l, x, eps):
            h = _layernorm(x, p_l["ln_2.weight"], p_l["ln_2.bias"], eps)
            ff = jax.nn.gelu(h @ p_l["mlp.fc_in.weight"]
                             + p_l["mlp.fc_in.bias"], approximate=True)
            return ff @ p_l["mlp.fc_out.weight"] + p_l["mlp.fc_out.bias"]

        class ParallelResidual(GPT2ServingModel):
            def block(self, p_l, x, cache_l, slots, paged, slots_t=None):
                q, k, v = self.attn_proj(p_l, x)
                cache_l = write_tokens(cache_l, slots, k[0], v[0])
                att = paged_ragged_attention(q[0], cache_l, *paged)
                att = att.astype(x.dtype).reshape(1, x.shape[1], -1)
                return (x + att @ p_l["attn.proj.weight"]
                        + p_l["attn.proj.bias"]
                        + mlp(p_l, x, self.eps)), cache_l

        def dense_next_token(params, cfg, ids):
            """Full recomputation over the whole sequence, no cache."""
            t, nh, hd = len(ids), cfg.num_attention_heads, cfg.head_dim
            eps = cfg.layer_norm_epsilon
            emb = params["embed"]
            x = (emb["word_embeddings.weight"][jnp.asarray(ids)]
                 + emb["position_embeddings.weight"][jnp.arange(t)])[None]
            mask = jnp.tril(jnp.ones((t, t), bool))
            blocks = params["blocks"]
            for li in range(blocks["ln_1.weight"].shape[0]):
                p_l = {k: w[li] for k, w in blocks.items()}
                h = _layernorm(x, p_l["ln_1.weight"], p_l["ln_1.bias"],
                               eps)
                qkv = (h @ p_l["attn.qkv.weight"]
                       + p_l["attn.qkv.bias"]).reshape(1, t, 3, nh, hd)
                q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
                sc = jnp.einsum("btnd,bsnd->bnts", q, k) / np.sqrt(hd)
                sc = jnp.where(mask[None, None], sc, -1e30)
                att = jnp.einsum("bnts,bsnd->btnd",
                                 jax.nn.softmax(sc, -1), v)
                x = (x + att.reshape(1, t, nh * hd)
                     @ p_l["attn.proj.weight"] + p_l["attn.proj.bias"]
                     + mlp(p_l, x, eps))
            x = _layernorm(x, params["head"]["weight"],
                           params["head"]["bias"], eps)
            logits = x[0, -1] @ emb["word_embeddings.weight"].T
            return int(jnp.argmax(logits))

        monkeypatch.setattr(engine_mod, "GPT2ServingModel",
                            ParallelResidual)
        m = _make_model()
        # at gpt_tiny's init scale the blocks barely move the residual
        # stream and any block yields the same argmax; make them count
        for name, w in m.named_parameters():
            if ".h." in name and w.ndim == 2:
                w.set_value(w.numpy() * 12.0)
        rng = np.random.RandomState(5)
        prompts = [rng.randint(0, 128, (n,)).astype(np.int32)
                   for n in (3, 11, 20)]
        # token_budget 8 < the longest prompt: its prefill is chunked,
        # so later chunks and every decode read earlier K/V back
        # through the pool
        eng = LLMEngine(m, block_size=8, max_batch=4, max_model_len=64,
                        token_budget=8)
        outs = eng.generate(prompts, max_new_tokens=6)
        params = jax.device_get(eng.params)
        for p, out in zip(prompts, outs):
            ids = list(p)
            for _ in range(6):
                ids.append(dense_next_token(params, m.config, ids))
            np.testing.assert_array_equal(out, np.asarray(ids))
        # and the block really is not GPT-2's: the stock engine
        # generates something else from the same weights
        stock = LLMEngine.__new__(LLMEngine)
        monkeypatch.undo()
        stock.__init__(m, block_size=8, max_batch=4, max_model_len=64,
                       token_budget=8)
        assert any(not np.array_equal(a, b) for a, b in
                   zip(outs, stock.generate(prompts, max_new_tokens=6)))
        assert eng.block_manager.num_free_blocks == eng.num_blocks

    @pytest.mark.parametrize("kv", ["float", "int8"])
    def test_one_step_body_over_either_cache(self, kv):
        """Float and int8 KV go through the SAME step body: the cache
        is one pytree operand whose leaves decide whether a page write
        quantizes and attention dequantizes.  Same executable census,
        zero compiles after warmup, and greedy output equal to the
        dense teacher-forced argmax under the cache's own numerics
        (quality.engine_logits applies the int8 round trip)."""
        from paddle_tpu.inference.llm import LLMEngine
        from paddle_tpu.inference.llm.quality import engine_logits

        quantize = None if kv == "float" else {"weights": False,
                                               "kv_cache": True}
        leaves = {"k", "v"} if kv == "float" else \
            {"k", "v", "k_scale", "v_scale"}
        eng = LLMEngine(_make_model(), block_size=8, max_batch=4,
                        max_model_len=64, token_budget=16,
                        quantize=quantize)
        assert set(eng.kv_cache) == leaves
        for _kind, _tb, fn, args in eng.executable_grid():
            assert fn is eng._ragged and set(args[2]) == leaves
        watcher = eng.warmup()
        assert eng._ragged._cache_size() == 2       # buckets 8, 16
        rng = np.random.RandomState(6)
        prompts = [rng.randint(0, 128, (n,)).astype(np.int32)
                   for n in (4, 9, 21)]
        outs = eng.generate(prompts, max_new_tokens=6)
        watcher.assert_no_new_compiles()
        for p, out in zip(prompts, outs):
            dense = np.argmax(engine_logits(eng, out[:-1]), -1)
            np.testing.assert_array_equal(out[len(p):],
                                          dense[len(p) - 1:])
        assert eng.block_manager.num_free_blocks == eng.num_blocks


# ---------------------------------------------------------------------------
class TestPrefixCaching:
    """Automatic prefix caching + chunked prefill: shared prefixes are
    adopted (not recomputed) with bit-identical outputs, full cached
    pages survive fork/COW untouched, eviction reclaims LRU pages under
    pressure, and chunked traces leak nothing."""

    def test_shared_prefix_token_exact_with_cache_hits(self):
        from paddle_tpu.inference.llm import LLMEngine

        m = _make_model()
        rng = np.random.RandomState(6)
        prefix = rng.randint(0, 128, (24,)).astype(np.int32)  # 3 pages
        p1 = np.concatenate([prefix, rng.randint(0, 128, (4,))
                             .astype(np.int32)])
        p2 = np.concatenate([prefix, rng.randint(0, 128, (6,))
                             .astype(np.int32)])
        cold = LLMEngine(m, block_size=8, max_batch=4, max_model_len=64,
                         enable_prefix_caching=False)
        refs = [cold.generate([p], max_new_tokens=8)[0] for p in (p1, p2)]
        assert cold.prefix_cache_stats()["prefix_hit_tokens"] == 0

        warm = LLMEngine(m, block_size=8, max_batch=4, max_model_len=64)
        out1 = warm.generate([p1], max_new_tokens=8)[0]
        launches_before = warm.stats["chunk_launches"]
        out2 = warm.generate([p2], max_new_tokens=8)[0]
        np.testing.assert_array_equal(out1, refs[0])
        np.testing.assert_array_equal(out2, refs[1])
        st = warm.prefix_cache_stats()
        # p2 adopted p1's three full prefix pages at zero compute ...
        assert st["prefix_hit_tokens"] == 24
        assert st["reused_blocks"] == 3
        assert st["hit_rate"] > 0.3
        # ... so its whole prefill was ONE chunk (the 6-token suffix)
        assert warm.stats["chunk_launches"] - launches_before == 1
        assert warm.block_manager.num_free_blocks == warm.num_blocks

    def test_cow_never_touches_cached_full_page(self):
        from paddle_tpu.inference.llm import (
            BlockManager,
            prefix_block_hashes,
        )

        bm = BlockManager(num_blocks=8, block_size=4,
                          enable_prefix_caching=True)
        toks = list(range(6))               # page 0 full, page 1 partial
        bm.allocate("a", 6)
        h0 = prefix_block_hashes(toks, 4)[0]
        bm.register_full_block("a", 0, h0)
        cached_page = bm.block_table("a")[0]
        bm.fork("a", "b")
        # the child's divergent append copies the shared PARTIAL tail;
        # the hashed full page stays shared and untouched
        slot, cow = bm.append_slot("b")
        assert cow is not None
        src, dst = cow
        assert src == bm.block_table("a")[1]
        assert dst == bm.block_table("b")[1]
        assert bm.block_table("a")[0] == cached_page
        assert bm.block_table("b")[0] == cached_page
        # both owners gone: the cached page parks on the LRU list and a
        # later request adopts THE SAME physical page
        bm.free("a")
        bm.free("b")
        assert bm.num_free_blocks == 8 and bm.num_cached_blocks == 1
        t = bm.allocate("c", 5, cached_hashes=(h0,))
        assert t[0] == cached_page
        assert bm.prefix_reused_blocks == 1

    def test_eviction_under_pressure(self):
        from paddle_tpu.inference.llm import (
            BlockManager,
            NoFreeBlocksError,
            prefix_block_hashes,
        )

        bm = BlockManager(num_blocks=4, block_size=4,
                          enable_prefix_caching=True)
        toks = list(range(16))
        hs = prefix_block_hashes(toks, 4)
        bm.allocate("a", 16)
        for i, h in enumerate(hs):
            bm.register_full_block("a", i, h)
        bm.free("a")
        # the whole pool is cached-but-unreferenced: still fully free
        assert bm.num_free_blocks == 4 and bm.num_cached_blocks == 4
        # a fresh allocation evicts the least-recently-freed pages
        bm.allocate("b", 8)
        assert bm.prefix_evictions == 2 and bm.num_cached_blocks == 2
        # the evicted leading pages break the chain for a full match ...
        assert bm.match_prefix(hs) == 0
        # ... but a surviving page is still adoptable (1 adopt + 1 evict)
        bm.allocate("c", 8, cached_hashes=(hs[2],))
        assert bm.prefix_reused_blocks == 1
        assert bm.prefix_evictions == 3
        assert bm.num_free_blocks == 0
        with pytest.raises(NoFreeBlocksError):
            bm.allocate("d", 4)

    def test_chunked_prefill_trace_token_exact_no_leaks(self):
        from paddle_tpu.inference.llm import LLMEngine

        m = _make_model()
        rng = np.random.RandomState(7)
        prompts = [rng.randint(0, 128, (n,)).astype(np.int32)
                   for n in (40, 28)]
        refs = _fmt_reference(m, prompts, max_new=8)
        # budget 16 << the 40-token prompt: prefill spreads over several
        # steps as chunks (16, 16, 8) with decodes riding along
        eng = LLMEngine(m, block_size=8, max_batch=2, max_model_len=64,
                        token_budget=16)
        outs = eng.generate(prompts, max_new_tokens=8)
        for out, ref in zip(outs, refs):
            np.testing.assert_array_equal(out, ref)
        assert eng.stats["chunk_launches"] >= 5
        assert eng.block_manager.num_free_blocks == eng.num_blocks

    def test_single_step_mixes_prefill_chunk_and_decode_rows(self):
        """THE acceptance property of the ragged collapse: one device
        step carries a prefill chunk AND decode rows in one launch.
        Asserted two ways — the engine's mixed_steps stat, and a
        schedule spy that saw a ScheduledBatch whose row descriptors
        span both kinds — and the mixed trace stays token-exact."""
        from paddle_tpu.inference.llm import LLMEngine

        m = _make_model()
        rng = np.random.RandomState(5)
        short_p = rng.randint(0, 128, (3,)).astype(np.int32)
        long_p = rng.randint(0, 128, (40,)).astype(np.int32)
        refs = _fmt_reference(m, [short_p, long_p], max_new=8)
        eng = LLMEngine(m, block_size=8, max_batch=2, max_model_len=64,
                        token_budget=16)
        mixed_batches = []
        orig = eng.scheduler.schedule

        def spy():
            b = orig()
            kinds = {"chunk" if r.kind == "chunk" else "tok"
                     for r in b.rows}
            if len(kinds) == 2:
                mixed_batches.append(b)
            return b

        eng.scheduler.schedule = spy
        outs = eng.generate([short_p, long_p], max_new_tokens=8)
        for out, ref in zip(outs, refs):
            np.testing.assert_array_equal(out, ref)
        assert eng.stats["mixed_steps"] >= 1
        assert mixed_batches, "no step mixed a chunk with decode rows"
        assert any(r.kind == "decode" for b in mixed_batches
                   for r in b.rows)
        assert any(r.kind == "chunk" for b in mixed_batches
                   for r in b.rows)
        assert eng.stats["mixed_steps"] == len(mixed_batches)

    def test_warmup_family_covers_serving_no_new_compiles(self):
        from paddle_tpu.inference.llm import LLMEngine

        m = _make_model()
        eng = LLMEngine(m, block_size=8, max_batch=4, max_model_len=64,
                        token_budget=16)
        watcher = eng.warmup()     # armed over the ragged family
        # ONE family, O(log token_budget): buckets 8, 16
        assert eng._ragged._cache_size() == 2
        rng = np.random.RandomState(8)
        prompts = [rng.randint(0, 128, (n,)).astype(np.int32)
                   for n in (3, 17, 40, 9)]
        # the serving window must compile NOTHING: every chunk bucket
        # and decode batch bucket was covered by warmup — __exit__
        # raises RecompileError naming the offender otherwise
        with watcher:
            eng.generate(prompts, max_new_tokens=8)
        assert watcher.new_compiles() == []

    def test_compile_watcher_catches_injected_retrace(self,
                                                      compile_watcher):
        """The ragged signature is all-array (the retired chunk/decode
        scalar args are gone, and with them the classic python-scalar
        weak-type leak), so the surviving silent-retrace class is a
        token count that slips past the bucket grid — the watcher must
        name the off-bucket cache key, not just report a count."""
        import jax.numpy as jnp

        from paddle_tpu.framework.analysis import RecompileError
        from paddle_tpu.inference.llm import LLMEngine

        m = _make_model()
        eng = LLMEngine(m, block_size=8, max_batch=4, max_model_len=64,
                        token_budget=16)
        eng.warmup()
        from paddle_tpu.inference.llm.sampling import neutral_row_params

        ids = jnp.zeros((12,), jnp.int32)      # 12 is not a bucket
        tables = jnp.zeros((eng.max_batch, eng.max_pages), jnp.int32)
        positions = jnp.full((12,), -1, jnp.int32)
        rows = jnp.zeros((12,), jnp.int32)
        zr = jnp.zeros((eng.max_batch,), jnp.int32)
        cow_dst = jnp.full((eng.max_batch,), eng.num_blocks, jnp.int32)
        knobs = tuple(jnp.asarray(k)
                      for k in neutral_row_params(eng.max_batch))
        chan = jnp.zeros((12, eng.vocab_size), jnp.float32)
        with pytest.raises(RecompileError, match="ragged") as ei:
            with compile_watcher(eng._ragged, labels=("ragged",)):
                _, _, eng.kv_cache = eng._ragged(
                    eng.params, ids, eng.kv_cache, tables,
                    positions, rows, zr, zr, zr, zr, cow_dst,
                    *knobs, chan, chan)
        # the report names the offending cache KEY, not just a count —
        # the off-grid token axis is visible in the new signature
        msg = str(ei.value)
        assert "New cache keys" in msg
        assert "int32[12]" in msg


# ---------------------------------------------------------------------------
class TestTensorParallel:
    """tensor_parallel=N serving on virtual CPU devices: the sharded
    engine (Megatron params + head-sharded paged pool, shard_map'd
    executables) must be TOKEN-EXACT vs the single-device engine across
    the whole feature surface — prefix-cache adoption, preemption and
    recompute — and compile nothing after warmup() on the mesh."""

    def test_tp_token_exact_with_prefix_cache_hits(self):
        import jax

        from paddle_tpu.inference.llm import LLMEngine

        assert len(jax.devices()) >= 4      # conftest forces 8 virtual
        m = _make_model()
        rng = np.random.RandomState(10)
        prefix = rng.randint(0, 128, (24,)).astype(np.int32)  # 3 pages
        prompts = [np.concatenate([prefix, rng.randint(0, 128, (n,))
                                   .astype(np.int32)]) for n in (4, 6)]
        single = LLMEngine(m, block_size=8, max_batch=4, max_model_len=64)
        refs = [single.generate([p], max_new_tokens=8)[0] for p in prompts]

        tp = LLMEngine(m, block_size=8, max_batch=4, max_model_len=64,
                       tensor_parallel=4)
        assert tp.tp == 4
        outs = [tp.generate([p], max_new_tokens=8)[0] for p in prompts]
        for out, ref in zip(outs, refs):
            np.testing.assert_array_equal(out, ref)
        # the second prompt adopted the first's full prefix pages — the
        # cache hit path must survive the mesh (cached pages are written
        # shard-locally but addressed by one host-side allocator)
        assert tp.prefix_cache_stats()["prefix_hit_tokens"] == 24
        assert tp.block_manager.num_free_blocks == tp.num_blocks

    def test_tp_token_exact_through_preemption(self):
        from paddle_tpu.inference.llm import LLMEngine

        m = _make_model()
        rng = np.random.RandomState(11)
        prompts = [rng.randint(0, 128, (4,)).astype(np.int32)
                   for _ in range(3)]
        refs = _fmt_reference(m, prompts, max_new=28, max_length=40)
        # 5 pages < 3 seqs x 4 pages demanded -> preempt + recompute,
        # now with every page write fanned out across 4 pool shards
        tp = LLMEngine(m, block_size=8, num_blocks=5, max_batch=3,
                       max_model_len=40, tensor_parallel=4)
        outs = tp.generate(prompts, max_new_tokens=28)
        for out, ref in zip(outs, refs):
            np.testing.assert_array_equal(out, ref)
        assert tp.scheduler.num_preemptions > 0
        assert tp.block_manager.num_free_blocks == tp.num_blocks

    def test_tp_zero_new_compiles_after_warmup(self):
        from paddle_tpu.inference.llm import LLMEngine

        m = _make_model()
        tp = LLMEngine(m, block_size=8, max_batch=4, max_model_len=64,
                       token_budget=16, tensor_parallel=4)
        watcher = tp.warmup()
        assert tp._ragged._cache_size() == 2  # buckets 8, 16 — as tp=1
        rng = np.random.RandomState(12)
        prompts = [rng.randint(0, 128, (n,)).astype(np.int32)
                   for n in (3, 17, 40, 9)]
        with watcher:                        # raises on any mesh compile
            tp.generate(prompts, max_new_tokens=8)
        assert watcher.new_compiles() == []

    def test_tp_cache_is_sharded_along_heads(self):
        from jax.sharding import PartitionSpec as P

        from paddle_tpu.inference.llm import LLMEngine

        m = _make_model()
        tp = LLMEngine(m, block_size=8, max_batch=2, max_model_len=64,
                       tensor_parallel=4)
        # pool: [L, NB, Nkv/mp, bs, D] per shard — axis 2 carries 'mp'
        assert tp.kv_cache["k"].sharding.spec == \
            P(None, None, "mp", None, None)
        qkv = tp.params["blocks"]["attn.qkv.weight"]
        assert qkv.sharding.spec == P(None, None, "mp")
        proj = tp.params["blocks"]["attn.proj.weight"]
        assert proj.sharding.spec == P(None, "mp", None)

    def test_tp_validation(self):
        from paddle_tpu.inference.llm import LLMEngine

        m = _make_model()                   # 4 heads
        with pytest.raises(ValueError, match="not divisible"):
            LLMEngine(m, block_size=8, max_model_len=64,
                      tensor_parallel=3)
        with pytest.raises(ValueError, match="exceeds"):
            LLMEngine(m, block_size=8, max_model_len=64,
                      tensor_parallel=1024)

    def test_invariant_checker_catches_corruption(self):
        from paddle_tpu.inference.llm import BlockManager

        bm = BlockManager(num_blocks=4, block_size=4)
        bm.allocate("a", 8)
        bm.check_invariants()               # balanced books pass
        bm._free.append(bm._tables["a"][0])  # page both free and owned
        with pytest.raises(RuntimeError, match="free/ref"):
            bm.check_invariants()


# ---------------------------------------------------------------------------
class TestSamplingSeeds:
    def test_engine_seeds_diverge_and_default_is_deterministic(self):
        from paddle_tpu.inference.llm import LLMEngine

        m = _make_model()
        prompt = np.array([5, 6, 7], np.int32)

        def sample(seed):
            eng = (LLMEngine(m, block_size=8, max_batch=2,
                             max_model_len=64)
                   if seed is None else
                   LLMEngine(m, block_size=8, max_batch=2,
                             max_model_len=64, seed=seed))
            return eng.generate([prompt], max_new_tokens=16,
                                temperature=1.0)[0]

        a, b = sample(1), sample(2)
        assert not np.array_equal(a, b)     # different seeds diverge
        np.testing.assert_array_equal(sample(1), a)  # same seed repeats
        # default (no seed) stays the historical deterministic stream
        np.testing.assert_array_equal(sample(None), sample(None))

    def test_per_request_seed_beats_arrival_order(self):
        from paddle_tpu.inference.llm import LLMEngine

        m = _make_model()
        rng = np.random.RandomState(13)
        p1 = rng.randint(0, 128, (3,)).astype(np.int32)
        p2 = rng.randint(0, 128, (5,)).astype(np.int32)
        # solo replay: each request sampled alone with its seed
        eng = LLMEngine(m, block_size=8, max_batch=4, max_model_len=64)
        solo1 = eng.generate([p1], max_new_tokens=8, temperature=0.7,
                             seed=41)[0]
        solo2 = eng.generate([p2], max_new_tokens=8, temperature=0.7,
                             seed=42)[0]
        # batched replay on a fresh engine: the two streams interleave in
        # the shared decode batch, but per-request RNGs don't care
        eng2 = LLMEngine(m, block_size=8, max_batch=4, max_model_len=64)
        r1 = eng2.add_request(p1, max_new_tokens=8, temperature=0.7,
                              seed=41)
        r2 = eng2.add_request(p2, max_new_tokens=8, temperature=0.7,
                              seed=42)
        outs = {}
        while eng2.has_unfinished():
            for fo in eng2.step():
                outs[fo.request_id] = fo.all_ids
        np.testing.assert_array_equal(outs[r1], solo1)
        np.testing.assert_array_equal(outs[r2], solo2)

    def test_greedy_rows_stay_exact_beside_sampling_rows(self):
        from paddle_tpu.inference.llm import LLMEngine

        m = _make_model()
        rng = np.random.RandomState(14)
        greedy_p = rng.randint(0, 128, (6,)).astype(np.int32)
        ref = _fmt_reference(m, [greedy_p], max_new=10)[0]
        eng = LLMEngine(m, block_size=8, max_batch=4, max_model_len=64)
        rg = eng.add_request(greedy_p, max_new_tokens=10)
        rs = eng.add_request(rng.randint(0, 128, (4,)).astype(np.int32),
                             max_new_tokens=10, temperature=1.0)
        outs = {}
        while eng.has_unfinished():
            for fo in eng.step():
                outs[fo.request_id] = fo.all_ids
        # the greedy row rode a mixed batch (sampling rows fetch their
        # logits rows; greedy rows commit the device argmax) bit-exactly
        np.testing.assert_array_equal(outs[rg], ref)
        assert rs in outs


# ---------------------------------------------------------------------------
class TestSpeculative:
    """n-gram speculative decoding: the speculative engine must emit the
    EXACT token stream of the non-speculative engine (greedy and seeded
    sampling, prefix caching on, through preemption, under tensor
    parallelism) while compiling nothing after warmup — speculation is
    a pure latency optimisation, never a semantics change."""

    def _spec_prompts(self, n=5, seed=7):
        """Mix of repetitive (draftable) and random (undraftable)
        prompts, with a shared tail pair to exercise prefix caching."""
        rng = np.random.RandomState(seed)
        prompts = [np.tile(rng.randint(0, 128, 5), 3).astype(np.int32),
                   rng.randint(0, 128, (12,)).astype(np.int32),
                   np.tile(rng.randint(0, 128, 4), 4).astype(np.int32),
                   rng.randint(0, 128, (3,)).astype(np.int32),
                   np.tile(rng.randint(0, 128, 6), 2).astype(np.int32)]
        return prompts[:n]

    def _gen(self, spec, temp=0.0, seed=None, tp=None, num_blocks=None,
             max_new=46):
        from paddle_tpu.inference.llm import LLMEngine

        m = _make_model()
        kw = {}
        if num_blocks:
            kw["num_blocks"] = num_blocks
        eng = LLMEngine(m, block_size=8, max_batch=4, max_model_len=64,
                        token_budget=64, speculative=spec,
                        tensor_parallel=tp, **kw)
        watcher = eng.warmup()
        for i, p in enumerate(self._spec_prompts()):
            eng.add_request(p, max_new_tokens=max_new, temperature=temp,
                            seed=None if seed is None else seed + i)
        outs = {}
        while eng.has_unfinished():
            for r in eng.step():
                outs[r.request_id] = list(r.output_ids)
        watcher.assert_no_new_compiles()
        return outs, eng

    def test_ngram_drafter(self):
        from paddle_tpu.inference.llm import NgramDrafter, SpeculativeConfig

        d = NgramDrafter(SpeculativeConfig(num_tokens=4))
        # trailing [1, 2] recurs; continuation after the match is drafted
        assert d.propose([1, 2, 3, 4, 1, 2], 4) == [3, 4, 1, 2]
        # budget clamps the draft (both caller budget and num_tokens)
        assert d.propose([1, 2, 3, 4, 1, 2], 2) == [3, 4]
        assert d.propose([1, 2, 3, 4, 1, 2], 99) == [3, 4, 1, 2]
        # the MOST RECENT earlier occurrence wins, not the first
        assert d.propose([5, 9, 7, 5, 8, 5], 2) == [8, 5]
        # no recurrence -> no draft; zero budget -> no draft
        assert d.propose([1, 2, 3, 4, 5], 4) == []
        assert d.propose([1, 2, 1, 2], 0) == []
        assert d.propose([7], 4) == []
        # longer n-gram matches beat shorter ones: trailing [2, 3]
        # matches at index 1 even though a bare [3] occurs later
        d3 = NgramDrafter(SpeculativeConfig(num_tokens=2, max_ngram=2))
        assert d3.propose([1, 2, 3, 9, 3, 6, 2, 3], 2) == [9, 3]

    def test_speculative_config_resolve(self):
        from paddle_tpu.inference.llm import SpeculativeConfig as SC

        assert SC.resolve(None) is None
        assert SC.resolve(False) is None
        assert SC.resolve(True).num_tokens == 4
        assert SC.resolve(6).num_tokens == 6
        assert SC.resolve({"num_tokens": 2, "max_ngram": 5}).max_ngram == 5
        cfg = SC(num_tokens=3)
        assert SC.resolve(cfg) is cfg
        # method-string sugar: the model-based drafters resolve by name
        assert SC.resolve("draft-model").uses_draft_model
        assert SC.resolve("tree").method == "tree"
        assert not SC.resolve(4).uses_draft_model
        with pytest.raises(ValueError, match="num_tokens"):
            SC(num_tokens=0)
        with pytest.raises(ValueError, match="min_ngram"):
            SC(min_ngram=3, max_ngram=2)
        with pytest.raises(ValueError, match="method"):
            SC.resolve("4")
        with pytest.raises(ValueError, match="draft_layers"):
            SC(draft_layers=0)
        with pytest.raises(TypeError, match="speculative"):
            SC.resolve(4.5)

    def test_greedy_token_exact_and_no_new_compiles(self):
        spec, eng = self._gen(4)
        base, _ = self._gen(None)
        assert spec == base
        st = eng.spec_stats()
        # the repetitive prompts must actually exercise the fast path
        assert st["draft_tokens"] > 0
        assert st["accepted_tokens"] > 0
        assert st["acceptance_rate"] > 0.5

    def test_token_exact_through_preemption(self):
        # 18 pages cannot hold 5 sequences at full length: speculation
        # must survive preempt/recompute (draft slots rolled back, the
        # victim's drafts dropped) and still match bit for bit
        spec, eng = self._gen(4, num_blocks=18)
        base, _ = self._gen(None)
        assert spec == base
        assert eng.scheduler.num_preemptions > 0
        eng.block_manager.check_invariants()
        assert eng.block_manager.num_free_blocks == 18

    def test_seeded_sampling_token_exact(self):
        # per-request streams: ONE gumbel draw per emitted token, in
        # position order, makes sample-and-match literal rejection
        # sampling — the stream consumption must align bitwise
        spec, _ = self._gen(4, temp=0.8, seed=123)
        base, _ = self._gen(None, temp=0.8, seed=123)
        assert spec == base
        # the shared engine stream CANNOT match non-spec (multi-token
        # commits change which request draws when — that is exactly why
        # per-request seeds exist), but it must stay deterministic:
        # same engine config, same trace, same tokens
        spec_e, _ = self._gen(2, temp=0.6)
        spec_e2, _ = self._gen(2, temp=0.6)
        assert spec_e == spec_e2

    def test_tp_token_exact(self):
        import jax

        assert len(jax.devices()) >= 2      # conftest forces 8 virtual
        spec, eng = self._gen(4, tp=2)
        base, _ = self._gen(None)
        assert spec == base
        assert eng.spec_stats()["accepted_tokens"] > 0

    def test_verify_attention_matches_flattened_decode(self):
        """A verify row's T query tokens share ONE block-table row on
        the ragged XLA path — its output must be BITWISE the [B*T]
        flattened single-token decode batch over replicated tables
        (that identity is what makes spec greedy == plain greedy)."""
        import jax.numpy as jnp

        from ragged_rows import rows_attention

        rng = np.random.RandomState(3)
        b, t, nq, nkv, d, bs, pages = 2, 3, 4, 2, 16, 8, 4
        q = jnp.asarray(rng.randn(b, t, nq, d), jnp.float32)
        kp = jnp.asarray(rng.randn(b * pages, bs, nkv, d), jnp.float32) \
            .transpose(0, 2, 1, 3)
        vp = jnp.asarray(rng.randn(b * pages, bs, nkv, d), jnp.float32) \
            .transpose(0, 2, 1, 3)
        tables = jnp.asarray(
            rng.permutation(b * pages)[:b * pages]
            .reshape(b, pages), jnp.int32)
        ctx = jnp.asarray([[5, 6, 7], [0, 1, 2]], jnp.int32)

        out = rows_attention(q, kp, vp, tables, ctx)
        flat = rows_attention(
            q.reshape(b * t, 1, nq, d), kp, vp,
            jnp.repeat(tables, t, axis=0), ctx.reshape(b * t, 1))
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(flat).reshape(b, t, nq, d))
        # the dispatcher (interpret mode on CPU) — same semantics
        pal = rows_attention(q, kp, vp, tables, ctx, interpret=True)
        np.testing.assert_allclose(np.asarray(pal), np.asarray(out),
                                   rtol=2e-5, atol=2e-5)

    def test_generate_and_server_validation(self):
        from paddle_tpu.inference.llm import LLMEngine
        from paddle_tpu.inference.serving import _GenerativeAdapter

        m = _make_model()
        eng = LLMEngine(m, block_size=8, max_batch=2, max_model_len=32)
        prompts = [np.arange(4, dtype=np.int32)]
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.generate(prompts, max_new_tokens=0)
        with pytest.raises(ValueError, match="temperature"):
            eng.generate(prompts, max_new_tokens=4, temperature=-0.5)
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.add_request(prompts[0], max_new_tokens=-3)
        with pytest.raises(ValueError, match="temperature"):
            eng.add_request(prompts[0], temperature=-1e-9)
        # the socket adapter rejects bad knobs BEFORE queueing, so the
        # wire client gets a clear error instead of a hung generation
        adapter = _GenerativeAdapter(eng)
        try:
            with pytest.raises(ValueError, match="max_new_tokens"):
                adapter.run([prompts[0], np.int64(0)])
            with pytest.raises(ValueError, match="temperature"):
                adapter.run([prompts[0], np.int64(4),
                             np.float32(-2.0)])
        finally:
            adapter.stop()


class TestLookahead:
    """Async lookahead pipeline: planning step N+1 under step N's device
    window must be a pure latency optimisation — the staged plan either
    reproduces the sync schedule bitwise or is discarded, so every token
    stream matches the lookahead=False engine exactly, across prefix
    hits, preemption, seeded sampling, forks, TP, LoRA, and the n-gram
    speculative path."""

    def _prompts(self, n=5, seed=7):
        rng = np.random.RandomState(seed)
        prompts = [np.tile(rng.randint(0, 128, 5), 3).astype(np.int32),
                   rng.randint(0, 128, (12,)).astype(np.int32),
                   np.tile(rng.randint(0, 128, 4), 4).astype(np.int32),
                   rng.randint(0, 128, (3,)).astype(np.int32),
                   np.tile(rng.randint(0, 128, 6), 2).astype(np.int32)]
        return prompts[:n]

    def _build(self, lookahead, tp=None, num_blocks=None, spec=None,
               **kw):
        from paddle_tpu.inference.llm import LLMEngine

        m = _make_model()
        if num_blocks:
            kw["num_blocks"] = num_blocks
        return LLMEngine(m, block_size=8, max_batch=4, max_model_len=64,
                         token_budget=64, speculative=spec,
                         tensor_parallel=tp, lookahead=lookahead, **kw)

    def _gen(self, lookahead, temp=0.0, seed=None, n=1, stagger=0,
             max_new=24, **kw):
        eng = self._build(lookahead, **kw)
        watcher = eng.warmup()
        prompts = self._prompts()

        def add(i):
            eng.add_request(prompts[i], max_new_tokens=max_new,
                            temperature=temp,
                            seed=None if seed is None else seed + i,
                            n=n)

        nxt = 2 if stagger else len(prompts)
        for i in range(nxt):
            add(i)
        outs = {}
        steps = 0
        while eng.has_unfinished() or nxt < len(prompts):
            steps += 1
            # staggered admission lands mid-serve, so arrivals keep
            # invalidating the staged plan at the same LOGICAL step in
            # both legs (step counts match because schedules match)
            if stagger and nxt < len(prompts) and steps % stagger == 0:
                add(nxt)
                nxt += 1
            for r in eng.step():
                outs[r.request_id] = list(r.output_ids)
        watcher.assert_no_new_compiles()
        eng.block_manager.check_invariants()
        return outs, eng

    def test_greedy_token_exact_and_pipeline_active(self):
        la, eng = self._gen(True)
        base, _ = self._gen(False)
        assert la == base
        st = eng.lifecycle_stats()
        # the pipeline must actually fire: plans staged AND claimed
        assert st["staged_steps"] > 0
        assert st["staged_hits"] > 0
        assert st["staged_hits"] <= st["staged_steps"]
        # the measured gauge rides lifecycle_stats (plan time is
        # clocked whether or not it hid under device time)
        assert 0.0 <= st["host_overhead_fraction"] <= 1.0
        assert st["host_plan_s"] >= 0.0

    def test_staggered_admission_token_exact(self):
        # arrivals between stage and launch invalidate the plan; the
        # claim validation must reject and fall back to a sync schedule
        la, eng = self._gen(True, stagger=3)
        base, _ = self._gen(False, stagger=3)
        assert la == base
        assert eng.lifecycle_stats()["staged_steps"] > 0

    def test_token_exact_through_preemption(self):
        # 18 pages force preempt/recompute: a staged plan whose rows
        # get preempted under it must be discarded exactly
        la, eng = self._gen(True, num_blocks=18)
        base, beng = self._gen(False, num_blocks=18)
        assert la == base
        assert eng.scheduler.num_preemptions > 0
        assert eng.scheduler.num_preemptions == \
            beng.scheduler.num_preemptions
        assert eng.block_manager.num_free_blocks == 18

    def test_seeded_sampling_and_forks_token_exact(self):
        la, _ = self._gen(True, temp=0.8, seed=123, n=2)
        base, _ = self._gen(False, temp=0.8, seed=123, n=2)
        assert la == base
        # forks actually ran: child ids are "<parent>.<k>" strings
        assert any("." in str(rid) for rid in la)

    def test_tp_token_exact(self):
        import jax

        assert len(jax.devices()) >= 2       # conftest forces 8 virtual
        la, eng = self._gen(True, tp=2)
        base, _ = self._gen(False, tp=2)
        assert la == base
        assert eng.lifecycle_stats()["staged_hits"] > 0

    def test_ngram_spec_token_exact(self):
        # lookahead never stages over rows carrying draft tokens, but
        # the two optimisations must compose token-exactly
        la, eng = self._gen(True, spec=4)
        base, _ = self._gen(False, spec=4)
        plain, _ = self._gen(False)
        assert la == base == plain
        assert eng.spec_stats()["accepted_tokens"] > 0

    def test_lora_token_exact(self):
        la, eng = self._gen_lora(True)
        base, _ = self._gen_lora(False)
        assert la == base
        assert eng.lifecycle_stats()["staged_hits"] > 0

    def _gen_lora(self, lookahead):
        eng = self._build(lookahead, lora=dict(rank=4, max_adapters=4))
        rng = np.random.RandomState(11)
        w = {}
        for key in eng.lora.targets:
            L, d_in, d_out = eng._lora_shapes[key]
            w[key] = (
                np.asarray(rng.randn(L, d_in, eng.lora.rank) * 0.05,
                           np.float32),
                np.asarray(rng.randn(L, eng.lora.rank, d_out) * 0.05,
                           np.float32))
        eng.add_adapter("t1", w)
        watcher = eng.warmup()
        for i, p in enumerate(self._prompts()):
            eng.add_request(p, max_new_tokens=20,
                            adapter_id="t1" if i % 2 else None)
        outs = {}
        while eng.has_unfinished():
            for r in eng.step():
                outs[r.request_id] = list(r.output_ids)
        watcher.assert_no_new_compiles()
        return outs, eng

    # -------------------------------------------- satellite 3: rollback --
    def test_abort_between_stage_and_launch_rolls_back(self):
        """An abort landing while a staged plan is armed must discard
        the plan and roll back its slot reservations EXACTLY — outputs
        match a sync engine given the identical abort schedule, and no
        page leaks."""
        from paddle_tpu.inference.llm import FinishReason

        la = self._build(True)
        sync = self._build(False)
        for eng in (la, sync):
            eng.warmup()
            # 3 prompts < max_batch: nothing waits, so staging is live
            # while r1 runs and the armed-plan window is guaranteed
            for i, p in enumerate(self._prompts(n=3)):
                eng.add_request(p, max_new_tokens=24,
                                request_id=f"r{i}")
        outs = {"la": {}, "sync": {}}
        aborted = False
        steps = 0
        while la.has_unfinished() or sync.has_unfinished():
            steps += 1
            assert steps < 512
            # abort driven by the LOOKAHEAD leg's staging state so the
            # scenario is guaranteed: the plan is armed (staged, not
            # yet claimed) when the abort lands.  Both legs abort at
            # the same logical step, so exactness is comparable.
            if not aborted and la._staged is not None \
                    and any(r.request_id == "r1"
                            for r in la.scheduler.running):
                assert any(row.request.request_id == "r1"
                           for row in la._staged[0])
                la.abort_request("r1")
                sync.abort_request("r1")
                aborted = True
                # the abort must invalidate the armed plan: the epoch
                # bump makes the next claim reject and discard it
                assert la._staged_epoch != la._plan_epoch
            for eng, key in ((la, "la"), (sync, "sync")):
                if eng.has_unfinished():
                    for r in eng.step():
                        outs[key][r.request_id] = r
        assert aborted
        assert set(outs["la"]) == set(outs["sync"])
        for rid, r in outs["la"].items():
            assert list(r.output_ids) == \
                list(outs["sync"][rid].output_ids), rid
            assert r.finish_reason == outs["sync"][rid].finish_reason
        assert outs["la"]["r1"].finish_reason == FinishReason.ABORTED
        for eng in (la, sync):
            eng.block_manager.check_invariants()
            assert eng.block_manager.num_free_blocks == eng.num_blocks

    def test_quarantine_of_claimed_plan_rolls_back(self):
        """A launch that fails AFTER a staged plan was claimed must
        quarantine its rows and roll back every staged slot
        reservation exactly: books return to num_cached, no leaked
        pages, and the engine keeps serving fresh work."""
        from paddle_tpu.inference.llm import FinishReason

        eng = self._build(True, retry={"max_attempts": 1,
                                       "base_delay_s": 0.0,
                                       "jitter": 0.0})
        eng.warmup()
        for i, p in enumerate(self._prompts(n=3)):
            eng.add_request(p, max_new_tokens=24, request_id=f"r{i}")
        orig = eng._ragged_launch
        state = {"armed": False, "fired": False}

        def boom(*a, **k):
            if state["armed"]:
                state["armed"] = False
                state["fired"] = True
                raise RuntimeError("injected launch failure")
            return orig(*a, **k)

        eng._ragged_launch = boom
        outs = {}
        steps = 0
        while eng.has_unfinished():
            steps += 1
            assert steps < 512
            if not state["fired"] and eng._staged is not None:
                state["armed"] = True      # next launch IS the claim
            before = eng.stats["staged_hits"]
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", RuntimeWarning)
                for r in eng.step():
                    outs[r.request_id] = r
            if state["fired"] and before != eng.stats["staged_hits"]:
                # the failing launch really was the claimed plan
                assert eng.stats["staged_hits"] == before + 1
        assert state["fired"]
        assert eng.stats["quarantined"] > 0
        errs = [r for r in outs.values()
                if r.finish_reason == FinishReason.ERROR]
        assert errs and all("injected launch failure" in r.error
                            for r in errs)
        # exact rollback: every page returned, invariants clean
        eng.block_manager.check_invariants()
        assert eng.block_manager.num_free_blocks == eng.num_blocks
        # and the engine still serves (staging resumes post-quarantine)
        eng.add_request(self._prompts(n=1)[0], max_new_tokens=8,
                        request_id="fresh")
        while eng.has_unfinished():
            for r in eng.step():
                outs[r.request_id] = r
        assert outs["fresh"].finish_reason in ("stop", "length")
        assert len(outs["fresh"].output_ids) > 0
        assert eng.block_manager.num_free_blocks == eng.num_blocks


class TestDraftModel:
    """Model-based (draft-model / tree) speculation: a second set of
    zero-padded block leaves riding the SAME ragged executable family
    must change latency only — token streams match plain decode bitwise
    (greedy and seeded), the warmup census gains no executables, and
    the tree sibling promotion is exercised deterministically."""

    def _prompts(self, n=4, seed=19):
        # varied random prompts so the n-gram drafter misses and the
        # model path is the one doing the work
        rng = np.random.RandomState(seed)
        return [rng.randint(0, 128, (4 + 3 * i,)).astype(np.int32)
                for i in range(n)]

    def _gen(self, spec, temp=0.0, seed=None, num_blocks=None,
             max_new=20, mute_ngram=True, token_budget=64,
             n_prompts=4):
        from paddle_tpu.inference.llm import DraftModelDrafter, LLMEngine

        m = _make_model()
        kw = {}
        if num_blocks:
            kw["num_blocks"] = num_blocks
        eng = LLMEngine(m, block_size=8, max_batch=4, max_model_len=64,
                        token_budget=token_budget, speculative=spec,
                        **kw)
        if mute_ngram and isinstance(eng.drafter, DraftModelDrafter):
            # min_ngram=1 hits constantly on small-vocab toy output;
            # silence it so the MODEL path is what gets verified
            eng.drafter._ngram.propose = lambda *a, **k: []
        watcher = eng.warmup()
        for i, p in enumerate(self._prompts(n=n_prompts)):
            eng.add_request(p, max_new_tokens=max_new, temperature=temp,
                            seed=None if seed is None else seed + i)
        outs = {}
        while eng.has_unfinished():
            for r in eng.step():
                outs[r.request_id] = list(r.output_ids)
        watcher.assert_no_new_compiles()
        eng.block_manager.check_invariants()
        return outs, eng

    def test_greedy_token_exact_model_path(self):
        cfg = {"method": "draft-model", "num_tokens": 4,
               "draft_layers": 1}
        spec, eng = self._gen(cfg)
        base, _ = self._gen(None)
        assert spec == base
        st = eng.spec_stats()
        assert st["method"] == "draft-model"
        assert st["model_drafts"] > 0
        assert st["draft_tokens"] > 0

    def test_full_copy_draft_acceptance_is_total(self):
        # draft_layers == num_layers: the zero-padding identity makes
        # the draft the target, so greedy acceptance must be 1.0 —
        # this is the end-to-end proof the draft KV bookkeeping
        # (catch-up, chain feed, rollback) is position-exact
        cfg = {"method": "draft-model", "num_tokens": 3,
               "draft_layers": 2}
        spec, eng = self._gen(cfg)
        base, _ = self._gen(None)
        assert spec == base
        st = eng.spec_stats()
        assert st["model_drafts"] > 0
        assert st["acceptance_rate"] == 1.0

    def test_seeded_sampling_token_exact(self):
        cfg = {"method": "draft-model", "num_tokens": 4,
               "draft_layers": 1}
        spec, eng = self._gen(cfg, temp=0.8, seed=321)
        base, _ = self._gen(None, temp=0.8, seed=321)
        assert spec == base
        assert eng.spec_stats()["model_drafts"] > 0

    def test_tree_token_exact_through_preemption(self):
        cfg = {"method": "tree", "num_tokens": 3, "draft_layers": 1}
        spec, eng = self._gen(cfg, num_blocks=18, max_new=32)
        base, beng = self._gen(None, num_blocks=18, max_new=32)
        assert spec == base
        assert beng.scheduler.num_preemptions > 0
        assert eng.block_manager.num_free_blocks == 18

    def test_tree_sibling_promotion_exact(self):
        """Drive the tree's second branch deterministically: feed a
        WRONG first draft plus the true next token as the sibling —
        every step must miss on branch one, promote the sibling fork,
        and still emit the plain-decode stream bitwise."""
        from paddle_tpu.inference.llm import LLMEngine

        # 2 requests at max_batch=4: the scheduler only admits a tree
        # sibling row while running + trees < max_batch
        base, _ = self._gen(None, max_new=14, n_prompts=2)
        m = _make_model()
        eng = LLMEngine(m, block_size=8, max_batch=4, max_model_len=64,
                        token_budget=64,
                        speculative={"method": "tree", "num_tokens": 3,
                                     "draft_layers": 1})
        dr = eng.drafter
        dr._ngram.propose = lambda *a, **k: []
        eng._draft_phase = lambda: None      # we inject the proposals
        watcher = eng.warmup()
        for p in self._prompts(n=2):
            eng.add_request(p, max_new_tokens=14)
        outs = {}
        while eng.has_unfinished():
            dr.proposals.clear()
            dr.siblings.clear()
            for req in eng.scheduler.running:
                rid = req.request_id
                done = len(req.output_ids)
                if req.prefill_done and done + 1 < req.max_new_tokens \
                        and done < len(base[rid]):
                    correct = int(base[rid][done])
                    wrong = (correct + 1) % eng.vocab_size
                    dr.proposals[rid] = [wrong]
                    dr.siblings[rid] = correct
            for r in eng.step():
                outs[r.request_id] = list(r.output_ids)
        watcher.assert_no_new_compiles()
        assert outs == base
        st = eng.spec_stats()
        assert st["tree_hits"] > 0           # sibling forks promoted
        eng.block_manager.check_invariants()
        assert eng.block_manager.num_free_blocks == eng.num_blocks

    def test_census_unchanged_and_draft_pool_accounted(self):
        # the draft params ride the ragged executable family as its
        # params operand: bring-up compiles EXACTLY what a plain
        # engine compiles, and the draft pool keeps separate books
        from paddle_tpu.inference.llm import LLMEngine

        m = _make_model()
        plain = LLMEngine(m, block_size=8, max_batch=4,
                          max_model_len=64, token_budget=16)
        n_plain = len(plain.warmup().compile_ms)
        eng = LLMEngine(m, block_size=8, max_batch=4, max_model_len=64,
                        token_budget=16,
                        speculative={"method": "draft-model",
                                     "num_tokens": 2,
                                     "draft_layers": 1})
        watcher = eng.warmup()
        assert len(watcher.compile_ms) == n_plain
        assert eng._draft_bm is not None
        assert eng._draft_bm.num_free_blocks == eng.num_blocks
        eng.add_request(self._prompts(n=1)[0], max_new_tokens=8)
        while eng.has_unfinished():
            eng.step()
        watcher.assert_no_new_compiles()
        # departed requests release their draft pages
        assert eng._draft_bm.num_free_blocks == eng.num_blocks
        assert eng.block_manager.num_free_blocks == eng.num_blocks


# ---------------------------------------------------------------------------
def test_spec_bench_smoke(tmp_path):
    """benchmarks/bench_serving.py --spec runs end to end on tiny
    parameters, asserts its own token-exactness gate, drafts something
    on the repetitive trace, and writes the artifact (the >= 1.5x
    speedup claim is the slow-tier / PERF.md job — at this scale the
    ratio is noise, only the plumbing and exactness are tested)."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    artifact = str(tmp_path / "BENCH_spec.json")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    rc = subprocess.run(
        [sys.executable,
         os.path.join(repo, "benchmarks", "bench_serving.py"),
         "--spec", "2", "--requests", "3", "--max-new", "6",
         "--max-batch", "2", "--repeats", "1", "--artifact", artifact],
        capture_output=True, text=True, timeout=300, env=env, cwd=repo)
    assert rc.returncode == 0, rc.stderr[-1500:]
    row = json.loads(rc.stdout.strip().splitlines()[-1])
    assert row["metric"] == "llm_serving_spec"
    assert row["token_exact"] is True
    assert row["spec_tokens"] == 2
    assert row["draft_tokens"] > 0
    assert row["acceptance_rate"] >= 0.0
    assert row["value"] > 0 and row["vs_nonspec"] is not None
    assert row["tpot_p50_ms"] is not None
    assert row["e2e_p50_ms"] is not None
    with open(artifact) as f:
        art = json.load(f)
    assert art["ok"] is True and art["rc"] == 0
    assert art["bench"]["metric"] == "llm_serving_spec"


# ---------------------------------------------------------------------------
def test_mixed_bench_smoke(tmp_path):
    """benchmarks/bench_serving.py --mixed runs end to end on tiny
    parameters and passes its own gates: token-exact vs the serial
    (unmixable) engine, >= 1 genuinely mixed step, zero leaked pages,
    zero post-warmup compiles, and a warmup family strictly below the
    retired per-phase grid's golden count — with warmup_ms /
    compile_count embedded in the artifact."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    artifact = str(tmp_path / "BENCH_mixed.json")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    rc = subprocess.run(
        [sys.executable,
         os.path.join(repo, "benchmarks", "bench_serving.py"),
         "--mixed", "--requests", "6", "--max-new", "6",
         "--max-batch", "4", "--token-budget", "16",
         "--artifact", artifact],
        capture_output=True, text=True, timeout=300, env=env, cwd=repo)
    assert rc.returncode == 0, rc.stderr[-1500:]
    row = json.loads(rc.stdout.strip().splitlines()[-1])
    assert row["metric"] == "llm_serving_mixed"
    assert row["token_exact"] is True
    assert row["mixed_steps"] >= 1
    assert row["baseline_mixed_steps"] == 0
    assert row["leaked_pages"] == 0
    assert row["new_compiles"] == 0
    assert row["compile_count"] < row["old_golden_compile_count"]
    # the per-bucket warmup timing satellite: every compiled bucket
    # reports a wall-clock figure in every artifact
    assert set(row["warmup_ms"]) == {"ragged[8]", "ragged[16]"}
    assert all(v > 0 for v in row["warmup_ms"].values())
    with open(artifact) as f:
        art = json.load(f)
    assert art["ok"] is True and art["rc"] == 0
    assert art["bench"]["metric"] == "llm_serving_mixed"
    assert art["bench"]["compile_count"] == 2


# ---------------------------------------------------------------------------
class _SlowStubEngine:
    """LLMEngine-shaped stub whose step() blocks until released — probes
    AsyncLLMEngine's locking without any device work."""

    def __init__(self):
        self.step_started = threading.Event()
        self.release_step = threading.Event()
        self.step_done = threading.Event()
        self._pending = []
        self._next = 0

    def add_request(self, prompt_ids, **kwargs):
        rid = self._next
        self._next += 1
        self._pending.append(rid)
        return rid

    def has_unfinished(self):
        return bool(self._pending)

    def step(self):
        import types

        self.step_started.set()
        assert self.release_step.wait(timeout=30)
        fin = [types.SimpleNamespace(request_id=r) for r in self._pending]
        self._pending = []
        self.step_done.set()
        return fin


class TestAsyncEngineLocking:
    def test_submit_during_slow_step_returns_before_step_ends(self):
        import time

        from paddle_tpu.inference.llm import AsyncLLMEngine

        stub = _SlowStubEngine()
        a = AsyncLLMEngine(stub)
        try:
            r1 = a.submit([1, 2, 3])
            assert stub.step_started.wait(timeout=10)
            # the loop thread is now INSIDE engine.step() and will stay
            # there until released; a submit must not block on it
            t0 = time.monotonic()
            r2 = a.submit([4, 5])
            submit_s = time.monotonic() - t0
            assert not stub.step_done.is_set()   # step still in flight
            assert submit_s < 1.0
            stub.release_step.set()
            assert a.result(r1, timeout=10).request_id == r1
            # r2 was admitted mid-step; the stub's next step finishes it
            assert a.result(r2, timeout=10).request_id == r2
        finally:
            stub.release_step.set()
            a.stop()


# ---------------------------------------------------------------------------
class TestServingDelegation:
    """PredictorServer(engine=...) serves generation over the socket
    protocol; concurrent connections batch inside the engine."""

    @staticmethod
    def _query(port, ids, max_new):
        from paddle_tpu.inference.serving import (
            _recv_exact,
            _recv_tensor,
            _send_tensor,
        )

        s = socket.create_connection(("127.0.0.1", port))
        try:
            s.sendall(struct.pack("<I", 2))
            _send_tensor(s, np.asarray(ids, np.int64))
            _send_tensor(s, np.asarray(max_new, np.int64))
            status, n_out = struct.unpack("<BI", _recv_exact(s, 5))
            assert status == 0, _recv_exact(s, n_out).decode()
            return [_recv_tensor(s) for _ in range(n_out)][0]
        finally:
            s.close()

    def test_concurrent_clients_token_exact(self):
        from paddle_tpu.inference.llm import LLMEngine
        from paddle_tpu.inference.serving import PredictorServer

        m = _make_model()
        rng = np.random.RandomState(3)
        prompts = [rng.randint(0, 128, (n,)).astype(np.int32)
                   for n in (3, 7, 12)]
        refs = _fmt_reference(m, prompts, max_new=8)
        eng = LLMEngine(m, block_size=8, max_batch=4, max_model_len=64)
        srv = PredictorServer(engine=eng)
        try:
            results = [None] * len(prompts)

            def worker(i):
                results[i] = self._query(srv.port, prompts[i], 8)

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(len(prompts))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
        finally:
            srv.stop()
        for got, ref in zip(results, refs):
            assert got is not None
            np.testing.assert_array_equal(got[0], ref)
        assert eng.block_manager.num_free_blocks == eng.num_blocks

    def test_requires_exactly_one_backend(self):
        from paddle_tpu.inference.serving import PredictorServer

        with pytest.raises(ValueError, match="exactly one"):
            PredictorServer()

    def test_socket_sampling_seed_is_reproducible(self):
        from paddle_tpu.inference.llm import LLMEngine
        from paddle_tpu.inference.serving import (
            PredictorServer,
            _recv_exact,
            _recv_tensor,
            _send_tensor,
        )

        def query(port, ids, max_new, temperature, seed):
            s = socket.create_connection(("127.0.0.1", port))
            try:
                s.sendall(struct.pack("<I", 4))
                _send_tensor(s, np.asarray(ids, np.int64))
                _send_tensor(s, np.asarray(max_new, np.int64))
                _send_tensor(s, np.asarray(temperature, np.float32))
                _send_tensor(s, np.asarray(seed, np.int64))
                status, n_out = struct.unpack("<BI", _recv_exact(s, 5))
                assert status == 0, _recv_exact(s, n_out).decode()
                return [_recv_tensor(s) for _ in range(n_out)][0]
            finally:
                s.close()

        m = _make_model()
        prompt = np.array([9, 10, 11], np.int64)
        eng = LLMEngine(m, block_size=8, max_batch=4, max_model_len=64)
        srv = PredictorServer(engine=eng)
        try:
            # same wire seed -> same sampled completion, every time
            a = query(srv.port, prompt, 10, 0.8, 77)
            b = query(srv.port, prompt, 10, 0.8, 77)
            c = query(srv.port, prompt, 10, 0.8, 78)
        finally:
            srv.stop()
        np.testing.assert_array_equal(a, b)
        assert not np.array_equal(a, c)     # different seed diverges


# ---------------------------------------------------------------------------
def test_shared_prefix_bench_smoke():
    """benchmarks/bench_serving.py --shared-prefix runs end to end on
    tiny parameters, emits parseable JSON, and actually hits the prefix
    cache (throughput/TTFT claims are the slow-tier / PERF.md job —
    at this scale the numbers are noise, only the plumbing is tested)."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    rc = subprocess.run(
        [sys.executable,
         os.path.join(repo, "benchmarks", "bench_serving.py"),
         "--shared-prefix", "--requests", "4", "--prefix-len", "16",
         "--max-new", "4", "--max-batch", "2"],
        capture_output=True, text=True, timeout=300, env=env, cwd=repo)
    assert rc.returncode == 0, rc.stderr[-1500:]
    row = json.loads(rc.stdout.strip().splitlines()[-1])
    assert row["metric"] == "llm_serving_shared_prefix"
    assert row["value"] > 0
    assert row["vs_baseline"] is not None
    assert row["hit_rate"] > 0.3
    assert row["reused_blocks"] > 0
    assert row["preemptions"] == 0


# ---------------------------------------------------------------------------
def test_tp_bench_smoke(tmp_path):
    """benchmarks/bench_serving.py --tp 2 runs end to end on 2 virtual
    CPU devices (the bench forces the device count itself — no conftest
    help in the subprocess), asserts its own token-exactness gate, and
    emits the MULTICHIP-style artifact."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    artifact = str(tmp_path / "MULTICHIP_serving.json")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)          # the bench must set this itself
    rc = subprocess.run(
        [sys.executable,
         os.path.join(repo, "benchmarks", "bench_serving.py"),
         "--tp", "2", "--requests", "4", "--max-new", "4",
         "--artifact", artifact],
        capture_output=True, text=True, timeout=300, env=env, cwd=repo)
    assert rc.returncode == 0, rc.stderr[-1500:]
    row = json.loads(rc.stdout.strip().splitlines()[-1])
    assert row["metric"] == "llm_serving_tp"
    assert row["tp"] == 2 and row["n_devices"] == 2
    assert row["token_exact"] is True
    assert row["value"] > 0
    with open(artifact) as f:
        art = json.load(f)
    assert art["ok"] is True and art["rc"] == 0
    assert art["n_devices"] == 2 and art["skipped"] is False
    assert "serving_tp(2)" in art["tail"]


# ---------------------------------------------------------------------------
@pytest.mark.slow
class TestServingSoak:
    """Nightly-style soak: a Poisson-ish wave of mixed requests through
    a deliberately small pool — heavy preemption, zero leaks, every
    request token-exact vs the dense reference."""

    def test_soak_token_exact_no_leaks(self):
        from paddle_tpu.inference.llm import LLMEngine

        m = _make_model(num_layers=3)
        rng = np.random.RandomState(4)
        n_requests = 24
        prompts = [rng.randint(0, 128, (int(rng.randint(2, 14)),))
                   .astype(np.int32) for _ in range(n_requests)]
        max_new = [int(rng.randint(2, 12)) for _ in range(n_requests)]
        fmt_refs = {}
        from paddle_tpu.incubate.nn import FusedMultiTransformer

        fmt = FusedMultiTransformer(m, max_length=64)
        for i, p in enumerate(prompts):
            fmt_refs[i] = fmt.generate(p[None],
                                       max_new_tokens=max_new[i])[0]

        eng = LLMEngine(m, block_size=8, num_blocks=10, max_batch=4,
                        max_model_len=40)
        pending = list(range(n_requests))
        rid_to_i, outs = {}, {}
        while pending or eng.has_unfinished():
            # staggered arrivals: a couple of new requests per step
            for _ in range(2):
                if pending:
                    i = pending.pop(0)
                    rid = eng.add_request(prompts[i],
                                          max_new_tokens=max_new[i])
                    rid_to_i[rid] = i
            for fo in eng.step():
                outs[rid_to_i[fo.request_id]] = fo.all_ids
        for i in range(n_requests):
            np.testing.assert_array_equal(outs[i], fmt_refs[i])
        assert eng.block_manager.num_free_blocks == eng.num_blocks
