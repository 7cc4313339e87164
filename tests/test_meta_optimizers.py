"""Meta-optimizer strategies, hapi callbacks/flops, TensorArray, amp
debugging, sparse 3D, auto-parallel tuner.

Reference targets: fleet/meta_optimizers/ (gradient_merge, localsgd, dgc,
lars/lamb), hapi callbacks + dynamic_flops, phi TensorArray,
amp/debugging.py, sparse conv kernels, auto_parallel static/cost + tuner
+ mapper.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer


def _model_and_data():
    paddle.seed(0)
    m = nn.Linear(4, 1)
    x = paddle.to_tensor(np.random.RandomState(0).rand(8, 4)
                         .astype(np.float32))
    return m, x


class TestMetaOptimizers:
    def test_gradient_merge_applies_every_k(self):
        from paddle_tpu.distributed.fleet.meta_optimizers import (
            GradientMergeOptimizer,
        )

        m, x = _model_and_data()
        inner = optimizer.SGD(learning_rate=0.1,
                              parameters=m.parameters())
        opt = GradientMergeOptimizer(inner, k_steps=2, avg=True)
        w0 = m.weight.numpy().copy()
        (m(x) ** 2).mean().backward()
        opt.step()
        opt.clear_grad()
        np.testing.assert_array_equal(m.weight.numpy(), w0)  # not yet
        g1 = m.weight.grad.numpy().copy()  # grads kept accumulating
        (m(x) ** 2).mean().backward()
        assert not np.allclose(m.weight.grad.numpy(), g1 * 0)
        opt.step()
        opt.clear_grad()
        assert not np.allclose(m.weight.numpy(), w0)  # applied at k=2
        assert m.weight.grad is None or \
            np.allclose(m.weight.grad.numpy(), 0)

    def test_gradient_merge_avg_matches_big_batch(self):
        from paddle_tpu.distributed.fleet.meta_optimizers import (
            GradientMergeOptimizer,
        )

        rng = np.random.RandomState(1)
        xs = rng.rand(4, 8, 4).astype(np.float32)

        def run_merged():
            paddle.seed(3)
            m = nn.Linear(4, 1)
            opt = GradientMergeOptimizer(
                optimizer.SGD(learning_rate=0.1,
                              parameters=m.parameters()),
                k_steps=4, avg=True)
            for i in range(4):
                (m(paddle.to_tensor(xs[i])) ** 2).mean().backward()
                opt.step()
                opt.clear_grad()
            return m.weight.numpy()

        def run_big():
            paddle.seed(3)
            m = nn.Linear(4, 1)
            opt = optimizer.SGD(learning_rate=0.1,
                                parameters=m.parameters())
            loss = sum((m(paddle.to_tensor(xs[i])) ** 2).mean()
                       for i in range(4)) / 4.0
            loss.backward()
            opt.step()
            return m.weight.numpy()

        np.testing.assert_allclose(run_merged(), run_big(), rtol=1e-5)

    def test_dgc_sparsifies_with_error_feedback(self):
        from paddle_tpu.distributed.fleet.meta_optimizers import (
            DGCMomentumOptimizer,
        )

        paddle.seed(0)
        m = nn.Linear(16, 16, bias_attr=False)
        opt = DGCMomentumOptimizer(
            optimizer.SGD(learning_rate=1.0, parameters=m.parameters()),
            sparsity=0.75)
        x = paddle.to_tensor(np.random.rand(4, 16).astype(np.float32))
        w0 = m.weight.numpy().copy()
        (m(x) ** 2).mean().backward()
        opt.step()
        delta = m.weight.numpy() - w0
        # at most ~25% of entries moved this step
        moved = (np.abs(delta) > 0).mean()
        assert moved <= 0.30, moved
        # unsent velocity exists and feeds back
        assert opt._v and any(
            np.abs(np.asarray(r)).sum() > 0 for r in opt._v.values())

    def test_dgc_momentum_correction_delayed_coordinate_algebra(self):
        """Lin et al. momentum correction (the property the residual-only
        form lacked): a coordinate delayed n steps under constant grad g
        accumulates v = sum of momentum-corrected u terms — for m=0.9,
        3 steps: v = 3 + 2m + m^2 = 5.61g, not the residual form's 3g.
        Sent coordinates restart (u cleared), so the hot coordinate
        ships exactly g every step."""
        from paddle_tpu.core.tensor import Tensor
        from paddle_tpu.distributed.fleet.meta_optimizers import (
            DGCMomentumOptimizer,
        )
        import jax.numpy as jnp

        paddle.seed(1)
        m1 = nn.Linear(1, 2, bias_attr=False)  # weight [1, 2]
        dgc = DGCMomentumOptimizer(
            optimizer.SGD(learning_rate=1.0, parameters=m1.parameters()),
            sparsity=0.5, momentum=0.9)
        p = m1.parameters()[0]
        w0 = p.numpy().copy()
        g = np.array([[10.0, 1.0]], np.float32)
        sent_hot = []
        for _ in range(3):
            p.grad = Tensor(jnp.asarray(g), stop_gradient=True)
            dgc.step()
            sent_hot.append(float(np.asarray(p.grad._data)[0, 0]))
            dgc.clear_grad()
        # hot coordinate restarts every send: ships exactly g each step
        np.testing.assert_allclose(sent_hot, [10.0, 10.0, 10.0])
        # delayed coordinate: v = (1) + (1 + (1+m)) + ... = 3 + 2m + m^2
        m = 0.9
        v_cold = float(np.asarray(dgc._v[id(p)])[0, 1])
        np.testing.assert_allclose(v_cold, 3 + 2 * m + m ** 2, rtol=1e-5)
        # cold coordinate untouched in the weights; hot moved 3*lr*g
        delta = p.numpy() - w0
        np.testing.assert_allclose(delta[0, 0], -30.0, rtol=1e-5)
        np.testing.assert_allclose(delta[0, 1], 0.0, atol=1e-7)

    def test_dgc_sent_positions_restart_momentum(self):
        """Momentum factor masking: a coordinate that was just sent has
        cleared u and v buffers."""
        from paddle_tpu.distributed.fleet.meta_optimizers import (
            DGCMomentumOptimizer,
        )

        paddle.seed(2)
        m = nn.Linear(6, 6, bias_attr=False)
        opt = DGCMomentumOptimizer(
            optimizer.SGD(learning_rate=0.5, parameters=m.parameters()),
            sparsity=0.8, momentum=0.9)
        x = paddle.to_tensor(np.random.RandomState(1)
                             .rand(3, 6).astype(np.float32))
        (m(x) ** 2).mean().backward()
        opt.step()
        p = m.parameters()[0]
        sent_mask = np.abs(np.asarray(p.grad._data)) > 0
        u = np.asarray(opt._u[id(p)])
        v = np.asarray(opt._v[id(p)])
        assert (u[sent_mask] == 0).all()
        assert (v[sent_mask] == 0).all()
        assert (np.abs(v[~sent_mask]) > 0).any()  # delayed coords keep v

    def test_strategy_dgc_replaces_momentum_inner(self):
        """Review regression: wrapping a Momentum inner would apply
        momentum twice — the compiler swaps it for SGD and inherits its
        coefficient (reference dgc_optimizer replaces Momentum)."""
        from paddle_tpu.distributed.fleet import DistributedStrategy
        from paddle_tpu.distributed.fleet.meta_optimizers import (
            DGCMomentumOptimizer,
            apply_strategy_to_optimizer,
        )

        m, _ = _model_and_data()
        s = DistributedStrategy()
        s.dgc = True
        opt = apply_strategy_to_optimizer(
            optimizer.Momentum(learning_rate=0.1, momentum=0.8,
                               parameters=m.parameters()), s)
        assert isinstance(opt, DGCMomentumOptimizer)
        assert type(opt._inner).__name__ == "SGD"
        assert opt.momentum == 0.8  # inherited from the swapped Momentum

    def test_strategy_compiler_stacks_wrappers(self):
        from paddle_tpu.distributed.fleet import DistributedStrategy
        from paddle_tpu.distributed.fleet.meta_optimizers import (
            GradientMergeOptimizer,
            apply_strategy_to_optimizer,
        )

        m, _ = _model_and_data()
        s = DistributedStrategy()
        s.lamb = True
        s.gradient_merge = True
        s.gradient_merge_configs = {"k_steps": 2, "avg": True}
        opt = apply_strategy_to_optimizer(
            optimizer.SGD(learning_rate=0.1, parameters=m.parameters()), s)
        assert isinstance(opt, GradientMergeOptimizer)
        assert type(opt._inner).__name__ == "Lamb"

    def test_lars_trains(self):
        paddle.seed(0)
        m = nn.Linear(4, 1)
        opt = optimizer.Lars(learning_rate=1.0, lars_coeff=0.1,
                             parameters=m.parameters())
        x = paddle.to_tensor(
            np.random.RandomState(7).rand(16, 4).astype(np.float32))
        losses = []
        for _ in range(50):
            loss = ((m(x) - 1.0) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.numpy()))
        assert losses[-1] < 0.5 * losses[0]

    def test_recompute_wrapper_preserves_forward(self):
        from paddle_tpu.distributed.fleet import DistributedStrategy
        from paddle_tpu.distributed.fleet.meta_optimizers import (
            apply_recompute_to_model,
        )

        paddle.seed(0)
        m = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 1))
        x = paddle.to_tensor(np.random.rand(2, 4).astype(np.float32),
                             stop_gradient=False)
        ref = m(x).numpy()
        s = DistributedStrategy()
        s.recompute = True
        m2 = apply_recompute_to_model(m, s)
        out = m2(x)
        np.testing.assert_allclose(out.numpy(), ref, rtol=1e-6)
        out.sum().backward()  # grads flow through the recompute wrapper
        assert x.grad is not None


class TestHapiDepth:
    def test_reduce_lr_on_plateau(self):
        from paddle_tpu.hapi import ReduceLROnPlateau

        m, _ = _model_and_data()
        opt = optimizer.SGD(learning_rate=0.1, parameters=m.parameters())

        class FakeModel:
            _optimizer = opt

        cb = ReduceLROnPlateau(monitor="loss", factor=0.5, patience=1,
                               verbose=0)
        cb.set_model(FakeModel())
        cb.on_eval_end({"loss": 1.0})
        cb.on_eval_end({"loss": 1.0})   # wait=1
        cb.on_eval_end({"loss": 1.0})   # wait=2 > patience -> reduce
        assert abs(opt.get_lr() - 0.05) < 1e-9

    def test_visualdl_writes_scalars(self, tmp_path):
        import json

        from paddle_tpu.hapi import VisualDL

        cb = VisualDL(log_dir=str(tmp_path))
        cb.on_train_begin()
        cb.on_train_batch_end(0, {"loss": 1.5})
        cb.on_eval_end({"loss": 1.2})
        cb.on_train_end()
        lines = [json.loads(ln) for ln in
                 open(tmp_path / "scalars.jsonl")]
        tags = {l["tag"] for l in lines}
        assert "train/loss" in tags and "eval/loss" in tags

    def test_flops_from_xla(self):
        m = nn.Linear(64, 32)
        f = paddle.flops(m, (8, 64))
        assert f >= 2 * 8 * 64 * 32

    def test_throughput_monitor(self):
        from paddle_tpu.hapi import ThroughputMonitor

        cb = ThroughputMonitor(batch_size=32, log_freq=1000, verbose=0)
        cb.on_epoch_begin(0)
        for i in range(5):
            cb.on_train_batch_end(i, {})
        assert cb.samples_per_sec > 0 and cb.avg_step_ms > 0


class TestTensorArray:
    def test_write_read_length(self):
        arr = paddle.create_array()
        t = paddle.to_tensor(np.ones(3, np.float32))
        paddle.array_write(t, 0, arr)
        paddle.array_write(t * 2, 2, arr)
        assert paddle.array_length(arr) == 3
        np.testing.assert_allclose(paddle.array_read(arr, 2).numpy(),
                                   2 * np.ones(3))

    def test_traced_index_raises(self):
        from paddle_tpu.jit import to_static

        arr = paddle.create_array()

        @to_static
        def f(i):
            return paddle.array_write(i, i, arr)

        with pytest.raises(Exception):
            f(paddle.to_tensor(np.int32(0)))


class TestAmpDebugging:
    def test_tensor_checker_aborts_on_nan(self):
        from paddle_tpu.amp import debugging as dbg

        cfg = dbg.TensorCheckerConfig(enable=True)
        dbg.enable_tensor_checker(cfg)
        try:
            zero = paddle.to_tensor(np.zeros(2, np.float32))
            with pytest.raises(FloatingPointError):
                _ = paddle.to_tensor(np.ones(2, np.float32)) / zero
        finally:
            dbg.disable_tensor_checker()

    def test_skipped_op_list(self):
        from paddle_tpu.amp import debugging as dbg

        cfg = dbg.TensorCheckerConfig(enable=True,
                                      skipped_op_list=["divide"])
        dbg.enable_tensor_checker(cfg)
        try:
            zero = paddle.to_tensor(np.zeros(2, np.float32))
            out = paddle.to_tensor(np.ones(2, np.float32)) / zero
            assert np.isinf(out.numpy()).all()
        finally:
            dbg.disable_tensor_checker()

    def test_collect_operator_stats(self, capsys):
        from paddle_tpu.amp import debugging as dbg

        x = paddle.to_tensor(np.random.rand(4, 4).astype(np.float32))
        with dbg.collect_operator_stats():
            _ = paddle.matmul(x, x)
            _ = x + x
        printed = capsys.readouterr().out
        assert "matmul" in printed and "float32" in printed


class TestSparse3D:
    def test_subm_conv_keeps_sites_and_matches_dense(self):
        from paddle_tpu import sparse
        from paddle_tpu.core.tensor import Tensor

        rng = np.random.RandomState(0)
        D = 5
        sites = rng.choice(D * D * D, 10, replace=False)
        coords = np.stack(np.unravel_index(sites, (D, D, D)), 1)
        idx4 = np.concatenate([np.zeros((10, 1), np.int64), coords], 1)
        vals = rng.rand(10, 2).astype(np.float32)
        st = sparse.sparse_coo_tensor(idx4.T, Tensor(np.asarray(vals)))

        conv = sparse.nn.SubmConv3D(2, 3, kernel_size=3, bias_attr=False)
        out = conv(st)
        assert out.nnz == 10  # submanifold: sparsity unchanged

        dense = np.zeros((D, D, D, 2), np.float32)
        for c, v in zip(coords, vals):
            dense[tuple(c)] = v
        w = np.asarray(conv.weight.numpy())
        out_idx = np.asarray(out.indices().numpy()).T
        out_vals = out.values().numpy()
        order = {tuple(c): i for i, c in enumerate(out_idx)}
        for r, c in enumerate(idx4):
            acc = np.zeros(3, np.float32)
            k = 0
            for dz in range(3):
                for dy in range(3):
                    for dx in range(3):
                        z, y, x = c[1] + dz - 1, c[2] + dy - 1, c[3] + dx - 1
                        if 0 <= z < D and 0 <= y < D and 0 <= x < D:
                            acc += dense[z, y, x] @ w[k]
                        k += 1
            np.testing.assert_allclose(out_vals[order[tuple(c)]], acc,
                                       rtol=1e-4, atol=1e-5)

    def test_full_conv_dilates_and_pool_reduces(self):
        from paddle_tpu import sparse
        from paddle_tpu.core.tensor import Tensor

        idx4 = np.array([[0, 2, 2, 2]], np.int64)
        vals = np.ones((1, 1), np.float32)
        st = sparse.sparse_coo_tensor(idx4.T, Tensor(vals),
                                      shape=(1, 5, 5, 5, 1))
        conv = sparse.nn.Conv3D(1, 1, kernel_size=3, padding=1,
                                bias_attr=False)
        out = conv(st)
        assert out.nnz == 27  # one site dilates to its 3x3x3 support

        pool = sparse.nn.MaxPool3D(2)
        pooled = pool(out)
        assert pooled.nnz < out.nnz


class TestParallelTuner:
    def _estimator(self, n_dev=8, hbm=16e9):
        from paddle_tpu.distributed.auto_parallel import (
            ClusterSpec,
            CostEstimator,
        )

        # pin v5p-class constants: these tests probe the MODEL's behavior
        # under a known scenario, not this host's detected capabilities
        cluster = ClusterSpec(num_devices=n_dev, hbm_bytes=hbm,
                              flops_bf16=459e12, ici_bandwidth=9.8e10)
        return CostEstimator(cluster, n_params=1.3e9,
                             flops_per_token=6 * 1.3e9,
                             tokens_per_batch=8 * 2048,
                             hidden_size=2048, num_layers=24)

    def test_tuner_respects_memory_limit(self):
        from paddle_tpu.distributed.auto_parallel import ParallelTuner

        est = self._estimator(hbm=8e9)  # tight: dp=8 pure won't fit
        best = ParallelTuner(est).tune()
        assert est.memory_bytes(
            best["dp"], best["mp"], best["pp"],
            recompute=best["recompute"], sp=best["sp"],
            n_micro=best["n_micro"],
            virtual_pp=best["virtual_pp"]) <= 8e9
        assert best["dp"] * best["mp"] * best["pp"] == 8

    def test_tuner_prefers_pure_dp_for_small_models(self):
        from paddle_tpu.distributed.auto_parallel import (
            ClusterSpec,
            CostEstimator,
            ParallelTuner,
        )

        # small model: dp grad-allreduce is negligible, mp/pp only add
        # activation comm and bubble — pure dp must win
        cluster = ClusterSpec(num_devices=8, hbm_bytes=1e12)
        est = CostEstimator(cluster, n_params=1e6,
                            flops_per_token=6e6,
                            tokens_per_batch=8 * 2048,
                            hidden_size=256, num_layers=4)
        best = ParallelTuner(est).tune()
        assert best["mp"] == 1 and best["pp"] == 1 and not best["recompute"]

    def test_tuner_offloads_to_pp_when_dp_comm_dominates(self):
        from paddle_tpu.distributed.auto_parallel import ParallelTuner

        # 1.3B params on 8 chips with a small batch: per-step gradient
        # allreduce dwarfs compute, so the tuner should pick pp/mp > 1
        est = self._estimator(hbm=1e12)
        best = ParallelTuner(est).tune()
        assert best["mp"] * best["pp"] > 1

    def test_too_big_model_raises(self):
        from paddle_tpu.distributed.auto_parallel import ParallelTuner

        est = self._estimator(hbm=1e6)
        with pytest.raises(RuntimeError, match="HBM"):
            ParallelTuner(est).tune()

    def test_cluster_spec_calibrates_from_device(self):
        """ClusterSpec() without overrides reads the attached device kind;
        unknown kinds (this CPU mesh) get measured-matmul flops instead of
        fictional v5p constants (round-2 verdict weak #8)."""
        from paddle_tpu.distributed.auto_parallel import ClusterSpec
        from paddle_tpu.distributed.auto_parallel.tuner import _DEVICE_KINDS

        c = ClusterSpec()
        assert c.device_kind  # detected, not assumed
        assert c.flops_bf16 > 0
        if c.device_kind.lower() not in _DEVICE_KINDS:
            # measured on this host: a laptop-class CPU does 1e9..1e14
            assert 1e8 < c.flops_bf16 < 1e15
        assert c.hbm_bytes > 0

    def test_search_space_includes_sp_micro_vpp(self):
        from paddle_tpu.distributed.auto_parallel import ParallelTuner

        est = self._estimator(hbm=1e12)
        cands = ParallelTuner(est).candidates()
        assert any(c["sp"] for c in cands if c["mp"] > 1)
        assert any(c["n_micro"] > 1 for c in cands if c["pp"] > 1)
        assert any(c["virtual_pp"] > 1 for c in cands if c["pp"] > 1)
        # vpp divides layers/pp; microbatches divide the dp batch
        for c in cands:
            if c["pp"] > 1:
                assert est.layers % (c["pp"] * c["virtual_pp"]) == 0
                assert est.tokens_per_batch % (c["dp"] * c["n_micro"]) == 0

    def test_gpt124m_pick_is_sane_and_refine_measures(self):
        """GPT-124M on the 8-device virtual mesh: analytic pick must be a
        valid factorization that fits, and the measured refinement returns
        finite step times for buildable candidates (reference
        profile-based OptimizationTuner loop)."""
        import jax

        from paddle_tpu.distributed.auto_parallel import (
            ClusterSpec,
            CostEstimator,
            ParallelTuner,
        )
        from paddle_tpu import optimizer
        from paddle_tpu.models.gpt import gpt_tiny

        n_params = 124e6
        cluster = ClusterSpec(num_devices=8)
        est = CostEstimator(cluster, n_params=n_params,
                            flops_per_token=6 * n_params,
                            tokens_per_batch=8 * 128,
                            hidden_size=768, num_layers=12)
        tuner = ParallelTuner(est, micro_options=(1, 2), vpp_options=(1,))
        best = tuner.tune()
        assert best["dp"] * best["mp"] * best["pp"] == 8
        assert best["est_memory"] <= cluster.hbm_bytes
        # 124M at 1k tokens/device is small: no recompute needed
        assert not best["recompute"]

        # measured refinement on a REAL tiny model (the cost inputs above
        # describe 124M; timing uses gpt_tiny to keep CI fast — the loop
        # exercises build/compile/measure/re-rank end to end)
        est_tiny = CostEstimator(cluster, n_params=1e6,
                                 flops_per_token=6e6,
                                 tokens_per_batch=8 * 32,
                                 hidden_size=64, num_layers=4)
        tuner = ParallelTuner(est_tiny, mp_limit=2, pp_limit=2,
                              micro_options=(1, 2), vpp_options=(1,))

        import paddle_tpu as paddle

        def batch_factory(cand):
            rng = np.random.RandomState(0)
            ids = rng.randint(0, 128, (8, 32)).astype(np.int32)
            return paddle.to_tensor(ids), paddle.to_tensor(ids)

        results = tuner.refine(
            model_factory=lambda: gpt_tiny(num_layers=4),
            optimizer_factory=lambda m: optimizer.AdamW(
                learning_rate=1e-3, parameters=m.parameters()),
            batch_factory=batch_factory, top_k=2, steps=1)
        assert len(results) == 2
        ok = [r for r in results if np.isfinite(r["measured_step_time"])]
        assert ok, results  # at least one candidate built and timed
        assert results == sorted(results,
                                 key=lambda r: r["measured_step_time"])

        # review regression: top_k=1 (tune returns a bare dict) must work
        one = tuner.refine(
            model_factory=lambda: gpt_tiny(num_layers=4),
            optimizer_factory=lambda m: optimizer.AdamW(
                learning_rate=1e-3, parameters=m.parameters()),
            batch_factory=batch_factory, top_k=1, steps=1)
        assert len(one) == 1 and "dp" in one[0]

    def test_mapper_builds_mesh(self):
        from paddle_tpu.distributed.auto_parallel import Mapper

        mesh = Mapper().build_mesh(dp=2, mp=2, pp=2)
        assert mesh.axis_names == ("dp", "pp", "mp")
        assert mesh.devices.shape == (2, 2, 2)
        with pytest.raises(ValueError):
            Mapper().build_mesh(dp=3, mp=1, pp=1)


class TestRound4MetaOptimizers:
    def test_adaptive_localsgd_schedule_follows_reference_formula(self):
        """Reference adaptive schedule (localsgd_optimizer.py
        AdaptiveLocalSGD): next_k = clip(ceil(sqrt(lr0*loss /
        (lr*loss0) * init_k)), 1, 16).  With loss == loss0 at fixed lr
        the first sync sets k = ceil(sqrt(init_k)); a 16x loss drop
        then drives k to 1."""
        from paddle_tpu.distributed.fleet.meta_optimizers import (
            AdaptiveLocalSGDOptimizer,
        )

        m, x = _model_and_data()
        opt = optimizer.SGD(learning_rate=0.1,
                            parameters=m.parameters())
        a = AdaptiveLocalSGDOptimizer(opt, init_k_steps=16, begin_step=1)

        def run(lv, n):
            for _ in range(n):
                out = m(x)
                loss = (out * 0.0).sum() + lv  # controlled loss value
                loss.backward()
                a.step(loss=loss)
                a.clear_grad()

        run(4.0, 16)       # pins loss0=4, lr0=0.1; sync at step 16
        # ratio 1.0 -> k = ceil(sqrt(1 * 16)) = 4
        assert a.k_steps == 4, a.k_steps
        run(0.25, 4)       # next sync: ratio 1/16 -> ceil(sqrt(1)) = 1
        assert a.k_steps == 1, a.k_steps
        run(400.0, 1)      # loss blowup: ratio 100 -> sqrt(1600)=40,
        assert a.k_steps == 16  # clipped to the max of 16

    def test_adaptive_localsgd_strategy_wiring(self):
        from paddle_tpu.distributed.fleet import DistributedStrategy
        from paddle_tpu.distributed.fleet.meta_optimizers import (
            AdaptiveLocalSGDOptimizer,
            apply_strategy_to_optimizer,
        )

        m, _ = _model_and_data()
        opt = optimizer.SGD(learning_rate=0.1,
                            parameters=m.parameters())
        s = DistributedStrategy()
        s.adaptive_localsgd = True
        s.adaptive_localsgd_configs = {"init_k_steps": 4}
        wrapped = apply_strategy_to_optimizer(opt, s)
        assert isinstance(wrapped, AdaptiveLocalSGDOptimizer)
        assert wrapped.init_k_steps == 4

    def test_asp_strategy_keeps_pruned_weights_pruned(self):
        from paddle_tpu.distributed.fleet import DistributedStrategy
        from paddle_tpu.distributed.fleet.meta_optimizers import (
            apply_strategy_to_optimizer,
        )
        from paddle_tpu.incubate.asp import calculate_density, prune_model

        paddle.seed(0)
        m = nn.Linear(8, 8)
        prune_model(m)   # 2:4 masks
        opt = optimizer.SGD(learning_rate=0.1,
                            parameters=m.parameters())
        s = DistributedStrategy()
        s.asp = True
        wrapped = apply_strategy_to_optimizer(opt, s)
        x = paddle.to_tensor(np.random.RandomState(1)
                             .rand(4, 8).astype(np.float32))
        for _ in range(3):
            loss = (m(x) ** 2).sum()
            loss.backward()
            wrapped.step()
            wrapped.clear_grad()
        # density stays exactly 0.5: the strategy-wired optimizer
        # re-applies the masks after every step
        assert abs(calculate_density(m.weight.numpy()) - 0.5) < 1e-6

    def test_asp_over_adaptive_localsgd_composes(self):
        """Review regression: the ASP wrapper must pass step(loss=...)
        through to AdaptiveLocalSGD underneath."""
        from paddle_tpu.distributed.fleet import DistributedStrategy
        from paddle_tpu.distributed.fleet.meta_optimizers import (
            apply_strategy_to_optimizer,
        )
        from paddle_tpu.incubate.asp import calculate_density, prune_model

        paddle.seed(0)
        m = nn.Linear(8, 8)
        prune_model(m)
        opt = optimizer.SGD(learning_rate=0.1,
                            parameters=m.parameters())
        s = DistributedStrategy()
        s.asp = True
        s.adaptive_localsgd = True
        wrapped = apply_strategy_to_optimizer(opt, s)
        x = paddle.to_tensor(np.random.RandomState(1)
                             .rand(4, 8).astype(np.float32))
        for _ in range(3):
            loss = (m(x) ** 2).sum()
            loss.backward()
            wrapped.step(loss=loss)   # must not TypeError
            wrapped.clear_grad()
        assert abs(calculate_density(m.weight.numpy()) - 0.5) < 1e-6

    def test_fp16_allreduce_quantizes_grads_before_step(self):
        """The wrapper must round-trip gradients through fp16 (the wire
        format): a value that fp16 can't represent exactly shows the
        quantization, and the strategy compiler wires it."""
        from paddle_tpu.core.tensor import Tensor
        from paddle_tpu.distributed.fleet import DistributedStrategy
        from paddle_tpu.distributed.fleet.meta_optimizers import (
            FP16AllReduceOptimizer,
            apply_strategy_to_optimizer,
        )
        import jax.numpy as jnp

        m, _ = _model_and_data()
        s = DistributedStrategy()
        s.fp16_allreduce = True
        opt = apply_strategy_to_optimizer(
            optimizer.SGD(learning_rate=1.0, parameters=m.parameters()),
            s)
        assert isinstance(opt, FP16AllReduceOptimizer)
        p = m.parameters()[0]
        w0 = p.numpy().copy()
        g = np.full(p.shape, 0.1, np.float32)   # 0.1 is inexact in fp16
        p.grad = Tensor(jnp.asarray(g), stop_gradient=True)
        opt.step()
        applied = w0 - p.numpy()                # = lr * g_after_roundtrip
        fp16_g = np.float32(np.float16(0.1))
        np.testing.assert_allclose(applied, fp16_g, rtol=1e-7)
        assert not np.allclose(applied, 0.1)    # quantization is real

    def test_fp16_allreduce_composition_rules(self):
        """Review regressions: merge wraps fp16 (one quantized allreduce
        per MERGED update, not per micro-step); localsgd + fp16 refused."""
        from paddle_tpu.distributed.fleet import DistributedStrategy
        from paddle_tpu.distributed.fleet.meta_optimizers import (
            FP16AllReduceOptimizer,
            GradientMergeOptimizer,
            apply_strategy_to_optimizer,
        )

        m, _ = _model_and_data()
        s = DistributedStrategy()
        s.fp16_allreduce = True
        s.gradient_merge = True
        s.gradient_merge_configs = {"k_steps": 2, "avg": True}
        opt = apply_strategy_to_optimizer(
            optimizer.SGD(learning_rate=1.0, parameters=m.parameters()),
            s)
        assert isinstance(opt, GradientMergeOptimizer)
        assert isinstance(opt._inner, FP16AllReduceOptimizer)

        s2 = DistributedStrategy()
        s2.fp16_allreduce = True
        s2.localsgd = True
        with pytest.raises(ValueError, match="localsgd"):
            apply_strategy_to_optimizer(
                optimizer.SGD(learning_rate=1.0,
                              parameters=m.parameters()), s2)
