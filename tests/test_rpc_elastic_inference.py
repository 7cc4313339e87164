"""RPC API, elastic relaunch, and inference depth (jit cache, mixed
precision, dist inference, KV-cache fused decode).

Reference targets: python/paddle/distributed/rpc/rpc.py,
fleet/elastic/manager.py (watch->rescale->restart),
inference AnalysisPredictor (+ convert_to_mixed_precision, DistModel),
fused_multi_transformer inference ops.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cpu_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return env


# -------------------------------------------------------------------- rpc --

class TestRpc:
    def test_single_process_rpc(self):
        from paddle_tpu.distributed import rpc

        me = rpc.init_rpc("solo", rank=0, world_size=1)
        try:
            assert me.name == "solo" and me.rank == 0
            assert rpc.rpc_sync("solo", max, args=(3, 7)) == 7
            fut = rpc.rpc_async(0, pow, args=(2, 10))
            assert fut.result(timeout=30) == 1024
            with pytest.raises(ZeroDivisionError):
                rpc.rpc_sync("solo", lambda: 1 / 0)
            infos = rpc.get_all_worker_infos()
            assert len(infos) == 1
        finally:
            rpc.shutdown()

    def test_two_process_rpc(self, tmp_path):
        script = tmp_path / "rpc_worker.py"
        script.write_text(textwrap.dedent(f"""
            import os, sys
            sys.path.insert(0, {REPO!r})
            from paddle_tpu.distributed import rpc

            rank = int(os.environ["PADDLE_TRAINER_ID"])
            rpc.init_rpc(f"worker{{rank}}")
            other = f"worker{{1 - rank}}"
            # remote computation on the peer
            got = rpc.rpc_sync(other, eval, args=("7*6",))
            assert got == 42, got
            # async to self by rank id
            assert rpc.rpc_async(rank, len, args=("abc",)).result(30) == 3
            rpc.shutdown()
            print("RPC RANK", rank, "OK")
        """))
        log_dir = str(tmp_path / "logs")
        rc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "2", "--log_dir", log_dir, str(script)],
            cwd=REPO, capture_output=True, timeout=180, env=_cpu_env())
        assert rc.returncode == 0, rc.stderr.decode()[-1500:]
        for r in range(2):
            with open(os.path.join(log_dir, f"workerlog.{r}")) as f:
                assert f"RPC RANK {r} OK" in f.read()


# ---------------------------------------------------------------- elastic --

class TestElasticRelaunch:
    def test_launcher_relaunches_after_failure(self, tmp_path):
        marker = tmp_path / "attempt"
        script = tmp_path / "flaky.py"
        script.write_text(textwrap.dedent(f"""
            import os, sys
            marker = {str(marker)!r}
            n = int(open(marker).read()) if os.path.exists(marker) else 0
            open(marker, "w").write(str(n + 1))
            restart = os.environ.get("PADDLE_RESTART_COUNT")
            if n == 0:
                print("first attempt: failing (restart", restart, ")")
                sys.exit(3)
            print("second attempt: ok (restart", restart, ")")
        """))
        log_dir = str(tmp_path / "logs")
        rc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "1", "--max_restarts", "2",
             "--log_dir", log_dir, str(script)],
            cwd=REPO, capture_output=True, timeout=120, env=_cpu_env())
        assert rc.returncode == 0, (rc.stderr.decode(), rc.stdout.decode())
        assert "elastic restart 1/2" in rc.stderr.decode()
        with open(os.path.join(log_dir, "workerlog.0.restart1")) as f:
            assert "second attempt: ok (restart 1" in f.read()

    def test_relaunch_fires_with_multiple_local_ranks(self, tmp_path):
        """Advisor round-2 regression: with nproc_per_node > 1 the failure
        teardown path used to set the operator-shutdown flag, so
        --max_restarts never fired."""
        marker = tmp_path / "attempt"
        script = tmp_path / "flaky2.py"
        script.write_text(textwrap.dedent(f"""
            import os, sys, time
            marker = {str(marker)!r} + os.environ["PADDLE_LOCAL_RANK"]
            n = int(open(marker).read()) if os.path.exists(marker) else 0
            open(marker, "w").write(str(n + 1))
            if n == 0 and os.environ["PADDLE_LOCAL_RANK"] == "1":
                sys.exit(4)          # only rank 1 fails, only first attempt
            time.sleep(1.0)          # rank 0 survives until torn down
            print("rank", os.environ["PADDLE_TRAINER_ID"], "ok")
        """))
        log_dir = str(tmp_path / "logs")
        rc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "2", "--max_restarts", "2",
             "--log_dir", log_dir, str(script)],
            cwd=REPO, capture_output=True, timeout=120, env=_cpu_env())
        assert rc.returncode == 0, (rc.stderr.decode(), rc.stdout.decode())
        assert "elastic restart 1/2" in rc.stderr.decode()
        with open(os.path.join(log_dir, "workerlog.0.restart1")) as f:
            assert "rank 0 ok" in f.read()

    def test_no_restart_without_flag(self, tmp_path):
        script = tmp_path / "fail.py"
        script.write_text("import sys; sys.exit(5)\n")
        rc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "1", "--log_dir",
             str(tmp_path / "logs"), str(script)],
            cwd=REPO, capture_output=True, timeout=120, env=_cpu_env())
        assert rc.returncode == 5
        assert "elastic restart" not in rc.stderr.decode()

    def test_rescale_assigns_new_ranks(self):
        from paddle_tpu.distributed.fleet.elastic import ElasticManager
        from paddle_tpu.distributed.store import TCPStore

        store = TCPStore("127.0.0.1", 0, is_master=True, world_size=1)
        m_a = ElasticManager(store, node_id="a", timeout=2.0)
        m_c = ElasticManager(store, node_id="c", timeout=2.0)
        m_a.register()
        m_c.register()
        # "b" never registered -> dead; survivors get dense new ranks
        ranks, dead = m_a.rescale(["a", "b", "c"])
        assert dead == ["b"]
        assert ranks == {"a": 0, "c": 1}


# -------------------------------------------------------------- inference --

class TestInferenceDepth:
    def _model(self):
        paddle.seed(0)
        return nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))

    def test_predictor_compiles_and_caches(self):
        from paddle_tpu import inference

        cfg = inference.Config()
        cfg.set_model_obj(self._model())
        pred = inference.create_predictor(cfg)
        x = np.random.rand(2, 8).astype(np.float32)
        out1 = pred.run([x])[0]
        assert len(pred._compiled_cache) == 1
        out2 = pred.run([x + 1])[0]
        assert len(pred._compiled_cache) == 1  # same signature: cache hit
        pred.run([np.random.rand(5, 8).astype(np.float32)])
        assert len(pred._compiled_cache) == 2  # new shape: new executable
        assert out1.shape == (2, 4) and not np.allclose(out1, out2)

    def test_weight_updates_are_picked_up(self):
        """Only the executable is cached — weights must stay live."""
        from paddle_tpu import inference

        m = self._model()
        cfg = inference.Config()
        cfg.set_model_obj(m)
        pred = inference.create_predictor(cfg)
        x = np.random.rand(2, 8).astype(np.float32)
        out1 = pred.run([x])[0]
        for p in m.parameters():
            p._data = p._data * 0.0
        out2 = pred.run([x])[0]
        np.testing.assert_allclose(out2, 0.0, atol=1e-6)
        assert not np.allclose(out1, out2)

    def test_mixed_precision_converts_params(self):
        from paddle_tpu import inference

        m = self._model()
        cfg = inference.Config()
        cfg.set_model_obj(m)
        cfg.enable_mixed_precision("bfloat16")
        pred = inference.create_predictor(cfg)
        assert all(str(p._data.dtype) == "bfloat16"
                   for p in m.state_dict().values())
        out = pred.run([np.random.rand(2, 8).astype(np.float32)])[0]
        assert str(out.dtype) == "bfloat16"

    def test_dist_inference_shards_batch(self):
        from paddle_tpu import inference
        from paddle_tpu.distributed.fleet.topology import build_mesh

        mesh = build_mesh(dp=8)
        cfg = inference.Config()
        m = self._model()
        cfg.set_model_obj(m)
        cfg.enable_dist_inference(mesh)
        pred = inference.create_predictor(cfg)
        x = np.random.rand(16, 8).astype(np.float32)
        out = pred.run([x])[0]
        # numeric parity with single-device
        cfg2 = inference.Config()
        cfg2.set_model_obj(self._model())
        ref = inference.create_predictor(cfg2).run([x])[0]
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)

    def test_zero_copy_handle_path(self):
        from paddle_tpu import inference

        cfg = inference.Config()
        cfg.set_model_obj(self._model())
        pred = inference.create_predictor(cfg)
        h = pred.get_input_handle(pred.get_input_names()[0])
        x = np.random.rand(3, 8).astype(np.float32)
        h.copy_from_cpu(x)
        assert pred.run() is True
        out = pred.get_output_handle(pred.get_output_names()[0]).copy_to_cpu()
        assert out.shape == (3, 4)


class TestFusedMultiTransformer:
    @pytest.mark.slow
    def test_decode_matches_full_forward(self):
        from paddle_tpu.incubate.nn import FusedMultiTransformer
        from paddle_tpu.models.gpt import gpt_tiny

        paddle.seed(0)
        m = gpt_tiny(num_layers=3, hidden_dropout_prob=0.0,
                     attention_probs_dropout_prob=0.0)
        m.eval()
        fmt = FusedMultiTransformer(m, max_length=64)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 128, (2, 10)).astype(np.int32)
        out = fmt.generate(ids, max_new_tokens=6)

        cur = ids.copy()
        for _ in range(6):
            logits = m(paddle.to_tensor(cur)).numpy()
            nxt = logits[:, -1].argmax(-1).astype(np.int32)
            cur = np.concatenate([cur, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(out, cur)

    def test_sampled_generation_and_limits(self):
        from paddle_tpu.incubate.nn import FusedMultiTransformer
        from paddle_tpu.models.gpt import gpt_tiny

        paddle.seed(0)
        m = gpt_tiny(num_layers=2)
        m.eval()
        fmt = FusedMultiTransformer(m, max_length=32)
        ids = np.array([[5, 6, 7]], np.int32)
        out = fmt.generate(ids, max_new_tokens=4, temperature=0.8,
                           top_k=10, seed=1)
        assert out.shape == (1, 7)
        assert (out[:, :3] == ids).all()
        with pytest.raises(ValueError, match="exceeds max_length"):
            fmt.generate(ids, max_new_tokens=64)
