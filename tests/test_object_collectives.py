"""Object collectives (reference
``python/paddle/distributed/communication/`` all_gather_object /
broadcast_object_list / scatter_object_list †) + the gather/wait/
destroy_process_group namespace parity additions."""
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.parallel.launch.rendezvous import KVServer
from paddle_tpu.parallel.object_collectives import _dec, _enc, _exchange

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestObjectCollectivesSingleProcess:
    def test_all_gather_object_world1(self):
        out = []
        dist.all_gather_object(out, {"vocab": 123})
        assert out == [{"vocab": 123}]

    def test_broadcast_object_list_world1_noop(self):
        lst = ["a", 1]
        dist.broadcast_object_list(lst, src=0)
        assert lst == ["a", 1]

    def test_scatter_object_list_world1(self):
        out = []
        dist.scatter_object_list(out, [["mine"]], src=0)
        assert out == [["mine"]]


class TestExchangeOverStore:
    def test_exchange_rank_ordered(self):
        srv = KVServer(port=0)
        try:
            from paddle_tpu.parallel.launch.rendezvous import connect
            results = {}

            def rank(r):
                store = connect(srv.endpoint)
                results[r] = _exchange(store, r, 3, seq=1,
                                       payload=_enc(f"obj{r}"))

            ts = [threading.Thread(target=rank, args=(r,)) for r in range(3)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=30)
            for r in range(3):
                assert [_dec(p) for p in results[r]] == \
                    ["obj0", "obj1", "obj2"]
        finally:
            srv.stop()

    def test_exchange_timeout_when_rank_missing(self):
        srv = KVServer(port=0)
        try:
            from paddle_tpu.parallel.launch.rendezvous import connect
            store = connect(srv.endpoint)
            with pytest.raises(TimeoutError, match="1/2 ranks"):
                _exchange(store, 0, 2, seq=9, payload=_enc("x"),
                          timeout=0.5)
        finally:
            srv.stop()


class TestObjectCollectivesMultiProcess:
    def test_two_process_all_gather_and_scatter(self, tmp_path):
        """Two real processes exchange objects through the rendezvous
        store — the exact PADDLE_MASTER_KV transport trainers get from
        the launcher."""
        srv = KVServer(port=0)
        child = (
            "import os, json, sys\n"
            "import jax\n"
            "jax.config.update('jax_platforms', 'cpu')\n"
            "import paddle_tpu.distributed as dist\n"
            "r = int(os.environ['PADDLE_TRAINER_ID'])\n"
            "out = []\n"
            "dist.all_gather_object(out, {'rank': r})\n"
            "assert out == [{'rank': 0}, {'rank': 1}], out\n"
            "mine = []\n"
            "dist.scatter_object_list(mine, ['for0', 'for1'] if r == 0 "
            "else None, src=0)\n"
            "assert mine == [f'for{r}'], mine\n"
            "lst = ['seed', r] if r == 0 else [None, None]\n"
            "dist.broadcast_object_list(lst, src=0)\n"
            "assert lst == ['seed', 0], lst\n"
            "print('RANK_OK', r)\n")
        try:
            procs = []
            for r in range(2):
                env = dict(os.environ)
                env["PYTHONPATH"] = REPO + os.pathsep + env.get(
                    "PYTHONPATH", "")
                env["JAX_PLATFORMS"] = "cpu"
                env["PADDLE_TRAINER_ID"] = str(r)
                env["PADDLE_TRAINERS_NUM"] = "2"
                env["PADDLE_MASTER_KV"] = srv.endpoint
                procs.append(subprocess.Popen(
                    [sys.executable, "-c", child], env=env,
                    stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                    text=True))
            for r, p in enumerate(procs):
                out, _ = p.communicate(timeout=120)
                assert p.returncode == 0, out[-800:]
                assert f"RANK_OK {r}" in out
        finally:
            srv.stop()


class TestNamespaceParity:
    def test_gather_and_wait(self):
        t = paddle.to_tensor(np.asarray([1.0, 2.0], np.float32))
        lst = []
        dist.gather(t, lst, dst=0)
        assert len(lst) >= 1
        np.testing.assert_allclose(lst[0].numpy(), [1.0, 2.0])
        dist.wait(t)  # fence: must not raise

    def test_destroy_process_group(self):
        from paddle_tpu.parallel import env as env_mod
        dist.init_parallel_env()
        assert env_mod.is_initialized()
        dist.destroy_process_group()
        assert not env_mod.is_initialized()
        dist.init_parallel_env()  # restore for other tests
