"""Elastic manager tests (reference:
``test/collective/fleet/test_elastic_manager.py`` † — membership, TTL
eviction, scale events — with the KV store standing in for ETCD)."""
import os
import signal
import subprocess
import sys
import time

import pytest

from paddle_tpu.parallel.fleet.elastic import ElasticManager, ElasticStatus
from paddle_tpu.parallel.launch.rendezvous import KVServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mgr(srv, node, np="1:4", hb=0.1, ttl=0.6):
    return ElasticManager(srv.endpoint, "ejob", node, np=np,
                          heartbeat_interval=hb, ttl=ttl)


class TestElasticManager:
    def test_membership_and_ttl_eviction(self):
        srv = KVServer(port=0)
        try:
            a = _mgr(srv, "a").start()
            b = _mgr(srv, "b").start()
            time.sleep(0.2)
            assert a.live_nodes() == ["a", "b"]
            # b stops heartbeating -> evicted after TTL
            b._stop.set()
            b._thread.join()
            deadline = time.time() + 3
            while "b" in a.live_nodes():
                assert time.time() < deadline, "b never evicted"
                time.sleep(0.1)
            assert a.live_nodes() == ["a"]
            a.stop()
        finally:
            srv.stop()

    def test_wait_ready_ranks_and_epoch(self):
        srv = KVServer(port=0)
        try:
            a = _mgr(srv, "a", np="2:3").start()
            b = _mgr(srv, "b", np="2:3").start()
            ea, ra, wa, ta = a.wait_ready(timeout=10)
            eb, rb, wb, tb = b.wait_ready(timeout=10)
            assert (wa, wb) == (2, 2)
            assert ea == eb and ta == tb
            assert sorted([ra, rb]) == [0, 1]
            # deterministic: sorted node ids
            assert ta == {"a": 0, "b": 1}
            a.stop(); b.stop()
        finally:
            srv.stop()

    def test_hold_below_min(self):
        srv = KVServer(port=0)
        try:
            a = _mgr(srv, "a", np="2:4").start()
            time.sleep(0.2)
            assert a.status() == ElasticStatus.HOLD
            with pytest.raises(TimeoutError):
                a.wait_ready(timeout=0.8)
            a.stop()
        finally:
            srv.stop()

    def test_scale_up_bumps_epoch(self):
        srv = KVServer(port=0)
        try:
            a = _mgr(srv, "a", np="1:3").start()
            e1, r1, w1, _ = a.wait_ready(timeout=10)
            assert (r1, w1) == (0, 1)
            assert not a.has_changed(e1)
            b = _mgr(srv, "b", np="1:3").start()
            deadline = time.time() + 5
            while not a.has_changed(e1):
                assert time.time() < deadline, "scale-up never detected"
                time.sleep(0.1)
            e2, r2, w2, t2 = a.wait_ready(timeout=10)
            # epoch IS the membership signature: deterministic, race-free
            assert e2 != e1 and w2 == 2 and t2 == {"a": 0, "b": 1}
            assert e2 == "a:0,b:1"
            a.stop(); b.stop()
        finally:
            srv.stop()

    def test_commit_round_blocks_non_master(self):
        """ADVICE r3: per-node stability alone is not agreement. A
        non-master must NOT return from wait_ready until the master has
        published the membership table it also sees."""
        import threading
        srv = KVServer(port=0)
        try:
            a = _mgr(srv, "a", np="2:3").start()
            b = _mgr(srv, "b", np="2:3").start()
            out = {}

            def b_wait():
                out["b"] = b.wait_ready(timeout=10)
            t = threading.Thread(target=b_wait)
            t.start()
            # b's view is stable well within 1s, but no commit exists yet
            time.sleep(1.0)
            assert "b" not in out, "non-master returned without a commit"
            ea, ra, wa, ta = a.wait_ready(timeout=10)  # master: publishes
            t.join(timeout=10)
            assert not t.is_alive() and "b" in out
            eb, rb, wb, tb = out["b"]
            assert (ea, ta) == (eb, tb) and sorted([ra, rb]) == [0, 1]
            # the committed table is readable on the store
            import json as _json
            doc = _json.loads(a._kv.get(a._commit_key))
            assert doc["sig"] == ea and doc["table"] == {"a": 0, "b": 1}
            a.stop(); b.stop()
        finally:
            srv.stop()

    def test_scale_down_reassigns_ranks(self):
        srv = KVServer(port=0)
        try:
            a = _mgr(srv, "a", np="1:3").start()
            b = _mgr(srv, "b", np="1:3").start()
            e1, _, w1, _ = a.wait_ready(timeout=10)
            assert w1 == 2
            b.stop()  # deletes its key: immediate scale-down
            deadline = time.time() + 5
            while not a.has_changed(e1):
                assert time.time() < deadline
                time.sleep(0.1)
            e2, r2, w2, _ = a.wait_ready(timeout=10)
            assert w2 == 1 and r2 == 0 and e2 != e1
            a.stop()
        finally:
            srv.stop()


class TestLauncherElastic:
    def test_launch_elastic_completes_single_node(self, tmp_path):
        toy = os.path.join(REPO, "tests", "_launch_toy.py")
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"
        env["PADDLE_ELASTIC_HEARTBEAT_INTERVAL"] = "0.1"
        env["PADDLE_ELASTIC_TTL"] = "1.0"
        p = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--procs", "1", "--master", "127.0.0.1:0", "--elastic_level",
             "1", "--nnodes", "1:3", "--log_dir", str(tmp_path / "logs"),
             toy, str(tmp_path)],
            capture_output=True, text=True, env=env, timeout=90, cwd=REPO)
        assert p.returncode == 0, p.stderr[-800:]
        import json
        with open(tmp_path / "env.0.json") as f:
            e = json.load(f)
        assert e["PADDLE_TRAINERS_NUM"] == "1"

    @pytest.mark.slow  # ~50 s multi-relaunch e2e; the single-node
    # completes-cleanly e2e above is the default-run representative
    def test_elastic_scale_resumes_from_checkpoint(self, tmp_path):
        """VERDICT r3 item 6 — the 5.3<->5.4 loop e2e: train 2 steps on a
        mp4 x sharding2 layout, an external agent triggers a scale event,
        the launcher relaunches with world=2, and the trainer resumes from
        the distributed checkpoint via reshard-on-load into a DIFFERENT
        mp2 x sharding4 layout. Loss must continue the phase-1 trajectory
        (match a serial uninterrupted oracle within tolerance)."""
        import json
        toy = os.path.join(REPO, "tests", "_elastic_ckpt_toy.py")
        announce = tmp_path / "kv.endpoint"
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            " --xla_force_host_platform_device_count=8")
        env["PADDLE_ELASTIC_HEARTBEAT_INTERVAL"] = "0.1"
        env["PADDLE_ELASTIC_TTL"] = "1.0"
        env["PADDLE_LAUNCH_KV_ANNOUNCE"] = str(announce)
        proc = subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--procs", "1", "--master", "127.0.0.1:0", "--elastic_level",
             "1", "--nnodes", "1:3", "--log_dir", str(tmp_path / "logs"),
             toy, str(tmp_path)],
            env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        joined = None
        try:
            # phase 1 finishes its 2 steps and checkpoints
            deadline = time.time() + 120
            while not (tmp_path / "phase.1.json").exists():
                assert time.time() < deadline, "phase 1 never checkpointed"
                assert proc.poll() is None, proc.stdout.read()[-800:]
                time.sleep(0.3)
            endpoint = None
            while endpoint is None or not endpoint.strip():
                endpoint = announce.read_text() if announce.exists() else None
                time.sleep(0.1)
                assert time.time() < deadline
            # external agent joins -> membership change -> relaunch
            joined = ElasticManager(endpoint.strip(), "default", "node-zz",
                                    np="1:3", heartbeat_interval=0.1,
                                    ttl=1.0).start()
            while not (tmp_path / "phase.2.json").exists():
                assert time.time() < deadline, "no post-scale resume"
                assert proc.poll() is None
                time.sleep(0.3)
        finally:
            if joined is not None:
                joined.stop()
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                proc.kill()
        p1 = json.load(open(tmp_path / "phase.1.json"))
        p2 = json.load(open(tmp_path / "phase.2.json"))
        assert p1["world"] == 1 and p2["world"] == 2
        assert p1["degrees"] != p2["degrees"]  # layouts really differed
        assert p2["start"] == 2               # resumed, not restarted
        # oracle: the same 4 steps uninterrupted, serial in this process
        import paddle_tpu as paddle
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.optimizer import AdamW
        from paddle_tpu.parallel import mesh as mesh_mod
        sys.path.insert(0, os.path.join(REPO, "tests"))
        import _elastic_ckpt_toy as toy_mod
        mesh_mod._STATE["mesh"] = None
        paddle.seed(0)
        import numpy as np
        model = toy_mod.MpMLP()
        opt = AdamW(learning_rate=0.05, parameters=model.parameters())
        step = TrainStep(model,
                         lambda out, label: ((out - label) ** 2).mean(), opt)
        rng = np.random.RandomState(0)
        x = paddle.to_tensor(rng.randn(8, 16).astype(np.float32))
        y = paddle.to_tensor(rng.randn(8, 16).astype(np.float32))
        oracle = [float(step.step((x,), (y,)).value) for _ in range(4)]
        np.testing.assert_allclose(p1["losses"], oracle[:2], rtol=2e-4,
                                   atol=2e-5)
        np.testing.assert_allclose(p2["losses"], oracle[2:], rtol=2e-4,
                                   atol=2e-5)

    @pytest.mark.slow
    def test_launch_restarts_on_scale_up(self, tmp_path):
        """A second node agent joins mid-run: the launcher must tear down
        its trainers and respawn them with the doubled world size."""
        sleeper = tmp_path / "sleeper.py"
        sleeper.write_text(
            "import json, os, sys, time\n"
            "d = sys.argv[1]\n"
            "n = os.environ['PADDLE_TRAINERS_NUM']\n"
            "open(os.path.join(d, f'world.{n}'), 'w').write(n)\n"
            "time.sleep(60)\n")
        announce = tmp_path / "kv.endpoint"
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"
        env["PADDLE_ELASTIC_HEARTBEAT_INTERVAL"] = "0.1"
        env["PADDLE_ELASTIC_TTL"] = "1.0"
        env["PADDLE_LAUNCH_KV_ANNOUNCE"] = str(announce)
        proc = subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--procs", "1", "--master", "127.0.0.1:0", "--elastic_level",
             "1", "--nnodes", "1:3", "--log_dir", str(tmp_path / "logs"),
             str(sleeper), str(tmp_path)],
            env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        joined = None
        try:
            deadline = time.time() + 30
            while not (tmp_path / "world.1").exists():
                assert time.time() < deadline, "first spawn never happened"
                assert proc.poll() is None
                time.sleep(0.2)
            endpoint = None
            while endpoint is None or not endpoint.strip():
                endpoint = announce.read_text() if announce.exists() else None
                time.sleep(0.1)
                assert time.time() < deadline
            joined = ElasticManager(endpoint.strip(), "default", "node-zz",
                                    np="1:3", heartbeat_interval=0.1,
                                    ttl=1.0).start()
            deadline = time.time() + 45
            while not (tmp_path / "world.2").exists():
                assert time.time() < deadline, "no relaunch at world=2"
                assert proc.poll() is None
                time.sleep(0.2)
        finally:
            if joined is not None:
                joined.stop()
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
