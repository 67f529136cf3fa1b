"""Launcher CLI (reference: ``python/paddle/distributed/launch/main.py`` +
controllers/master/job).

``python -m paddle_tpu.distributed.launch [--nnodes N] [--master ip:port]
[--rank R] train.py args...``

TPU model (SURVEY.md §3.3): ONE process per host — per-chip fan-out is XLA's
job, so there is no per-device Pod/Container spawn. The launcher:

1. resolves the coordinator (rank-0 host) address,
2. exports paddle-compatible env (PADDLE_TRAINER_ID, PADDLE_TRAINERS_NUM,
   PADDLE_MASTER, PADDLE_CURRENT_ENDPOINT),
3. execs the training script (optionally respawning on failure — elastic
   restart loop; preemption-aware resume comes from checkpoints).

Single-host multi-process simulation (CPU tests only): ``--procs K`` forks
K local processes against the CPU backend. On a TPU host K stays 1: a chip
belongs to one process at a time.
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time

from ...utils.log import get_logger

logger = get_logger("launch")


def build_parser():
    p = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    p.add_argument("--master", default=None,
                   help="coordinator ip:port (rank-0 host)")
    p.add_argument("--nnodes", default="1",
                   help="number of hosts, or min:max for elastic")
    p.add_argument("--rank", type=int,
                   default=int(os.environ.get("PADDLE_TRAINER_ID", "0")))
    # A chip belongs to one process at a time and one process drives every
    # chip of its host, so on a TPU host this stays 1. Values above 1 exist
    # for the CPU tests only: K local processes against the CPU backend.
    p.add_argument("--nproc_per_node", "--procs", dest="procs", type=int,
                   default=1, help="local processes (CPU tests only; on a "
                                   "TPU host one process owns the chips)")
    p.add_argument("--log_dir", default="log")
    p.add_argument("--run_mode", default="collective")
    p.add_argument("--job_id", default="default")
    p.add_argument("--devices", "--gpus", default=None,
                   help="accepted for CLI parity; devices come from the TPU "
                        "runtime")
    p.add_argument("--rdzv_backend", default="http",
                   choices=("http", "tcp"),
                   help="rank-0 rendezvous store: threaded HTTP KV (http) "
                        "or the native C++ TCPStore (tcp)")
    p.add_argument("--max_restart", type=int, default=3)
    p.add_argument("--elastic_level", type=int, default=-1)
    p.add_argument("--restart_backoff", type=float, default=3.0,
                   help="base seconds for exponential restart backoff")
    p.add_argument("script", type=str)
    p.add_argument("script_args", nargs=argparse.REMAINDER)
    return p


def _child_env(args, local_rank, nnodes_min, kv_endpoint=None):
    env = dict(os.environ)
    world = nnodes_min * max(args.procs, 1)
    rank = args.rank * max(args.procs, 1) + local_rank
    env["PADDLE_TRAINER_ID"] = str(rank)
    env["PADDLE_TRAINERS_NUM"] = str(world)
    if args.master:
        env["PADDLE_MASTER"] = args.master
        host = args.master.split(":")[0]
        env["PADDLE_CURRENT_ENDPOINT"] = f"{host}:{35000 + rank}"
    if kv_endpoint:
        env["PADDLE_MASTER_KV"] = kv_endpoint
    env["PADDLE_LOCAL_RANK"] = str(local_rank)
    env["PADDLE_JOB_ID"] = args.job_id
    env["FLAGS_selected_tpus"] = str(local_rank)
    return env


def launch():
    args = build_parser().parse_args()
    nnodes = args.nnodes.split(":")
    nmin = int(nnodes[0])
    os.makedirs(args.log_dir, exist_ok=True)
    cmd_base = [sys.executable, args.script] + args.script_args

    # rank-0 rendezvous store (reference controllers/master.py): an HTTP KV
    # service for worker bootstrap/barrier. It binds an EPHEMERAL port on
    # the master host — NOT the --master port itself, which stays free for
    # the jax.distributed coordinator (PADDLE_MASTER) — and the resolved
    # endpoint is exported to workers as PADDLE_MASTER_KV.
    kv_server = None
    if args.master and args.rank == 0:
        from .rendezvous import KVServer, NativeKVServer
        host, _, mport = args.master.partition(":")
        # elastic multi-node: bind DETERMINISTICALLY at master-port+1 so
        # non-master launchers can reach the store without an env handoff
        kv_port = (int(mport) + 1 if args.elastic_level >= 1 and mport
                   and int(mport) > 0 else 0)
        try:
            if args.rdzv_backend == "tcp":
                try:
                    kv_server = NativeKVServer(port=kv_port,
                                               host=host or "127.0.0.1")
                except Exception as e:
                    logger.warning(f"native TCPStore unavailable ({e}); "
                                   f"falling back to the HTTP store")
            if kv_server is None:
                kv_server = KVServer(port=kv_port, host=host or "127.0.0.1")
            logger.info(f"rendezvous KV store serving on {kv_server.endpoint}")
        except OSError as e:
            logger.warning(f"KV store not started ({e}); assuming an "
                           f"external rendezvous service")

    # elastic membership (reference fleet/elastic/manager.py †): heartbeat
    # this node into the KV store; each spawn round uses the LIVE world
    # size and deterministic rank, and a membership change mid-run tears
    # the trainers down for a re-rendezvous relaunch. NON-master launchers
    # reach the store through PADDLE_MASTER_KV (operator-provided) or the
    # deterministic master-port+1 convention below.
    elastic_mgr = None
    # the endpoint exported to trainer children as PADDLE_MASTER_KV: the
    # local server when we host it, else whatever endpoint this launcher
    # RESOLVED (probe or operator env) — so child env is consistent across
    # master and non-master nodes (ADVICE r3)
    kv_export = kv_server.endpoint if kv_server is not None else None
    if args.elastic_level >= 1:
        kv_endpoint_for_elastic = None
        if kv_server is not None:
            kv_endpoint_for_elastic = kv_server.endpoint
        elif os.environ.get("PADDLE_MASTER_KV"):
            kv_endpoint_for_elastic = os.environ["PADDLE_MASTER_KV"]
        elif args.master:
            host, _, port = args.master.partition(":")
            if port and int(port) > 0:
                # the master may have FALLEN BACK to the HTTP store even if
                # this launcher asked for tcp — probe both protocols and
                # keep whichever answers, instead of trusting our own flag
                first = "tcp://" if args.rdzv_backend == "tcp" else ""
                other = "" if first else "tcp://"
                base = f"{host}:{int(port) + 1}"
                kv_endpoint_for_elastic = _probe_endpoint(
                    [first + base, other + base])
        if kv_export is None:
            kv_export = kv_endpoint_for_elastic
        if kv_endpoint_for_elastic is not None:
            from ..fleet.elastic import ElasticManager
            # unique per-launcher identity (two launchers default to
            # --rank 0; colliding ids would silently collapse membership).
            # The master sorts FIRST ("0-" prefix) so it keeps rank 0 and
            # with it the PADDLE_MASTER coordinator role across epochs.
            import socket as _socket
            node_id = (("0-master" if kv_server is not None else
                        f"1-{_socket.gethostname()}-{os.getpid()}"))
            try:
                elastic_mgr = ElasticManager(
                    kv_endpoint_for_elastic, args.job_id,
                    node_id=node_id, np=args.nnodes,
                    heartbeat_interval=float(os.environ.get(
                        "PADDLE_ELASTIC_HEARTBEAT_INTERVAL", "1.0")),
                    ttl=float(os.environ.get(
                        "PADDLE_ELASTIC_TTL", "5.0"))).start()
            except Exception as e:
                logger.warning(f"elastic manager unavailable ({e}); "
                               f"running with static membership")
        else:
            logger.warning("elastic mode needs a reachable KV store "
                           "(--master with a fixed port, or "
                           "PADDLE_MASTER_KV); running with static "
                           "membership")

    # tooling/tests: announce the rendezvous endpoint to a file so external
    # agents (scale-up nodes) can find the ephemeral store
    announce = os.environ.get("PADDLE_LAUNCH_KV_ANNOUNCE")
    if announce and kv_server is not None:
        with open(announce, "w") as f:
            f.write(kv_server.endpoint)

    # SIGTERM tears the job down and exits (never respawns). One flag +
    # handler for the WHOLE launcher lifetime: `procs` is mutated in place
    # each round, so a signal between rounds still hits live state.
    procs = []
    shutdown = {"requested": False}

    def terminate_all(signum=None, frame=None):
        if signum is not None:
            shutdown["requested"] = True
        for p, _ in procs:
            if p.poll() is None:
                p.terminate()

    signal.signal(signal.SIGTERM, terminate_all)

    restarts = 0
    while True:
        epoch = None
        nnodes_live = nmin
        if elastic_mgr is not None:
            try:
                epoch, my_rank, nnodes_live, table = elastic_mgr.wait_ready(
                    timeout=120.0)
            except TimeoutError as e:
                logger.error(f"elastic: cluster never reached np range: {e}")
                elastic_mgr.stop()
                if kv_server is not None:
                    kv_server.stop()
                return 1
            args.rank = my_rank
            logger.info(f"elastic: {nnodes_live} node(s), this node is "
                        f"rank {my_rank} ({table})")
        if shutdown["requested"]:
            break
        procs[:] = []
        for lr in range(max(args.procs, 1)):
            env = _child_env(args, lr, nnodes_live, kv_export)
            logfile = os.path.join(args.log_dir, f"workerlog.{lr}")
            out = open(logfile, "ab")
            logger.info(f"spawn rank {env['PADDLE_TRAINER_ID']}: "
                        f"{' '.join(cmd_base)} (log: {logfile})")
            p = subprocess.Popen(cmd_base, env=env,
                                 stdout=out if lr != 0 else None,
                                 stderr=subprocess.STDOUT if lr != 0 else None)
            procs.append((p, out))

        codes = []
        scale_restart = False
        try:
            if elastic_mgr is None:
                # non-elastic: block in wait() — no reason to busy-poll
                # for the whole job lifetime. Re-run terminate_all first in
                # case SIGTERM landed mid-spawn (the handler only saw the
                # children appended at that moment).
                if shutdown["requested"]:
                    terminate_all()
                for p, _ in procs:
                    p.wait()
            else:
                while True:
                    if shutdown["requested"]:
                        # SIGTERM may have landed mid-spawn, before some
                        # children existed when the handler ran
                        terminate_all()
                        for p, _ in procs:
                            p.wait()
                        break
                    if all(p.poll() is not None for p, _ in procs):
                        break
                    changed = False
                    try:
                        changed = elastic_mgr.has_changed(epoch)
                    except Exception as e:
                        # transient store failure must NOT crash the
                        # launcher with live trainers — treat as unchanged
                        logger.warning(f"membership probe failed: {e}")
                    if changed:
                        logger.warning("elastic: membership changed — "
                                       "tearing down trainers for "
                                       "re-rendezvous")
                        scale_restart = True
                        terminate_all()
                        for p, _ in procs:
                            p.wait()
                        break
                    time.sleep(0.3)
            codes = [p.poll() for p, _ in procs]
            for _, out in procs:
                if out is not None:
                    out.close()
        except KeyboardInterrupt:
            terminate_all()
            raise
        if shutdown["requested"]:
            break
        if scale_restart:
            _drop_stale_ranks(kv_server, args.job_id)
            continue  # scale events don't consume failure-restart budget
        if all(c == 0 for c in codes):
            logger.info("job finished successfully")
            if kv_server is not None:
                kv_server.stop()
            return 0
        restarts += 1
        if restarts > args.max_restart or args.elastic_level < 0:
            logger.error(f"job failed with exit codes {codes}")
            if kv_server is not None:
                kv_server.stop()
            return 1
        backoff = min(args.restart_backoff * (2 ** (restarts - 1)), 30.0)
        logger.warning(f"restart {restarts}/{args.max_restart} after failure "
                       f"{codes} (elastic mode, backoff {backoff:.1f}s)")
        terminate_all()
        if elastic_mgr is not None:
            # the store also holds elastic heartbeats/epochs now: drop only
            # the dead run's rank registrations, not the membership state
            _drop_stale_ranks(kv_server, args.job_id)
        elif kv_server is not None:
            # stale rank registrations from the failed run would satisfy the
            # next run's wait_world barrier with dead endpoints
            kv_server.clear()
        time.sleep(backoff)

    # `break` target: SIGTERM-requested shutdown
    logger.info("SIGTERM: trainers stopped, launcher exiting")
    if elastic_mgr is not None:
        elastic_mgr.stop()
    if kv_server is not None:
        kv_server.stop()
    return 143


def _probe_endpoint(candidates):
    """First endpoint whose store answers a get() — protocol detection for
    non-master launchers (the master may have fallen back to HTTP)."""
    from .rendezvous import connect
    for ep in candidates:
        try:
            connect(ep, timeout=3.0).get("/__probe__")
            return ep
        except Exception:
            continue
    logger.warning(f"no rendezvous store reachable at {candidates}")
    return None


def _drop_stale_ranks(kv_server, job_id):
    """Delete /job/<id>/rank/* so the next run's wait_world barrier cannot
    be satisfied by dead endpoints (membership/heartbeat keys survive).
    Also wipes /objcol* (object-collective payloads + run id): the wipe
    happens BEFORE the respawn — and before the elastic commit round other
    nodes' spawns wait on — so a restarted incarnation can never adopt the
    dead run's namespace or read its stale payloads."""
    if kv_server is None:
        return
    from .rendezvous import connect
    try:
        cli = connect(kv_server.endpoint)
        for key in cli.get_prefix(f"/job/{job_id}/rank/"):
            cli.delete(key)
        for key in cli.get_prefix("/objcol"):
            cli.delete(key)
        # the previous incarnation's jax coordinator endpoint is equally
        # stale: a restarted rank polling it would dial a dead port
        cli.delete(f"/job/{job_id}/jaxcoord")
    except Exception as e:
        logger.warning(f"stale-rank cleanup failed: {e}")


if __name__ == "__main__":
    sys.exit(launch())
