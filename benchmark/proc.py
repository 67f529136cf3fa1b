"""Child processes: start one in its own process group, always reap it.
Copied from ``chip_smoke.py`` (``_spawn`` / ``_reap``), whose pattern was
proven on the chip in PR 22: the parent imports no JAX, so the one child
owns every chip, and no run leaves a process behind."""
import os
import signal
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
RUN_DIR = os.path.join(HERE, "_run")        # git-ignored: logs and traces


def child_env(rehearse, chips):
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    env.setdefault("TPU_LOG_DIR", "disabled")
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            f"platform_device_count={chips}")
    return env


def spawn(cmd, env, err_path, stdin=None):
    os.makedirs(os.path.dirname(err_path), exist_ok=True)
    with open(err_path, "w") as err:
        return subprocess.Popen(cmd, env=env, cwd=ROOT, stderr=err,
                                stdin=stdin, stdout=subprocess.PIPE,
                                text=True, start_new_session=True)


def reap(p, grace_s=20):
    """Stop a child's whole process group and wait for it."""
    if p.poll() is None:
        try:
            os.killpg(p.pid, signal.SIGTERM)
        except ProcessLookupError:
            pass
        try:
            p.wait(grace_s)
        except subprocess.TimeoutExpired:
            os.killpg(p.pid, signal.SIGKILL)
            p.wait()
    else:
        try:        # the leader is gone; take any straggler of its group
            os.killpg(p.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass


def err_tail(err_path, n=3000):
    try:
        with open(err_path) as f:
            return f.read()[-n:]
    except OSError:
        return ""
