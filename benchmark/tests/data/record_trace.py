"""How ``small_v5e.xplane.pb`` beside this file was recorded (PR 23, one
TPU v5e): three calls of one jitted program (two bf16 matmuls and the
repo's flash-attention Pallas kernel) with a 20 ms host sleep after each,
inside ``jax.profiler.start_trace`` / ``stop_trace``. Run on the chip:

    python3 benchmark/tests/data/record_trace.py chiprun_out/probe

It also prints every plane, line and the first events with their stats,
which is what ``xplane_reduce.py`` was written against.
"""
import glob
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))


def main(out_dir):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from paddle_tpu.kernels.pallas_flash import flash_attention_pallas

    os.makedirs(out_dir, exist_ok=True)
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, len(jax.devices()))

    @jax.jit
    def program(a, b, q):
        with jax.named_scope("probe_matmuls"):
            c = jnp.dot(jnp.dot(a, b), b)
        o = flash_attention_pallas(q, q[:, :, :2], q[:, :, :2], True)
        return c.astype(jnp.float32).sum() + o.astype(jnp.float32).sum()

    k = jax.random.PRNGKey(0)
    a = jax.random.normal(k, (2048, 2048), jnp.bfloat16)
    q = jax.random.normal(k, (1, 1024, 8, 128), jnp.bfloat16)
    program(a, a, q).block_until_ready()
    jax.profiler.start_trace(out_dir)
    for i in range(3):
        with jax.profiler.TraceAnnotation("probe_step", step=i):
            program(a, a, q).block_until_ready()
        with jax.profiler.TraceAnnotation("probe_sleep"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    print("memory_stats", dev.memory_stats())
    path = sorted(glob.glob(os.path.join(
        out_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    print("trace", path, os.path.getsize(path), "bytes")
    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for ev in events[:12]:
                stats = {k: (str(v)[:60]) for k, v in ev.stats}
                print(f"    {ev.name[:70]!r} start_ns={ev.start_ns} "
                      f"dur_ns={ev.duration_ns} {stats}")


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/probe")
