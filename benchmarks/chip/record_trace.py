"""Records the small profiler trace that ``tests/benchmark`` checks the
trace reduction on, and prints the trace's planes, lines and a few events
of each (names and stats), so that a reader can see how a device trace is
laid out.

    python3 benchmarks/chip/record_trace.py <out_dir>

Five ``train_step`` spans, each holding a ``batch_fetch`` span (a 3 ms host
sleep, which leaves the device idle) and one jitted step: two matmuls, and
on more than one device an all-reduce and a collective-permute over all of
them.  The ``.xplane.pb`` lands under ``<out_dir>``.
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    out = sys.argv[1]
    devs = jax.devices()
    n = len(devs)
    mesh = jax.make_mesh((n,), ("x",), devices=devs)

    def body(a):
        b = jnp.tanh(a @ a) @ a
        if n > 1:
            b = b + jax.lax.psum(b, "x")
            b = jax.lax.ppermute(b, "x", [(i, (i + 1) % n) for i in range(n)])
        return b

    step = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("x"), out_specs=P("x")))
    x = jax.device_put(jnp.ones((512 * n, 512), jnp.float32), NamedSharding(mesh, P("x")))
    step(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    for i in range(5):
        with jax.profiler.StepTraceAnnotation("train_step", step_num=i):
            with jax.profiler.TraceAnnotation("batch_fetch"):
                time.sleep(0.003)
            x = step(x)
            x.block_until_ready()
    jax.profiler.stop_trace()

    sys.path.insert(0, __file__.rsplit("/", 1)[0])
    import trace_reduce
    from jax.profiler import ProfileData

    path = trace_reduce.find_xplane(out)
    pd = ProfileData.from_file(str(path))
    for plane in pd.planes:
        print("plane", repr(plane.name))
        for line in plane.lines:
            evs = list(line.events)
            print("  line", repr(line.name), len(evs))
            for ev in evs[:4]:
                print("    ", repr(ev.name), ev.start_ns, ev.duration_ns, [(k, str(v)[:80]) for k, v in ev.stats][:8])
    print("summary", trace_reduce.summarize(path))
    print("file", path, path.stat().st_size)


if __name__ == "__main__":
    main()
