"""Records the small profiler trace, and the compiled text of its one
module, that ``tests/benchmark`` checks the reduction by layer scope and
by trainer phase on (``scope_reduce.py``), and prints both reductions.

    python3 benchmarks/chip/record_scoped_trace.py <out_dir>

Five ``train_step`` spans, each holding the four ``trainer.*`` phase spans
of ``Trainer.run``: ``trainer.fetch`` (a ``batch_fetch`` span around a
3 ms host sleep, which leaves the device idle, then a batch made on the
host), ``trainer.put`` (its copy to the device), ``trainer.dispatch`` (the
jitted step) and ``trainer.sync`` (the read-back of one number).  The step
runs a loop of matmuls under the scope ``encoder`` and two matmuls under
``head``; one device.  The ``.xplane.pb`` lands under ``<out_dir>``, the
module's ``compiled.as_text()`` in ``<out_dir>/module.hlo.txt``.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    out = Path(sys.argv[1])
    out.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_enable_compilation_cache", False)  # the module's text is this script's own compile

    def body(a, w):
        with jax.named_scope("encoder"):
            h = jax.lax.fori_loop(0, 4, lambda i, h: jnp.tanh(h @ w), a)
        with jax.named_scope("head"):
            return jnp.tanh(h @ a) @ w

    step = jax.jit(body)
    host = np.ones((512, 512), np.float32)
    w = jnp.full((512, 512), 1.0 / 512, jnp.float32)
    compiled = step.lower(host, w).compile()
    (out / "module.hlo.txt").write_text(compiled.as_text())
    float(step(host, w)[0, 0])  # compiles the read-back's slice too, before the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out), profiler_options=opts)
    for i in range(5):
        with jax.profiler.StepTraceAnnotation("train_step", step_num=i):
            with jax.profiler.TraceAnnotation("trainer.fetch"):
                with jax.profiler.TraceAnnotation("batch_fetch"):
                    time.sleep(0.003)
                batch = host * (1.0 + i)
            with jax.profiler.TraceAnnotation("trainer.put", bytes=batch.nbytes):
                a = jnp.asarray(batch)
            with jax.profiler.TraceAnnotation("trainer.dispatch", rows=512, src_len=512, tgt_len=512):
                x = step(a, w)
            with jax.profiler.TraceAnnotation("trainer.sync"):
                float(x[0, 0])
    jax.profiler.stop_trace()

    sys.path.insert(0, str(Path(__file__).resolve().parent))
    import scope_reduce
    import trace_reduce

    path = trace_reduce.find_xplane(out)
    print("summary", trace_reduce.summarize(path))
    print("by scope and phase", scope_reduce.reduce(path, [compiled.as_text()]))
    print("file", path, path.stat().st_size, out / "module.hlo.txt", (out / "module.hlo.txt").stat().st_size)


if __name__ == "__main__":
    main()
