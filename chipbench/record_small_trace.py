"""Record the small TPU trace that ``test_chipbench_reduce.py`` reduces.

    python3 chipbench/record_small_trace.py trace_small

Runs on a TPU: a ``window`` span holding three ``sweep`` spans, each one
jitted matmul waited on, with a short sleep between them, then two ``probe``
spans after the window.  Prints the planes, lines and first events of the
``.xplane.pb`` it wrote, and its reduction; copy the file to
``chipbench/testdata/small.xplane.pb``.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from chipbench import trace

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum(axis=0))
    g = jax.jit(lambda x: (x * 2.0).sum())
    x = jnp.ones((1024, 1024))
    jax.block_until_ready((f(x), g(x)))
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("sweep"):
                f(x).block_until_ready()
            time.sleep(0.002)
    for _ in range(2):
        with jax.profiler.TraceAnnotation("probe"):
            g(x).block_until_ready()
    jax.profiler.stop_trace()
    path = trace.find_xplane(out)
    print("XPLANE", path, Path(path).stat().st_size)
    for plane in ProfileData.from_file(path).planes:
        print("PLANE", repr(plane.name))
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events))
            for ev in events[:4]:
                print("     ", repr(ev.name), ev.start_ns, ev.duration_ns)
    red = trace.reduce_trace(path)
    print("REDUCED", red.window_s, red.busy_s, red.idle_pct, red.ops,
          red.gaps, red.span_busy("sweep"), red.span_busy("probe"))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
