#!/usr/bin/env python3
"""Records the small trace kept as ``tests/data/small.xplane.pb``: three
tiny jitted programs under ``chipbench.*`` annotations on one chip.  Run
on the chip (``chiprun -- python chipbench/tests/record_small_trace.py
<out_dir>``); prints the planes and lines it found and what
``trace_reduce`` makes of them."""
import glob
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    import trace_reduce
    if jax.devices()[0].platform != "tpu":
        print("no TPU: nothing recorded")
        return 2
    square = jax.jit(lambda x: (x @ x).sum(), )
    scale = jax.jit(lambda x: x * 3 + 1)
    x = jnp.ones((1024, 1024), jnp.float32)
    jax.block_until_ready((square(x), scale(x)))        # compile outside
    tmp = os.path.join(out_dir, "trace_tmp")
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=opts)
    t0 = time.perf_counter()
    for name in ("a", "b"):
        with jax.profiler.TraceAnnotation(f"chipbench.sql.{name}"):
            y = scale(x)
        with jax.profiler.TraceAnnotation(f"chipbench.collect.{name}"):
            jax.block_until_ready(square(y))
            time.sleep(0.02)                # an idle gap under a span
            jax.block_until_ready(square(y))
    window_s = time.perf_counter() - t0
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    dst = os.path.join(out_dir, "small.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp)
    planes = jax.profiler.ProfileData.from_file(dst).planes
    for p in planes:
        print("PLANE", p.name)
        for ln in p.lines:
            evs = list(ln.events)
            print("  LINE", ln.name, len(evs))
            for e in evs[:6]:
                print("     ", e.name[:80], e.start_ns, e.duration_ns)
    print("window_s", window_s, "bytes", os.path.getsize(dst))
    print("REDUCED", json.dumps(trace_reduce.reduce_file(dst, 1)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
