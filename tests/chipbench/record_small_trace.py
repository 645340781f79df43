"""How tests/chipbench/data/small.xplane.pb was recorded (on the chip, by
hand: ``chiprun -- python3 tests/chipbench/record_small_trace.py``): three
annotated "work" spans of two matmuls and a sum each, with host sleeps
("nap") between them, and the worker's anchor annotation. The expected
numbers in test_xplane.py were read from this one file."""

import glob
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> None:
    import jax
    import jax.numpy as jnp

    out = os.path.join("chiprun_out", "small_trace")
    shutil.rmtree(out, ignore_errors=True)
    f = jax.jit(lambda x: ((x @ x) @ x).sum())
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    float(f(x))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 1
    jax.profiler.start_trace(out, profiler_options=opts)
    meta = {"anchor_epoch_ns": time.time_ns(), "spans": []}
    with jax.profiler.TraceAnnotation("chipbench.anchor"):
        pass
    for i in range(3):
        t0 = time.time_ns()
        with jax.profiler.TraceAnnotation("work"):
            float(f(x))
        t1 = time.time_ns()
        with jax.profiler.TraceAnnotation("nap"):
            time.sleep(0.02)
        meta["spans"] += [["work", t0, t1], ["nap", t1, time.time_ns()]]
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb"))[0]
    shutil.copy(path, os.path.join(out, "small.xplane.pb"))
    with open(os.path.join(out, "small.host.json"), "w") as fh:
        json.dump({**meta, "device": jax.devices()[0].device_kind}, fh)
    print(os.path.getsize(path), "bytes")


if __name__ == "__main__":
    main()
