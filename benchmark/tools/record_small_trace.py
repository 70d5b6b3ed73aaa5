#!/usr/bin/env python3
"""Record the small `.xplane.pb` that benchmark/tests/test_trace_reduce.py
reduces: a few dozen small matmuls on the chip, traced for a few
milliseconds. Writes chiprun_out/small/small.xplane.pb and small.xplane.json
(what the reduction gave when it was recorded); copy both to
benchmark/tests/data/. Not part of a run."""

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import jax.numpy as jnp

    from benchmark.harness import trace_reduce as TRD

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    out = os.path.join(ROOT, "chiprun_out", "small")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    x = jnp.ones((256, 256), jnp.bfloat16)
    step = jax.jit(lambda a: jnp.tanh(a @ a) * 0.01)
    jax.block_until_ready(step(x))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    jax.profiler.start_trace(os.path.join(out, "trace"), profiler_options=opts)
    for _ in range(12):
        x = step(x)
    jax.block_until_ready(x)
    jax.profiler.stop_trace()
    path = TRD.find_xplane(os.path.join(out, "trace"))
    shutil.copy(path, os.path.join(out, "small.xplane.pb"))
    red = TRD.reduce(TRD.load_planes(path))
    with open(os.path.join(out, "small.xplane.json"), "w") as f:
        json.dump({"recorded_on": jax.devices()[0].device_kind,
                   "what": "12 executions of jit(tanh(a @ a) * 0.01), a 256x256 bf16",
                   **{k: red[k] for k in ("chips", "busy_s", "window_s", "device_ops")}},
                  f, indent=1)
    shutil.rmtree(os.path.join(out, "trace"))
    print(os.path.getsize(os.path.join(out, "small.xplane.pb")), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
