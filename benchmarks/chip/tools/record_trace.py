"""Record the small TPU trace the trace-reduction test reads.

    python benchmarks/chip/tools/record_trace.py OUT_DIR

Two named jitted programs run a few times inside ``bench.window`` and
``serve.tick`` spans with host sleeps between them, so the trace has
device busy time, idle gaps with a host span open, and programs by jit
name.  The ``.xplane.pb`` lands under OUT_DIR; copy it to
``tests/data/small.xplane.pb``.
"""
import sys
import time

import jax
import jax.numpy as jnp


def prefill_step(x):
    return jnp.tanh(x @ x).sum()


def slot_decode(x):
    return (x * 2.0).sum()


def main(out: str):
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace: needs a TPU")
    a, b = jax.jit(prefill_step), jax.jit(slot_decode)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    a(x).block_until_ready(), b(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("serve.tick"):
                a(x).block_until_ready()
                b(x).block_until_ready()
            with jax.profiler.TraceAnnotation("serve.submit"):
                time.sleep(0.002)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
