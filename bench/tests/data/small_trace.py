"""Record the small v5e trace that ``test_xplane.py`` reduces:
``python3 bench/tests/data/small_trace.py <dir>`` on a TPU host."""
import sys
import jax
import jax.numpy as jnp
out = sys.argv[1]
@jax.jit
def f(x):
    def body(c):
        i, v = c
        return i + 1, jnp.take(v, (v * 7 + i) % v.size) + 1
    return jax.lax.while_loop(lambda c: c[0] < 4, body, (0, x))[1]
x = jnp.arange(1 << 12, dtype=jnp.int32)
f(x).block_until_ready()
jax.profiler.start_trace(out)
with jax.profiler.TraceAnnotation("bench.window"):
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.job"):
            y = int(f(x).sum())
jax.profiler.stop_trace()
