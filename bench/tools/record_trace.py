#!/usr/bin/env python3
"""Record the small device trace that ``bench/tests/test_trace.py``
reduces: three runs of one named program inside a ``bench.window``
host span, written under OUT_DIR; copy its ``.xplane.pb`` to
``bench/tests/data/`` to refresh the test's data.

    python bench/tools/record_trace.py OUT_DIR
"""

import sys
from pathlib import Path


def main() -> int:
    import jax
    import jax.numpy as jnp

    def bench_sample(x):
        return jnp.tanh(x @ x) * 2.0

    f = jax.jit(bench_sample)
    x = jnp.ones((1024, 1024), jnp.float32)
    jax.block_until_ready(f(x))
    out = Path(sys.argv[1])
    jax.profiler.start_trace(str(out))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                jax.block_until_ready(f(x))
    jax.profiler.stop_trace()
    print(f"device {jax.devices()[0].device_kind}; trace under {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
