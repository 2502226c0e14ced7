"""Record ``streaming_matmul_x3.xplane.pb`` on a TPU, as a traced run does.

    python tests/chipbench/fixtures/make_fixture.py <out-dir>

The harness's window marker, then three steps of a jitted
``tanh(streaming_matmul(a, b)) + 1`` (256x512 @ 512x256), 2 ms apart, with
device events only. The test reads its counts off by hand. The committed
file has the checkout's directory in its source locations replaced by
``<checkout>/`` (bytes of equal length, so the proto stays valid).
"""

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench.harness import window_mark  # noqa: E402
from repro.kernels.streaming import streaming_matmul  # noqa: E402

a = jnp.ones((256, 512), jnp.float32)
b = jnp.ones((512, 256), jnp.float32)
f = jax.jit(lambda a, b: jnp.tanh(streaming_matmul(a, b)) + 1.0)
f(a, b).block_until_ready()
mark = window_mark()
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
opts.host_tracer_level = 0
jax.profiler.start_trace(sys.argv[1], profiler_options=opts)
mark()
for _ in range(3):
    time.sleep(0.002)
    f(a, b).block_until_ready()
jax.profiler.stop_trace()
