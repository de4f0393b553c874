"""Single-device baseline: BASELINE.md config 1.

The reference's ``test/local_infer.py`` (``/root/reference/test/
local_infer.py:19-28``): ResNet-50, single device, `predict` loop,
req/s — the denominator every distributed number is compared against.
Here: one real TPU chip, jitted forward, batch=1 requests.

Same measurement methodology as the repo-root bench.py (on-device
lax.scan with a data-dependent carry, timed around a host fetch).

Prints one JSON line; vs_baseline shares bench.py's A100 denominator
(single-image requests underutilize any accelerator — this is the
latency-bound number, by design).
"""

from __future__ import annotations

import statistics
import sys
import time

sys.path.insert(0, ".")  # repo root

from benchmarks.common import emit  # noqa: E402

A100_IMAGES_PER_SEC = 3000.0
ITERS = 100
TRIALS = 3


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from adapt_tpu.models.resnet import resnet50

    graph = resnet50(num_classes=1000, dtype=jnp.bfloat16)
    x0 = jax.random.normal(jax.random.PRNGKey(0), (1, 224, 224, 3), jnp.float32)
    variables = jax.jit(graph.init)(jax.random.PRNGKey(0), x0)

    def bench_fn(variables, x):
        def body(x, _):
            y = graph.apply(variables, x)
            x = x * 0.999 + (jnp.mean(y) * 1e-6).astype(x.dtype)
            return x, y[0, 0]

        x, ys = lax.scan(body, x, None, length=ITERS)
        return jnp.mean(ys)

    fwd = jax.jit(bench_fn)
    np.asarray(fwd(variables, x0))  # compile + warm

    times = []
    for i in range(TRIALS):
        x_trial = x0 + (i + 1) * 1e-6  # distinct per trial
        t0 = time.perf_counter()
        np.asarray(fwd(variables, x_trial))
        times.append(time.perf_counter() - t0)

    req_s = ITERS / statistics.median(times)
    emit(
        "local_infer_resnet50_bs1_req_per_s",
        req_s,
        "req/s",
        req_s / A100_IMAGES_PER_SEC,
        platform=jax.devices()[0].platform,
        device=str(jax.devices()[0]),
        batch=1,
        iters=ITERS,
        trials=TRIALS,
        trial_seconds=[round(t, 4) for t in times],
    )


if __name__ == "__main__":
    main()
