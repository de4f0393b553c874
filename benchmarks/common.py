"""Shared helpers for the benchmark drivers.

Every driver prints exactly one JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}
matching the repo-root ``bench.py`` contract, so results are machine
comparable across configs (BASELINE.md "configs to reproduce").

Timing rule baked in here: JAX dispatch is asynchronous, so a timed
region ends in a host fetch (``np.asarray``) or ``block_until_ready`` of
real outputs, and a parent that starts measurement children imports no
JAX itself (one process per chip).
"""

from __future__ import annotations

import json
import os


#: Current round's artifact directory (drivers append JSONL rows here).
#: Env-overridable so old rows can be regenerated in place if needed.
ROUND = os.environ.get("BENCH_ROUND", "r05")


def out_path(name: str) -> str:
    """``benchmarks/results/<round>/<name>`` for the append-only JSONL
    artifact convention (error rows land BESIDE good rows, never over
    them)."""
    return os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "results", ROUND, name
    )


def force_cpu_mesh(n_devices: int) -> None:
    """Force an ``n_devices`` virtual CPU mesh (post-import safe). Thin
    wrapper over ``__graft_entry__._force_virtual_cpu`` — the drivers put
    the repo root on sys.path, so the one implementation is shared."""
    from __graft_entry__ import _force_virtual_cpu

    _force_virtual_cpu(n_devices)


def distinct_inputs(key, shape, n: int):
    """``n`` device-resident inputs, each unique (no request is served
    from another's result)."""
    import jax

    return [
        jax.device_put(jax.random.normal(jax.random.fold_in(key, i), shape))
        for i in range(n)
    ]


def emit(
    metric: str, value: float, unit: str, vs_baseline: float, **extra
) -> None:
    """The one-JSON-line contract; ``extra`` fields (platform, device,
    trial timings, notes) append after the four required keys."""
    print(
        json.dumps(
            {
                "metric": metric,
                "value": round(value, 4),
                "unit": unit,
                "vs_baseline": round(vs_baseline, 4),
                **extra,
            }
        ),
        flush=True,
    )


def measure_scan_throughput(
    graph, x0, iters: int, trials: int, param_dtype: str | None = None
) -> tuple[float, list[float]]:
    """The shared timed region (``bench.py``, ``local_infer.py``,
    ``tpu_models.py``): ITERS forward passes of ``graph`` inside one
    jitted ``lax.scan`` whose carry makes every iteration data-dependent
    on the last (XLA cannot hoist the body; per-call dispatch is
    amortized away), timed around a host fetch. Returns (images_per_sec,
    per-trial wall seconds).

    ``param_dtype="bfloat16"`` makes weights bf16-RESIDENT (flax keeps
    params f32 by default and casts per use — residency halves the
    weight bytes every iteration streams from HBM)."""
    import statistics
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    variables = jax.jit(graph.init)(jax.random.PRNGKey(0), x0)
    if param_dtype is not None:
        target = jnp.dtype(param_dtype)
        variables = jax.tree.map(
            lambda x: x.astype(target)
            if x.dtype == jnp.float32
            else x,
            variables,
        )

    def bench_fn(variables, x):
        def body(x, _):
            y = graph.apply(variables, x)
            x = x * 0.999 + (jnp.mean(y) * 1e-6).astype(x.dtype)
            return x, y[0, 0]

        x, ys = lax.scan(body, x, None, length=iters)
        return jnp.mean(ys)

    fwd = jax.jit(bench_fn)
    np.asarray(fwd(variables, x0))  # compile + warm

    times = []
    for i in range(trials):
        x_trial = x0 + (i + 1) * 1e-6  # distinct per trial
        t0 = time.perf_counter()
        np.asarray(fwd(variables, x_trial))
        times.append(time.perf_counter() - t0)
    dt = statistics.median(times)
    return x0.shape[0] * iters / dt, times


def int_flag(argv: list[str], name: str, default: int) -> int:
    """Parse ``--name N`` from argv; malformed/missing values fall back to
    the default instead of raising."""
    if name in argv:
        try:
            return int(argv[argv.index(name) + 1])
        except (IndexError, ValueError):
            pass
    return default


def str_flag(
    argv: list[str], name: str, default: str, choices: tuple[str, ...] | None = None
) -> str:
    """Parse ``--name VALUE``; missing values, values that look like the
    next flag, or values outside ``choices`` fall back to the default
    (as :func:`int_flag`)."""
    if name in argv:
        idx = argv.index(name) + 1
        if idx < len(argv) and not argv[idx].startswith("--"):
            value = argv[idx]
            if choices is None or value in choices:
                return value
    return default


def run_child_json(
    cmd: list,
    metric: str,
    unit: str,
    timeout_s: float,
    *,
    env: dict | None = None,
    allow_cpu: bool = False,
    out_path: str | None = None,
) -> int:
    """The shared parent half of the subprocess measurement contract:
    the parent imports no JAX (the child owns the chip), runs ``cmd``,
    scans stdout for the first parseable '{'-line and rejects a CPU row
    inside a TPU measurement (unless ``allow_cpu`` — an explicit --cpu
    validation run). Prints exactly one JSON line; a failed child
    prints an error record AND returns 1, so no caller can mistake a
    zero row for a measurement. ``out_path`` additionally APPENDS the
    record as one JSONL row (append, not overwrite: an error row lands
    beside earlier measurements, never over them). Drivers that need
    more than one child mode (artifact writers like mfu_sweep) keep
    their own loops; every plain one-JSON-line driver should use
    this."""
    import subprocess

    record, err = None, ""
    try:
        proc = subprocess.run(
            cmd,
            capture_output=True,
            text=True,
            timeout=timeout_s,
            env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        for ln in proc.stdout.splitlines():
            ln = ln.strip()
            if ln.startswith("{"):
                try:
                    record = json.loads(ln)
                    break
                except json.JSONDecodeError:
                    continue  # stray '{'-prefixed noise; keep scanning
        if proc.returncode != 0 or record is None:
            record = None
            err = (proc.stderr or proc.stdout or "").strip()[-300:]
        elif record.get("platform") == "cpu" and not allow_cpu:
            record = None
            err = "TPU run silently fell back to the CPU backend"
    except subprocess.TimeoutExpired:
        err = f"child timed out after {timeout_s:.0f}s"
    failed = record is None
    if failed:
        record = {
            "metric": metric,
            "value": 0.0,
            "unit": unit,
            "vs_baseline": 0.0,
            "error": err,
        }
    if out_path is not None:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "a") as f:
            json.dump(record, f)
            f.write("\n")
    print(json.dumps(record), flush=True)
    return 1 if failed else 0
