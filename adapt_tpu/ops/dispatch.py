"""Kernel dispatch: where a Pallas kernel may run, which path an op took.

Every ``pallas_call`` in ``adapt_tpu.ops`` is COMPILED on a TPU and
INTERPRETED on the CPU backend (the tests' virtual mesh); no other
backend is served. The three questions every dispatcher asks — am I on
a TPU, may I interpret, which implementation did ``prefer`` resolve to —
are answered here once, and every resolution is booked so that a route
to the XLA oracle is visible (``kernel_dispatch_stats``, exported as
``engine.kernel_dispatch.*`` gauges by ``utils.profiling``).
"""

from __future__ import annotations

import jax

#: Last-resolved path + lifetime counts per op. Counts move at TRACE
#: time (dispatch is resolved when the surrounding program lowers, not
#: per executed tick), so the books answer "which path is this serving
#: program built on".
_KERNEL_DISPATCHES: dict[str, dict[str, float]] = {}


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def device_cores() -> int:
    """TensorCores the first device reports (a v5e: 1; 1 where the
    backend does not say)."""
    return getattr(jax.devices()[0], "num_cores", None) or 1


def pallas_interpret() -> bool:
    """THE ``interpret=`` decision for every ``pallas_call``: compiled
    on a TPU, interpreted on the CPU backend, an error anywhere else —
    a kernel that quietly interprets on an unexpected backend would
    report that backend's walls under a device metric."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"adapt_tpu's Pallas kernels compile for TPU and interpret on "
        f"CPU; backend {backend!r} is neither"
    )


def _books(op: str) -> dict[str, float]:
    return _KERNEL_DISPATCHES.setdefault(
        op, {"pallas": 0.0, "xla": 0.0, "last": 0.0}
    )


def record_kernel_dispatch(op: str, path: str) -> None:
    """Record one dispatch resolution for ``op`` (``"pallas"`` or
    ``"xla"``)."""
    d = _books(op)
    d[path] += 1.0
    d["last"] = 1.0 if path == "pallas" else 0.0


def record_kernel_choice(op: str, **choices: float) -> None:
    """Book what a kernel dispatcher derived for ``op`` from its
    operands (heads a grid step, the split), beside the path it took:
    the newest resolution's values, so a run can say what engaged."""
    _books(op).update({k: float(v) for k, v in choices.items()})


def kernel_dispatch_stats() -> dict[str, dict[str, float]]:
    """Snapshot of the per-op dispatch books (copies — safe to mutate)."""
    return {op: dict(d) for op, d in _KERNEL_DISPATCHES.items()}


def resolve_prefer(
    op: str, prefer: str | None, unsupported: str | None, auto: bool
) -> bool:
    """THE ``prefer`` rule every dispatcher shares; returns True when
    the Pallas kernel serves ``op`` and books the decision either way.

    ``unsupported`` is None when the kernel can serve these operands,
    else the constraint they break, in words. ``prefer=None`` takes the
    kernel iff it is supported and ``auto`` (each op's own measured or
    stated rule) says so. ``"xla"`` forces the oracle. ``"pallas"``
    forces the kernel, and on operands it cannot serve RAISES with the
    reason: serving the oracle under a name that asked for the kernel
    is the silent perf cliff these books exist to expose."""
    if prefer not in (None, "pallas", "xla"):
        raise ValueError(
            f"prefer={prefer!r}: expected None, 'pallas' or 'xla'"
        )
    if prefer == "pallas" and unsupported:
        raise ValueError(f"{op}: prefer='pallas' but {unsupported}")
    use_kernel = not unsupported and (
        auto if prefer is None else prefer == "pallas"
    )
    record_kernel_dispatch(op, "pallas" if use_kernel else "xla")
    return use_kernel
