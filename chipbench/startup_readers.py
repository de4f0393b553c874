"""Readers of the ``startup.*`` metrics: what set-up cost inside the
program, from the gauges its compile account keeps
(``adapt_tpu.utils.profiling.CompileSentinel``) and its batcher's
constructor stamps. The gauges are process-cumulative and
``records["gauges"]`` holds them as they stood at the window's close;
on a correct run nothing compiled inside the window, so that is
set-up's account. A program without the gauge (any commit before the
account; a persistent cache that is off, for the cache's own) gives
None, and the line leaves the metric out.
"""

from __future__ import annotations


def _gauge(rec, name: str):
    value = rec.get("gauges", {}).get(name)
    return None if value is None else float(value)


def programs(trace, rec, kind):
    """Backend compiles or cache loads: the ``compiles N`` of the
    engine's own ``setup:`` line, counted by the program."""
    return _gauge(rec, "engine.compile.programs")


def trace_s(trace, rec, kind):
    """Python to jaxpr, outermost spans only (a program's library
    calls trace inside it)."""
    return _gauge(rec, "engine.compile.trace_s")


def lower_s(trace, rec, kind):
    """Jaxpr to an MLIR module."""
    return _gauge(rec, "engine.compile.lower_s")


def backend_s(trace, rec, kind):
    """XLA's compile, or the load from the persistent cache."""
    return _gauge(rec, "engine.compile.backend_s")


def cache_misses(trace, rec, kind):
    """Programs compiled and written to the persistent cache: 0 on a
    warm run."""
    return _gauge(rec, "engine.compile.cache_misses")


def step_program_s(trace, rec, kind):
    """The decode step program's three stages, every variant: the
    program whose trace and lowering grow with the model's depth."""
    return _gauge(rec, "engine.compile.seconds.continuous.step_chunk")


def construct_s(trace, rec, kind):
    """The batcher's constructor: pools, state, tables."""
    return _gauge(rec, "engine.construct_s")
