"""A hand-made trace for the readers' tests, shaped as the driver's loop
leaves one (``chipbench/decode_runs.py``): every tick of the records
inside the traced window is a ``chipbench.tick`` span, one that had live
rows launches a run of the decode program (``engine.launch``, then
``_step_chunk`` on the device), the operations run inside the run of
the first such tick, and the device goes on to another program after
the last run (so that run is whole). One second of the records' clock is 1e9 ns
of the trace's."""

from chipbench import xtrace
from chipbench.decode_runs import LAUNCH, PROGRAM, TICK

NS = 1_000_000_000


def trace_of(rec, ops, modules):
    """``ops`` is {operation: device seconds}, ``modules`` {program:
    (runs, device seconds)}; the decode program's seconds are its first
    run's, and the other runs take 1 ms each. Without the decode program
    among ``modules`` nothing runs on the device but the ``ops``, under
    no program."""
    tr = rec["trace"]
    host = [
        (tr["t0"] * NS - 10, tr["t0"] * NS, xtrace.WINDOW_OPEN),
        (tr["t1"] * NS, tr["t1"] * NS + 10, xtrace.WINDOW_CLOSE),
    ]
    dev_ops, dev_modules, at = [], [], None
    seconds = modules.get(PROGRAM, (0, 0.0))[1]
    for (t0, t1, *_), contexts in zip(rec["ticks"], rec["tick_contexts"]):
        if not (tr["t0"] <= t0 and t1 <= tr["t1"]):
            continue
        host.append((t0 * NS, t1 * NS, TICK))
        if not contexts:
            continue
        host.append((t0 * NS + 100, t0 * NS + 200, LAUNCH))
        if PROGRAM in modules:
            start = t0 * NS + 200
            length = seconds * NS if at is None else NS // 1000
            dev_modules.append((start, start + length, PROGRAM))
            at = start if at is None else at
    if dev_modules:
        end = max(e for _, e, _ in dev_modules)
        dev_modules.append((end, end + 1000, "prefill"))  # what ran next
    at = tr["t0"] * NS if at is None else at
    for name, s in ops.items():
        dev_ops.append((at, at + s * NS, name))
        at += s * NS
    for name, (runs, s) in modules.items():
        if name != PROGRAM:
            dev_modules += [(0, s * NS / runs, name)] * runs
    return xtrace.Trace([xtrace.DeviceTrace(dev_ops, dev_modules)], host)
