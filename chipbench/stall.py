"""A watch for the tick that stalls for seconds (PERF.md section 7).

Roughly one run in twenty-five loses seconds inside ONE ``srv.tick()``
and reads a rate far from its set's. The watch changes no number: it
is one thread that sleeps, wakes twenty times a second to note the
wall clock and the process's CPU time, and, when the tick the driver
is in has lasted over a second, writes down where every Python thread
stands and what state the kernel has every task of the process in.
From that a far-off run says of itself which it was:

- the watch kept waking and the main thread's stack is at a blocking
  read: the runtime (or the device) kept the thread waiting;
- the watch was silent for as long as the tick and the process used no
  CPU: the whole process stood still, and the window's counters
  (steal, pressure, faults, the main thread's time on a run queue)
  say whether the machine did;
- silent, and the CPU time ran: a thread held the interpreter.
"""

from __future__ import annotations

import collections
import sys
import threading
import time
import traceback
from pathlib import Path

PERIOD_S = 0.05
LATE_S = 1.0


def _read(path: str) -> str:
    try:
        return Path(path).read_text()
    except OSError:
        return ""


def host_counters(tid: int) -> dict[str, float]:
    """Seconds (faults: counts) the machine has so far spent in ways
    that stall a process; read at both edges of the window."""
    out: dict[str, float] = {}
    cpu = _read("/proc/stat").split("\n", 1)[0].split()
    if len(cpu) > 8:
        out["iowait_s"] = int(cpu[5]) / 100.0
        out["steal_s"] = int(cpu[8]) / 100.0
    for kind in ("cpu", "memory", "io"):
        for line in _read(f"/proc/pressure/{kind}").splitlines():
            if line.startswith("some") and "total=" in line:
                out[f"psi_{kind}_s"] = int(line.rsplit("total=", 1)[1]) / 1e6
    stat = _read("/proc/self/stat").rsplit(")", 1)[-1].split()
    if len(stat) > 9:
        out["majflt"] = float(stat[9])
    sched = _read(f"/proc/self/task/{tid}/schedstat").split()
    if len(sched) > 1:
        out["main_on_cpu_s"] = int(sched[0]) / 1e9
        out["main_runqueue_s"] = int(sched[1]) / 1e9
    return out


def _tasks() -> str:
    """Every task of the process that is not asleep in the ordinary
    way, by name, state and the kernel function it waits in."""
    seen: collections.Counter = collections.Counter()
    for task in Path("/proc/self/task").iterdir():
        stat = _read(f"{task}/stat")
        if ")" not in stat:
            continue
        name = stat[stat.index("(") + 1: stat.rindex(")")]
        state = stat.rsplit(")", 1)[1].split()[0]
        wchan = _read(f"{task}/wchan").strip() or "-"
        if state != "S" or task.name == str(threading.main_thread().native_id):
            seen[f"{name}:{state}:{wchan}"] += 1
    return "  ".join(f"{n}x {k}" for k, n in seen.most_common(12))


def _task_cpu() -> dict[str, tuple[str, float]]:
    """CPU seconds so far of every task of the process, by task id."""
    out = {}
    for task in Path("/proc/self/task").iterdir():
        stat = _read(f"{task}/stat")
        if ")" in stat:
            f = stat.rsplit(")", 1)[1].split()
            name = stat[stat.index("(") + 1: stat.rindex(")")]
            out[task.name] = (name, (int(f[11]) + int(f[12])) / 100.0)
    return out


class StallWatch:
    def __init__(self):
        self.main = threading.main_thread()
        #: The driver sets this around ``srv.tick()``: the tick's start.
        self.tick_t0: float | None = None
        self.samples: list[tuple[float, float]] = []
        self.caught: list[tuple[float, float, str]] = []
        self.edges: list[dict[str, float]] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="chipbench.stallwatch", daemon=True
        )

    def start(self) -> None:
        self.edges.append(host_counters(self.main.native_id))
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join()
        self.edges.append(host_counters(self.main.native_id))

    def _run(self) -> None:
        seen = before = None
        while not self._stop.wait(PERIOD_S):
            now = time.perf_counter()
            self.samples.append((now, time.process_time()))
            t0 = self.tick_t0
            if before is not None and t0 != seen:
                # The caught tick is over: which tasks worked meanwhile.
                at, late, text = self.caught[-1]
                used = sorted(
                    ((cpu - before.get(tid, (name, 0.0))[1], name, tid)
                     for tid, (name, cpu) in _task_cpu().items()),
                    reverse=True,
                )[:4]
                text += f"\nCPU from then to {now - at:.2f} s: " + "  ".join(
                    f"{name}/{tid} {cpu:.2f}" for cpu, name, tid in used
                )
                self.caught[-1] = (at, late, text)
                before = None
            if t0 is None or t0 == seen or now - t0 < LATE_S:
                continue
            seen = t0
            before = _task_cpu()
            frames = sys._current_frames()
            text = [f"tasks: {_tasks()}"]
            for th in threading.enumerate():
                if th is threading.current_thread() or th.ident not in frames:
                    continue
                text.append(f"thread {th.name}:")
                text.extend(
                    s.rstrip() for s in
                    traceback.format_stack(frames[th.ident], limit=8)
                )
            self.caught.append((t0, now - t0, "\n".join(text)))

    def lines(self, t0: float, t1: float, t_open: float) -> list[str]:
        """What the watch saw of the tick from ``t0`` to ``t1`` (the
        window's longest), and the machine's counters over the window."""
        inside = [
            s for s in self.samples if t0 - PERIOD_S <= s[0] <= t1 + PERIOD_S
        ]
        silence = max(
            (b[0] - a[0] for a, b in zip(inside, inside[1:])), default=t1 - t0
        )
        cpu = inside[-1][1] - inside[0][1] if inside else float("nan")
        moved = {
            k: self.edges[-1][k] - v for k, v in self.edges[0].items()
            if k in self.edges[-1]
        } if len(self.edges) > 1 else {}
        out = [
            f"stall watch: in the longest tick ({(t1 - t0) * 1e3:.1f} ms) the "
            f"watch woke {len(inside)} times, longest silence "
            f"{silence * 1e3:.0f} ms, process CPU {cpu:.2f} s  | window: "
            + "  ".join(f"{k} {v:.2f}" for k, v in moved.items())
        ]
        for at, late, text in self.caught:
            out.append(f"stall watch: the tick begun at window+"
                       f"{at - t_open:.1f}s had lasted {late:.2f} s:")
            out.extend("    " + ln for ln in text.splitlines())
        return out
