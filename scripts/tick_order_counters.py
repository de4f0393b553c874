"""The benchmark's command with one more line before the result: what
the tick loop's own counters read inside the measured window, which
``chipbench/run.py`` snapshots (``records["counters"]``) and no
per-layer metric prints yet.

    chiprun -- python3 scripts/tick_order_counters.py \
        --workload gpt2xl_doc --seed 7 --seconds 51 --trace 1 [--root DIR]

Every argument goes to ``chipbench/run.py`` of ``--root`` (default:
this checkout; another one is an unpacked ``git archive`` in an
ignored directory). The line:

    tick order: overlapped A synchronous B (share S) \
        rows_past_end R of N rows decoded (share T)

``overlapped`` / ``synchronous`` are ``runtime.ticks_overlapped`` /
``runtime.ticks_synchronous`` (commits whose dispatch was, or was not,
followed by another dispatch before it landed), ``rows_past_end`` is
``runtime.rows_past_end`` (rows a tick decoded for a request the
commit before it had retired), rows decoded is what ``tick()``
returned, summed over the window's ticks. A checkout without the
counters reads 0 for each.
"""

from __future__ import annotations

import sys
from pathlib import Path


def main(argv: list[str]) -> int:
    root = Path(__file__).resolve().parents[1]
    if "--root" in argv:
        root = Path(argv[argv.index("--root") + 1]).resolve()
    sys.path.insert(0, str(root))
    from chipbench import manifest as mf
    from chipbench import run

    part_of = mf.part_of

    def told(config, part):
        fn = part_of(config, part)
        if part != "engine":
            return fn

        def engine(*args, **kw):
            out = fn(*args, **kw)
            if out is not None:
                rec = out["records"]
                c = rec["counters"]
                over = c.get("runtime.ticks_overlapped", 0.0)
                sync = c.get("runtime.ticks_synchronous", 0.0)
                past = c.get("runtime.rows_past_end", 0.0)
                rows = sum(
                    t[2] for t in rec["ticks"]
                    if rec["t_open"] < t[1] <= rec["t_close"]
                )
                print(
                    f"tick order: overlapped {over:.0f} "
                    f"synchronous {sync:.0f} "
                    f"(share {over / max(over + sync, 1):.4f}) "
                    f"rows_past_end {past:.0f} of {rows} rows decoded "
                    f"(share {past / max(rows, 1):.4f})",
                    flush=True,
                )
            return out

        return engine

    mf.part_of = told
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
