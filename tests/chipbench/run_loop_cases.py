"""What the ``test_chipbench_run_loop*.py`` files share: the rehearsal
call and the list of (cell, control) cases, every control of every
configuration once. The cases are cut by configuration into files,
because under ``--dist loadfile`` one file is one worker's and the
K-EXAONE rehearsals alone take minutes."""

import json
from pathlib import Path

from chipbench import run as bench_run

ROOT = Path(__file__).parents[2]
#: The cells ``another_arch/`` adds, and their configurations.
ADDED = {"tiny_moe_bursts": "tiny-moe", "tiny_moe_bf16_bursts": "tiny-moe-bf16"}


def rehearse(capsys, *argv):
    assert bench_run.main(["--rehearse", "--seconds", "1.5", *argv]) == 0
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines() if ln.startswith("rehearsal ")]
    # A rehearsal prints no result object and no timing.
    assert not any(ln.lstrip().startswith("{") for ln in out.splitlines())
    assert "hist " not in out and "setup:" not in out
    return lines


def control_cases(only=(), but=()):
    """(cell, control): every control of every configuration, the
    benchmark's and the added ones, once, in its first cell; of the
    configurations ``only`` names, or of all ``but`` those. A file
    that names none has ``drop_block`` (``run.py``)."""
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    files = {c["name"]: ROOT / c["file"] for c in bm["configs"]}
    first = {}
    for w in bm["workloads"]:
        first.setdefault(w["config"], w["name"])
    for cell, config in ADDED.items():
        first[config] = cell
        files[config] = (
            Path(__file__).parent / f"another_arch/configs/{config}.json"
        )
    return [
        (first[config], control)
        for config, f in files.items()
        if (config in only if only else config not in but)
        for control in json.loads(f.read_text())["correct"].get(
            "controls", ["drop_block"])
    ]
