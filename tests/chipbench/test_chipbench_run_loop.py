"""The run loop, reached through the CPU rehearsal at tiny widths, and
addition by data: another ARCHITECTURE (its configuration, builder,
plain reference, traffic mix, per-layer metric and cell), found and
run with no edit to a file that is there."""

import argparse
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import run as bench_run
from chipbench import decode_runs, xtrace, yardstick
from run_loop_cases import ADDED, ROOT, control_cases
from run_loop_cases import rehearse as _rehearse

#: The added cells (``run_loop_cases.ADDED``): the mixture in float32,
#: and served in bfloat16, held to a reference that says which
#: positions it vouches for (``another_arch/plain.py``).
ADDED_CELL, ADDED_BF16 = ADDED
#: Metrics that are there and that the added cell joins by appending
#: its name to their ``workloads`` in BENCHMARK.json, and nowhere else.
JOINED = (
    "tick.host_ms.batch", "model.decode_step_ms.batch",
    "kernel.paged_decode_batch_roofline",
)
NEW_METRIC = "kernel.query_heads_per_kv_head"
#: The configurations whose controls this file walks: a closed list.
#: Every other configuration has ``test_chipbench_run_loop_<its name>.py``
#: (``run_loop_cases``), so one appended lengthens no file that is there.
CONFIGS = ("gpt2-xl", "cerebras-gpt-1.3b", *ADDED.values())


def _tree_hash(directory: Path) -> str:
    h = hashlib.sha256()
    for f in sorted(directory.rglob("*")):
        if f.is_file() and "__pycache__" not in f.parts:
            h.update(str(f.relative_to(directory)).encode())
            h.update(f.read_bytes())
    return h.hexdigest()


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    """What a later PR does for an architecture the harness has never
    seen: a directory of its own beside an untouched ``chipbench/``,
    and entries in ``BENCHMARK.json``. Returns the root and the hash of
    the copied ``chipbench/`` as it was before anything ran."""
    root = tmp_path_factory.mktemp("added")
    shutil.copytree(
        ROOT / "chipbench", root / "chipbench",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    shutil.copytree(
        Path(__file__).parent / "another_arch", root / "chipbench_more",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    bm["paths"].append("chipbench_more")
    for cell, config in ADDED.items():
        body = json.loads(
            (root / f"chipbench_more/configs/{config}.json").read_text()
        )
        bm["configs"].append({
            "name": config, "source": body["source"],
            "file": f"chipbench_more/configs/{config}.json", "reduced": [],
            "why": "addition-by-data test: GQA, rotary positions, top-2 "
            f"mixture, served in {body['dtype']}",
        })
        bm["workloads"].append({
            "name": cell, "config": config, "traffic": "bursts",
            "chips": 1, "why": "addition-by-data test",
        })
    for m in bm["end_to_end"] + bm["per_layer"]:
        if m["name"] in ("out_tok_per_s", *JOINED):
            m["workloads"].extend(ADDED)
    bm["per_layer"].append({
        "name": NEW_METRIC, "unit": "heads", "better": "lower",
        "source": "program_counter", "layer": "attention kernels",
        "moves": "out_tok_per_s", "workloads": list(ADDED),
    })
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root, _tree_hash(root / "chipbench")


def _cell_argv(request, cell):
    """--workload, and for an added cell the --root it lives under."""
    if cell not in ADDED:
        return ["--workload", cell]
    root, _ = request.getfixturevalue("added")
    return ["--root", str(root), "--workload", cell]


@pytest.mark.parametrize(
    "cell,e2e",
    [
        ("gpt2xl_chat", "['itl_p95_ms', 'setup_s']"),
        ("cgpt1b3_batchgen", "['out_tok_per_s', 'setup_s']"),
        ("gpt2xl_doc", "['out_tok_per_s', 'setup_s']"),
        (ADDED_CELL, "['out_tok_per_s', 'setup_s']"),
        (ADDED_BF16, "['out_tok_per_s', 'setup_s']"),
    ],
)
def test_rehearsal_walks_the_cell(request, capsys, cell, e2e):
    plain, traced = _rehearse(capsys, *_cell_argv(request, cell))
    assert "correct=True" in plain and "failed=0" in plain
    assert f"would report {e2e}" in plain
    # The traced pass reports host-side per-layer metrics only: no
    # device plane exists on the CPU, so device readers return nothing.
    assert "correct=True" in traced
    assert "roofline" not in traced and "decode_step_ms" not in traced


def test_two_rehearsals_of_one_cell_at_once_are_both_correct():
    """Two test workers may rehearse one cell with ``--trace 1`` at the
    same time (a cell's walk in one file, its readers' in another):
    each traces into a directory of its own, so neither empties the
    other's under its reader (which ended that process: ``Failed to
    parse XSpace``)."""
    argv = [sys.executable, str(ROOT / "chipbench/run.py"), "--rehearse",
            "--seconds", "1.5", "--workload", "gpt2xl_chat"]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    both = [
        subprocess.Popen(argv, env=env, text=True, stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT)
        for _ in range(2)
    ]
    try:
        outs = [p.communicate(timeout=600)[0] for p in both]
    finally:
        for p in both:
            p.kill()
            p.wait(timeout=60)
    for p, out in zip(both, outs):
        assert p.returncode == 0, out[-2000:]
        lines = [ln for ln in out.splitlines() if ln.startswith("rehearsal ")]
        assert len(lines) == 2 and all("correct=True" in ln for ln in lines)


@pytest.mark.parametrize("cell,control", control_cases(only=CONFIGS))
def test_a_control_makes_the_run_incorrect(request, capsys, cell, control):
    """The self-test of `correct`: with one block (or, for a mixture,
    one expert) left out of the plain reference THE CONFIGURATION
    NAMES, the served logprobs must disagree: one untraced pass
    (``run.py`` walks no traced one under a fault). Every configuration
    but ``CONFIGS`` has a file of its own
    (``test_chipbench_run_loop_*.py``): under ``--dist loadfile`` one
    file is one worker's."""
    (plain,) = _rehearse(
        capsys, *_cell_argv(request, cell), "--fault", control
    )
    assert "correct=False" in plain


def test_a_fault_the_configuration_does_not_name_is_refused(capsys):
    """Before any weight is drawn: the GPT-2 reference knows no
    expert."""
    with pytest.raises(SystemExit, match="drop_expert"):
        bench_run.main(["--rehearse", "--workload", "gpt2xl_chat",
                        "--fault", "drop_expert"])
    assert "correctness:" not in capsys.readouterr().out


def _one_pass(root, cell, trace=0):
    """One rehearsal pass of one cell through ``run_one``."""
    args = argparse.Namespace(
        seed=0, seconds=1.5, trace=trace, rehearse=True, fault="", sweep="",
    )
    return bench_run.run_one(bench_run.mf.load(root), cell, args, root)


def test_a_broken_served_path_makes_the_run_incorrect(capsys, monkeypatch):
    """The fault in the program's place, not the reference's: one served
    answer altered where the batcher hands it out, the rest of the run
    as it is (only the look for a chip is the rehearsal's)."""
    from adapt_tpu.runtime.continuous import ContinuousBatcher

    logprobs = ContinuousBatcher.logprobs

    def altered(self, rid):
        out = logprobs(self, rid).copy()
        out[-1] += 0.5
        return out

    monkeypatch.setattr(ContinuousBatcher, "logprobs", altered)
    _one_pass(ROOT, "gpt2xl_chat")
    out = capsys.readouterr().out
    assert "-> WRONG" in out and "correct=False" in out


def test_records_carry_the_windows_counters_and_each_ticks_contexts(
    added, capsys, monkeypatch
):
    """What a new mechanism's readers need and could not reach:
    the window's counter deltas and gauges, the pool's peak by every
    ``pages_in_use*`` key of ``stats()``, and each tick's contexts."""
    from chipbench import lm_engine

    root, _ = added
    seen = {}
    run_cell = lm_engine.run_cell

    def keep(*a):
        seen.update(run_cell(*a))
        return seen

    monkeypatch.setattr(lm_engine, "run_cell", keep)
    _one_pass(root, ADDED_BF16, trace=1)
    assert "vouched 21 of 24 (at least 18)" in capsys.readouterr().out
    rec = seen["records"]
    assert rec["counters"]["continuous.ticks"] > 0  # a delta, not a total
    assert rec["counters"]["continuous.ticks"] < len(rec["ticks"])
    assert rec["gauges"]["memory.pool_bytes"] > 0
    assert rec["pool_peaks"] == {"pages_in_use": rec["pool_peak_pages"]}
    assert rec["pool_peak_pages"] > 0
    assert len(rec["tick_contexts"]) == len(rec["ticks"]) > 0
    assert any(len(c) > 1 for c in rec["tick_contexts"])
    for contexts, tick in zip(rec["tick_contexts"], rec["ticks"]):
        assert sum(contexts) == tick[3]
    assert any("vouched 21 of 24" in ln for ln in seen["compared"])


@pytest.mark.parametrize(
    "slots,pairs,pages",
    [
        (32, [(247, 80), (64, 290)], 32 * 3 + 1),  # 354 tokens: 3 pages
        (8, [(768, 64)], 8 * 7 + 1),  # 832 tokens: 7 pages
        (24, [(256, 768)], 24 * 8 + 1),  # 1024 tokens: 8 pages
        (4, [(32, 32)], 4 * 3 + 1),  # the correctness sample's 309 tokens
    ],
)
def test_pool_rule_gives_every_slot_the_longest_request(slots, pairs, pages):
    from chipbench.lm_engine import pool_pages

    serving = {"slots": slots, "page_size": 128, "prefill_chunk": 256,
               "prompt_buckets": [256, 384]}
    assert pool_pages(serving, pairs, 1024) == pages


def test_a_run_without_the_chip_fails_and_prints_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        bench_run.main(["--workload", "gpt2xl_chat", "--seconds", "1"])
    assert e.value.code not in (0, None)
    assert "{" not in capsys.readouterr().out


@pytest.mark.parametrize("key", ["engine", "builder", "reference", "correct"])
def test_a_configuration_that_does_not_say_is_an_error(added, key, capsys):
    """No default engine, builder, reference or tolerance: a default
    would be one architecture's."""
    root, _ = added
    path = root / "chipbench_more/configs/tiny-moe.json"
    whole = path.read_text()
    cfg = json.loads(whole)
    del cfg[key]
    path.write_text(json.dumps(cfg))
    try:
        with pytest.raises(KeyError, match=key):
            bench_run.main(["--rehearse", "--seconds", "1", "--root",
                            str(root), "--workload", ADDED_CELL])
    finally:
        path.write_text(whole)
    assert "rehearsal " not in capsys.readouterr().out


def test_addition_by_data(added, capsys, monkeypatch):
    """A GQA + rotary + top-2-mixture decoder is served and held to its
    own reference, joins metrics that are there through BENCHMARK.json
    alone, and brings a metric that reads ``records["shape"]``; the
    copied ``chipbench/`` keeps its hash."""
    root, before = added
    plain, traced = _rehearse(
        capsys, "--root", str(root), "--workload", ADDED_CELL
    )
    assert "correct=True" in plain and "failed=0" in plain
    assert NEW_METRIC in traced
    # The joined metrics read a device plane, and a CPU run has none:
    # put one in the trace's place (one kernel call, one step program,
    # one tick span; the run it holds whole is the last one that a tick
    # with live rows launched: how ticks and runs pair on a chip is
    # test_chipbench_decode_runs.py's) and the readers that are there
    # answer for the new cell from its ``shape`` and its records.
    device = xtrace.DeviceTrace(
        ops=[(1_000, 9_000, "_paged_impl")],
        modules=[(0, 10_000, "_step_chunk")],
    )
    monkeypatch.setattr(xtrace, "load", lambda path: xtrace.Trace(
        [device], [(0, 20_000, "chipbench.tick")]
    ))
    monkeypatch.setattr(decode_runs, "_pair", lambda trace, rec: [(
        max(i for i, c in enumerate(rec["tick_contexts"]) if c), 0, 10_000
    )])
    monkeypatch.setitem(yardstick.PEAKS, "cpu", (1e12, 1e11))
    _, traced = _rehearse(
        capsys, "--root", str(root), "--workload", ADDED_CELL
    )
    for name in (*JOINED, NEW_METRIC):
        assert name in traced, name
    assert _tree_hash(root / "chipbench") == before
