"""The run loop, reached through the CPU rehearsal at tiny widths, and
addition by data: another ARCHITECTURE (its configuration, builder,
plain reference, traffic mix, per-layer metric and cell), found and
run with no edit to a file that is there."""

import hashlib
import json
import shutil
from pathlib import Path

import pytest

from chipbench import run as bench_run
from chipbench import xtrace, yardstick

ROOT = Path(__file__).parents[2]
ADDED_CELL = "tiny_moe_bursts"
#: Metrics that are there and that the added cell joins by appending
#: its name to their ``workloads`` in BENCHMARK.json, and nowhere else.
JOINED = (
    "tick.host_ms.batch", "model.decode_step_ms.batch",
    "kernel.paged_decode_batch_roofline",
)
NEW_METRIC = "kernel.query_heads_per_kv_head"


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
    bm["configs"].append({
        "name": "tiny-moe", "source": "https://example.org/addition-by-data-test",
        "file": "chipbench_more/configs/tiny-moe.json", "reduced": [],
        "why": "addition-by-data test: GQA, rotary positions, top-2 mixture",
    })
    bm["workloads"].append({
        "name": ADDED_CELL, "config": "tiny-moe", "traffic": "bursts",
        "chips": 1, "why": "addition-by-data test",
    })
    for m in bm["end_to_end"] + bm["per_layer"]:
        if m["name"] in ("out_tok_per_s", *JOINED):
            m["workloads"].append(ADDED_CELL)
    bm["per_layer"].append({
        "name": NEW_METRIC, "unit": "heads", "better": "lower",
        "source": "program_counter", "layer": "attention kernels",
        "moves": "out_tok_per_s", "workloads": [ADDED_CELL],
    })
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root, _tree_hash(root / "chipbench")


def _rehearse(capsys, *argv):
    assert bench_run.main(["--rehearse", "--seconds", "1.5", *argv]) == 0
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines() if ln.startswith("rehearsal ")]
    # A rehearsal prints no result object and no timing.
    assert not any(ln.lstrip().startswith("{") for ln in out.splitlines())
    assert "hist " not in out and "setup:" not in out
    return lines


def _cell_argv(request, cell):
    """--workload, and for the added cell the --root it lives under."""
    if cell != ADDED_CELL:
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


@pytest.mark.parametrize("cell", ["gpt2xl_chat", ADDED_CELL])
def test_a_dropped_block_makes_the_run_incorrect(request, capsys, cell):
    """The self-test of `correct`: with one block left out of the plain
    reference THE CONFIGURATION NAMES, the served logprobs must
    disagree."""
    plain, traced = _rehearse(
        capsys, *_cell_argv(request, cell), "--fault", "drop_block"
    )
    assert "correct=False" in plain and "correct=False" in traced


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
    # one tick span) and the readers that are there answer for the new
    # cell from its ``shape`` and its records.
    device = xtrace.DeviceTrace(
        ops=[(1_000, 9_000, "_paged_impl")],
        modules=[(0, 10_000, "_step_chunk")],
    )
    monkeypatch.setattr(xtrace, "load", lambda path: xtrace.Trace(
        [device], [(0, 20_000, "chipbench.tick")]
    ))
    monkeypatch.setitem(yardstick.PEAKS, "cpu", (1e12, 1e11))
    _, traced = _rehearse(
        capsys, "--root", str(root), "--workload", ADDED_CELL
    )
    for name in (*JOINED, NEW_METRIC):
        assert name in traced, name
    assert _tree_hash(root / "chipbench") == before
