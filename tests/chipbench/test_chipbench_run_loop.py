"""The run loop, reached through the CPU rehearsal at tiny widths, and
addition by data: a new configuration, traffic mix, per-layer metric
and cell, found and run with no edit to a file that is there."""

import json
import shutil
from pathlib import Path

import pytest

from chipbench import run as bench_run

ROOT = Path(__file__).parents[2]


def _rehearse(capsys, *argv):
    assert bench_run.main(["--rehearse", "--seconds", "1.5", *argv]) == 0
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines() if ln.startswith("rehearsal ")]
    # A rehearsal prints no result object and no timing.
    assert not any(ln.lstrip().startswith("{") for ln in out.splitlines())
    assert "hist " not in out and "setup:" not in out
    return lines


@pytest.mark.parametrize(
    "cell,e2e",
    [
        ("gpt2xl_chat", "['itl_p95_ms', 'setup_s']"),
        ("cgpt1b3_batchgen", "['out_tok_per_s', 'setup_s']"),
        ("gpt2xl_doc", "['out_tok_per_s', 'setup_s']"),
    ],
)
def test_rehearsal_walks_the_cell(capsys, cell, e2e):
    plain, traced = _rehearse(capsys, "--workload", cell)
    assert "correct=True" in plain and "failed=0" in plain
    assert f"would report {e2e}" in plain
    # The traced pass reports host-side per-layer metrics only: no
    # device plane exists on the CPU, so device readers return nothing.
    assert "correct=True" in traced
    assert "roofline" not in traced and "decode_step_ms" not in traced


def test_a_dropped_block_makes_the_run_incorrect(capsys):
    """The self-test of `correct`: with one block left out of the
    plain reference the served logprobs must disagree."""
    plain, traced = _rehearse(
        capsys, "--workload", "gpt2xl_chat", "--fault", "drop_block"
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


def test_addition_by_data(tmp_path, capsys):
    """What a later PR does: add files and entries, edit nothing."""
    extra = tmp_path / "chipbench_more"
    for d in ("configs", "traffic", "metrics"):
        (extra / d).mkdir(parents=True)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench")
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "chipbench/configs/gpt2-xl.json").read_text())
    cfg["name"] = "tiny-new"
    cfg["rehearse"]["model"]["n_layer"] = 1
    (extra / "configs/tiny-new.json").write_text(json.dumps(cfg))
    (extra / "traffic/bursts.json").write_text(json.dumps({
        "name": "bursts", "loop": "closed", "clients": 3, "cycle": 4,
        "prompt": {"dist": "uniform", "min": 20, "max": 60},
        "output": {"dist": "fixed", "value": 6},
    }))
    (extra / "new_reader.py").write_text(
        "def first_tokens(trace, rec, kind):\n"
        "    return float(len(rec['ttft_ms']))\n"
    )
    (extra / "__init__.py").write_text("")
    (extra / "metrics/client.first_tokens.json").write_text(json.dumps({
        "name": "client.first_tokens", "layer": "client", "unit": "requests",
        "moves": "out_tok_per_s", "workloads": ["tiny_bursts"],
        "reader": "chipbench_more.new_reader:first_tokens",
    }))
    bm["paths"].append("chipbench_more")
    bm["configs"].append({
        "name": "tiny-new", "source": cfg["source"],
        "file": "chipbench_more/configs/tiny-new.json", "reduced": [],
        "why": "addition-by-data test",
    })
    bm["workloads"].append({
        "name": "tiny_bursts", "config": "tiny-new", "traffic": "bursts",
        "chips": 1, "why": "addition-by-data test",
    })
    for m in bm["end_to_end"]:
        if m["name"] == "out_tok_per_s":
            m["workloads"].append("tiny_bursts")
    bm["per_layer"].append({
        "name": "client.first_tokens", "unit": "requests", "better": "higher",
        "source": "host_clock", "layer": "client", "moves": "out_tok_per_s",
        "workloads": ["tiny_bursts"],
    })
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    plain, traced = _rehearse(
        capsys, "--root", str(tmp_path), "--workload", "tiny_bursts"
    )
    assert "correct=True" in plain and "failed=0" in plain
    assert "client.first_tokens" in traced
