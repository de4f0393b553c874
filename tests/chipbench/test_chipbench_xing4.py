"""``xing4.0-29b-a4b`` and its cell ``xing4_longgen8k`` as
``BENCHMARK.json`` holds them: the file against the catalog, what the
manifest gained (after what was there; the ``startup.*`` lists joined
since PR 52), the readers on hand-made records, and the cell's
rehearsal in both trace modes
(``test_chipbench_run_loop.py::test_rehearsal_walks_the_cell``'s list
is written out). The file's four controls run in
``test_chipbench_run_loop_xing4.py``."""

import json
from pathlib import Path

import pytest

from chipbench import manifest as mf
from chipbench import run as bench_run
from chipbench import traffic as tg
from chipbench import xing4_readers as xr
from chipbench import xing4_reference as ref
from run_loop_cases import STARTUP_REHEARSED
from paired_trace import trace_of

ROOT = Path(__file__).parents[2]
CELL = "xing4_longgen8k"

BM = mf.load(ROOT)
CONFIG = mf.config_of(BM, mf.cell(BM, CELL), ROOT)
DERIVED = {
    "n_routed_experts_published", "positions_served", "num_experts",
    "mlp_layer_types", "sliding_windows",
}


def test_the_file_holds_the_published_keys_twice_and_equal():
    model = CONFIG["model"]
    assert set(model) - set(CONFIG) == DERIVED == set(CONFIG["derived"])
    for key in set(model) - DERIVED:
        assert CONFIG[key] == model[key], key
    assert CONFIG["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers",
    ]
    assert CONFIG["published"] == {
        "num_hidden_layers": 40, "n_routed_experts": 64,
        "vocab_size": 131072, "num_nextn_predict_layers": 1,
    }
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():
        [entry] = [
            e for e in map(json.loads, catalog.read_text().splitlines())
            if e["name"] == "Xing4.0-29B-A4B"
        ]
        assert CONFIG["source"] == entry["source_url"]
        for key, value in entry["config"].items():
            if key in CONFIG["reduced"]:
                assert CONFIG["published"][key] == value, key
            else:
                assert CONFIG[key] == value, key


@pytest.mark.parametrize("key,value", [
    ("hidden_size", 3584), ("num_attention_heads", 32), ("q_lora_rank", 768),
    ("kv_lora_rank", 512), ("qk_nope_head_dim", 128),
    ("qk_rope_head_dim", 64), ("v_head_dim", 128),
    ("intermediate_size", 9216), ("moe_intermediate_size", 1024),
    ("num_experts_per_tok", 4), ("routed_scaling_factor", 2),
    ("hc_mult", 4), ("hc_sinkhorn_iters", 20),
])
def test_every_published_width_is_kept(key, value):
    assert CONFIG[key] == CONFIG["model"][key] == value
    assert key not in CONFIG["reduced"]


def test_the_cut_stays_within_the_guides_floors():
    m = CONFIG["model"]
    assert m["num_hidden_layers"] - m["first_k_dense_replace"] == 4
    assert m["n_routed_experts"] == 8 and m["n_routed_experts_published"] == 64
    assert m["vocab_size"] * 8 == CONFIG["published"]["vocab_size"]
    assert m["mlp_layer_types"] == ["dense"] * 2 + ["sparse"] * 4
    assert m["num_experts"] == m["n_routed_experts"]


def test_the_reference_states_what_the_file_says():
    m, arch = CONFIG["model"], ref.ARCH
    assert arch["rope_base"] == m["rope_theta"]
    assert arch["eps"] == m["rms_norm_eps"] and arch["hc_eps"] == m["hc_eps"]
    assert arch["hc_iters"] == m["hc_sinkhorn_iters"]
    assert arch["hc_clamp"] == (
        m["mhc_h_res_clamp_min"], m["mhc_h_res_clamp_max"]
    )
    assert arch["top_k"] == m["num_experts_per_tok"]
    assert arch["scale"] == m["routed_scaling_factor"]
    rs = m["rope_scaling"]
    assert arch["yarn"] == {
        "factor": rs["factor"],
        "original_max": rs["original_max_position_embeddings"],
        "beta_fast": rs["beta_fast"], "beta_slow": rs["beta_slow"],
        "mscale": rs["mscale"], "mscale_all_dim": rs["mscale_all_dim"],
    }
    # every control the reference knows is one the file names: at 128
    # steps a request one_stream, second order, reads false too
    assert tuple(CONFIG["correct"]["controls"]) == ref.CONTROLS
    assert CONFIG["correct"]["sample_steps"] == 128
    assert len(ref.MARGINS) == 4  # one a sparse layer kept
    for key in ("assumed", "memory", "deployment", "reduced_why"):
        assert CONFIG[key], key
    assert "576 values a position" in CONFIG["deployment"]


NAME = "xing4.0-29b-a4b"
#: What the manifest held before this cell, in its order. Held to
#: what stood BEFORE the entry only: a later cell, configuration or
#: metric comes after it and moves nothing here.
CELLS_BEFORE = [
    "gpt2xl_chat", "cgpt1b3_batchgen", "gpt2xl_doc", "kexaone_longgen",
    "falconh1_longgen",
]
CONFIGS_BEFORE = [
    "gpt2-xl", "cerebras-gpt-1.3b", "k-exaone-236b-a23b",
    "falcon-h1-34b-instruct",
]
NEW_METRICS = ["kernel.latent_decode_roofline", "mla.decode_share_pct"]


def _before(entries, name):
    names = [e["name"] for e in entries]
    return names[: names.index(name)]


def test_the_entries_are_appended_and_the_file_stays_in_its_limits():
    """What was there stays first and in its order; on every list the
    cell joined, only cells that were there stand before it."""
    assert _before(BM["workloads"], CELL) == CELLS_BEFORE
    assert _before(BM["configs"], NAME) == CONFIGS_BEFORE
    config = next(c for c in BM["configs"] if c["name"] == NAME)
    assert config["file"] == f"chipbench/configs/{NAME}.json"
    for m in BM["end_to_end"] + BM["per_layer"]:
        cells = m.get("workloads", [])
        if CELL in cells:
            assert set(cells[: cells.index(CELL)]) <= set(CELLS_BEFORE), (
                m["name"]
            )
    # a later PR may list a directory of its own after these two
    assert (BM["run_seconds"], BM["paths"][:2]) == (
        51, ["chipbench", "tests/chipbench"]
    )
    assert len((ROOT / "BENCHMARK.json").read_text()) <= 64 * 1024


def test_the_manifest_gains_the_cell_and_nothing_else_moves():
    cell = mf.cell(BM, CELL)
    assert cell == {**cell, "config": NAME, "traffic": "longgen8k",
                    "chips": 1}
    e2e = [m["name"] for m in mf.metrics_of(BM, CELL, "end_to_end")]
    assert e2e == ["out_tok_per_s", "setup_s"]
    layer = {m["name"] for m in mf.metrics_of(BM, CELL, "per_layer")}
    assert layer >= set(NEW_METRICS) | {
        "sched.slots_active_mean", "kv.pool_peak_pct.batch",
        "tick.host_ms.batch", "model.decode_step_ms.batch",
        "model.prefill_ms_per_ktok.batch",
        "moe.tokens_per_expert_mean", "moe.load_max_over_mean",
    } | {f"tick.idle_{k}_ms.batch" for k in (
        "admit", "first_token", "launch", "fetch", "commit", "outside")}
    # not its kernels: no paged, grouped, state-space or delta-rule one
    assert not layer & {
        "kernel.paged_decode_batch_roofline", "kernel.paged_chunk_roofline",
        "kernel.paged_decode_grouped_roofline", "kernel.ssm_step_roofline",
        "kernel.kda_step_roofline", "kernel.expert_product_roofline",
    }
    for m in BM["per_layer"]:
        if CELL in m.get("workloads", []):
            # where set-up goes (the seven startup.*), else the rate
            assert m["moves"] == (
                "setup_s" if m["name"].startswith("startup.")
                else "out_tok_per_s"
            ), m["name"]
            assert callable(mf.reader_of(BM, m["name"], ROOT))
    # its own two came together, the kernel's roofline first
    assert _before(BM["per_layer"], NEW_METRICS[1])[-1] == NEW_METRICS[0]
    for name in NEW_METRICS:
        m = next(m for m in BM["per_layer"] if m["name"] == name)
        assert m["workloads"][0] == CELL, name


def test_the_traffic_fills_every_page_a_slot_may_hold():
    traffic = mf.traffic_of(BM, mf.cell(BM, CELL), ROOT)
    pairs = tg.templates(traffic, 8192)
    assert len(pairs) == 32
    # the evenly spaced quantiles stop short of the bounds: 7929 tokens
    assert max(p + o for p, o in pairs) == 7929
    assert -(-7929 // 128) == 62
    assert all(64 <= p <= 256 and 2048 <= o <= 7936 for p, o in pairs)
    serving = CONFIG["serving"]
    assert serving["prompt_buckets"][-1] == 8192
    assert max(p for p, _ in pairs) <= serving["prompt_buckets"][0]


def _record():
    return dict(
        shape=dict(heads=32, layers=6, latent_row=576, latent_values=512),
        serving=dict(chunk=8), itemsize=2,
        trace=dict(t0=0.0, t1=10.0),
        ticks=[(1.0, 2.0, 2, 0), (3.0, 4.0, 0, 0), (11.0, 12.0, 2, 0)],
        tick_contexts=[(1000, 3000), (), (5, 5)],
    )


def _trace(ops, modules):
    return trace_of(_record(), ops, modules)


def test_the_readers_find_nothing_where_the_program_has_no_such_kernel():
    rec = _record()
    for reader in (xr.latent_decode_roofline, xr.decode_share_pct):
        assert reader(None, rec, "TPU v5e") is None
        assert reader(_trace({"_paged_impl": 1.0}, {}), rec, "TPU v5e") is None
    # a parent's records: the kernel's name in a trace, no latent shape
    old = {**rec, "shape": dict(heads=32, layers=6)}
    seen = _trace({xr.KERNEL: 1.0}, {"_step_chunk": (1, 4.0)})
    assert xr.latent_decode_roofline(seen, old, "TPU v5e") is None
    assert xr.decode_share_pct(seen, rec, "TPU v5e") == 25.0
    # one traced tick that decoded: 2 rows at 4000 positions, 8 steps,
    # 6 layers; bytes are the larger floor
    nbytes = sum(
        (4000 + 2 * j) * 576 * 2 + 2 * 32 * 1088 * 2 for j in range(8)
    ) * 6
    got = xr.latent_decode_roofline(seen, rec, "TPU v5e")
    assert got == pytest.approx(100.0 * nbytes / 819e9 / 1.0)


def rehearse(capsys, *argv):
    assert bench_run.main([
        "--rehearse", "--seconds", "1.5", "--workload", CELL, *argv,
    ]) == 0
    out = capsys.readouterr().out
    assert not any(ln.lstrip().startswith("{") for ln in out.splitlines())
    assert "hist " not in out and "setup:" not in out
    return [ln for ln in out.splitlines() if ln.startswith("rehearsal ")]


def test_the_rehearsal_walks_the_cell_in_both_trace_modes(capsys):
    plain, traced = rehearse(capsys)
    assert "correct=True" in plain and "failed=0" in plain
    assert "would report ['out_tok_per_s', 'setup_s']" in plain
    # No device plane on the CPU: the device readers return nothing,
    # the counters' readers report.
    assert "correct=True" in traced
    assert "roofline" not in traced and "decode_share" not in traced
    assert "moe.tokens_per_expert_mean" in traced
    assert "kv.pool_peak_pct.batch" in traced
    # where set-up goes: what the program books of the seven without a
    # persistent cache
    for name in STARTUP_REHEARSED:
        assert f"'{name}'" in traced, name
