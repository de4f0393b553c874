"""``solar-open2-250b`` and its cell ``solaropen2_longgen`` as
``BENCHMARK.json`` holds them: the file against the catalog, what the
manifest gained (after what was there; the ``startup.*`` lists joined
since PR 52), the yardstick's counts, the readers on hand-made records,
and ONE rehearsal of the cell's deployment (a module fixture builds
model and batcher once) from which ``correct`` and every control are
read. The cell's walk through ``run.py``, control by control, is
``test_chipbench_run_loop_solar_open2.py``."""

import contextlib
import json
import types
from pathlib import Path

import numpy as np
import pytest

from chipbench import manifest as mf
from chipbench import solar_open2_readers as sr
from chipbench import solar_open2_reference as ref
from chipbench import solar_open2_yardstick as sy
from chipbench import traffic as tg
from paired_trace import trace_of

ROOT = Path(__file__).parents[2]
CELL = "solaropen2_longgen"
NAME = "solar-open2-250b"

BM = mf.load(ROOT)
CONFIG = mf.config_of(BM, mf.cell(BM, CELL), ROOT)
DERIVED = {
    "n_routed_experts_published", "positions_served", "kda_low_rank",
    "num_experts", "mlp_layer_types", "sliding_windows",
}
REDUCED = {"num_hidden_layers": 48, "n_routed_experts": 320,
           "vocab_size": 196608}


def test_the_file_holds_the_published_keys_twice_and_equal():
    model = CONFIG["model"]
    assert set(model) - set(CONFIG) == DERIVED == set(CONFIG["derived"])
    for key in set(model) - DERIVED:
        assert CONFIG[key] == model[key], key
    assert CONFIG["reduced"] == list(REDUCED)
    assert CONFIG["published"] == REDUCED
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():
        [entry] = [
            e for e in map(json.loads, catalog.read_text().splitlines())
            if e["name"] == "Solar-Open2-250B"
        ]
        config = next(c for c in BM["configs"] if c["name"] == NAME)
        assert CONFIG["source"] == config["source"] == entry["source_url"]
        for key, value in entry["config"].items():
            if key in CONFIG["reduced"]:
                assert CONFIG["published"][key] == value, key
            else:
                assert CONFIG[key] == value, key


@pytest.mark.parametrize("key,value", [
    ("hidden_size", 4096), ("num_attention_heads", 64),
    ("num_key_value_heads", 8), ("head_dim", 128),
    ("intermediate_size", 10240), ("moe_intermediate_size", 1280),
    ("num_experts_per_tok", 8), ("n_shared_experts", 1),
    ("routed_scaling_factor", 1), ("use_rope", False),
    ("use_gqa_gate", True), ("kda_allow_neg_eigval", True),
    ("linear_attn_config", {"short_conv_kernel_size": 4, "head_dim": 128,
                            "num_heads": 64, "num_kv_heads": None}),
])
def test_every_published_width_is_kept(key, value):
    assert CONFIG[key] == CONFIG["model"][key] == value
    assert key not in CONFIG["reduced"]


def test_the_cut_is_one_period_and_an_eighth():
    m = CONFIG["model"]
    assert m["num_hidden_layers"] == 4  # GQA, KDA, KDA, KDA
    assert [i in m["gqa_layers"] for i in range(4)] == [True] + [False] * 3
    assert m["n_routed_experts"] * 8 == m["n_routed_experts_published"] == 320
    assert m["vocab_size"] * 8 == CONFIG["published"]["vocab_size"]
    assert m["mlp_layer_types"] == ["sparse"] * 4
    assert m["num_experts"] == m["n_routed_experts"] == 40
    assert set(CONFIG["reduced_why"]) == set(REDUCED)
    for key in ("assumed", "memory", "deployment"):
        assert CONFIG[key], key
    assert "8 chips share each layer" in CONFIG["deployment"]
    # two layers, one of each kind, at toy widths in a rehearsal
    r = CONFIG["rehearse"]["model"]
    assert r["num_hidden_layers"] == 2 and r["n_routed_experts"] == 4


def test_the_reference_states_what_the_file_says():
    m, arch = CONFIG["model"], ref.ARCH
    assert arch["eps"] == m["rms_norm_eps"]
    assert arch["top_k"] == m["num_experts_per_tok"]
    assert arch["scale"] == m["routed_scaling_factor"]
    assert arch["beta_max"] == (2.0 if m["kda_allow_neg_eigval"] else 1.0)
    assert tuple(CONFIG["correct"]["controls"]) == ref.CONTROLS
    assert len(ref.CONTROLS) <= 4 and "drop_expert" in ref.FAULTS
    assert set(ref.CONTROLS) <= set(ref.FAULTS) | set(ref.PRECISION)
    assert "state_bfloat16" in ref.PRECISION  # a reading: correct.why
    assert CONFIG["correct"]["sample_steps"] == 128
    assert len(ref.MARGINS) == m["num_hidden_layers"]
    chunk = CONFIG["serving"]["prefill_chunk"]
    assert ref.SAMPLE_RESET == (40, chunk - 17, chunk + 45)
    for key in ("low_rank", "router", "qk_norm", "decay", "dtype", "weights"):
        assert CONFIG["assumed"][key], key


#: What the manifest held before this cell, in its order. Held to
#: what stood BEFORE the entry only: a later cell, configuration or
#: metric comes after it and moves nothing here.
CELLS_BEFORE = [
    "gpt2xl_chat", "cgpt1b3_batchgen", "gpt2xl_doc", "kexaone_longgen",
    "falconh1_longgen", "xing4_longgen8k",
]
CONFIGS_BEFORE = [
    "gpt2-xl", "cerebras-gpt-1.3b", "k-exaone-236b-a23b",
    "falcon-h1-34b-instruct", "xing4.0-29b-a4b",
]
NEW_METRICS = {"kernel.kda_step_roofline": "higher",
               "kda.step_share_pct": "lower"}


def _before(entries, name):
    names = [e["name"] for e in entries]
    return names[: names.index(name)]


def test_the_manifest_gains_the_cell_after_what_was_there():
    cell = mf.cell(BM, CELL)
    assert cell == {**cell, "config": NAME, "traffic": "longgen", "chips": 1}
    assert _before(BM["workloads"], CELL) == CELLS_BEFORE
    assert _before(BM["configs"], NAME) == CONFIGS_BEFORE
    config = next(c for c in BM["configs"] if c["name"] == NAME)
    assert config["file"] == f"chipbench/configs/{NAME}.json"
    assert config["reduced"] == list(REDUCED)
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
    for m in BM["end_to_end"] + BM["per_layer"]:
        cells = m.get("workloads", [])
        if CELL not in cells:
            continue
        # appended: only cells that were there stand before it
        assert set(cells[: cells.index(CELL)]) <= set(CELLS_BEFORE), m["name"]
        if "moves" in m:
            # where set-up goes (the seven startup.*), else the rate
            assert m["moves"] == (
                "setup_s" if m["name"].startswith("startup.")
                else "out_tok_per_s"
            ), m["name"]
            assert callable(mf.reader_of(BM, m["name"], ROOT))
    per_layer = {m["name"]: m for m in BM["per_layer"]}
    for name, better in NEW_METRICS.items():
        m = per_layer[name]
        assert m["better"] == better and m["workloads"][0] == CELL
        assert m["layer"] == "linear-attention layer"
    assert len((ROOT / "BENCHMARK.json").read_text()) <= 64 * 1024
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200


def test_the_traffic_is_longgen_as_it_was():
    traffic = mf.traffic_of(BM, mf.cell(BM, CELL), ROOT)
    assert traffic["name"] == "longgen" and "serving" not in traffic
    pairs = tg.templates(traffic, CONFIG["model"]["positions_served"])
    assert max(p + o for p, o in pairs) == 1857  # 15 pages of 128: 1920
    serving = CONFIG["serving"]
    assert serving["slots"] in (256, 192) and serving["chunk"] == 8
    assert (serving["page_size"], serving["prefill_chunk"]) == (128, 256)
    assert max(p for p, _ in pairs) <= serving["prompt_buckets"][0]
    assert serving["prompt_buckets"][-1] == 2048


def test_the_yardstick_counts_a_rows_state_once_in_and_once_out():
    flops, nbytes = sy.kda_step_cost(1, 64, 128, 2)
    state = 64 * 128 * 128
    assert nbytes == (
        2 * state * 4  # the float32 state, read and written
        + 3 * 8192 * 2  # q, k, v in bfloat16
        + 8192 * 4 + 64 * 4  # g and beta in float32
        + 8192 * 4  # o in float32
    ) == 8_503_552
    assert flops == 7 * state
    assert flops / nbytes < 1.0  # bytes are the bound, far under the ridge
    assert sy.kda_step_cost(10, 64, 128, 2) == (10 * flops, 10 * nbytes)
    assert sy.kda_step_cost(0, 64, 128, 2) == (0, 0)


def _record():
    return dict(
        shape=dict(heads=64, layers=1, kda_layers=3, kda_heads=64,
                   kda_head_dim=128),
        serving=dict(chunk=8), itemsize=2,
        trace=dict(t0=0.0, t1=10.0),
        ticks=[(1.0, 2.0, 2, 0), (3.0, 4.0, 0, 0), (11.0, 12.0, 2, 0)],
        tick_contexts=[(1000, 300), (), (5, 5)],
    )


def _trace(ops, modules):
    return trace_of(_record(), ops, modules)


def test_the_readers_find_nothing_where_the_program_has_no_such_kernel():
    rec = _record()
    for reader in (sr.kda_step_roofline, sr.kda_step_share_pct):
        assert reader(None, rec, "TPU v5e") is None
        assert reader(
            _trace({"_ssm_step_impl": 1.0}, {}), rec, "TPU v5e"
        ) is None
    # a parent's records: the kernel's name in a trace, no such shape
    old = {**rec, "shape": dict(heads=64, layers=1)}
    seen = _trace({sr.KERNEL: 0.001}, {"_step_chunk": (1, 0.004)})
    assert sr.kda_step_roofline(seen, old, "TPU v5e") is None
    assert sr.kda_step_share_pct(seen, rec, "TPU v5e") == 25.0
    # one traced tick that decoded: 2 rows, 8 steps, 3 layers; the
    # contexts do not enter (a state is as large at any position)
    got = sr.kda_step_roofline(seen, rec, "TPU v5e")
    assert got == pytest.approx(
        100.0 * 2 * 8 * 3 * 8_503_552 / 819e9 / 0.001
    )
    assert 0 < got < 100


# -- one rehearsal: `correct` and every control from one deployment ------------


@pytest.fixture(scope="module")
def sample():
    """The cell's deployment at its rehearsal sizes as
    ``lm_engine.run_cell`` builds it, ONCE; the correctness sample
    served once through ``lm_engine.correctness_sample``: what it
    compared, the served logprobs, the ids it read, the weights."""
    from adapt_tpu.runtime.continuous import ContinuousBatcher
    from chipbench import lm_engine as eng

    traffic = mf.traffic_of(BM, mf.cell(BM, CELL), ROOT)
    model = {**CONFIG["model"], **CONFIG["rehearse"]["model"]}
    serving = {**CONFIG["serving"], **CONFIG["rehearse"]["serving"]}
    lm, variables, shape = mf.part_of(CONFIG, "builder")(
        model, CONFIG["dtype"], 2**31 + 5
    )
    correct = CONFIG["correct"]
    pairs = tg.templates(traffic, shape["max_len"])
    srv = ContinuousBatcher(
        lm, variables, slots=serving["slots"], chunk=serving["chunk"],
        kv_layout="paged", page_size=serving["page_size"],
        pool_pages=eng.pool_pages(
            serving, pairs, shape["max_len"], eng._sample_steps(correct)
        ),
        prefill_chunk=serving["prefill_chunk"],
        prompt_buckets=tuple(serving["prompt_buckets"]),
    )
    kept, claimed = {}, []
    hand_out = srv.logprobs

    def logprobs(rid):
        claimed.append(np.asarray(hand_out(rid), np.float32))
        return claimed[-1]

    def capture(variables, ids, fault=""):
        kept["ids"] = ids
        return ref.next_token_logprobs(variables, ids, fault)

    srv.logprobs = logprobs
    compared = eng.correctness_sample(
        eng.Driver(srv, shape["vocab"], 5, contextlib.nullcontext),
        variables, serving, shape["max_len"], capture, correct,
    )
    stats = srv.stats()
    srv.close()
    return types.SimpleNamespace(
        compared=compared, got=np.concatenate(claimed), ids=kept["ids"],
        variables=variables, stats=stats, serving=serving, correct=correct,
    )


def _judge(s, **kw):
    """``lm_engine.correctness_sample``'s rule over the kept sample,
    against the reference under ``kw`` (a fault, an ``arch``)."""
    from chipbench import lm_engine as eng

    steps = eng._sample_steps(s.correct)
    lens = eng._sample_prompts(s.serving["prefill_chunk"], 2048, steps)
    want, sure = (np.asarray(a) for a in ref.next_token_logprobs(
        s.variables, s.ids, **kw
    ))
    err, mask = [], []
    for row, n in enumerate(lens):
        at = slice(n - 1, n - 1 + steps)
        err.append(want[row, at])
        mask.append(sure[row, at])
    err = np.abs(s.got - np.concatenate(err))
    mask = np.concatenate(mask)
    least = int(np.ceil(s.correct["min_vouched"] * err.size))
    worst = float(err[mask].max()) if mask.any() else float("nan")
    return bool(worst <= s.correct["logprob_tol"] and mask.sum() >= least)


def test_the_rehearsed_deployment_is_correct(sample):
    assert sample.compared.ok, sample.compared.line()
    assert sample.compared.compared == 3 * sample.correct["sample_steps"]
    assert _judge(sample)  # the rule as this file restates it
    # one KDA layer's states, no pool for it: one group
    assert sample.stats["state_slots"] == sample.serving["slots"]
    assert sample.stats["state_bytes"] > 0
    assert "pool_pages.full" not in sample.stats


@pytest.mark.parametrize("control", ref.CONTROLS)
def test_a_control_reads_wrong_against_the_same_served_sample(
    sample, control
):
    assert not _judge(sample, fault=control)
