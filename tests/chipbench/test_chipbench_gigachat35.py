"""``gigachat3.5-432b-a28b`` and its cell ``gigachat35_longgen8k`` as
``BENCHMARK.json`` holds them: the file against the catalog, what the
manifest gained (after what was there; the ``startup.*`` lists joined
since PR 52), the readers on hand-made records, and ONE rehearsal of
the cell's deployment (a module fixture builds model and batcher once)
from which ``correct`` and every control are read. The cell's walk
through ``run.py``, control by control, is
``test_chipbench_run_loop_gigachat35.py``."""

import contextlib
import json
import types
from pathlib import Path

import numpy as np
import pytest

from chipbench import gigachat35_readers as gr
from chipbench import gigachat35_reference as ref
from chipbench import manifest as mf
from chipbench import solar_open2_readers as sr
from chipbench import traffic as tg
from chipbench import xing4_readers as xr
from paired_trace import trace_of

ROOT = Path(__file__).parents[2]
CELL = "gigachat35_longgen8k"
NAME = "gigachat3.5-432b-a28b"

BM = mf.load(ROOT)
CONFIG = mf.config_of(BM, mf.cell(BM, CELL), ROOT)
DERIVED = {
    "n_routed_experts_published", "positions_served", "first_layer",
    "num_experts", "mlp_layer_types", "sliding_windows",
}
REDUCED = {"num_hidden_layers": 40, "n_routed_experts": 256,
           "vocab_size": 128256, "num_nextn_predict_layers": 2}


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
            if e["name"] == "GigaChat3.5-432B-A28B"
        ]
        config = next(c for c in BM["configs"] if c["name"] == NAME)
        assert CONFIG["source"] == config["source"] == entry["source_url"]
        for key, value in entry["config"].items():
            if key in CONFIG["reduced"]:
                assert CONFIG["published"][key] == value, key
            else:
                assert CONFIG[key] == value, key


@pytest.mark.parametrize("key,value", [
    ("hidden_size", 7168), ("num_attention_heads", 64),
    ("q_lora_rank", 1536), ("kv_lora_rank", 512),
    ("qk_nope_head_dim", 128), ("qk_rope_head_dim", 64),
    ("v_head_dim", 128), ("linear_num_key_heads", 32),
    ("linear_num_value_heads", 64), ("linear_key_head_dim", 128),
    ("linear_value_head_dim", 128), ("linear_conv_kernel_dim", 4),
    ("intermediate_size", 18432), ("moe_intermediate_size", 2048),
    ("num_experts_per_tok", 8), ("n_shared_experts", 1),
    ("routed_scaling_factor", 2.5), ("swiglu_limit", 10),
    ("gated_attention", True), ("layernorm_type", "pre_post"),
    ("linear_sigmoid_gate_scale", 2), ("first_k_dense_replace", 3),
])
def test_every_published_width_is_kept(key, value):
    assert CONFIG[key] == CONFIG["model"][key] == value
    assert key not in CONFIG["reduced"]


def test_the_cut_is_a_dense_layer_a_period_and_a_share_of_32():
    from chipbench import gigachat35

    m = CONFIG["model"]
    assert m["num_hidden_layers"] == 5 and m["first_layer"] == 2
    kept = range(m["first_layer"], m["first_layer"] + 5)
    assert [i in m["full_attention_layers"] for i in kept] == [
        False, True, False, False, False
    ]
    assert m["mlp_layer_types"] == ["dense"] + ["sparse"] * 4 == [
        "dense" if i < m["first_k_dense_replace"] else "sparse" for i in kept
    ]
    # the guide's floors: 8 experts a chip, an eighth of the vocabulary
    assert m["n_routed_experts"] == m["num_experts"] == 8
    assert m["n_routed_experts"] * 32 == m["n_routed_experts_published"] == 256
    assert m["vocab_size"] * 8 == CONFIG["published"]["vocab_size"]
    assert m["num_nextn_predict_layers"] == 0
    assert set(CONFIG["reduced_why"]) == set(REDUCED)
    for key in ("assumed", "memory", "deployment"):
        assert CONFIG[key], key
    assert "32 chips share each layer" in CONFIG["deployment"]
    specs = gigachat35.specs(m)
    assert [s.linear is not None for s in specs] == [
        True, False, True, True, True
    ]
    lin, lat = specs[0].linear, specs[1].latent
    assert (lin.heads, lin.qk_heads, lin.head_dim) == (64, 32, 128)
    assert lin.conv_dim == 16384 and lin.head_decay and not lin.neg_eigval
    assert lin.rank is None and lin.gate_scale == 2.0
    assert lat.row == 576 and specs[1].heads == 64 and specs[1].attn_gate
    assert round(lat.softmax_scale, 5) == 0.10530
    assert all(s.sandwich_norm for s in specs)
    assert specs[0].swiglu_limit == 10.0 and specs[0].mlp_dim == 18432
    e = specs[1].experts
    assert (e.num_experts, e.top_k, e.held, e.scale, e.swiglu_limit) == (
        256, 8, (0, 8), 2.5, 10.0
    )
    # three layers at toy widths in a rehearsal: dense GDN, sparse MLA,
    # sparse GDN
    r = gigachat35.specs({**m, **CONFIG["rehearse"]["model"]})
    assert [(s.linear is not None, s.mlp) for s in r] == [
        (True, "gated_silu"), (False, "experts"), (True, "experts")
    ]


def test_the_reference_states_what_the_file_says():
    m, arch = CONFIG["model"], ref.ARCH
    assert arch["eps"] == m["rms_norm_eps"] == m["linear_attn_o_norm_eps"]
    assert arch["top_k"] == m["num_experts_per_tok"]
    assert arch["scale"] == m["routed_scaling_factor"]
    assert arch["limit"] == m["swiglu_limit"]
    assert arch["gate_scale"] == m["linear_sigmoid_gate_scale"]
    assert arch["rope_base"] == m["rope_theta"]
    rs = m["rope_scaling"]
    assert arch["yarn"] == dict(
        factor=rs["factor"], original_max=rs["original_max_position_embeddings"],
        beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
        mscale=rs["mscale"], mscale_all_dim=rs["mscale_all_dim"],
    )
    assert tuple(CONFIG["correct"]["controls"]) == ref.CONTROLS
    assert len(ref.CONTROLS) <= 4
    assert set(ref.CONTROLS) <= set(ref.FAULTS) | set(ref.PRECISION)
    assert {"no_gate", "no_clamp"} <= set(ref.FAULTS)  # readings
    assert CONFIG["correct"]["sample_steps"] == 128
    assert len(ref.MARGINS) == m["mlp_layer_types"].count("sparse")
    chunk = CONFIG["serving"]["prefill_chunk"]
    assert ref.SAMPLE_RESET == (40, chunk - 17, chunk + 45)
    for key in ("norm", "attention_gate", "swiglu_limit", "router", "decay",
                "qk_norm", "conv", "linear_gate", "rope", "dtype", "weights"):
        assert CONFIG["assumed"][key], key


#: What the manifest held before this cell, in its order. Held to
#: what stood BEFORE the entry only: a later cell, configuration or
#: metric comes after it and moves nothing here.
CELLS_BEFORE = [
    "gpt2xl_chat", "cgpt1b3_batchgen", "gpt2xl_doc", "kexaone_longgen",
    "falconh1_longgen", "xing4_longgen8k", "solaropen2_longgen",
]
CONFIGS_BEFORE = [
    "gpt2-xl", "cerebras-gpt-1.3b", "k-exaone-236b-a23b",
    "falcon-h1-34b-instruct", "xing4.0-29b-a4b", "solar-open2-250b",
]


def _before(entries, name):
    names = [e["name"] for e in entries]
    return names[: names.index(name)]


def test_the_manifest_gains_the_cell_after_what_was_there():
    cell = mf.cell(BM, CELL)
    assert cell == {**cell, "config": NAME, "traffic": "longgen8k", "chips": 1}
    assert _before(BM["workloads"], CELL) == CELLS_BEFORE
    assert _before(BM["configs"], NAME) == CONFIGS_BEFORE
    config = next(c for c in BM["configs"] if c["name"] == NAME)
    assert config["file"] == f"chipbench/configs/{NAME}.json"
    assert config["reduced"] == list(REDUCED)
    e2e = [m["name"] for m in mf.metrics_of(BM, CELL, "end_to_end")]
    assert e2e == ["out_tok_per_s", "setup_s"]
    layer = {m["name"] for m in mf.metrics_of(BM, CELL, "per_layer")}
    assert layer >= {
        "memory.state_gb", "kernel.kda_step_roofline", "kda.step_share_pct",
        "kernel.latent_decode_roofline", "mla.decode_share_pct",
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
    state = dict(
        next(m for m in BM["per_layer"] if m["name"] == "memory.state_gb")
    )
    assert state.pop("workloads")[0] == CELL
    assert state == {
        "name": "memory.state_gb", "unit": "GB", "better": "lower",
        "source": "program_counter", "layer": "KV memory",
        "moves": "out_tok_per_s",
    }
    assert not any(m["name"] == "memory.state_gb" for m in BM["per_layer"][
        : [m["name"] for m in BM["per_layer"]].index("kda.step_share_pct")
    ])
    assert len((ROOT / "BENCHMARK.json").read_text()) <= 64 * 1024
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200


def test_the_traffic_is_longgen8k_as_it_was():
    traffic = mf.traffic_of(BM, mf.cell(BM, CELL), ROOT)
    assert traffic["name"] == "longgen8k" and "serving" not in traffic
    xing4 = mf.cell(BM, "xing4_longgen8k")
    assert xing4["traffic"] == "longgen8k"  # one file, two cells
    pairs = tg.templates(traffic, CONFIG["model"]["positions_served"])
    assert max(p + o for p, o in pairs) == 7929  # 62 pages of 128
    serving = CONFIG["serving"]
    assert serving["slots"] in (192, 128) and serving["chunk"] == 8
    assert (serving["page_size"], serving["prefill_chunk"]) == (128, 256)
    assert serving["prompt_buckets"] == mf.config_of(BM, xing4, ROOT)[
        "serving"]["prompt_buckets"]


def _record():
    return dict(
        shape=dict(heads=64, layers=1, latent_row=576, latent_values=512,
                   kda_layers=4, kda_heads=64, kda_head_dim=128),
        serving=dict(chunk=8), itemsize=2,
        trace=dict(t0=0.0, t1=10.0),
        ticks=[(1.0, 2.0, 2, 0), (3.0, 4.0, 0, 0), (11.0, 12.0, 2, 0)],
        tick_contexts=[(1000, 300), (), (5, 5)],
        gauges={"memory.state_bytes": 3_296_722_944.0}, stats={},
    )


def _trace(ops, modules):
    return trace_of(_record(), ops, modules)


def test_both_families_of_readers_take_the_builders_shape():
    """One traced tick that decoded 2 rows for 8 steps: the state
    update's floor counts four layers of 64 heads (``g`` a channel and
    q, k a value head: 0.6% over this layer's own operands, which a
    floor that errs high would not forgive and this one does: 8.5 MB a
    row either way), the latent kernel's one layer of 64 heads."""
    from chipbench import xing4_yardstick as xy
    from chipbench import yardstick

    rec = _record()
    seen = _trace(
        {sr.KERNEL: 0.002, xr.KERNEL: 0.001}, {"_step_chunk": (1, 0.008)}
    )
    got = sr.kda_step_roofline(seen, rec, "TPU v5e")
    assert got == pytest.approx(
        100.0 * 2 * 8 * 4 * 8_503_552 / 819e9 / 0.002
    )
    assert 0 < got < 100
    assert sr.kda_step_share_pct(seen, rec, "TPU v5e") == 25.0
    assert xr.decode_share_pct(seen, rec, "TPU v5e") == 12.5
    floor = sum(
        yardstick.floor_seconds(
            *xy.latent_decode_cost(1300 + 2 * j, 2, 64, 576, 512, 2),
            "TPU v5e",
        )
        for j in range(8)
    )
    got = xr.latent_decode_roofline(seen, rec, "TPU v5e")
    assert got == pytest.approx(100.0 * floor / 0.001) and 0 < got < 100


def test_the_state_reader_reads_the_gauge_or_the_stats_or_nothing():
    rec = _record()
    assert gr.state_gb(None, rec, "TPU v5e") == pytest.approx(3.296722944)
    late = {**rec, "gauges": {}, "stats": {"state_bytes": 2_000_000_000}}
    assert gr.state_gb(None, late, "TPU v5e") == 2.0
    # a parent's records, or a model with no state: the line leaves it out
    for old in ({**rec, "gauges": {}}, {"stats": {"state_bytes": 0}}, {}):
        assert gr.state_gb(None, old, "TPU v5e") is None


# -- one rehearsal: `correct` and every control from one deployment ------------

#: A seed at which the toy widths read well inside the limits set for
#: the published ones (0.058 sound, 0.52 under float8; at 64 channels
#: bfloat16 reaches further than at 7168: seeds 1-10 read 0.058-0.197
#: sound, and seed 0, which ``test_chipbench_run_loop.py`` walks
#: control by control, 0.22).
SEED = 9


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
        model, CONFIG["dtype"], SEED
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
        shape=shape,
    )


def _judge(s, **kw):
    """``lm_engine.correctness_sample``'s rule over the kept sample,
    against the reference under ``kw`` (a fault, an ``arch``)."""
    from chipbench import lm_engine as eng

    steps = eng._sample_steps(s.correct)
    lens = eng._sample_prompts(
        s.serving["prefill_chunk"], s.shape["max_len"], steps
    )
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
    # two GDN layers' states beside ONE group of latent pages
    assert sample.stats["state_slots"] == sample.serving["slots"]
    assert sample.stats["state_bytes"] > 0
    assert sample.stats["pool_row_values"] == sample.shape["latent_row"]
    assert "pool_pages.full" not in sample.stats


@pytest.mark.parametrize("control", ref.CONTROLS)
def test_a_control_reads_wrong_against_the_same_served_sample(
    sample, control
):
    assert not _judge(sample, fault=control)
