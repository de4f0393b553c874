"""``deepseek-v3.2-exp`` and its cell ``dsv32_longgen32k`` as
``BENCHMARK.json`` holds them: the file against the catalog, its memory
arithmetic against the shapes, what the manifest gained (after what was
there), the reference's independence, the readers on hand-made records,
and ONE rehearsal of the cell's deployment (a module fixture builds
model and batcher once) from which ``correct`` and every control are
read. The cell's walk through ``run.py``, control by control, is
``test_chipbench_run_loop_deepseek_v32.py``."""

import ast
import contextlib
import json
import types
from pathlib import Path

import numpy as np
import pytest

from chipbench import deepseek_v32_readers as dr
from chipbench import deepseek_v32_reference as ref
from chipbench import deepseek_v32_yardstick as dy
from chipbench import manifest as mf
from chipbench import traffic as tg
from paired_trace import trace_of

ROOT = Path(__file__).parents[2]
CELL = "dsv32_longgen32k"
NAME = "deepseek-v3.2-exp"

BM = mf.load(ROOT)
CONFIG = mf.config_of(BM, mf.cell(BM, CELL), ROOT)
DERIVED = {
    "n_routed_experts_published", "positions_served", "first_layer",
    "num_experts", "mlp_layer_types", "sliding_windows",
}
REDUCED = {"num_hidden_layers": 61, "n_routed_experts": 256,
           "vocab_size": 129280, "num_nextn_predict_layers": 1}


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
            if e["name"] == "DeepSeek-V3.2-Exp"
        ]
        config = next(c for c in BM["configs"] if c["name"] == NAME)
        assert CONFIG["source"] == config["source"] == entry["source_url"]
        for key, value in entry["config"].items():
            if key in CONFIG["reduced"]:
                assert CONFIG["published"][key] == value, key
            else:
                assert CONFIG[key] == value, key


@pytest.mark.parametrize("key,value", [
    ("hidden_size", 7168), ("num_attention_heads", 128),
    ("q_lora_rank", 1536), ("kv_lora_rank", 512),
    ("qk_nope_head_dim", 128), ("qk_rope_head_dim", 64),
    ("v_head_dim", 128), ("index_n_heads", 64), ("index_head_dim", 128),
    ("index_topk", 2048), ("intermediate_size", 18432),
    ("moe_intermediate_size", 2048), ("num_experts_per_tok", 8),
    ("n_shared_experts", 1), ("n_group", 8), ("topk_group", 4),
    ("routed_scaling_factor", 2.5), ("first_k_dense_replace", 3),
    ("max_position_embeddings", 163840),
])
def test_every_published_width_is_kept(key, value):
    assert CONFIG[key] == CONFIG["model"][key] == value
    assert key not in CONFIG["reduced"]


def test_the_cut_is_a_dense_layer_four_sparse_ones_and_a_share_of_32():
    from chipbench import deepseek_v32

    m = CONFIG["model"]
    assert m["num_hidden_layers"] == 5 and m["first_layer"] == 2
    kept = range(m["first_layer"], m["first_layer"] + 5)
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
    specs = deepseek_v32.specs(m)
    assert [s.mlp for s in specs] == ["gated_silu"] + ["experts"] * 4
    lat = specs[0].latent
    assert all(s.latent == lat and s.heads == 128 for s in specs)
    assert (lat.row, lat.q_rank, lat.qk_dim, lat.v_dim) == (576, 1536, 192, 128)
    ix = lat.index
    assert (ix.heads, ix.dim, ix.rope_dim, ix.top_k) == (64, 128, 64, 2048)
    assert round(lat.softmax_scale, 4) == 0.1352
    assert specs[0].mlp_dim == 18432 and specs[0].swiglu_limit is None
    e = specs[1].experts
    assert (e.num_experts, e.top_k, e.held, e.scale, e.groups) == (
        256, 8, (0, 8), 2.5, (8, 4)
    )
    assert e.shared_dim == 2048 and e.swiglu_limit is None
    # a rehearsal at toy widths keeps a dense and a sparse layer, both
    # selecting, and a top-k its contexts pass
    r = deepseek_v32.specs({**m, **CONFIG["rehearse"]["model"]})
    assert [s.mlp for s in r] == ["gated_silu", "experts"]
    assert r[0].latent.index.top_k == ref.REHEARSAL["index_topk"]
    assert r[0].latent.index.dim < ref.PUBLISHED_INDEX_DIM == ix.dim
    chunk = CONFIG["rehearse"]["serving"]["prefill_chunk"]
    assert chunk - 17 + 128 > r[0].latent.index.top_k


def test_the_memory_block_is_the_shapes_arithmetic():
    """The file's numbers, recomputed from the widths: parameters leaf
    by leaf, a page of both planes, the pool by the engine's own rule."""
    from chipbench import lm_engine as eng

    m, mem = CONFIG["model"], CONFIG["memory"]
    d, h = m["hidden_size"], m["num_attention_heads"]
    mla = (
        d * m["q_lora_rank"]
        + m["q_lora_rank"] * h * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"])
        + d * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
        + m["kv_lora_rank"] * h * (m["qk_nope_head_dim"] + m["v_head_dim"])
        + h * m["v_head_dim"] * d + m["q_lora_rank"] + m["kv_lora_rank"]
    )
    indexer = (
        m["q_lora_rank"] * m["index_n_heads"] * m["index_head_dim"]
        + d * m["index_head_dim"] + d * m["index_n_heads"]
        + 2 * m["index_head_dim"]
    )
    expert = 3 * d * m["moe_intermediate_size"]
    dense = 3 * d * m["intermediate_size"]
    sparse = (
        (m["n_routed_experts"] + m["n_shared_experts"]) * expert
        + d * m["n_routed_experts_published"]
        + m["n_routed_experts_published"]
    )
    layers = 5 * (mla + indexer + 2 * d) + dense + 4 * sparse
    params = layers + 2 * m["vocab_size"] * d + d
    assert mem["weights_bytes_computed"] == 2 * params
    row = m["kv_lora_rank"] + m["qk_rope_head_dim"]
    page = CONFIG["serving"]["page_size"]
    assert mem["page_bytes_computed"] == 2 * page * (
        row + m["index_head_dim"]
    ) == 180224
    traffic = mf.traffic_of(BM, mf.cell(BM, CELL), ROOT)
    pairs = tg.templates(traffic, m["positions_served"])
    pages = eng.pool_pages(
        CONFIG["serving"], pairs, m["positions_served"],
        CONFIG["correct"]["sample_steps"],
    )
    assert mem["pool_pages_computed"] == pages
    assert mem["pool_bytes_computed"] == pages * 5 * 180224
    for key in ("weights_bytes", "page_bytes", "pool", "sum",
                "temporaries_bytes", "tokens_per_expert"):
        assert mem[key], key


def test_the_reference_states_what_the_file_says():
    m, arch = CONFIG["model"], ref.ARCH
    assert arch["eps"] == m["rms_norm_eps"]
    assert arch["top_k"] == m["num_experts_per_tok"]
    assert arch["scale"] == m["routed_scaling_factor"]
    assert (arch["n_group"], arch["topk_group"]) == (
        m["n_group"], m["topk_group"]
    )
    assert arch["index_topk"] == m["index_topk"]
    assert arch["rope_base"] == m["rope_theta"]
    rs = m["rope_scaling"]
    assert arch["yarn"] == dict(
        factor=rs["factor"], original_max=rs["original_max_position_embeddings"],
        beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
        mscale=rs["mscale"], mscale_all_dim=rs["mscale_all_dim"],
    )
    assert tuple(CONFIG["correct"]["controls"]) == ref.CONTROLS
    assert len(ref.CONTROLS) == 4 and "drop_selection" in ref.CONTROLS
    assert CONFIG["correct"]["sample_steps"] == 128
    assert len(ref.MARGINS) == m["mlp_layer_types"].count("sparse")
    # the sample's contexts pass the top-k: half the cache is left out
    chunk = CONFIG["serving"]["prefill_chunk"]
    assert chunk - 17 > m["index_topk"] and chunk + 45 > chunk
    for key in ("indexer", "indexer_precision", "indexer_ties", "rope",
                "router", "shared_expert", "residual", "extra_layer",
                "dtype", "weights"):
        assert CONFIG["assumed"][key], key


def test_the_reference_imports_nothing_of_the_program_or_the_benchmark():
    tree = ast.parse((ROOT / "chipbench/deepseek_v32_reference.py").read_text())
    seen = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            seen |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            seen.add((node.module or "").split(".")[0])
    assert seen == {"__future__", "functools", "math", "jax"}


#: What the manifest held before this cell, in its order. Held to what
#: stood BEFORE the entry only: a later cell, configuration or metric
#: comes after it and moves nothing here.
CELLS_BEFORE = [
    "gpt2xl_chat", "cgpt1b3_batchgen", "gpt2xl_doc", "kexaone_longgen",
    "falconh1_longgen", "xing4_longgen8k", "solaropen2_longgen",
    "gigachat35_longgen8k",
]
CONFIGS_BEFORE = [
    "gpt2-xl", "cerebras-gpt-1.3b", "k-exaone-236b-a23b",
    "falcon-h1-34b-instruct", "xing4.0-29b-a4b", "solar-open2-250b",
    "gigachat3.5-432b-a28b",
]
NEW_METRICS = (
    "kernel.sparse_latent_roofline", "dsa.step_share_pct", "dsa.selected_pct",
)


def _before(entries, name):
    names = [e["name"] for e in entries]
    return names[: names.index(name)]


def test_the_manifest_gains_the_cell_after_what_was_there():
    cell = mf.cell(BM, CELL)
    assert cell == {**cell, "config": NAME, "traffic": "longgen32k", "chips": 1}
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
    assert {m for m in layer if m.startswith("startup.")} == {
        f"startup.{k}" for k in (
            "programs", "trace_s", "lower_s", "backend_s", "cache_misses",
            "step_program_s", "construct_s")
    }
    # the dense latent kernel does not run in this cell's step
    assert not layer & {"kernel.latent_decode_roofline", "mla.decode_share_pct"}
    for m in BM["end_to_end"] + BM["per_layer"]:
        cells = m.get("workloads", [])
        if CELL not in cells:
            continue
        # appended: only cells that were there stand before it
        assert set(cells[: cells.index(CELL)]) <= set(CELLS_BEFORE), m["name"]
        if "moves" in m:
            assert m["moves"] == (
                "setup_s" if m["name"].startswith("startup.")
                else "out_tok_per_s"
            ), m["name"]
            assert callable(mf.reader_of(BM, m["name"], ROOT))
    names = [m["name"] for m in BM["per_layer"]]
    assert tuple(names[names.index(NEW_METRICS[0]):][:3]) == NEW_METRICS
    for name in NEW_METRICS:
        m = BM["per_layer"][names.index(name)]
        assert m["workloads"][0] == CELL and m["unit"] == "%"
        assert (m["layer"], m["moves"]) == ("attention kernels", "out_tok_per_s")
    assert len((ROOT / "BENCHMARK.json").read_text()) <= 64 * 1024
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200


def test_the_traffic_reaches_32k_and_sets_the_pool():
    traffic = mf.traffic_of(BM, mf.cell(BM, CELL), ROOT)
    assert traffic["name"] == "longgen32k" and "serving" not in traffic
    assert (traffic["loop"], traffic["clients"]) == ("closed", "slots")
    pairs = tg.templates(traffic, CONFIG["model"]["positions_served"])
    assert max(p + o for p, o in pairs) == 32217  # 252 pages of 128
    assert all(64 <= p <= 256 and 8192 <= o <= 32512 for p, o in pairs)
    serving = CONFIG["serving"]
    assert serving["slots"] in (32, 24) and serving["chunk"] == 8
    assert (serving["page_size"], serving["prefill_chunk"]) == (128, 4096)
    assert serving["prompt_buckets"][-1] == 32768
    standing = tg.standing_population(pairs, serving["slots"])
    contexts = [r.prompt_len for r in standing]
    assert min(contexts) < 1024 and max(contexts) > 30000
    assert 11000 < sum(contexts) / len(contexts) < 13000


def _record():
    return dict(
        shape=dict(heads=128, layers=5, latent_row=576, latent_values=512,
                   index_row=128, index_topk=2048),
        serving=dict(chunk=8), itemsize=2,
        trace=dict(t0=0.0, t1=10.0),
        ticks=[(1.0, 2.0, 2, 0), (3.0, 4.0, 0, 0), (11.0, 12.0, 2, 0)],
        tick_contexts=[(10000, 300), (), (5, 5)],
        counters={"dsa.positions_scored": 4000.0,
                  "dsa.positions_selected": 1000.0, "dsa.steps": 40.0},
    )


def _trace(ops, modules):
    return trace_of(_record(), ops, modules)


def test_the_floor_is_token_granular_and_the_readers_read_it():
    """One traced tick that decoded 2 rows for 8 steps: every live
    position's index key once a layer, and the selected rows (at most
    2,048 a row) once a layer."""
    assert dy.sparse_latent_cost([10000, 300], 5, 2048, 128, 576, 2) == (
        5 * 2 * ((10000 + 300) * 128 + (2048 + 300) * 576)
    )
    rec = _record()
    seen = _trace({dr.KERNELS[0]: 0.004}, {"_step_chunk": (1, 0.016)})
    nbytes = sum(
        dy.sparse_latent_cost([10000 + j, 300 + j], 5, 2048, 128, 576, 2)
        for j in range(8)
    )
    got = dr.sparse_latent_roofline(seen, rec, "TPU v5e")
    assert got == pytest.approx(100.0 * nbytes / 819e9 / 0.004)
    assert 0 < got < 100
    assert dr.step_share_pct(seen, rec, "TPU v5e") == 25.0
    assert dr.selected_pct(None, rec, "TPU v5e") == 25.0
    # a parent's trace and counters: each line leaves its metric out
    old = _trace({"_latent_impl": 0.004}, {"_step_chunk": (1, 0.016)})
    assert dr.sparse_latent_roofline(old, rec, "TPU v5e") is None
    assert dr.step_share_pct(old, rec, "TPU v5e") is None
    assert dr.sparse_latent_roofline(None, rec, "TPU v5e") is None
    assert dr.selected_pct(None, {**rec, "counters": {}}, "TPU v5e") is None
    assert dr.selected_pct(None, {}, "TPU v5e") is None
    plain = {**rec, "shape": dict(heads=32, layers=1, latent_row=576)}
    assert dr.sparse_latent_roofline(seen, plain, "TPU v5e") is None


# -- one rehearsal: `correct` and every control from one deployment ------------

#: A seed at which the toy widths read inside the limits set for the
#: published ones (at 64 channels bfloat16 reaches further than at
#: 7168, and one position of 192 swapped at the cut moves a toy logit
#: further than one of 2,048: ``SEEDS_READ`` below).
SEED = 1


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
    # ONE group of pages, each a row and an index key a position
    assert sample.stats["pool_row_values"] == (
        sample.shape["latent_row"] + sample.shape["index_row"]
    )
    assert sample.stats["state_bytes"] == 0
    assert "pool_pages.full" not in sample.stats
    assert sample.stats["prefix_cache"] == "off: a selecting cache"


@pytest.mark.parametrize("control", ref.CONTROLS)
def test_a_control_reads_wrong_against_the_same_served_sample(
    sample, control
):
    assert not _judge(sample, fault=control)
