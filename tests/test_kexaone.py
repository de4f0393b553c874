"""K-EXAONE's mechanisms at a small size on the CPU, against the plain
reference the benchmark's configuration names
(``chipbench/k_exaone_reference.py``): a per-layer pattern of window
and full attention with a dense layer 0 (``L L L G L``), the routed
expert layer with a chip's share of the experts, and a pool and a page
table a cache group in the batcher."""

import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import log_softmax_score

from adapt_tpu.models.moe import ExpertSpec, RoutedExperts
from adapt_tpu.models.transformer_lm import logits_full
from adapt_tpu.runtime.continuous import ContinuousBatcher
from adapt_tpu.runtime.paged import window_hold_pages
from chipbench import k_exaone
from chipbench import k_exaone_reference as ref
from chipbench import manifest as mf

ROOT = Path(__file__).parents[1]
WINDOW, PAGE, CHUNK, PREFILL = 16, 8, 4, 16
ARCH = {"window": WINDOW, "top_k": 2}


def _model(**over):
    """The published keys at toy widths: 5 layers ``L L L G L``, layer
    0 dense, 2 of 8 experts held, top-2, ``head_dim`` 16 where
    ``hidden / heads`` is 8."""
    config = json.loads(
        (ROOT / "chipbench/configs/k-exaone-236b-a23b.json").read_text()
    )
    model = dict(config["model"])
    model.update(
        vocab_size=64, hidden_size=32, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, intermediate_size=64,
        moe_intermediate_size=16, num_experts=2, num_experts_published=8,
        num_experts_per_tok=2, positions_served=96,
        sliding_windows=[w and WINDOW for w in model["sliding_windows"]],
    )
    model.update(over)
    return model


@pytest.fixture(scope="module")
def built():
    return k_exaone.build(_model(), "float32", 7)


def _chosen(logits, ids):
    lp = jax.nn.log_softmax(logits[:, :-1], -1)
    return np.asarray(jnp.take_along_axis(lp, ids[:, 1:, None], -1)[..., 0])


def test_full_forward_matches_the_plain_reference(built):
    lm, variables, _ = built
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 60), 0, 64)
    want, vouched = ref.next_token_logprobs(variables, ids, arch=ARCH)
    assert np.asarray(vouched).mean() > 0.5
    np.testing.assert_allclose(
        _chosen(logits_full(lm, variables, ids), ids), np.asarray(want),
        atol=2e-4,
    )


def _batcher(lm, variables, slots=2, **kw):
    return ContinuousBatcher(
        lm, variables, slots=slots, chunk=CHUNK, page_size=PAGE,
        prefill_chunk=PREFILL, prompt_buckets=(16, 32, 48, 64), **kw
    )


@pytest.fixture(scope="module")
def served(built):
    """Five requests on two slots (more than fit one tick): whole-prompt
    and chunked prefill, every one decoding past the window; each
    tick's ``stats()`` and the pages every slot holds in each group."""
    lm, variables, _ = built
    srv = _batcher(lm, variables)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 64, n).astype(np.int32)
               for n in (5, 37, 16, 23, 9)]
    rids = [srv.submit(p, 30) for p in prompts]
    seen = []
    while srv.stats()["completed"] < len(rids):
        srv.tick()
        seen.append((srv.stats(), [
            [len(pager.owned(s.idx)) for s in srv.slots]
            for pager in srv._pagers
        ]))
    tokens = srv.run()
    out = [
        (p, np.asarray(tokens[r]), np.asarray(srv.logprobs(r)))
        for p, r in zip(prompts, rids)
    ]
    groups = [(g.name, g.window, g.blocks) for g in srv._groups]
    srv.close()
    return out, seen, groups


def test_prefill_then_paged_decode_matches_the_plain_reference(built, served):
    """Prefill (whole and chunked) then decoding through the paged
    cache, a table a group, against the reference's full forward."""
    _, variables, _ = built
    for prompt, toks, lps in served[0]:
        assert len(toks) == 30
        ids = jnp.asarray(np.concatenate([prompt, toks]))[None]
        want, _ = ref.next_token_logprobs(variables, ids, arch=ARCH)
        n = len(prompt)
        np.testing.assert_allclose(
            lps, np.asarray(want)[0, n - 1: n - 1 + 30], atol=5e-4
        )


def test_a_window_layer_holds_its_window_while_a_full_layer_grows(served):
    _, seen, groups = served
    assert groups == [
        ("full", None, (3,)), ("window", WINDOW, (0, 1, 2, 4)),
    ]
    bound = window_hold_pages(WINDOW, PAGE, CHUNK, PREFILL)
    assert bound == 4  # ceil((16 + 4 - 2) / 8) + 1; a prefill pass: 2 + 2
    full_peak = 0
    for stats, (full, window) in seen:
        assert max(window) <= bound
        assert stats["pages_in_use.window"] == sum(window)
        assert stats["pages_in_use.full"] == sum(full)
        assert stats["pages_in_use"] == stats["pages_in_use.full"]
        assert stats["pool_pages.window"] == 2 * bound + 1
        full_peak = max(full_peak, max(full))
    # A full layer keeps the whole request: 37 + 30 tokens are 9 pages.
    assert full_peak == 9 > bound


@pytest.mark.parametrize("order", ["drained", "overlapped"])
def test_the_expert_counters_say_how_the_routing_fell(built, order):
    """The counters book what the DEVICE routed: under the overlapped
    order that is one chunk more than with every tick drained, the one
    dispatched before the host learned that the request had ended
    (``runtime.rows_past_end``)."""
    from adapt_tpu.utils.metrics import global_metrics
    from conftest import drained

    lm, variables, _ = built
    srv = _batcher(lm, variables)
    if order == "drained":
        drained(srv)
    snap = global_metrics().snapshot(window=True)
    srv.submit(np.arange(10, dtype=np.int32), 13)
    srv.run()
    srv.close()
    c = global_metrics().snapshot(since=snap)["counters"]
    past_end = c.get("runtime.rows_past_end", 0)
    assert past_end == (0 if order == "drained" else 1)
    # 12 tokens after the prefill's, and the chunk past the end.
    steps = (3 + past_end) * CHUNK
    assert c["moe.steps"] == steps
    # Live steps x top-2 x 4 sparse layers; layer 0 is dense.
    assert c["moe.assignments_total"] == steps * 2 * 4
    # snapshot(since=) lists every counter the process holds, 0 for the
    # untouched: a `moe.tokens.0.*` left by a model whose block 0 is
    # sparse, served earlier in this worker, is not this run's.
    per_expert = {
        k: v for k, v in c.items() if k.startswith("moe.tokens.") and v
    }
    assert {k.split(".")[2] for k in per_expert} <= {"1", "2", "3", "4"}
    assert sum(per_expert.values()) == c["moe.assignments_held"]
    assert 0 < c["moe.experts_hit"] <= steps * 4 * 2
    assert c["moe.assignments_held"] <= c["moe.assignments_total"]


def test_the_shares_of_a_sparse_layer_add_up_to_the_whole():
    """8 chips, 4 of 32 experts each, route over all 32: the routed
    parts of the 8 shares plus the shared expert ONCE are the uncut
    layer, and the uncut layer is the reference's."""
    d, hid, n_exp, k = 24, 16, 32, 8
    whole = ExpertSpec(n_exp, hid, k, score="sigmoid", normalize=True,
                       scale=2.5, select_bias=True, shared_dim=hid)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, d))
    params = RoutedExperts(whole).init(jax.random.PRNGKey(1), x)["params"]
    params["router_bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(2), (n_exp,)
    )
    full = RoutedExperts(whole).apply({"params": params}, x)
    routed_only = {
        **params,
        **{n: jax.tree.map(jnp.zeros_like, params[n])
           for n in ("shared_gate", "shared_up", "shared_down")},
    }
    shared = full - RoutedExperts(whole).apply({"params": routed_only}, x)
    total = shared
    for chip in range(8):
        lo = 4 * chip
        spec = ExpertSpec(n_exp, hid, k, score="sigmoid", normalize=True,
                          scale=2.5, select_bias=True, shared_dim=hid,
                          held=(lo, 4))
        mine = {
            **params,
            **{n: params[n][lo: lo + 4] for n in ("w_gate", "w_up", "w_down")},
        }
        total = total + (
            RoutedExperts(spec).apply({"params": mine}, x) - shared
        )
    np.testing.assert_allclose(total, full, atol=1e-5)
    want, _ = ref._experts(params, x, {**ref.ARCH, "top_k": k}, False)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(full, want, atol=1e-5)


@pytest.mark.parametrize("score,top_k,normalize", [
    ("softmax", 1, False), ("softmax", 2, False), ("softmax", 3, True),
    ("sigmoid", 2, True),
])
def test_routed_experts_is_the_mixture_it_states(score, top_k, normalize):
    """All experts held: the sorted grouped product against the sum
    written out expert by expert (the masked-dense form)."""
    d, hid, n_exp = 16, 8, 4
    spec = ExpertSpec(n_exp, hid, top_k, score=score, normalize=normalize)
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 5, d))
    layer = RoutedExperts(spec)
    p = layer.init(jax.random.PRNGKey(5), x)["params"]
    got = layer.apply({"params": p}, x)
    logits = x @ p["router"]
    s = jax.nn.sigmoid(logits) if score == "sigmoid" else jax.nn.softmax(logits)
    w, idx = jax.lax.top_k(s, top_k)
    if normalize:
        w = w / w.sum(-1, keepdims=True)
    want = jnp.zeros_like(x)
    for e in range(n_exp):
        w_e = jnp.where(idx == e, w, 0).sum(-1, keepdims=True)
        h = jax.nn.silu(x @ p["w_gate"][e]) * (x @ p["w_up"][e])
        want = want + w_e * (h @ p["w_down"][e])
    np.testing.assert_allclose(got, want, atol=1e-5)
    # Token-independent: a token alone gets what it gets in the batch.
    np.testing.assert_allclose(
        layer.apply({"params": p}, x[1:2, 2:3]), got[1:2, 2:3], atol=1e-6
    )


@pytest.mark.parametrize("what,kw", [
    ("radix prefix cache", dict(fn="prefix_cached")),
    ("fan-out", dict(fn="submit_fanout")),
])
def test_what_cannot_run_over_cache_groups_says_so(built, what, kw):
    lm, variables, _ = built
    srv = _batcher(lm, variables)
    with pytest.raises(ValueError, match="2 layer groups"):
        if kw["fn"] == "prefix_cached":
            srv.prefix_cached(np.arange(20, dtype=np.int32))
        else:
            srv.submit_fanout(np.arange(20, dtype=np.int32), 2, 4)
    assert srv.stats()["prefix_cache"].startswith("off")
    srv.close()


#: sha256 (16 hex) of the lowered text of ``_step_chunk`` and of the
#: first bucket's ``prefill`` at each GPT-2 configuration's rehearsal
#: sizes. ``log_softmax`` was read on the commit before the block-spec
#: refactor (PR 30's tree) with this same function: the GPT-2 specs are
#: held still. PR 50 moved both texts ON PURPOSE and in the score alone:
#: step and prefill score through ``chosen_logprob``'s one log-sum-exp
#: where a whole log-softmax stood. Scored the way PR 30's tree scored
#: (``conftest.log_softmax_score``), both still lower to ``log_softmax`` to the
#: digit: everything but the score is the text it was. ``served`` is
#: what the batcher dispatches since PR 50.
GPT2_LOWERED = {
    ("gpt2-xl", "log_softmax"): ("d113387e5b3f28a5", "d6a656519e6b5a03"),
    ("cerebras-gpt-1.3b", "log_softmax"): (
        "29a8f1d1ce1d060f", "630bcf9702481864"),
    ("gpt2-xl", "served"): ("394374b9285166c5", "f15c62f5955d342f"),
    ("cerebras-gpt-1.3b", "served"): (
        "579522a9516e48f1", "cc4629c06deaaa74"),
}


@pytest.mark.parametrize("name,tail", sorted(GPT2_LOWERED))
def test_the_gpt2_programs_lower_to_the_text_they_had(
    name, tail, monkeypatch
):
    # The rehearsal's rows of 64 are not whole lane tiles, so an engine
    # would hold the embedding tables padded (PR 47: another text, by
    # two pads' worth). Held as the model gives them, as every
    # whole-tile width is, the programs are the ones they were.
    from adapt_tpu.runtime import continuous

    monkeypatch.setattr(continuous, "lane_tiled", lambda embed: embed)
    if tail == "log_softmax":
        monkeypatch.setattr(
            continuous, "chosen_logprob", log_softmax_score
        )
    config = json.loads((ROOT / f"chipbench/configs/{name}.json").read_text())
    model = {**config["model"], **config["rehearse"]["model"]}
    serving = {**config["serving"], **config["rehearse"]["serving"]}
    lm, variables, _ = mf.part_of(config, "builder")(model, config["dtype"], 0)
    srv = ContinuousBatcher(
        lm, variables, slots=serving["slots"], chunk=serving["chunk"],
        kv_layout="paged", page_size=serving["page_size"], pool_pages=17,
        prefill_chunk=serving["prefill_chunk"],
        prompt_buckets=tuple(serving["prompt_buckets"]),
    )
    step = type(srv)._step_chunk.lower(
        srv, srv._served, srv._caches, srv._dstate, srv._current_table(),
        truncate=False, nucleus=False, epoch=0,
    ).as_text()
    b = serving["prompt_buckets"][0]
    pre = srv._prefill_fn(b).lower(
        srv._served, jnp.zeros((1, b), jnp.int32),
        jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.float32),
        jnp.zeros((1, 2), jnp.uint32), truncate=False, nucleus=False,
    ).as_text()
    srv.close()
    got = tuple(
        hashlib.sha256(t.encode()).hexdigest()[:16] for t in (step, pre)
    )
    assert got == GPT2_LOWERED[name, tail], got


def test_the_configuration_file_holds_the_published_keys_twice_and_equal():
    """The catalog's keys at the file's top level (what a checker of
    the file against the catalog reads) and under ``model`` (what the
    builder gets) are one set of values; only the keys in ``reduced``
    differ from the published ones the file states beside them."""
    body = json.loads(
        (ROOT / "chipbench/configs/k-exaone-236b-a23b.json").read_text()
    )
    model = body["model"]
    extra = {"num_experts_published", "positions_served"}
    assert set(model) - set(body) == extra
    for key in set(model) - extra:
        assert body[key] == model[key], key
    assert set(body["published"]) == set(body["reduced"])
    for key, value in body["published"].items():
        assert model[key] != value, key
    assert model["num_experts_published"] == body["published"]["num_experts"]
    n = model["num_hidden_layers"]
    assert [w or None for w in model["sliding_windows"][:n]] == [
        128, 128, 128, None, 128]
    assert model["mlp_layer_types"][:n] == ["dense"] + ["sparse"] * 4


def test_the_rehearsal_walks_the_cell_in_both_trace_modes(capsys):
    """``tests/chipbench/test_chipbench_run_loop.py`` picks this
    configuration's three controls up from ``BENCHMARK.json``; its
    list of cells to walk is its own, so the walk is here."""
    from chipbench import run as bench_run

    assert bench_run.main(
        ["--rehearse", "--seconds", "1.5", "--workload", "kexaone_longgen"]
    ) == 0
    plain, traced = [
        ln for ln in capsys.readouterr().out.splitlines()
        if ln.startswith("rehearsal ")
    ]
    assert "correct=True" in plain and "failed=0" in plain
    assert "would report ['out_tok_per_s', 'setup_s']" in plain
    # No device plane on the CPU: the readers of the counters and of
    # the pools report, the device readers return nothing.
    assert "correct=True" in traced
    for name in ("moe.tokens_per_expert_mean", "moe.load_max_over_mean",
                 "kv.pool_peak_pct.full", "kv.pool_peak_pct.window",
                 "sched.slots_active_mean"):
        assert name in traced
    assert "roofline" not in traced and "decode_step_ms" not in traced
