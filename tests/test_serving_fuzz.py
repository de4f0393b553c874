"""Serving-stack property fuzz: random knobs x random traffic.

The deterministic tests pin fixed scenarios; this fuzz draws random
model configurations (GQA / MoE / sliding window / RoPE), random pool
geometries (one page a request, or small pages in a pool sized to
random pressure, chunked prefill), and random traffic (prompt lengths, steps, sampling knobs,
staggered arrivals), then holds every served stream to THE invariant:
token-identical to solo ``generate()`` for that request. Seeded — a
failure reproduces from the printed draw.

This is the serving-side sibling of ``test_stress.py``'s membership
fuzz (SURVEY.md §5's race-detection analog): the interactions it
covers (prefix sharing under eviction under windows under chunked
admissions...) grow combinatorially and deserve randomized coverage,
not just the fixed cases."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapt_tpu.models.transformer_lm import generate, transformer_lm
from adapt_tpu.runtime.continuous import ContinuousBatcher

VOCAB = 31


def _random_model(rs):
    kv_heads = rs.choice([None, 2])
    window = rs.choice([None, 10, 18])
    pos = rs.choice(["learned", "rope"])
    moe = rs.choice([None, 4])
    lm = transformer_lm(
        VOCAB, 32, 2, 4, 48,
        max_len=96,
        kv_heads=kv_heads,
        moe_experts=moe,
        moe_top_k=2 if moe else 1,
        window=None if window is None else int(window),
        pos=pos,
        name="fuzz_lm",
    )
    desc = dict(kv_heads=kv_heads, window=window, pos=pos, moe=moe)
    variables = lm.graph.init(
        jax.random.PRNGKey(int(rs.randint(1 << 30))),
        jnp.zeros((1, 4), jnp.int32),
    )
    return lm, variables, desc


def _random_batcher(rs, lm, variables):
    # One draw in four keeps the default geometry (page 128: a whole
    # 96-position request inside one page, worst-case pool); the rest
    # run small pages under pool pressure. The draws are consumed in
    # the order the seeds were recorded with.
    page = int(rs.choice([128, 16, 16, 16]))
    kw = {"page_size": page}
    if page == 16:
        pps = -(-lm.max_len // 16)
        slots = int(rs.choice([2, 3]))
        worst = slots * pps + 1
        # Random pool pressure from cozy down to ~60% of worst case.
        kw["pool_pages"] = int(rs.randint(max(3, int(0.6 * worst)), worst + 1))
        if rs.random_sample() < 0.5:
            kw["prefill_chunk"] = 16
        kw["slots"] = slots
    else:
        kw["slots"] = int(rs.choice([2, 3]))
    return ContinuousBatcher(lm, variables, chunk=int(rs.choice([1, 2, 4])),
                             **kw), dict(kw)


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5, 6, 7])
def test_serving_fuzz_streams_match_solo(seed):
    rs = np.random.RandomState(seed)
    lm, variables, mdesc = _random_model(rs)
    bat, bdesc = _random_batcher(rs, lm, variables)
    print(f"fuzz draw: model={mdesc} batcher={bdesc}")

    n_req = 7
    reqs = []
    shared = rs.randint(0, VOCAB, size=int(rs.randint(16, 33))).astype(
        np.int32
    )
    for i in range(n_req):
        if rs.random_sample() < 0.4:  # shared-prefix traffic
            tail = rs.randint(0, VOCAB, size=rs.randint(1, 8)).astype(
                np.int32
            )
            prompt = np.concatenate([shared, tail])
        else:
            prompt = rs.randint(0, VOCAB, size=rs.randint(2, 40)).astype(
                np.int32
            )
        steps = int(rs.randint(2, min(20, lm.max_len - len(prompt))))
        kw = {}
        if rs.random_sample() < 0.4:  # sampled request
            kw = dict(
                temperature=float(rs.uniform(0.5, 1.2)),
                top_k=int(rs.randint(2, VOCAB)),
                rng=jax.random.PRNGKey(1000 + i),
            )
            if rs.random_sample() < 0.5:
                kw["top_p"] = float(rs.uniform(0.5, 1.0))
        reqs.append((prompt, steps, kw))

    ids = {}
    for i, (prompt, steps, kw) in enumerate(reqs):
        ids[bat.submit(prompt, steps, **kw)] = i
        if rs.random_sample() < 0.5:  # staggered arrivals
            bat.tick()
    out = bat.run()
    assert set(out) == set(ids)
    chunked = bdesc.get("prefill_chunk") is not None
    for rid, i in ids.items():
        prompt, steps, kw = reqs[i]
        if chunked and kw.get("temperature"):
            # Chunked prefill's documented contract is greedy-bitwise /
            # sampled-distributional (fp reassociation at chunk
            # boundaries); skip exact comparison for sampled requests.
            assert len(out[rid]) <= steps
            continue
        want = np.asarray(
            generate(lm, variables, jnp.asarray(prompt)[None], steps, **kw)
        )[0]
        got = out[rid]
        # No request sets eos_id, so a short stream IS a truncation bug
        # — never skip the comparison on it.
        assert len(got) == steps, (
            f"req {i} truncated: {len(got)}/{steps} tokens "
            f"(model={mdesc}, batcher={bdesc})"
        )
        np.testing.assert_array_equal(
            got, want,
            err_msg=f"req {i} diverged (model={mdesc}, "
            f"batcher={bdesc}, kw={kw})",
        )
    st = bat.stats()
    assert st["pages_in_use"] == 0 and st["active"] == 0