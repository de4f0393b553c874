"""The emitted token's score is the chosen logit less ONE log-sum-exp
(``chosen_logprob``: no ``(rows, vocabulary)`` log-softmax), in every
program that scores; the batcher reads its static sampling flags from
the batch in one place and counts the ticks on which a row sampled; a
tick that mixes greedy and sampled rows serves its greedy rows bit for
bit what an all-greedy tick serves them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapt_tpu.models.transformer_lm import chosen_logprob, lm_tiny
from adapt_tpu.runtime.continuous import ContinuousBatcher
from adapt_tpu.utils.metrics import global_metrics
from conftest import drained, log_softmax_score

VOCAB, SLOTS = 37, 3
STEP = "continuous.step_chunk"
GREEDY = (False, False)


@pytest.fixture(scope="module")
def lm_setup():
    lm = lm_tiny(vocab=VOCAB, max_len=48)
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    return lm, variables


def _logits(case):
    rng = np.random.RandomState(3)
    if case == "one":
        return rng.randn(5, 1).astype(np.float32) * 9
    x = (rng.randn(6, 261) * 7).astype(np.float32)
    if case == "minus_inf":
        # A filtered row: most entries -inf, as a top-k leaves them.
        x[1, 5:] = -np.inf
        x[4, ::2] = -np.inf
    return x


@pytest.mark.parametrize("case", ["random", "minus_inf", "one"])
def test_the_score_is_log_softmax_at_the_chosen_token(case):
    x = _logits(case)
    rng = np.random.RandomState(4)
    tokens = rng.randint(0, x.shape[1], size=x.shape[0]).astype(np.int32)
    tokens[0] = int(np.argmax(x[0]))
    if case == "minus_inf":
        tokens[1] = 2  # finite in a filtered row
        tokens[4] = 4  # a filtered entry itself: -inf, as log_softmax
    want = jnp.take_along_axis(
        jax.nn.log_softmax(jnp.asarray(x), axis=-1), tokens[:, None], axis=-1
    )[:, 0]
    got = jax.jit(chosen_logprob)(jnp.asarray(x), jnp.asarray(tokens))
    assert got.shape == (x.shape[0],) and got.dtype == jnp.float32
    assert not np.any(np.isnan(np.asarray(got)))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    if case == "minus_inf":
        assert np.asarray(got)[4] == -np.inf
    if case == "one":
        np.testing.assert_array_equal(got, np.zeros(5, np.float32))


def test_the_score_of_a_row_with_no_finite_logit_is_not_finite():
    x = jnp.full((2, 7), -jnp.inf).at[1].set(jnp.arange(7.0))
    got = np.asarray(chosen_logprob(x, jnp.asarray([3, 3])))
    want = np.asarray(jax.nn.log_softmax(x, axis=-1))[:, 3]
    assert np.isnan(got[0]) and np.isnan(want[0])
    np.testing.assert_allclose(got[1], want[1], atol=1e-6)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _wide(jaxpr, shape):
    """Primitives whose result is a float32 array of ``shape``."""
    return sorted(
        eqn.primitive.name
        for eqn in _eqns(jaxpr)
        for out in eqn.outvars
        if getattr(out.aval, "shape", None) == shape
        and out.aval.dtype == jnp.float32
    )


def test_the_score_writes_only_the_operand_of_its_one_sum():
    """Over the whole ``(rows, vocabulary)``: ``exp(logits - max)``,
    which the one sum reads (a compiler fuses the three). A log-softmax
    is a second ``sub``, the array written for one value a row."""
    shape = (6, 261)
    jaxpr = jax.make_jaxpr(chosen_logprob)(
        jnp.zeros(shape, jnp.float32), jnp.zeros(shape[:1], jnp.int32)
    ).jaxpr
    assert _wide(jaxpr, shape) == ["exp", "sub"]
    names = [eqn.primitive.name for eqn in _eqns(jaxpr)]
    assert names.count("reduce_sum") == 1 and names.count("reduce_max") == 1

    was = jax.make_jaxpr(log_softmax_score)(
        jnp.zeros(shape, jnp.float32), jnp.zeros(shape[:1], jnp.int32)
    ).jaxpr
    assert _wide(was, shape).count("sub") == 2


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_every_program_that_scores_takes_the_one_sum(
    lm_setup, program, monkeypatch
):
    """The draw's own arrays (temperature, Gumbel noise) stand beside
    the score's in both programs; scored through a log-softmax, either
    holds one more ``(rows, vocabulary)`` difference."""
    from adapt_tpu.runtime import continuous

    lm, variables = lm_setup

    def differences(score):
        monkeypatch.setattr(continuous, "chosen_logprob", score)
        srv = ContinuousBatcher(lm, variables, slots=SLOTS, chunk=2)
        if program == "step":
            rows = SLOTS
            jaxpr = type(srv)._step_chunk.trace(
                srv, srv._served, srv._caches, srv._dstate,
                srv._current_table(), truncate=False, nucleus=False,
                epoch=0,
            ).jaxpr.jaxpr
        else:
            rows, b = 1, srv.prompt_buckets[0]
            jaxpr = srv._prefill_fn(b).trace(
                srv._served, jnp.zeros((1, b), jnp.int32),
                jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.float32),
                jnp.zeros((1, 2), jnp.uint32), truncate=False,
                nucleus=False,
            ).jaxpr.jaxpr
        srv.close()
        return _wide(jaxpr, (rows, VOCAB)).count("sub")

    assert differences(chosen_logprob) == differences(log_softmax_score) - 1


def _serve(lm_setup, third, chunk):
    """Two greedy requests and ``third`` through one drained batcher:
    ``({name: (tokens, logprobs)}, variants, stats, counters)``."""
    lm, variables = lm_setup
    snap = global_metrics().snapshot(window=True)
    bat = drained(ContinuousBatcher(lm, variables, slots=SLOTS, chunk=chunk))
    rids = {
        "g1": bat.submit(np.asarray([1, 2, 3, 4], np.int32), 9),
        # A greedy request WITH its knobs set reads none of them.
        "g2": bat.submit(np.asarray([5, 6, 7], np.int32), 7, top_k=5,
                         top_p=0.8),
    }
    if third is not None:
        rids["third"] = bat.submit(np.asarray([8, 9, 10], np.int32), 4,
                                   **third)
    out = bat.run()
    served = {k: (out[r], bat.logprobs(r)) for k, r in rids.items()}
    variants = set(bat._variants[STEP])
    stats = bat.stats()
    counters = global_metrics().snapshot(since=snap)["counters"]
    bat.close()
    return served, variants, stats, counters


@pytest.fixture(scope="module")
def all_greedy(lm_setup):
    return _serve(lm_setup, {}, chunk=1)


def test_an_all_greedy_batcher_books_the_one_variant(all_greedy):
    _, variants, stats, counters = all_greedy
    assert variants == {GREEDY}
    assert stats["ticks"] == counters["continuous.ticks"] == 8
    assert stats["ticks_sampled"] == 0
    assert counters.get("continuous.ticks_sampled", 0) == 0


@pytest.mark.parametrize("third,variant", [
    (dict(temperature=0.9, rng=jax.random.PRNGKey(7)), (False, False)),
    (dict(temperature=0.9, top_k=5, rng=jax.random.PRNGKey(7)),
     (True, False)),
    (dict(temperature=1.2, top_p=0.7, rng=jax.random.PRNGKey(9)),
     (False, True)),
])
def test_a_mixed_batch_serves_its_greedy_rows_bit_for_bit(
    lm_setup, all_greedy, third, variant
):
    want, _, _, _ = all_greedy
    got, variants, stats, counters = _serve(lm_setup, third, chunk=1)
    # The sampled request's 4 tokens: one from its prefill, then three
    # ticks with a row that sampled; the greedy rows' last five had
    # none, and ran the program with no sort in it.
    assert variants == {variant, GREEDY}
    assert stats["ticks"] == counters["continuous.ticks"] == 8
    assert stats["ticks_sampled"] == counters["continuous.ticks_sampled"] == 3
    for name in ("g1", "g2"):
        np.testing.assert_array_equal(got[name][0], want[name][0])
        np.testing.assert_array_equal(got[name][1], want[name][1])


def test_a_batch_of_two_serves_what_a_batch_of_three_does(
    lm_setup, all_greedy
):
    want, _, _, _ = all_greedy
    got, variants, stats, _ = _serve(lm_setup, None, chunk=1)
    assert variants == {GREEDY} and stats["ticks_sampled"] == 0
    for name in ("g1", "g2"):
        np.testing.assert_array_equal(got[name][0], want[name][0])
        np.testing.assert_array_equal(got[name][1], want[name][1])
