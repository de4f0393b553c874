"""`correct` where the model makes hard choices: the bfloat16 mixture of
``another_arch/`` served by ONE batcher (the weights swapped seed by
seed, as ``--seed`` draws them) and compared by
``lm_engine.correctness_sample`` itself, against the reference the
configuration names, which says which positions it vouches for. The
readings these cases hold stand in the file's ``correct.why``."""

import contextlib
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

from chipbench import lm_engine
from chipbench import manifest as mf
from chipbench.builders import init_weights

HERE = Path(__file__).parent
#: Seeds at which the plain maximum reads a flipped tie (0.1771, 0.1734,
#: 0.1167, 0.0483): the mask at work, not only at rest.
FLIPPED = (0, 8, 11, 21)
SEEDS = (0, 1, 2, 3, 4, 5, 6, 8, 10, 11, 13, 16, 21)


class Served:
    def __init__(self, root):
        from adapt_tpu.runtime.continuous import ContinuousBatcher

        self.config = json.loads(
            (root / "chipbench_more/configs/tiny-moe-bf16.json").read_text()
        )
        self.correct = self.config["correct"]
        self.reference = mf.part_of(self.config, "reference")
        self.lm, variables, self.shape = mf.part_of(self.config, "builder")(
            self.config["model"], self.config["dtype"], 0
        )
        self.seed = 0
        s = self.serving = dict(self.config["serving"])
        s["pool_pages"] = lm_engine.pool_pages(s, [(60, 6)], 1024)
        self.srv = ContinuousBatcher(
            self.lm, variables, slots=s["slots"], chunk=s["chunk"],
            kv_layout=s["kv_layout"], page_size=s["page_size"],
            pool_pages=s["pool_pages"], prefill_chunk=s["prefill_chunk"],
            prompt_buckets=tuple(s["prompt_buckets"]),
        )

    def sample(self, seed, fault="", reference=None, correct=None):
        """What a run at ``--seed`` compares: that seed's weights and
        token ids, the first three requests of a new driver."""
        import jax.numpy as jnp

        if seed != self.seed:  # a draw compiles: once a seed
            self.seed, self.srv.variables = seed, init_weights(
                self.lm, jnp.dtype(self.config["dtype"]), seed
            )
        drv = lm_engine.Driver(
            self.srv, self.shape["vocab"], seed, contextlib.nullcontext
        )
        return lm_engine.correctness_sample(
            drv, self.srv.variables, self.serving, self.shape["max_len"],
            reference or self.reference, correct or self.correct, fault,
        )


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    root = tmp_path_factory.mktemp("vouched")
    shutil.copytree(
        HERE / "another_arch", root / "chipbench_more",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    sys.path.insert(0, str(root))
    s = Served(root)
    yield s
    s.srv.close()
    sys.path.remove(str(root))


@pytest.mark.parametrize("seed", SEEDS)
def test_vouched_positions_carry_the_comparison(served, seed):
    tol = served.correct["logprob_tol"]
    c = served.sample(seed)
    assert c.ok and c.worst <= 0.03 < tol, c
    assert c.compared == 24 and c.vouched >= c.least == 18, c
    if seed in FLIPPED:
        # The plain maximum would have compared this and answered false.
        assert c.kept_out > tol, c
    assert "vouched" in c.line() and "not vouched" in c.line()
    for control in served.correct["controls"]:
        wrong = served.sample(seed, fault=control)
        assert not wrong.ok and wrong.worst > 2 * tol, (control, wrong)
        assert wrong.vouched >= wrong.least, (control, wrong)


def _poisoned(served, where, masked=True):
    """The reference with one NaN among the positions compared: the
    first one it vouches for (``where`` true) or does not."""

    def reference(variables, ids, fault=""):
        logp, vouched = (
            np.array(a) for a in served.reference(variables, ids, fault)
        )
        at = slice(39, 39 + lm_engine.SAMPLE_STEPS)  # row 0: prompt of 40
        col = 39 + int(np.flatnonzero(vouched[0, at] == where)[0])
        logp[0, col] = np.nan
        return (logp, vouched) if masked else logp

    return reference


@pytest.mark.parametrize("masked", [True, False])
def test_a_reference_that_returns_nan_is_not_correct(served, masked):
    """Python's ``max(0.0, nan)`` is 0.0: a NaN in the reference used
    to pass."""
    c = served.sample(0, reference=_poisoned(served, True, masked))
    assert not c.ok and np.isnan(c.worst), c
    assert (c.kept_out is None) == (not masked)


def test_a_nan_the_reference_does_not_vouch_for_is_shown_not_judged(served):
    c = served.sample(0, reference=_poisoned(served, False))
    assert c.ok and np.isnan(c.kept_out), c


def test_a_served_value_that_is_not_finite_is_not_correct(served,
                                                          monkeypatch):
    """Wherever it stands: nobody's mask covers the program's own NaN."""
    logprobs = served.srv.logprobs

    def served_nan(rid):
        out = np.array(logprobs(rid))
        out[:] = np.nan
        return out

    monkeypatch.setattr(served.srv, "logprobs", served_nan)
    c = served.sample(0)
    assert not c.ok and np.isnan(c.worst) and c.vouched == c.compared, c


def test_a_reference_that_vouches_for_too_little_is_not_correct(served):
    def shy(variables, ids, fault=""):
        logp, vouched = served.reference(variables, ids, fault)
        vouched = np.array(vouched)
        vouched[:, ::2] = False
        return logp, vouched

    c = served.sample(1, reference=shy)
    assert c.worst <= c.tol and c.vouched < c.least and not c.ok, c


def test_a_mask_needs_min_vouched_in_the_file(served):
    correct = {"logprob_tol": served.correct["logprob_tol"]}
    with pytest.raises(KeyError, match="min_vouched"):
        served.sample(1, correct=correct)


def test_a_plain_array_is_compared_as_before(served):
    """No mask: every position vouched, the plain maximum compared; at
    seed 0 that is the flipped tie, over this file's tolerance."""
    from chipbench_more import plain

    c = served.sample(0, reference=plain.next_token_logprobs)
    assert c.vouched == c.compared == 24 and c.kept_out is None
    assert not c.ok and abs(c.worst - 0.1771) < 5e-4, c
    assert "not vouched" not in c.line()
    assert c.line().startswith(
        "correctness: served logprobs vs plain reference, max|err| 0.1771 "
        "(tolerance 0.045), vouched 24 of 24 -> WRONG"
    )
