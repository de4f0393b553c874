"""K-EXAONE's plain reference vouches layer by layer: a held expert
inside a sparse layer's margin keeps the position out, one beyond it
does not; and a served answer with ONE router choice flipped, made
from the reference's own ``ARCH["flip"]`` on a toy model served by the
batcher and judged by ``lm_engine.correctness_sample`` itself, reads
as the configuration's ``correct.why`` says: kept out where the flip
lies inside its layer's margin (the rule before PR 41, ``MARGIN_BEFORE``
in every layer, vouched for it and read WRONG), still WRONG beyond."""

import contextlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import k_exaone
from chipbench import k_exaone_reference as ref
from chipbench import lm_engine

ROOT = Path(__file__).parents[2]
CONFIG = json.loads(
    (ROOT / "chipbench/configs/k-exaone-236b-a23b.json").read_text()
)
WINDOW = 16
ARCH = {"window": WINDOW, "top_k": 2}
LAYERS = 4  # sparse layers of the five kept
MARGINS = list(ref.margins(LAYERS))
#: The one margin every sparse layer had before PR 41 (in this gap's
#: units: 0.04 then, over the held expert's own slope alone, is
#: 0.04 / sqrt(2) for two experts at the same slope).
MARGIN_BEFORE = 0.028
SERVING = dict(
    slots=4, chunk=4, kv_layout="paged", page_size=8, prefill_chunk=32,
    prompt_buckets=[16, 32, 48, 64, 96, 128],
)


def test_the_margins_widen_with_depth_and_none_is_under_the_old_one():
    assert MARGINS == sorted(MARGINS) and MARGINS[0] >= MARGIN_BEFORE
    assert MARGINS[-1] > MARGIN_BEFORE


def _layer_params(key, bias=None):
    """Eight experts of width 8, the first two held, router = I: a
    token's hidden state IS its router logits."""
    d = hid = 8
    keys = jax.random.split(jax.random.PRNGKey(key), 6)
    return dict(
        router=jnp.eye(d),
        router_bias=jnp.zeros(8) if bias is None else jnp.asarray(bias),
        w_gate=jax.random.normal(keys[0], (2, d, hid)),
        w_up=jax.random.normal(keys[1], (2, d, hid)),
        w_down=jax.random.normal(keys[2], (2, hid, d)),
        shared_gate={"kernel": jax.random.normal(keys[3], (d, hid))},
        shared_up={"kernel": jax.random.normal(keys[4], (d, hid))},
        shared_down={"kernel": jax.random.normal(keys[5], (hid, d))},
    )


def _logits(score):
    score = np.asarray(score, np.float64)
    return jnp.asarray(np.log(score / (1 - score)), jnp.float32)[None, None]


@pytest.mark.parametrize("layer", range(LAYERS))
@pytest.mark.parametrize("side,vouched", [(0.9, False), (1.1, True)])
def test_a_held_expert_inside_its_layers_margin_keeps_the_position_out(
    layer, side, vouched
):
    """Top-2 of eight: experts 2 and 3 are in, and held expert 0 is
    the first out, as far under 0.60 as expert 3 is over it: ``side``
    times this layer's margin between the two, in units of what unit
    noise on both their logits moves their difference by."""
    score = np.full(8, 0.1)
    delta = side * MARGINS[layer] * np.hypot(0.24, 0.24) / 2
    score[[2, 3, 0]] = 0.9, 0.60 + delta, 0.60 - delta
    slope = score * (1 - score)
    gap_by_hand = 2 * delta / np.hypot(slope[0], slope[3])
    arch = {**ref.ARCH, **ARCH}
    p = _layer_params(layer)
    out, gap = ref._experts(p, _logits(score), arch, False)
    assert float(gap[0, 0]) == pytest.approx(gap_by_hand, rel=1e-3)
    gaps = jnp.full((LAYERS, 1, 1), jnp.inf).at[layer].set(gap)
    assert bool(ref.vouched(gaps)[0, 0]) is vouched
    # Flipped, the held expert is in and the last in (3) is out: the
    # output moves by what a served model's other choice moves it.
    flipped, _ = ref._experts(
        p, _logits(score), arch, False, flip=np.ones((1, 1), bool)
    )
    assert float(jnp.abs(flipped - out).max()) > 0.1


def test_a_saturated_held_expert_is_passed_by_a_neighbour_that_is_not():
    """Held expert 0's sigmoid is saturated (score 0.999; a bias of
    -0.4 brings it to the bar), so rounding barely moves IT, and the
    distance over its own slope alone (the gap before PR 41) reads
    0.8 logits and more; expert 3, first out at the middle of its
    sigmoid, needs 0.0032 logits to pass it. The gap counts both."""
    score = np.full(8, 0.1)
    score[[2, 0, 3]] = 0.9, 0.999, 0.5982
    bias = np.zeros(8)
    bias[0] = -0.4
    _, gap = ref._experts(
        _layer_params(0, bias), _logits(score), {**ref.ARCH, **ARCH}, False
    )
    apart = (0.999 - 0.4) - 0.5982
    own_slope_alone = apart / (0.999 * 0.001)
    assert own_slope_alone > 0.8
    assert float(gap[0, 0]) == pytest.approx(
        apart / np.hypot(0.999 * 0.001, 0.5982 * 0.4018), rel=1e-2
    )
    assert float(gap[0, 0]) < min(MARGINS)


def _model():
    model = dict(CONFIG["model"])
    model.update(
        vocab_size=64, hidden_size=32, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, intermediate_size=64,
        moe_intermediate_size=16, num_experts=1, num_experts_published=8,
        num_experts_per_tok=2, positions_served=128,
        sliding_windows=[w and WINDOW for w in model["sliding_windows"]],
    )
    return model


class Toy:
    """The toy model in float32 behind one batcher: served, it follows
    the plain reference to 5e-4, so the only disagreement a sample
    shows is the one a test plants."""

    def __init__(self):
        from adapt_tpu.runtime.continuous import ContinuousBatcher

        self.lm, self.variables, self.shape = k_exaone.build(
            _model(), "float32", 7
        )
        s = self.serving = dict(SERVING)
        s["pool_pages"] = lm_engine.pool_pages(s, [(32, 8)], 128, 32)
        self.srv = ContinuousBatcher(
            self.lm, self.variables, slots=s["slots"], chunk=s["chunk"],
            kv_layout=s["kv_layout"], page_size=s["page_size"],
            pool_pages=s["pool_pages"], prefill_chunk=s["prefill_chunk"],
            prompt_buckets=tuple(s["prompt_buckets"]),
        )
        steps = lm_engine._sample_steps(CONFIG["correct"])
        lens = lm_engine._sample_prompts(s["prefill_chunk"], 128, steps)
        #: (row, column) of each compared position in the reference's
        #: (b, s - 1) arrays, in the order the engine compares them.
        self.at = [
            (row, n - 1 + j) for row, n in enumerate(lens)
            for j in range(steps)
        ]

    def sample(self, variables, reference, planted=None):
        """The engine's comparison of this model as served;
        ``planted``: (index among the 24, value) one served logprob
        replaced where the batcher hands it out."""
        self.srv.variables = variables
        drv = lm_engine.Driver(self.srv, 64, 7, contextlib.nullcontext)
        hand_out, handed = self.srv.logprobs, []

        def logprobs(rid):
            out = np.array(hand_out(rid), np.float32)
            for j in range(len(out)):
                if planted and planted[0] == len(handed) * len(out) + j:
                    out[j] = planted[1]
            handed.append(rid)
            return out

        self.srv.logprobs = logprobs
        try:
            return lm_engine.correctness_sample(
                drv, variables, self.serving, 128, reference,
                CONFIG["correct"],
            ), drv
        finally:
            del self.srv.logprobs


@pytest.fixture(scope="module")
def toy():
    t = Toy()
    yield t
    t.srv.close()


def now(variables, ids, fault=""):
    return ref.next_token_logprobs(variables, ids, fault, arch=ARCH)


def before(variables, ids, fault=""):
    logp, gaps = ref.logprobs_and_gaps(variables, ids, fault, arch=ARCH)
    return logp, (gaps >= MARGIN_BEFORE).all(0)


def _with_bias(variables, block, value):
    """The tree with held expert 0's selection bias in ``block`` set."""
    params = variables[block]["params"]
    experts = dict(params["experts"])
    experts["router_bias"] = experts["router_bias"].at[0].set(value)
    return {**variables, block: {"params": {**params, "experts": experts}}}


def _place(toy, layer, ids, row, col, target):
    """Move held expert 0 of sparse layer ``layer`` toward its bar
    until position (row, col)'s gap there is ``target``: by its
    selection bias, which no weight and no earlier layer depends on."""
    block = f"decoder_block_{layer + 1}"  # block 0 is the dense one
    b0 = float(
        toy.variables[block]["params"]["experts"]["router_bias"][0]
    )

    def gap(b):
        _, gaps = ref.logprobs_and_gaps(
            _with_bias(toy.variables, block, b), ids, arch=ARCH
        )
        return float(gaps[layer, row, col])

    # ONE expert is held, so the gap is its distance alone: linear in
    # the bias, at half the slope once it stands next to the bar (the
    # bar is then midway to it). A step sized by the local slope
    # lands on the target or short of it, never past the bar.
    b, g = b0, gap(b0)
    assert g > target
    for _ in range(12):
        if abs(g - target) < 1e-4:
            break
        slope = (gap(b + 1e-4) - g) / 1e-4
        b += (target - g) / slope
        g = gap(b)
    return _with_bias(toy.variables, block, b), g


@pytest.mark.parametrize("layer", range(LAYERS))
def test_one_flipped_choice_reads_as_its_layers_margin_says(toy, layer):
    tol = CONFIG["correct"]["logprob_tol"]
    sound, drv = toy.sample(toy.variables, now)
    assert sound.ok and sound.worst < 1e-3, sound
    rids = list(drv.reqs)[:3]
    width = max(
        len(drv.reqs[r]["ids"]) + len(drv.reqs[r]["tokens"]) for r in rids
    )
    ids = np.zeros((3, width), np.int32)
    for row, rid in enumerate(rids):
        seq = np.concatenate([drv.reqs[rid]["ids"], drv.reqs[rid]["tokens"]])
        ids[row, : len(seq)] = seq
    ids = jnp.asarray(ids)
    _, gaps = ref.logprobs_and_gaps(toy.variables, ids, arch=ARCH)
    gaps = np.asarray(gaps)
    inside = (MARGIN_BEFORE + MARGINS[layer]) / 2
    beyond = 1.25 * MARGINS[layer]
    # A compared position every layer vouches for with room, so that
    # what keeps it out afterwards is the expert this test moves.
    for index, (row, col) in enumerate(toy.at):
        if (gaps[:, row, col] < 3 * max(MARGINS)).any():
            continue
        mask = np.zeros(ids.shape, bool)
        mask[row, col] = True
        cases = []
        for target in (inside, beyond):
            placed, got = _place(toy, layer, ids, row, col, target)
            assert got == pytest.approx(target, abs=2e-3)
            flipped, _ = ref.logprobs_and_gaps(
                placed, ids, arch={**ARCH, "flip": {layer: mask}}
            )
            true, _ = ref.logprobs_and_gaps(placed, ids, arch=ARCH)
            moved = abs(float(flipped[row, col] - true[row, col]))
            cases.append((placed, float(flipped[row, col]), moved))
        if min(moved for *_, moved in cases) > 1.5 * tol:
            break
    else:
        pytest.fail("no compared position whose flipped choice moves its "
                    "logprob by 1.5 tolerances: choose another toy seed")
    (placed, value, moved), far = cases
    if MARGINS[layer] > MARGIN_BEFORE:
        # Inside this layer's margin and beyond the old one: the old
        # rule vouched for it and read WRONG, this one keeps it out.
        was, _ = toy.sample(placed, before, planted=(index, value))
        assert not was.ok and was.worst == pytest.approx(moved, abs=2e-3)
        c, _ = toy.sample(placed, now, planted=(index, value))
        assert c.ok and c.kept_out == pytest.approx(moved, abs=2e-3), c
        assert c.vouched >= c.least
    # Beyond the margin the flipped choice is vouched for and WRONG.
    placed, value, moved = far
    c, _ = toy.sample(placed, now, planted=(index, value))
    assert not c.ok and c.worst == pytest.approx(moved, abs=2e-3), c
