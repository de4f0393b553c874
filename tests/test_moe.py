"""MoE + expert-parallelism tests.

Correctness oracle: with capacity >= N every token reaches its chosen
expert(s), so routed output must equal a dense per-token loop over the
same expert MLPs. EP test: expert-sharded forward == replicated forward.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapt_tpu.core.mesh import MeshSpec, build_mesh
from adapt_tpu.models.moe import MoEMlp
from adapt_tpu.parallel.expert import (
    expert_shardings,
    expert_utilization,
    place_experts,
)

B, S, D, E, H = 2, 16, 8, 4, 32


def _dense_oracle(variables, x, top_k):
    """Route every token through its top-k experts with full capacity."""
    p = variables["params"]
    n = B * S
    tokens = np.asarray(x.reshape(n, D), np.float32)
    gates = jax.nn.softmax(
        jnp.asarray(tokens) @ p["gate"], axis=-1
    )
    gates = np.asarray(gates)
    out = np.zeros_like(tokens)
    for t in range(n):
        order = np.argsort(-gates[t])
        for choice in order[:top_k]:
            hidden = np.asarray(
                jax.nn.gelu(
                    jnp.asarray(tokens[t] @ np.asarray(p["w1"][choice]))
                    + jnp.asarray(p["b1"][choice])
                )
            )
            y = hidden @ np.asarray(p["w2"][choice]) + np.asarray(
                p["b2"][choice]
            )
            out[t] += gates[t, choice] * y
    return out.reshape(B, S, D)


@pytest.mark.parametrize("top_k", [1, 2])
def test_moe_matches_dense_oracle_full_capacity(rng, top_k):
    moe = MoEMlp(
        num_experts=E, hidden_dim=H, top_k=top_k, capacity_factor=float(E)
    )  # capacity >= N: nothing dropped
    x = jax.random.normal(rng, (B, S, D))
    variables = moe.init(jax.random.PRNGKey(1), x)
    y = moe.apply(variables, x)
    ref = _dense_oracle(variables, x, top_k)
    np.testing.assert_allclose(np.asarray(y), ref, rtol=2e-4, atol=2e-4)


def test_moe_capacity_drops_tokens(rng):
    moe = MoEMlp(num_experts=E, hidden_dim=H, top_k=1, capacity_factor=0.05)
    x = jax.random.normal(rng, (B, S, D))
    variables = moe.init(jax.random.PRNGKey(1), x)
    y, state = moe.apply(variables, x, mutable=["intermediates"])
    # capacity ~ 1 slot/expert: most tokens dropped -> many zero outputs.
    zero_rows = np.sum(
        np.all(np.asarray(y).reshape(-1, D) == 0.0, axis=-1)
    )
    assert zero_rows > 0
    aux = state["intermediates"]["aux_loss"][0]
    assert np.isfinite(float(aux)) and float(aux) >= 1.0 - 1e-3


def test_moe_aux_loss_uniform_is_one():
    # Perfectly uniform gates -> aux loss == 1 (its minimum).
    from adapt_tpu.models.moe import _one_hot_routing

    gates = jnp.full((8, 4), 0.25)
    _, _, aux = _one_hot_routing(gates, capacity=8, top_k=1)
    assert abs(float(aux) - 1.0) < 1e-5


def test_expert_parallel_matches_replicated(rng, devices):
    mesh = build_mesh(MeshSpec((("ep", 4),)), devices[:4])
    moe = MoEMlp(num_experts=E, hidden_dim=H, top_k=1, capacity_factor=2.0)
    x = jax.random.normal(rng, (B, S, D))
    variables = moe.init(jax.random.PRNGKey(1), x)
    ref = moe.apply(variables, x)

    shardings = expert_shardings(variables, mesh, num_experts=E)
    # gate [D, E]: not expert-stacked -> replicated; w1 [E, D, H]: sharded.
    flat = jax.tree_util.tree_leaves_with_path(shardings)
    specs = {
        jax.tree_util.keystr(path): s.spec for path, s in flat
    }
    assert any(spec == jax.sharding.PartitionSpec("ep", None, None)
               for spec in specs.values())
    placed = place_experts(variables, mesh, num_experts=E)
    y = jax.jit(moe.apply)(placed, x)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_expert_utilization_sums_to_one(rng):
    gates = jax.nn.softmax(jax.random.normal(rng, (64, E)), axis=-1)
    util = expert_utilization(gates)
    assert util.shape == (E,)
    assert abs(util.sum() - 1.0) < 1e-6


# -- MoE decoder LM (dropless per-token routing) ------------------------------


def _moe_lm():
    from adapt_tpu.models.transformer_lm import transformer_lm

    return transformer_lm(
        53, 32, 2, 4, 48, max_len=48, moe_experts=8, moe_top_k=2,
        name="moe_lm",
    )


def test_moe_decoder_mlp_is_per_token_independent(rng):
    """The parity-enabling property: each token's output depends only on
    its own hidden state — a batch of two rows equals the two rows
    computed separately (capacity routing would fail this)."""
    from adapt_tpu.models.moe import MoEDecoderMlp

    m = MoEDecoderMlp(num_experts=8, hidden_dim=16, top_k=2)
    x = jax.random.normal(rng, (2, 8, 8))
    variables = m.init(jax.random.PRNGKey(0), x)
    both = m.apply(variables, x)
    one = m.apply(variables, x[:1])
    two = m.apply(variables, x[1:])
    np.testing.assert_allclose(
        np.asarray(both), np.concatenate([one, two]), rtol=1e-6, atol=1e-6
    )


def test_moe_lm_cached_decode_matches_full_forward():
    """KV-cached greedy generate on the MoE decoder == stepwise argmax
    of the full causal forward — the same parity bar as the dense LM
    (dropless routing is what makes it reachable)."""
    from adapt_tpu.models.transformer_lm import generate

    lm = _moe_lm()
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    prompt = jax.random.randint(
        jax.random.PRNGKey(1), (2, 6), 0, 53, jnp.int32
    )
    from conftest import greedy_by_full_forward

    got = np.asarray(generate(lm, variables, prompt, steps=5))
    np.testing.assert_array_equal(
        got, greedy_by_full_forward(lm, variables, prompt, 5)
    )


def test_moe_lm_serves_through_paged_batcher():
    from adapt_tpu.models.transformer_lm import generate
    from adapt_tpu.runtime.continuous import ContinuousBatcher

    lm = _moe_lm()
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    rng = np.random.RandomState(21)
    prompts = [rng.randint(0, 53, size=n).astype(np.int32)
               for n in (5, 9, 3)]
    bat = ContinuousBatcher(
        lm, variables, slots=2, chunk=2, kv_layout="paged", page_size=16
    )
    ids = {bat.submit(p, 4): p for p in prompts}
    out = bat.run()
    for rid, p in ids.items():
        want = np.asarray(
            generate(lm, variables, jnp.asarray(p)[None], 4)
        )[0]
        np.testing.assert_array_equal(out[rid], want)


def test_moe_lm_expert_sharded_generate_matches(devices):
    """Experts placed over an 8-device ep mesh: generate() under GSPMD
    equals the replicated run token-for-token."""
    from adapt_tpu.models.transformer_lm import generate

    lm = _moe_lm()
    variables = lm.graph.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    prompt = jax.random.randint(
        jax.random.PRNGKey(2), (2, 5), 0, 53, jnp.int32
    )
    want = np.asarray(generate(lm, variables, prompt, steps=4))
    mesh = build_mesh(MeshSpec(axes=(("ep", len(devices)),)))
    placed = place_experts(variables, mesh, num_experts=8)
    got = np.asarray(generate(lm, placed, prompt, steps=4))
    np.testing.assert_array_equal(got, want)


def test_both_moe_layers_sow_one_aux_convention(rng):
    """The refactor's invariant: MoEMlp (capacity-routed) and
    MoEDecoderMlp (dropless) sow the SAME Switch-style aux_loss for the
    same inputs — one scale, one threshold, as the docstrings promise."""
    from adapt_tpu.models.moe import MoEDecoderMlp

    x = jax.random.normal(rng, (B, S, D))
    train = MoEMlp(num_experts=E, hidden_dim=H, top_k=1,
                   capacity_factor=float(E))
    serve = MoEDecoderMlp(num_experts=E, hidden_dim=H, top_k=1)
    tv = train.init(jax.random.PRNGKey(1), x)
    # Same gate weights -> same routing distribution for both layers.
    sv = jax.tree.map(lambda a: a, serve.init(jax.random.PRNGKey(1), x))
    sv["params"]["gate"] = tv["params"]["gate"]
    _, ts = train.apply(tv, x, mutable=["intermediates"])
    _, ss = serve.apply(sv, x, mutable=["intermediates"])
    np.testing.assert_allclose(
        float(ts["intermediates"]["aux_loss"][0]),
        float(ss["intermediates"]["aux_loss"][0]),
        rtol=1e-6,
    )
