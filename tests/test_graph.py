"""IR + partitioner unit tests.

Correctness oracle (SURVEY.md §4 build note, test #1): composed stage
outputs must equal the un-partitioned model output exactly — the property
the reference never tests but its design depends on (``src/dag_util.py``).
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapt_tpu.graph import (
    INPUT,
    InvalidCutError,
    LayerGraph,
    partition,
    valid_cut_points,
)
from adapt_tpu.graph.ir import Lambda
from adapt_tpu.graph.partition import balanced_cuts


def residual_mlp_graph(width=16, blocks=3):
    """A small DAG with residual joins: the minimal shape of the problem the
    reference's ``dag_util`` exists to solve (ResNet-style add joins)."""
    g = LayerGraph("res_mlp")
    g.add("embed", nn.Dense(width), INPUT)
    prev = "embed"
    for i in range(blocks):
        branch = g.add(f"block{i}_branch", nn.Dense(width), prev)
        prev = g.add(
            f"block{i}_out", Lambda(lambda a, b: jax.nn.relu(a + b), "addrelu"),
            (prev, branch),
        )
    g.add("head", nn.Dense(4), prev)
    return g


@pytest.fixture(scope="module")
def graph_and_vars():
    g = residual_mlp_graph()
    x = jnp.ones((2, 8))
    variables = g.init(jax.random.PRNGKey(0), x)
    return g, variables, x


def test_full_apply_shape(graph_and_vars):
    g, variables, x = graph_and_vars
    y = g.apply(variables, x)
    assert y.shape == (2, 4)


def test_eval_shapes(graph_and_vars):
    g, variables, x = graph_and_vars
    shapes = g.eval_shapes(variables, jax.ShapeDtypeStruct(x.shape, x.dtype))
    assert shapes["head"].shape == (2, 4)
    assert shapes["block1_out"].shape == (2, 16)


def test_topological_add_enforced():
    g = LayerGraph("bad")
    with pytest.raises(ValueError, match="unknown layer"):
        g.add("a", nn.Dense(3), "missing")


def test_duplicate_name_rejected():
    g = LayerGraph("dup")
    g.add("a", nn.Dense(3), INPUT)
    with pytest.raises(ValueError, match="duplicate"):
        g.add("a", nn.Dense(3), INPUT)


def test_valid_cut_points(graph_and_vars):
    g, _, _ = graph_and_vars
    cuts = valid_cut_points(g)
    # Branch layers are NOT valid cuts (the residual skip crosses them);
    # block outputs and embed are.
    assert "embed" in cuts
    for i in range(3):
        assert f"block{i}_out" in cuts
        assert f"block{i}_branch" not in cuts


@pytest.mark.parametrize(
    "cuts",
    [["block0_out"], ["embed", "block1_out"], ["block0_out", "block1_out", "block2_out"]],
)
def test_composed_stages_match_full_model(graph_and_vars, cuts):
    g, variables, x = graph_and_vars
    plan = partition(g, cuts)
    assert plan.num_stages == len(cuts) + 1
    stage_vars = plan.extract_variables(variables)
    y_full = g.apply(variables, x)
    y_composed = plan.compose(stage_vars, x)
    np.testing.assert_array_equal(np.asarray(y_full), np.asarray(y_composed))


def test_stage_coverage_disjoint_and_total(graph_and_vars):
    g, _, _ = graph_and_vars
    plan = partition(g, ["block0_out", "block2_out"])
    all_nodes = [n for s in plan.stages for n in s.node_names]
    assert sorted(all_nodes) == sorted(g.topo_order())
    assert len(all_nodes) == len(set(all_nodes))


def test_invalid_cut_rejected(graph_and_vars):
    g, _, _ = graph_and_vars
    with pytest.raises(InvalidCutError, match="skip connection"):
        partition(g, ["block1_branch"])


def test_unknown_cut_rejected(graph_and_vars):
    g, _, _ = graph_and_vars
    with pytest.raises(InvalidCutError, match="unknown cut"):
        partition(g, ["nope"])


def test_out_of_order_cuts_rejected(graph_and_vars):
    g, _, _ = graph_and_vars
    with pytest.raises(InvalidCutError):
        partition(g, ["block1_out", "block0_out"])


def test_balanced_cuts(graph_and_vars):
    g, variables, x = graph_and_vars
    cuts = balanced_cuts(g, 3)
    assert len(cuts) == 2
    plan = partition(g, cuts)  # must be a legal plan
    stage_vars = plan.extract_variables(variables)
    np.testing.assert_array_equal(
        np.asarray(plan.compose(stage_vars, x)), np.asarray(g.apply(variables, x))
    )


def test_stage_apply_jittable(graph_and_vars):
    g, variables, x = graph_and_vars
    plan = partition(g, ["block1_out"])
    stage_vars = plan.extract_variables(variables)
    s0 = jax.jit(plan.stage_apply(plan.stages[0]))
    s1 = jax.jit(plan.stage_apply(plan.stages[1]))
    y = s1(stage_vars[1], s0(stage_vars[0], x))
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(g.apply(variables, x)), rtol=1e-6
    )


def test_input_fanout_not_a_valid_cut():
    # INPUT consumed by two nodes: neither branch dominates; only the merge.
    g = LayerGraph("fan")
    g.add("a", nn.Dense(4), INPUT)
    g.add("b", nn.Dense(4), INPUT)
    g.add("c", Lambda(lambda p, q: p + q, "add"), ("a", "b"))
    g.add("d", nn.Dense(2), "c")
    assert valid_cut_points(g) == ["c"]
    with pytest.raises(InvalidCutError):
        partition(g, ["a"])


def test_output_cut_rejected(graph_and_vars):
    g, _, _ = graph_and_vars
    with pytest.raises(InvalidCutError, match="graph output"):
        partition(g, ["head"])


def test_balanced_cuts_partial_costs(graph_and_vars):
    g, _, _ = graph_and_vars
    costs = {n: 1.0 for n in g.topo_order() if "branch" in n}  # omit merges
    cuts = balanced_cuts(g, 2, costs=costs)
    assert len(cuts) == 1
    partition(g, cuts)


def test_balanced_cuts_too_many_stages(graph_and_vars):
    g, _, _ = graph_and_vars
    with pytest.raises(InvalidCutError):
        balanced_cuts(g, 20)


def test_compose_length_mismatch(graph_and_vars):
    g, variables, x = graph_and_vars
    plan = partition(g, ["block1_out"])
    sv = plan.extract_variables(variables)
    with pytest.raises(ValueError, match="stale plan"):
        plan.compose(sv[:1], x)


# -- architecture-by-value specs ---------------------------------------------


def _roundtrip(graph, x):
    """graph -> JSON -> graph; prove structural identity by running the
    ORIGINAL variables through the rebuilt graph (same node names, same
    module hyperparams => same variable trees, same outputs)."""
    import json

    import jax

    from adapt_tpu.graph.spec import graph_from_spec, graph_to_spec

    spec = json.loads(json.dumps(graph_to_spec(graph)))  # full wire trip
    rebuilt = graph_from_spec(spec)
    assert rebuilt.topo_order() == graph.topo_order()
    assert rebuilt.output == graph.output
    # One compiled program each (the same HLO twice gives the same
    # bits): eagerly every layer's operations compile one by one.
    variables = jax.jit(graph.init)(jax.random.PRNGKey(0), x)
    y_ref = jax.jit(graph.apply)(variables, x)
    y = jax.jit(rebuilt.apply)(variables, x)
    np.testing.assert_array_equal(np.asarray(y_ref), np.asarray(y))
    return rebuilt


def test_spec_roundtrip_vit_tiny():
    from adapt_tpu.models.vit import vit_tiny

    _roundtrip(vit_tiny(), jnp.ones((1, 32, 32, 3), jnp.float32))


def test_spec_roundtrip_resnet50():
    from adapt_tpu.models.resnet import resnet50

    # bf16 + s2d stem: dtype fields and stem variants must ship by value.
    _roundtrip(
        resnet50(num_classes=10, dtype=jnp.bfloat16, stem="s2d"),
        jnp.ones((1, 64, 64, 3), jnp.float32),
    )


def test_spec_roundtrip_efficientnet_b0():
    from adapt_tpu.models.efficientnet import efficientnet_b0

    # Exercises Callable act fields, float ratios and the "add" Lambda.
    _roundtrip(
        efficientnet_b0(num_classes=10), jnp.ones((1, 64, 64, 3), jnp.float32)
    )


def test_spec_rejects_unknown_lambda_and_foreign_imports():
    from adapt_tpu.graph.ir import Lambda, LayerGraph
    from adapt_tpu.graph.spec import graph_from_spec, graph_to_spec

    g = LayerGraph("bad")
    g.add("mystery", Lambda(lambda x: x * 3, "triple"))
    with pytest.raises(TypeError, match="LAMBDA_REGISTRY"):
        graph_to_spec(g)

    hostile = {
        "name": "evil",
        "output": "n",
        "nodes": [
            {
                "name": "n",
                "inputs": ["__input__"],
                "module": {"kind": "flax", "type": "os.system", "config": {}},
            }
        ],
    }
    with pytest.raises(ValueError, match="refusing to import"):
        graph_from_spec(hostile)
