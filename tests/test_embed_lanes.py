"""An engine holds its embedding tables with rows of whole lane tiles.

Where a model's ``dim`` is not a multiple of 128 (GPT-2-XL's 1600 is
12.5 tiles) the device's default layout puts a table's long axis on the
lanes, and every program that looks a row up first rewrites the whole
table row-major: 322 MB a program for GPT-2-XL's token table (PERF.md
section 6, PR 47). ``ContinuousBatcher`` therefore holds the tables
padded to the next tile (``transformer_lm.lane_tiled`` /
``embed_tables_for``). Held here: the compiled lookup for a described
v5e copies no table at either kind of width and is the plain module's
program at a whole-tile one; the padded lookup returns the plain one's
rows bit for bit; a batcher at such a width serves what ``generate()``
gives on the model's own tree, through whole-prompt and chunked prefill,
and hands out the model's tree. (Every toy model of the suite has rows
of 32 or 64, so every batcher test runs the padded arm; the whole-tile
arm's batcher is ``test_chip_lowering.py``'s, at 256.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import pool_copies

from adapt_tpu.models.transformer_lm import (
    TokenEmbed,
    embed_tables_for,
    generate,
    lane_tiled,
    transformer_lm,
)
from adapt_tpu.runtime.continuous import ContinuousBatcher

VOCAB, MAX_LEN = 50257, 1024


@pytest.mark.parametrize("dim", [1600, 2048])
def test_lookup_compiles_for_v5e_with_no_copy_of_a_table(
    one_chip, no_persistent_cache, dim
):
    """GPT-2-XL's and Cerebras-GPT's tables, bf16 rows, a decode step's
    lookup (``embed_positions`` of one token a slot) and a prefill
    pass's: the engine's form holds no ``copy`` of a table-shaped
    buffer; the plain module at 1600 holds the token table's (the guard
    sees what it guards), and at 2048 the two are one program."""
    plain = TokenEmbed(VOCAB, dim, MAX_LEN, dtype=jnp.bfloat16)
    tree = jax.eval_shape(
        lambda: {"embed": jax.tree.map(
            lambda t: t.astype(jnp.bfloat16) if t.shape[0] == VOCAB else t,
            plain.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)),
        )}
    )
    held = lane_tiled(plain)

    def on_chip(s, dtype=None):
        return jax.ShapeDtypeStruct(
            getattr(s, "shape", s), dtype or s.dtype, sharding=one_chip
        )

    def text(embed, ids):
        tables = jax.eval_shape(lambda t: embed_tables_for(embed, t), tree)
        return jax.jit(
            lambda t, ids, pos: embed.apply(
                t["embed"], ids, pos, method="embed_positions"
            )
        ).lower(
            jax.tree.map(on_chip, tables), on_chip(ids, jnp.int32),
            on_chip(ids, jnp.int32),
        ).compile().as_text(), jax.tree.leaves(tables)

    for ids in ((8, 1), (1, 256)):
        # (One call site: the text carries its source lines.)
        (got, tables), (want, _) = (text(e, ids) for e in (held, plain))
        # [0]: relayouts. (The compiler may stage the position table
        # through fast memory as it lies, a "move": at both widths.)
        for table in tables:
            assert pool_copies(got, table.shape)[0] == 0, table.shape
        if dim % 128:
            assert pool_copies(want, (VOCAB, dim))[0] == 1
        else:
            assert got == want


DIM = 160  # 1.25 lane tiles: held as 256


@pytest.mark.parametrize("kw", [
    {}, {"streams": 4}, {"scale": 0.5}, {"use_pos": False, "scale": 3.0},
], ids=["gpt2", "streams4", "scaled", "no-positions"])
def test_held_rows_are_the_plain_rows(kw):
    plain = TokenEmbed(97, DIM, 64, dtype=jnp.bfloat16, **kw)
    ids = jnp.asarray([[3, 96, 0, 41], [7, 7, 12, 95]], jnp.int32)
    tree = {"embed": plain.init(jax.random.PRNGKey(1), ids)}
    held = lane_tiled(plain)
    assert held.table_dim == 256
    padded = embed_tables_for(held, tree)
    assert {t.shape[-1] for t in jax.tree.leaves(padded)} == {256}
    back = embed_tables_for(plain, padded)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(a, b)
    assert embed_tables_for(plain, tree) is tree
    pos_ids = jnp.asarray([[0, 1, 2, 3], [-1, 0, 1, 63]], jnp.int32)
    for method, args in (
        ("__call__", (ids,)),
        ("embed_at", (ids[:, :1], jnp.int32(5))),
        ("embed_positions", (ids, pos_ids)),
    ):
        want = plain.apply(tree["embed"], *args, method=method)
        got = held.apply(padded["embed"], *args, method=method)
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(
            np.asarray(got, np.float32), np.asarray(want, np.float32)
        )


@pytest.fixture(scope="module")
def lm_setup():
    lm = transformer_lm(97, DIM, 2, 4, 2 * DIM, max_len=64, name="lanes")
    variables = jax.jit(lm.graph.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    return lm, variables


def test_batcher_serves_generates_tokens_from_held_tables(lm_setup):
    """A whole-prompt prefill and two chunked passes of 8, then decode:
    ``generate()``'s tokens and logprobs on the model's own tree.
    (After ``recover()``: ``test_recovery.py``, whose model's rows of 32
    are held as 128.)"""
    lm, variables = lm_setup
    prompts = np.zeros((2, 12), np.int32)
    prompts[0, :5] = np.arange(1, 6)
    prompts[1] = (np.arange(12) * 7 + 3) % 97
    lengths, steps = [5, 12], 8
    want, want_lp = generate(
        lm, variables, jnp.asarray(prompts), steps,
        prompt_lengths=jnp.asarray(lengths), return_logprobs=True,
    )
    bat = ContinuousBatcher(
        lm, variables, slots=2, chunk=2, page_size=8, prefill_chunk=8
    )
    assert bat.stats()["embed_row_pad"] == 96
    held = jax.tree.leaves(bat._served["embed"])
    assert {t.shape[-1] for t in held} == {256}
    rids = [bat.submit(p[:n], steps) for p, n in zip(prompts, lengths)]
    out = bat.run()
    for row, r in enumerate(rids):
        np.testing.assert_array_equal(out[r], np.asarray(want)[row])
        # (test_continuous's tolerance: a paged and a dense cache sum
        # in another order, at every width.)
        np.testing.assert_allclose(
            bat.logprobs(r), np.asarray(want_lp)[row], rtol=2e-4, atol=2e-4
        )
    # What the batcher hands out is the model's tree.
    own = jax.tree.leaves(bat.variables)
    for a, b in zip(own, jax.tree.leaves(variables)):
        np.testing.assert_array_equal(a, b)
    bat.close()
