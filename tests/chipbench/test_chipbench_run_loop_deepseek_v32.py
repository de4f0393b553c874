"""``test_chipbench_run_loop.py``'s control cases of ``deepseek-v3.2-exp``,
in a file of their own: under ``--dist loadfile`` one file is one
worker's; and the cell's sound walk in both trace modes."""

import pytest

from run_loop_cases import control_cases, rehearse, sound_walk

CONFIGS = ("deepseek-v3.2-exp",)
CELL = "dsv32_longgen32k"
#: A seed at which the SOUND rehearsal of this cell reads inside the
#: tolerance set for the published widths (at 64 channels bfloat16
#: reaches further, and one position of 192 swapped at the indexer's cut
#: moves a toy logit further than one of 2,048: the file's
#: ``correct.why`` has the toy readings seed by seed).
SEED = ("--seed", "11")


@pytest.mark.parametrize("cell,control", control_cases(only=CONFIGS))
def test_a_control_makes_the_run_incorrect(capsys, cell, control):
    """With the fault in the plain reference the configuration names,
    the served logprobs must disagree: one untraced pass (``run.py``
    walks no traced one under a fault)."""
    assert cell == CELL
    (plain,) = rehearse(
        capsys, "--workload", cell, *SEED, "--fault", control
    )
    assert "correct=False" in plain


def test_the_rehearsal_walks_the_cell_in_both_trace_modes(capsys):
    plain, traced = sound_walk(capsys, CELL, *SEED)
    assert "would report ['out_tok_per_s', 'setup_s']" in plain
    # the counters' share reads in a rehearsal; the kernel's do not (a
    # page of 128 on the CPU takes the plain arm: no such operation)
    assert "'dsa.selected_pct'" in traced
    assert "'kernel.sparse_latent_roofline'" not in traced
