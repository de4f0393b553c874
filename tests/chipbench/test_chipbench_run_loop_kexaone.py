"""``test_chipbench_run_loop.py``'s control cases of ``k-exaone-236b-a23b``,
in a file of their own: under ``--dist loadfile`` one file is one
worker's."""

import pytest

from run_loop_cases import control_cases, rehearse


@pytest.mark.parametrize("cell,control", control_cases(only=("k-exaone-236b-a23b",)))
def test_a_control_makes_the_run_incorrect(capsys, cell, control):
    """With the fault in the plain reference the configuration names,
    the served logprobs must disagree, in both passes."""
    plain, traced = rehearse(capsys, "--workload", cell, "--fault", control)
    assert "correct=False" in plain and "correct=False" in traced
