"""The `mixed-pallas` cases of two tests of tests/test_models.py: the tiny
mixed stack through the interpreted flash kernels takes 20-45 s a case, and a
test that long lives in a file of few tests (the rule in tests/conftest.py).
The case lists and the bodies stay where they are: each case here is the
origin's, and calls the origin's test."""

import pytest

from tests import test_models as origin
from tests.test_models import keeps_everything  # noqa: F401 - the fixture


@pytest.mark.parametrize("preset,saved", [case for case in origin._REMAT_CASES if origin._long(case)],
                         ids=origin._kept_id)
def test_a_recomputing_block_gives_the_loss_and_gradients_of_one_that_keeps_everything(
        preset, saved, keeps_everything):  # noqa: F811
    origin.test_a_recomputing_block_gives_the_loss_and_gradients_of_one_that_keeps_everything(
        preset, saved, keeps_everything)


@pytest.mark.parametrize("preset,kernels", [pytest.param(*case, id=name) for name, case
                                            in origin._KEPT_OUTPUT_CASES.items() if origin._long(case)])
def test_the_forward_kernel_is_not_run_again_in_the_backward_pass_when_output_and_lse_are_kept(
        preset, kernels):
    origin.test_the_forward_kernel_is_not_run_again_in_the_backward_pass_when_output_and_lse_are_kept(
        preset, kernels)
