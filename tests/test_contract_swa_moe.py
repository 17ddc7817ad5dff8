"""The ``swa_moe`` reference family under the tier-1 gate: window and full attention layers (``laguna``'s block).
As in ``tests/test_benchmark_contract.py`` nothing is copied: the functions
are the instrument's own (``benchmarks/tests/test_reference_swa_moe.py``), its
PURE cases."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

pytestmark = pytest.mark.usefixtures("_registry_and_environment_restored")

from benchmarks.tests import test_reference_swa_moe as _swa_moe  # noqa: E402
from benchmarks.tests.test_reference_swa_moe import (  # noqa: E402,F401
    served,
    test_served_logprobs_against_the_reference as
    test_swa_moe_served_logprobs_against_the_reference,
    test_the_family_keeps_the_contract_and_imports_nothing_of_the_program as
    test_swa_moe_keeps_the_contract_and_imports_nothing_of_the_program,
    test_the_lower_precision_controls_fail as
    test_swa_moe_lower_precision_controls_fail,
    test_the_probes_went_through_both_pools_and_released_window_pages,
    test_the_routing_margin_is_in_router_logit_units,
)


def test_swa_moe_seeded_weights_are_the_programs_bit_for_bit(
        seeded_tree_as_drawn):
    _swa_moe.test_seeded_weights_are_the_programs_bit_for_bit()
