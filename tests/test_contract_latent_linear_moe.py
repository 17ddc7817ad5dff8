"""The ``latent_linear_moe`` reference family under the tier-1 gate: gated delta-rule layers beside latent attention (``gigachat3_5``'s block).
As in ``tests/test_benchmark_contract.py`` nothing is copied: the functions
are the instrument's own (``benchmarks/tests/test_reference_latent_linear_moe.py``), its
PURE cases."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

pytestmark = pytest.mark.usefixtures("_registry_and_environment_restored")

from benchmarks.tests import test_reference_latent_linear_moe as _latent_linear  # noqa: E402,E501
from benchmarks.tests.test_reference_latent_linear_moe import (  # noqa: E402,F401,E501
    latent_linear_served,
    test_served_logprobs_against_the_reference as
    test_latent_linear_moe_served_logprobs_against_the_reference,
    test_the_family_keeps_the_contract_and_imports_nothing_of_the_program as
    test_latent_linear_moe_keeps_the_contract_and_imports_nothing,
    test_the_lower_precision_control_fails as
    test_latent_linear_moe_lower_precision_control_fails,
    test_the_probes_went_through_latent_pages_and_state,
    test_the_routing_margin_is_in_biased_score_units as
    test_latent_linear_moe_routing_margin_is_in_biased_score_units,
)


def test_latent_linear_moe_seeded_weights_are_the_programs_bit_for_bit(
        seeded_tree_as_drawn):
    _latent_linear.test_seeded_weights_are_the_programs_bit_for_bit()
