"""The ``shortcut_mla_moe`` reference family under the tier-1 gate: two latent sublayers a layer and a routed layer with identity experts on a shortcut (``longcat_flash``'s block).
As in ``tests/test_benchmark_contract.py`` nothing is copied: the functions
are the instrument's own (``benchmarks/tests/test_reference_shortcut_mla_moe.py``), its
PURE cases; with them the three per-layer readers the block brought and the
manifest's entries of its configuration and cell
(``benchmarks/tests/test_shortcut_readers.py``)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

pytestmark = pytest.mark.usefixtures("_registry_and_environment_restored")

from benchmarks.tests import test_reference_shortcut_mla_moe as _shortcut  # noqa: E402,E501
from benchmarks.tests.test_reference_shortcut_mla_moe import (  # noqa: E402,F401,E501
    test_served_logprobs_against_the_reference as
    test_shortcut_mla_moe_served_logprobs_against_the_reference,
    test_the_family_keeps_the_contract_and_imports_nothing_of_the_program as
    test_shortcut_mla_moe_keeps_the_contract_and_imports_nothing,
    test_the_routing_margin_is_in_biased_probability_units as
    test_shortcut_mla_moe_routing_margin_is_in_biased_probability_units,
)
from benchmarks.tests.test_shortcut_readers import (  # noqa: E402,F401
    test_the_counter_the_reader_names_is_the_one_the_registry_renders,
    test_the_dense_share_is_the_ffn_scope_over_the_busy_time,
    test_the_new_configuration_family_cell_and_metrics_load_and_validate,
    test_the_program_reads_the_configurations_file_as_its_family_does,
    test_the_routed_share_sums_its_four_scopes_and_wants_the_identity_part,
    test_the_zero_pair_share_is_a_ratio_of_two_deltas,
)


def test_shortcut_mla_moe_seeded_weights_are_the_programs_bit_for_bit(
        seeded_tree_as_drawn):
    _shortcut.test_seeded_weights_are_the_programs_bit_for_bit()
