"""The ``ssm_moe`` reference family under the tier-1 gate: layers of one sublayer, Mamba-2 mixers, NoPE GQA layers and routed relu^2 FFNs of two-matrix experts (``nemotron_h``'s block).
As in ``tests/test_benchmark_contract.py`` nothing is copied: the functions
are the instrument's own (``benchmarks/tests/test_reference_ssm_moe.py``), its
PURE cases; with them the three per-layer readers the block brought, the
selective scan's work from shapes and the manifest's entries of its
configuration and cell (``benchmarks/tests/test_ssm_readers.py``)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

pytestmark = pytest.mark.usefixtures("_registry_and_environment_restored")

from benchmarks.tests.test_reference_ssm_moe import (  # noqa: E402,F401
    ssm_served,
    test_seeded_weights_are_the_programs_bit_for_bit as
    test_ssm_moe_seeded_weights_are_the_programs_bit_for_bit,
    test_served_logprobs_against_the_reference as
    test_ssm_moe_served_logprobs_against_the_reference,
    test_the_family_keeps_the_contract_and_imports_nothing_of_the_program as
    test_ssm_moe_keeps_the_contract_and_imports_nothing_of_the_program,
    test_the_lower_precision_controls_fail as
    test_ssm_moe_lower_precision_controls_fail,
    test_the_probes_went_through_pages_and_state as
    test_ssm_moe_probes_went_through_pages_and_state,
    test_the_routing_margin_is_in_biased_score_units as
    test_ssm_moe_routing_margin_is_in_biased_score_units,
)
from benchmarks.tests.test_ssm_readers import (  # noqa: E402,F401
    test_a_reader_finds_nothing_in_a_program_without_state_space_layers,
    test_the_catalog_rows_numbers_are_the_files,
    test_the_counter_the_reader_names_is_the_one_the_registry_renders as
    test_the_ssm_counter_the_reader_names_is_the_one_the_registry_renders,
    test_the_new_configuration_family_cell_and_metrics_load_and_validate as
    test_the_ssm_configuration_family_cell_and_metrics_load_and_validate,
    test_the_program_reads_the_configurations_file_as_its_family_does as
    test_the_program_reads_the_ssm_configurations_file_as_its_family_does,
    test_the_roofline_reads_its_scope_and_the_familys_shapes,
    test_the_scan_row_share_is_a_ratio_of_two_deltas,
    test_the_selective_scans_work_is_counted_from_shapes,
    test_the_share_sums_its_three_scopes_over_the_busy_time,
)
