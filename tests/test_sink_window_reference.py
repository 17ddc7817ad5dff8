"""The ``swa_sink_moe`` reference family under the tier-1 gate, in a file of
its own (``--dist loadfile`` hands a file to one worker, and
``tests/test_benchmark_contract.py`` already builds four families' pods).
As there, nothing is copied: the functions are the instrument's own
(``benchmarks/tests/test_reference_swa_sink_moe.py``), its PURE cases; the
int4-weight control stays with the instrument's own suite (the int4-page
control, whose pages are the new ones of two widths, runs here)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# What a benchmark pod registers and exports is put back when this file is
# done, as in the files this one stands beside.
pytestmark = pytest.mark.usefixtures("_registry_and_environment_restored")

from benchmarks.tests import (  # noqa: E402
    test_reference_swa_sink_moe as _swa_sink_moe,
)
from benchmarks.tests.test_reference_swa_sink_moe import (  # noqa: E402,F401
    sink_served,
    test_served_logprobs_against_the_reference,
    test_the_family_keeps_the_contract_and_imports_nothing_of_the_program,
    test_the_lower_precision_controls_fail as _controls,
    test_the_probes_went_through_both_pools_and_released_window_pages,
    test_the_routing_margin_is_in_biased_score_units,
    test_the_window_read_share_weighs_a_kinds_bytes_by_its_layers,
    test_the_work_functions_count_keys_and_values_at_their_own_widths,
)


def test_the_int4_page_control_fails():
    _controls("kv_int4")


def test_seeded_weights_are_the_programs_bit_for_bit(seeded_tree_as_drawn):
    _swa_sink_moe.test_seeded_weights_are_the_programs_bit_for_bit()
