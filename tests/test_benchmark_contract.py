"""The benchmark's references and manifest under the tier-1 gate.

``benchmarks/tests`` is the instrument's own suite and runs apart from
``tests/`` (its rehearsal files start whole benchmark runs).  Its PURE
cases, those that run in this process on the CPU in about a minute, are
what holds a reference family to the program (the weights a seed means bit
for bit, the served log-probabilities, the lower-precision controls) and
the manifest to the contract; imported here, each counts in the tier-1 run
and a PR that breaks a reference cannot pass the gate unnoticed.  Nothing
is copied: the functions are the instrument's own.

This file holds the manifest, the step-clock readers (the window's and the
traced slice's), the decoder family and ``mla_moe``; every other reference family is a file of its own,
``tests/test_contract_<family>.py`` (``--dist loadfile`` hands a file to one
worker, and a family's pods are most of its cost: ROADMAP D9).  What a
benchmark pod registers and exports is put back when a file is done
(``conftest.py::_registry_and_environment_restored``)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

pytestmark = pytest.mark.usefixtures("_registry_and_environment_restored")


from benchmarks.tests import test_reference as _decoder  # noqa: E402
from benchmarks.tests import test_reference_mla_moe as _mla_moe  # noqa: E402
from benchmarks.tests.test_manifest import (  # noqa: E402,F401
    test_a_new_cell_loads_from_added_files_alone,
    test_a_new_family_loads_from_added_files_alone,
    test_manifest_and_every_file_it_names,
    test_validator_names_a_fault_of_a_family_or_a_share,
    test_validator_names_the_fault,
)
from benchmarks.tests.test_reference import (  # noqa: E402,F401
    test_served_logprobs_against_the_reference,
    test_the_quantile_is_nearest_rank_and_the_verdict_wants_enough_positions,
    test_the_routing_margin_is_small_where_two_experts_tie,
)
from benchmarks.tests.test_reference_mla_moe import (  # noqa: E402,F401
    test_served_logprobs_against_the_reference as
    test_mla_moe_served_logprobs_against_the_reference,
    test_the_family_keeps_the_contract_and_imports_nothing_of_the_program,
    test_the_routing_margin_is_in_biased_score_units,
)
from benchmarks.tests.test_slice_readers import (  # noqa: E402,F401
    test_a_run_without_a_marked_slice_reads_none,
    test_a_slice_that_stood_still_says_so_by_itself,
    test_the_ratio_is_none_when_the_window_ran_none_of_the_slices_kinds,
    test_the_ratio_weighs_the_windows_means_by_the_slices_own_kinds,
    test_the_readers_read_what_the_programs_own_window_hands_back,
    test_the_starved_share_is_the_starved_leg_over_all_legs_of_the_slice,
)
from benchmarks.tests.test_step_clock_readers import (  # noqa: E402,F401
    test_a_cycle_mean_is_its_kinds_own,
    test_a_parent_without_the_family_reads_none,
    test_stalled_seconds_read_zero_in_a_sound_run_and_sum_every_where,
    test_the_lag_percentile_is_read_off_the_bucket_deltas,
    test_the_readers_read_what_the_programs_registry_renders,
    test_the_starved_share_is_the_starved_leg_over_all_legs_of_all_kinds,
)


@pytest.mark.parametrize("name", _decoder.CONFIGS)
def test_seeded_weights_are_the_programs_bit_for_bit(name,
                                                     seeded_tree_as_drawn):
    _decoder.test_seeded_weights_are_the_programs_bit_for_bit(name)


def test_mla_moe_seeded_weights_are_the_programs_bit_for_bit(
        seeded_tree_as_drawn):
    _mla_moe.test_seeded_weights_are_the_programs_bit_for_bit()
