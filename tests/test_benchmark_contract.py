"""The benchmark's references and manifest under the tier-1 gate.

``benchmarks/tests`` is the instrument's own suite and runs apart from
``tests/`` (its rehearsal files start whole benchmark runs).  Its PURE
cases, those that run in this process on the CPU in about a minute, are
what holds a reference family to the program (the weights a seed means bit
for bit, the served log-probabilities, the lower-precision controls) and
the manifest to the contract; imported here, each counts in the tier-1 run
and a PR that breaks a reference cannot pass the gate unnoticed.  Nothing
is copied: the functions are the instrument's own."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="module", autouse=True)
def _registry_and_environment_restored():
    """``benchmarks/pod.py::build`` registers a configuration under its own
    name (a test size's is a preset's: ``tiny-mla-moe`` with half its experts
    held) and exports its deploy ``env`` (``ARKS_MIXED_CHUNK_TOKENS``), for
    the life of a benchmark process.  Here the process goes on to other test
    files (one xdist worker runs many: a later ``get_config("tiny-mla-moe")``
    or a step's chunking would read what a case here left), so both are put
    back when this file is done (module scope: set up before the imported
    ``served`` fixtures, which build pods, torn down after them)."""
    from arks_tpu.models import config
    registry, environ = dict(config._REGISTRY), dict(os.environ)
    yield
    config._REGISTRY.clear()
    config._REGISTRY.update(registry)
    os.environ.clear()
    os.environ.update(environ)


@pytest.fixture
def seeded_tree_as_drawn(monkeypatch):
    """The families' ``test_seeded_weights_are_the_programs_bit_for_bit``
    compare ``init_params_quantized``'s tree with the reference's leaf for
    leaf in the shape a leaf is DRAWN in, ``[L, E, H x D]`` (files of the
    benchmark: not every PR's to edit).  Since PR 48 the program stores the
    GQA stacks' q / k / v projections ``[L, H, D, E]`` (``tf.init_params``):
    the same numbers, transposed.  Those cases see the stored tree in the
    drawn order here; the stored order itself is held by
    ``tests/test_quant.py``."""
    import jax
    from arks_tpu.models import quant
    stored = quant.init_params_quantized

    def drawn(tree):
        out = {}
        for name, leaf in tree.items():
            if isinstance(leaf, dict) and not quant.is_quantized(leaf):
                out[name] = drawn(leaf)
            elif name in quant.HEAD_SPLIT_KEYS and jax.tree.leaves(
                    leaf)[0].ndim == 4:
                out[name] = jax.tree.map(
                    lambda a: a.reshape(a.shape[0], -1, a.shape[-1])
                    .swapaxes(-1, -2), leaf)
            else:
                out[name] = leaf
        return out

    monkeypatch.setattr(quant, "init_params_quantized",
                        lambda *a, **k: drawn(stored(*a, **k)))


from benchmarks.tests import (  # noqa: E402
    test_reference as _decoder,
    test_reference_linear_moe as _linear_moe,
    test_reference_swa_moe as _swa_moe,
)
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
from benchmarks.tests.test_reference_linear_moe import (  # noqa: E402,F401
    linear_served,
    test_served_logprobs_against_the_reference as
    test_linear_moe_served_logprobs_against_the_reference,
    test_the_family_keeps_the_contract_and_imports_nothing_of_the_program as
    test_linear_moe_keeps_the_contract_and_imports_nothing_of_the_program,
    test_the_lower_precision_controls_fail as
    test_linear_moe_lower_precision_controls_fail,
    test_the_probes_went_through_pages_and_state,
    test_the_routing_margin_is_in_biased_score_units as
    test_linear_moe_routing_margin_is_in_biased_score_units,
)
from benchmarks.tests.test_reference_latent_linear_moe import (  # noqa: E402,F401,E501
    latent_linear_served,
    test_seeded_weights_are_the_programs_bit_for_bit as
    test_latent_linear_moe_seeded_weights_are_the_programs_bit_for_bit,
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
from benchmarks.tests.test_reference_mla_moe import (  # noqa: E402,F401
    test_seeded_weights_are_the_programs_bit_for_bit as
    test_mla_moe_seeded_weights_are_the_programs_bit_for_bit,
    test_served_logprobs_against_the_reference as
    test_mla_moe_served_logprobs_against_the_reference,
    test_the_family_keeps_the_contract_and_imports_nothing_of_the_program,
    test_the_routing_margin_is_in_biased_score_units,
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


def test_swa_moe_seeded_weights_are_the_programs_bit_for_bit(
        seeded_tree_as_drawn):
    _swa_moe.test_seeded_weights_are_the_programs_bit_for_bit()


def test_linear_moe_seeded_weights_are_the_programs_bit_for_bit(
        seeded_tree_as_drawn):
    _linear_moe.test_seeded_weights_are_the_programs_bit_for_bit()
