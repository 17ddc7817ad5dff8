"""``step_host_gap_ms_p50`` for the cell whose tail is not judged (it moves
``output_tok_s`` there); the reading is the same reader's."""

from benchmarks import manifest

read = manifest.load_reader("step_host_gap_ms_p50")
