"""``slice_stall_s`` for the cells whose tail is not judged (it moves
``output_tok_s`` there); the reading is the same reader's."""

from benchmarks import manifest

read = manifest.load_reader("slice_stall_s")
