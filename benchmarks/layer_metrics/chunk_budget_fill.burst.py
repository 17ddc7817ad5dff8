"""``chunk_budget_fill`` for the flood cell, where it moves
``output_tok_s.burst``; the reading is the same reader's."""

from benchmarks import manifest

read = manifest.load_reader("chunk_budget_fill")
