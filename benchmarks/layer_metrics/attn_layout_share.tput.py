"""``attn_layout_share`` for the cell whose tail is not judged (it moves
``output_tok_s`` there); the reading is the same reader's."""

from benchmarks import manifest

read = manifest.load_reader("attn_layout_share")
