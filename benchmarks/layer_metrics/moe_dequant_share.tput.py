"""Share of the device's busy time under ``arks.moe_dequant``, in percent:
the expert weights dequantised to bfloat16 for the grouped matmuls (a
routed model's step; moves ``output_tok_s`` in the mixtral cell)."""

from benchmarks.layer_metrics import _scopes


def read(ctx):
    return _scopes.share(ctx, "arks.moe_dequant")
