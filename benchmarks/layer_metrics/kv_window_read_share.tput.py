"""The window layers' share of the page bytes a step's attention launches
stream, in percent, over the untraced window.  The program counts, at every
mixed dispatch, the bytes ONE layer of each kind streams
(``mixed_kv_bytes_total{kind}``: the (page, KV head) blocks that kind's
work list reads times its own pool's bytes a block); a step runs every
layer, so each kind's delta is weighted by how many layers of it the model
has (the cell's reference family: ``kernel_shapes`` / ``window_kernel_shapes``
``["layers"]``): ``Lw x window / (Lw x window + Lf x full)``.  A model
whose window layers cost the step the small share of cache bytes their
window promises reads low here; one whose launch streams whole pages for a
window of half a page reads as much as its full layers.  Nothing to read
where the program counts no such kinds (a parent without window layers),
or the family states no layers a kind."""

from benchmarks.layer_metrics import _counters

NAME = "mixed_kv_bytes_total"


def read(ctx):
    window = _counters.delta(ctx, NAME, kind="window")
    full = _counters.delta(ctx, NAME, kind="full")
    ref = ctx["cell"]["reference"]
    if window is None or full is None or not (
            hasattr(ref, "kernel_shapes")
            and hasattr(ref, "window_kernel_shapes")):
        return None
    arch = ref.arch(ctx["cell"]["config"])
    window *= ref.window_kernel_shapes(arch)["layers"]
    full *= ref.kernel_shapes(arch)["layers"]
    if not window + full:
        return None
    return 100.0 * window / (window + full)
