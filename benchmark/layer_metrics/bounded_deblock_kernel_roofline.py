"""deblock kernel: `deblock_kernel_roofline` (least time of the custom
call `tvt_deblock_wavefront` by its HBM bytes / the time its calls
took) where the kernel runs inside the `while` of a cut-aligned GOP's
bounded P-frame loop: the accepted reader and its one byte count
(`roofline_deblock.deblock_bytes`), called as they are. Not measured
where that reader finds no op of the kernel's name, or where no wave
of the window had a bound."""

from tvtbench.spec import load_module


def read(ev):
    if not load_module("layer_metrics",
                       "bounded_dev_deblock_ms_per_frame").bounded(ev):
        return None
    return load_module("layer_metrics", "deblock_kernel_roofline").read(ev)
