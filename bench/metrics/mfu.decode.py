"""Whole served step: FLOPs of every tile window run while traced (HI
and LO, from shapes) over the traced seconds times the chip's bf16
peak, in %."""
import counts


def read(ctx):
    if ctx.trace is None or not ctx.traced_windows:
        return None
    flops = sum(
        counts.window_flops(K, tiles, ctx.block)
        for _, K, _, _, tiles in ctx.traced_windows
    )
    return 100.0 * flops / (ctx.trace["window_s"] * ctx.peaks["flops_per_s"])
