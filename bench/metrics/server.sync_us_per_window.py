"""Server loop: host microseconds per tile window spent waiting for
finished layers — the program's `pharos.layer_sync` span (the
`jax.block_until_ready` at a layer's end) summed over the window, over
the window's `pharos.window_launch` calls
(`program_spans.span_us_per_window`)."""
import program_spans


def read(ctx):
    return program_spans.span_us_per_window(ctx, "pharos.layer_sync")
