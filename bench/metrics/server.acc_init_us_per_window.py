"""Server loop: host microseconds per tile window spent setting up
accumulators — the program's `pharos.acc_init` span (every
`PharosServer._start_layer`: a fresh `jnp.zeros` per layer of a job)
summed over the window, over the window's `pharos.window_launch` calls
(`program_spans.span_us_per_window`)."""
import program_spans


def read(ctx):
    return program_spans.span_us_per_window(ctx, "pharos.acc_init")
