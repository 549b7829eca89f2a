"""Server loop: host microseconds per tile window spent entering layers
— the program's `pharos.acc_init` span (every
`PharosServer._start_layer`: the reset of the job's ``next_tile``; the
layer's first window makes its accumulator) summed over the window,
over the window's `pharos.window_launch` calls
(`program_spans.span_us_per_window`)."""
import program_spans


def read(ctx):
    return program_spans.span_us_per_window(ctx, "pharos.acc_init")
