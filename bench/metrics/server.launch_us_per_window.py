"""Server loop: host microseconds per tile window spent launching it —
the program's `pharos.window_launch` span (`PharosServer._exec_window`:
geometry, the start scalar and the jit dispatch of the window kernel)
summed over the window, over its own count of calls
(`program_spans.span_us_per_window`). It also logs each span's longest
call in the window (`program_spans.log_longest_calls`)."""
import program_spans


def read(ctx):
    program_spans.log_longest_calls(ctx)
    return program_spans.span_us_per_window(ctx, "pharos.window_launch")
