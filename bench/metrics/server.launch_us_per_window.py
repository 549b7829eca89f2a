"""Server loop: host microseconds per tile window spent launching it —
the program's `pharos.window_launch` span (`PharosServer._exec_window`:
the `LaunchPlan` lookup and one jit dispatch of the window kernel, every
operand on the device) summed over the window, over its own count of
calls (`program_spans.span_us_per_window`)."""
import program_spans


def read(ctx):
    return program_spans.span_us_per_window(ctx, "pharos.window_launch")
