"""Server loop: host microseconds per tile window spent scheduling — the
program's `pharos.schedule` span (`PharosServer._step_stage`: the EDF
preemption check, the pool pop and the dispatch event) summed over the
window, over the window's `pharos.window_launch` calls
(`program_spans.span_us_per_window`)."""
import program_spans


def read(ctx):
    return program_spans.span_us_per_window(ctx, "pharos.schedule")
