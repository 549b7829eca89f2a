"""Server loop: host microseconds per tile window — the summed duration
of the window's `PharosServer.step` calls that ran a window, over the
windows they ran (`ServerReport.windows_executed`)."""


def read(ctx):
    if not ctx.windows_in_window:
        return None
    return ctx.step_busy_s / ctx.windows_in_window * 1e6
