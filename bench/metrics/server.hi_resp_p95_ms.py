"""Server loop: 95th percentile of the response (completion minus due
time) of every HI job due in the window, in ms; a job unfinished when
the run stops waiting counts at its age then. The tail that
``hi_on_time`` judges, read per layer: a host pause of a second or more
sets it on its own."""


def read(ctx):
    r = ctx.hi_resp_s
    return ctx.percentile(r, 95) * 1e3 if r else None
