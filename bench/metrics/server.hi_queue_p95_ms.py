"""Server loop: 95th percentile of a HI job's wait from its release on
the server to its first dispatch on a stage, from the server's
`repro.obs.TraceRecorder` events (``release`` -> first ``dispatch``),
in ms."""


def read(ctx):
    q = ctx.hi_queue_s
    return ctx.percentile(q, 95) * 1e3 if q else None
