"""Gateway: 95th percentile of how late the gateway released each job
behind its due time (`TenantStats.release_jitter`, every tenant the
gateway releases), in ms."""


def read(ctx):
    lags = ctx.release_jitter_s
    return ctx.percentile(lags, 95) * 1e3 if lags else None
