"""A client-side statistic the runner measured by the host's clock in this
(traced) run, by name: a tail that swings too widely between runs to carry a
bound stands here, beside the steadier statistic that is the end-to-end
metric."""


def read(ctx, name):
    return ctx.end_to_end.get(name)
