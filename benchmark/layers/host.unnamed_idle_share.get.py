"""`host.unnamed_idle_share.get`: what `host.unnamed_idle_share.put`
reads, in a cell that reads. The reduction is that metric's own reader:
one arithmetic under two names, because a metric moves ONE end-to-end
metric and this one moves `get_mib_s`."""


def read(ctx, spec):
    from benchmark import cells
    return cells.load_layer("host.unnamed_idle_share.put")["read"](ctx, spec)
