"""Seconds per step inside the benchmark's own host spans named in
``spans`` (each synced at its end by the entry), over the whole window;
with ``residual`` the step wall less those spans."""


def read(ctx, spans, residual=False):
    n = len(ctx.steps)
    inside = sum(ctx.spans.total(name, since=ctx.t_window) for name in spans)
    if n == 0 or inside <= 0:
        return None
    return (ctx.wall - inside if residual else inside) / n
