"""Peak device memory on the fullest chip after the window."""


def read(ctx, scale=1e-9):
    return ctx.peak_bytes * scale if ctx.peak_bytes > 0 else None
