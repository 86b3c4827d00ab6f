"""A number the runner measured itself in the window, by its key."""


def read(ctx, key: str, scale: float = 1.0):
    got = ctx.values.get(key)
    return None if got is None else got * scale
