"""The mean of one numeric attribute over the spans of one name."""

from stats import stat


def read(ctx, span: str, attr: str):
    return stat([float(s.attrs[attr]) for s in ctx.spans
                 if s.name == span and attr in s.attrs], "mean")
