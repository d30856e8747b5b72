"""A number the harness counted itself: ``ctx.facts[key]`` (times ``scale``)."""


def read(ctx, key: str, scale: float = 1.0):
    value = ctx.facts.get(key)
    return None if value is None else float(value) * scale
