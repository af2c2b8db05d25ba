"""A number the run's kind already holds (a span or a counter read around
the window): ``ctx["values"][key]``, scaled."""


def read(ctx: dict, key: str, scale: float = 1.0):
    value = ctx.get("values", {}).get(key)
    return None if value is None else value * scale
