"""``window_compiles.serve``: compiles of the gateway
(``jax_compiles_total``, all kinds) inside the
measured window. Source: the program's own counter. Should read 0."""


def read(ctx: dict):
    value = ctx.get("compiles")
    return None if value is None else float(value)
