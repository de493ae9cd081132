"""``window_compiles.train``: compiles of every process of the federation
(``jax_compiles_total`` of learner and controller, all kinds) inside the
measured window. Source: the program's own counter. Should read 0."""


def read(ctx: dict):
    value = ctx.get("compiles")
    return None if value is None else float(value)
