"""Compile events inside the window (backend compiles, persistent-cache loads
included) per fit, from jax.monitoring: a new operator spec per fit makes
every jitted function that takes the operator trace and load again."""


def read(ctx, device_kind):
    fits = ctx.counts.get("fits")
    return ctx.window_compiles.get("compiles", 0) / fits if fits else None
