"""Device time of the CLOMPR decoder's jitted program (module jit_clompr),
in milliseconds per fit."""

from chipbench import readers


def read(ctx, device_kind):
    return readers.per(readers.module_seconds(ctx, ["jit_clompr"]),
                       ctx.counts.get("fits"), 1e3)
