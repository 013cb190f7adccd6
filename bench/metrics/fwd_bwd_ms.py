"""Device milliseconds per step of the model's forward and backward
operations (name stack under jvp or transpose), averaged over the chips
(layer: model)."""


def read(ctx):
    s = ctx.trace.layer_s.get("fwd_bwd")
    if not s or ctx.steps == 0:
        return None
    return 1e3 * s / ctx.steps
