"""Device milliseconds per step under the train step's ``train.optimizer``
scope (the optimizer's update), averaged over the chips (layer: optimizer)."""


def read(ctx):
    s = ctx.trace.scope_s.get("train.optimizer")
    if not s or ctx.steps == 0:
        return None
    return 1e3 * s / ctx.steps
