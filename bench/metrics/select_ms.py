"""Device milliseconds per step under the round's ``spa.select`` scope
(top-k, or the fused kernel, and the payload's gather), averaged over the
chips (layer: round)."""


def read(ctx):
    s = ctx.trace.scope_s.get("spa.select")
    if not s or ctx.steps == 0:
        return None
    return 1e3 * s / ctx.steps
