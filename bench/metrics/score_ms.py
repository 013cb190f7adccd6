"""Device milliseconds per step under the round's ``spa.score`` scope
(the error-fed gradient and RegTop-k's score), averaged over the chips
(layer: round)."""


def read(ctx):
    s = ctx.trace.scope_s.get("spa.score")
    if not s or ctx.steps == 0:
        return None
    return 1e3 * s / ctx.steps
