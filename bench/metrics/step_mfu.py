"""The whole train step's share of the chips' peak: the configuration's
model FLOPs per step (forward and backward, nothing recomputed) times the
traced window's steps, over the window's seconds, the chips and each
chip's published bf16 peak (layer: train step)."""


def read(ctx):
    if ctx.window_s <= 0 or ctx.steps == 0:
        return None
    return 100.0 * ctx.flops_per_step * ctx.steps / (
        ctx.window_s * ctx.chips * ctx.peak_flops)
