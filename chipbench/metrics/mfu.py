"""The whole served step's share of the chips' int8 peak: the model's
operations per image (every convolution as a direct one, and the head)
times the images answered per second in the traced window, over chips
times the peak, in percent."""
from chipbench import costs


def read(ctx):
    if not ctx.images_traced:
        return None
    rate = ctx.images_traced / ctx.trace.window_s
    return 100.0 * costs.model_ops_per_image(ctx.arch, ctx.cfg) * rate / (
        ctx.chips * ctx.peaks["int8_ops_per_s"])
