"""Device time of the Mosaic kernels in the traced window per image
answered in it, in microseconds."""


def read(ctx):
    if not ctx.images_traced or not ctx.trace.kernels():
        return None
    return 1e6 * ctx.trace.time_s(mosaic=True) / ctx.images_traced
