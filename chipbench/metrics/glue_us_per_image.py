"""Device time of every operation that is not a Mosaic kernel (the XLA
glue: tile extraction and reassembly, batch norm, ReLU, adds, the direct
convolutions, pooling, the head) in the traced window per image answered
in it, in microseconds."""


def read(ctx):
    if not ctx.images_traced:
        return None
    return 1e6 * ctx.trace.time_s(mosaic=False) / ctx.images_traced
