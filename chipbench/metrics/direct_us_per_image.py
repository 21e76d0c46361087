"""Device self time of the program's ``direct`` stage scope (the
convolutions the engine routes to XLA's direct convolution: the stride-2
and 1x1 ones) in the traced window per image answered in it, in
microseconds."""


def read(ctx):
    return ctx.scopes.per_image_us(ctx.scopes.scope_s("direct"))
