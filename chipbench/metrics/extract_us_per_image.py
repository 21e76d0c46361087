"""Device self time of the program's ``wino_extract`` stage scope (tile
extraction: padding, the tile windows and their transpose) in the traced
window per image answered in it, in microseconds."""


def read(ctx):
    return ctx.scopes.per_image_us(ctx.scopes.scope_s("wino_extract"))
