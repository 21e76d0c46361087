"""Seconds of the serving loop's dispatcher stages on the host (its
``repro.serving.{coalesce,pad,put,dispatch,deliver}`` spans) in the traced
window per image answered in it, in microseconds. ``repro.serving.block``
is left out: there the dispatcher waits on the device."""

STAGES = tuple(f"repro.serving.{s}" for s in
               ("coalesce", "pad", "put", "dispatch", "deliver"))


def read(ctx):
    return ctx.scopes.per_image_us(ctx.scopes.span_s(STAGES))
