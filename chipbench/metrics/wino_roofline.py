"""Share of their roofline that the Mosaic kernels reach in the traced
window: the sum over kernel calls of the least time (the larger of ops over
the int8 peak and bytes over HBM bandwidth, from each call's shapes) over
the sum of their measured device times, in percent."""
from chipbench import costs


def read(ctx):
    kernels = ctx.trace.kernels()
    if not kernels:
        return None
    base = ctx.cfg["winograd"]["base"] != "canonical"
    least, spent, bound = 0.0, 0.0, {}
    for op in kernels:
        sig = costs.parse_shapes(op.signature)
        kind, ops, moved = costs.kernel_cost(sig[1:], sig[:1], base)
        t, by = costs.least_time(ops, moved, ctx.peaks)
        least += t
        spent += op.end - op.start
        bound.setdefault((kind, by), [0, 0.0, 0.0])
        b = bound[(kind, by)]
        b[0], b[1], b[2] = b[0] + 1, b[1] + t, b[2] + op.end - op.start
    for (kind, by), (n, t, s) in sorted(bound.items()):
        ctx.log(f"wino_roofline: {kind}: {n} calls bound by {by}, least "
                f"{t * 1e3:.3f}ms of {s * 1e3:.3f}ms measured")
    return 100.0 * least / spent
