"""Seconds of JAX tracing, lowering and backend compiles in the program's
set-up phases (pack, calibrate, restore, warmup), from the program's own
compile counter (``repro.telemetry``), with each phase's split in the log.
None where the program keeps no such counter."""


def read(ctx):
    try:
        from repro import telemetry
    except ImportError:
        return None
    phases = telemetry.snapshot()["phases"]
    setup = {p: phases[p] for p in telemetry.SETUP_PHASES if p in phases}
    if not setup:
        return None
    for name, c in setup.items():
        ctx.log(f"setup_compile_s: {name}: trace {c['trace_count']} in "
                f"{c['trace_s']:.3f}s, lower {c['lower_count']} in "
                f"{c['lower_s']:.3f}s, backend {c['backend_count']} in "
                f"{c['backend_s']:.3f}s, persistent cache {c['cache_hits']} "
                f"hits, {c['cache_misses']} misses")
    return sum(c["trace_s"] + c["lower_s"] + c["backend_s"]
               for c in setup.values())
