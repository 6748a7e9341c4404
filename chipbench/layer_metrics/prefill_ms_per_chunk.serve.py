"""Device milliseconds of one prefill chunk: the time of the engine's
prefill-chunk program (XLA module ``jit_prefill_chunk``) in the trace,
over the chunks the window served."""


def read(ctx):
    s = ctx.summary
    n = ctx.counters.get("prefill_chunks")
    if s is None or not n:
        return None
    t = s.module_s.get("jit_prefill_chunk", 0.0) / s.chips
    return 1e3 * t / n if t > 0 else None
