"""tok_per_s: prompt plus generated tokens the engine processed inside the
window (the ServeMetrics counters ``decode_tokens`` and
``prefill_chunk_tokens``, read at the first step boundary at or after the
window's opening and its close), over the seconds between the two reads."""


def read(art):
    run = art["run"]
    a, b = run.snap_open, run.snap_close
    if a is None or b is None or b[0] <= a[0]:
        return None
    return ((b[1] - a[1]) + (b[2] - a[2])) / (b[0] - a[0])
