"""Mean device time of the chunk-prefill program (the jitted
``chunk_prefill``) in the traced stretch."""


def read(w):
    d = w.trace.module_durations("chunk_prefill") if w.trace else []
    return 1e3 * sum(d) / len(d) if d else None
