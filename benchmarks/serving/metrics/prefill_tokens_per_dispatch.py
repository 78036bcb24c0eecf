"""Prompt tokens fed per chunk-prefill dispatch in the traced ticks, as
the program counts them (``tokens`` of each ``serve.chunk.dispatch``
span): prompt work per read of the weights."""
from benchmarks.serving import program_spans


def per_dispatch(ticks, records) -> float | None:
    chunks = [r for recs in program_spans.by_tick(ticks, records)
              for r in recs if r.name == "chunk.dispatch"]
    if not chunks:
        return None
    return sum(r.meta["tokens"] for r in chunks) / len(chunks)


def read(w):
    records = program_spans.recorded()
    return per_dispatch(w.traced_ticks(), records) if records else None
