"""Mean rows advanced per decode dispatch in the window, over the
scheduler's slots."""


def read(w):
    rows = [t.decode_rows for t in w.ticks if t.decode_rows]
    return sum(rows) / len(rows) / w.slots if rows else None
