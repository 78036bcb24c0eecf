"""Mean, over the window's ticks, of the KV pool's blocks in use over
its blocks (``total_blocks - free_blocks``, read before each tick)."""


def read(w):
    return sum(t.kv_used for t in w.ticks) / len(w.ticks) \
        if w.ticks else None
