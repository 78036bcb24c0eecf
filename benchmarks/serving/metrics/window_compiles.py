"""Programs compiled, or loaded from the persistent cache, while the
window ran: each is a stall that set-up should have taken."""


def read(w):
    return w.compiles
