"""Every output token streamed in the window, over the window's
seconds."""


def read(w):
    return sum(w.t0 <= t <= w.end for r in w.recs for t in r.times) \
        / w.seconds
