"""Share of the traced stretch in which no operation ran on the device
(1 - busy union / stretch)."""


def read(w):
    return 1.0 - w.trace.busy_s / w.trace.window_s if w.trace else None
