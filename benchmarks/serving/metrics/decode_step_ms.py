"""Mean device time of the decode program (the jitted ``slot_step``) in
the traced stretch."""


def read(w):
    d = w.trace.module_durations("slot_step") if w.trace else []
    return 1e3 * sum(d) / len(d) if d else None
