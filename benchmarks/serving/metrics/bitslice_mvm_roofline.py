"""Share of its roofline the int8 MVM kernel reached in the traced
stretch: least time of the ticks' work (``kernel_work/bitslice_mvm``)
at the chip's peaks, over the device time of its events."""


def read(w):
    return w.roofline("bitslice_mvm")
