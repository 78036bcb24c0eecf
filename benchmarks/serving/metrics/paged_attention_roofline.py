"""Share of its roofline the paged-attention kernel reached in the
traced stretch: least time of the ticks' work
(``kernel_work/paged_attention``) at the chip's peaks, over the device
time of its events."""


def read(w):
    return w.roofline("paged_attention")
