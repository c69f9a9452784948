"""Paged flash decode kernel: least time of the attention work the decode steps needed (live K/V read once, q and o) over the kernel's device time, in %."""


def read(view):
    return view.decode_roofline()
