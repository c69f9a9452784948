"""Device idle share of the traced window: 1 - busy/window, in %."""


def read(view):
    return view.idle_share()
