"""Host time per engine tick: each engine.step span less the device busy time inside it, ms."""


def read(view):
    return view.tick_host_ms()
