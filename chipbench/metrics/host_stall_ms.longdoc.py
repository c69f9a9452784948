"""Median over the window's engine ticks (serve.tick spans) of the device-0 idle time inside each tick, ms."""
import scopes


def read(view):
    return scopes.host_stall_ms(view)
