"""Device time per call of the jitted decode step (decode_step_rows), ms."""


def read(view):
    return view.call_ms("decode_step_rows")
