"""Device time per decode call (decode_step_rows) of the ops whose innermost program scope is `layers`: all the layer scan's own unscoped ops (the stacked KV arena and weight slices, the arena written back, their layout copies), ms."""
import scopes


def read(view):
    return scopes.arena_move_ms(view)
