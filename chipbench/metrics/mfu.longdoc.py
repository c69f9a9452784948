"""Model FLOPs the traced steps needed (matmuls per processed token, LM head per sampled position, attention) over window x peak bf16 FLOP/s, in %."""


def read(view):
    return view.mfu()
