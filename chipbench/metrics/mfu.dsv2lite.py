"""Model FLOPs the traced steps needed (MLA and MoE matmuls per processed token, the held experts counted at their expected 6 * 8 / 64 = 0.75 routed pairs per token; the LM head per sampled position; absorbed attention; ``flops_mla_moe.py``) over window x peak bf16 FLOP/s, in %."""
import flops_mla_moe as FM


def read(view):
    dims = FM.load()
    total = sum(FM.step_flops(dims, kv, c) for _, _, kv, c in view.steps)
    if total <= 0 or view.window_s <= 0 or not view.devices:
        return None
    return 100.0 * total / (view.window_s * view.peak["flops_bf16"])
