"""Paged MLA decode kernel (``paged_mla_decode``): least time of the absorbed attention the traced decode calls needed (each row's live latent and roped key read once, 1152 B per token and layer, plus its query operands and result; 2 H (512 + 64) + 2 H 512 FLOPs per key; ``flops_mla_moe.py``) over the kernel's device time, in %."""
import flops as F
import flops_mla_moe as FM
import tracing as T


def read(view):
    kt = sum(e.dur for e in view.kernel_events("decode_step_rows")
             if T.short_name(e.name).startswith("paged_mla_decode")) * 1e-9
    dims = FM.load()
    need = sum(F.min_time(*FM.decode_attn(dims, kv), view.peak["flops_bf16"],
                          view.peak["hbm_bytes_per_s"])[0]
               for _, _, kv, _ in view.steps if kv)
    return 100.0 * need / kt if kt > 0 and need > 0 else None
