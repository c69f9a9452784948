"""Device time per decode call (decode_step_rows) of the expert layer: the ops traced under the program's ``moe.router``, ``moe.dispatch``, ``moe.experts`` and ``moe.shared`` scopes, and the grouped-matmul kernels XLA emits for ``ragged_dot`` (``ragged-dot-*``, which carry no scope path), ms."""
import scopes
import tracing as T


def _expert_op(o) -> bool:
    return ("/moe." in "/" + o.path.split(";")[0]
            or T.short_name(o.name).startswith("ragged-dot"))


def read(view):
    calls = view.calls("decode_step_rows")
    if not calls:
        return None
    ops = [o for o in scopes.trace_ops(view) if not o.control and _expert_op(o)]
    total = sum(o.dur for c in calls for o in ops
                if o.plane == c.plane and c.start <= o.start and o.end <= c.end)
    return 1e-6 * total / len(calls) if total > 0 else None
