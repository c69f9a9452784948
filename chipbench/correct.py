"""The comparison that decides ``correct``: a seeded sample of the requests
the window finished, the longest among them, scored by the plain reference
of the configuration. For each served token, the gap by which the
reference's logit of that token lies below the reference's best logit at
that position; the widest gap over the sample is held to the cell's limit.
Greedy decoding serves the program's argmax, so a sound run only loses to
rounding near ties, and a token altered where it is produced shows as a
wide gap."""
from __future__ import annotations

import numpy as np

from bench import load_module


def sample(cell, reqs, tracks, finished, seed) -> list[int]:
    """The longest finished request, then others drawn from the seed, until
    the mix's ``check_tokens`` served tokens or ``check_max_requests``."""
    if not finished:
        return []
    size = lambda rid: len(reqs[rid].prompt) + len(tracks[rid].tokens)
    first = max(finished, key=lambda rid: (size(rid), -rid))
    rest = [rid for rid in sorted(finished) if rid != first]
    rest = [rest[i] for i in np.random.default_rng([seed, 7]).permutation(len(rest))]
    out, n = [first], len(tracks[first].tokens)
    for rid in rest:
        if n >= cell.mix["check_tokens"] or len(out) >= cell.mix["check_max_requests"]:
            break
        out.append(rid)
        n += len(tracks[rid].tokens)
    return out


def gaps(cell, conf, params, prompt, served, max_len, max_out, precision="f32"):
    """Per served token: (reference gap of the served token, reference gap
    of the token the ``precision`` forward puts first). With
    precision="f32" the second is 0 by construction."""
    import jax.numpy as jnp

    ref = load_module(cell.dir / "configs" / f"{conf['reference']}.py")
    arch = ref.switches(conf)
    n = len(served)
    seq = np.zeros((max_len,), np.int32)
    ctx = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    seq[:len(ctx)] = ctx
    read = np.zeros((max_out,), np.int32)
    read[:n] = len(prompt) - 1 + np.arange(n)
    lg = ref.logits_at(params, jnp.asarray(seq), jnp.asarray(read), arch=arch,
                       precision="f32")
    best = lg.max(-1)
    tok = jnp.zeros((max_out,), jnp.int32).at[:n].set(jnp.asarray(served, jnp.int32))
    g_served = best - jnp.take_along_axis(lg, tok[:, None], -1)[:, 0]
    if precision == "f32":
        return np.asarray(g_served)[:n], np.zeros(n)
    other = ref.logits_at(params, jnp.asarray(seq), jnp.asarray(read), arch=arch,
                          precision=precision).argmax(-1)
    g_other = best - jnp.take_along_axis(lg, other[:, None], -1)[:, 0]
    return np.asarray(g_served)[:n], np.asarray(g_other)[:n]


def check(cell, conf, params, reqs, tracks, finished, seed, max_len,
          precision="f32") -> dict:
    """The comparison over the seeded sample. With ``precision="f32"`` it
    judges the served tokens; with a lower precision it judges the control:
    the tokens that precision's reference puts first at the same positions,
    held to the same limit, and the served tokens' widest gap is returned
    beside it as ``program_gap``."""
    limit = cell.setup["limits"]["max_logit_gap"]
    picked = sample(cell, reqs, tracks, finished, seed)
    widest = {"program": 0.0, "control": 0.0}
    judged = "program" if precision == "f32" else "control"
    wrong = n_tok = 0
    for rid in picked:
        g, gc = gaps(cell, conf, params, reqs[rid].prompt, tracks[rid].tokens, max_len,
                     cell.mix["output"]["max"], precision)
        widest["program"] = max(widest["program"], float(g.max()))
        widest["control"] = max(widest["control"], float(gc.max()))
        wrong += int((g if judged == "program" else gc).max() > limit)
        n_tok += len(g)
    compared = [["max_logit_gap", widest[judged], limit],
                ["checked_tokens", n_tok, cell.mix["check_tokens"] // 2]]
    ok = (bool(picked) and widest[judged] <= limit
          and n_tok >= cell.mix["check_tokens"] // 2)
    out = {"ok": ok, "compared": compared, "wrong_requests": wrong,
           "requests": picked}
    if judged == "control":
        out["program_gap"] = widest["program"]
    return out
