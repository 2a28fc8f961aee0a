"""Client-side statistics of served requests, shared by the LM metric
readers: all on the host clock, all over the whole window."""
from __future__ import annotations

import numpy as np


def percentile_ms(values, q) -> float | None:
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q)) * 1e3


def ttfts(ctx) -> list[float]:
    """Seconds from due to first token, for each request due inside the
    window; one never answered counts with the time it was waited."""
    out = []
    for r in ctx.requests:
        if not ctx.t0 <= r.due < ctx.t1:
            continue
        out.append((r.times[0] if r.times else ctx.wait_end) - r.due)
    return out


def gaps(ctx) -> list[float]:
    """Gaps between consecutive tokens whose later token arrived in the
    window."""
    out = []
    for r in ctx.requests:
        t = np.asarray(r.times)
        if len(t) < 2:
            continue
        g = np.diff(t)
        inside = (t[1:] >= ctx.t0) & (t[1:] <= ctx.t1)
        out.extend(g[inside].tolist())
    return out


def tokens_in(ctx, lo: float, hi: float) -> int:
    return sum(int(np.count_nonzero((np.asarray(r.times) >= lo)
                                    & (np.asarray(r.times) <= hi)))
               for r in ctx.requests)


def token_flops(m: dict, ctx_len) -> np.ndarray:
    """Useful FLOPs of producing one token that attends over
    ``ctx_len`` positions (itself included): every projection and the
    unembedding once, attention's two products over the context."""
    dense = 2.0 * (m["n_layers"] * m["layer_params"] + m["embed_params"])
    attn = 4.0 * m["n_layers"] * m["n_heads"] * m["head_dim"]
    return dense + attn * np.asarray(ctx_len, np.float64)


def prefill_flops(m: dict, n: int) -> float:
    """Useful FLOPs of a prefill of ``n`` tokens: every projection for
    each token, causal attention, and the unembedding of the last one."""
    proj = 2.0 * m["n_layers"] * m["layer_params"] * n
    attn = 4.0 * m["n_layers"] * m["n_heads"] * m["head_dim"] \
        * n * (n + 1) / 2.0
    return proj + attn + 2.0 * m["embed_params"]


def useful_flops(ctx, lo: float, hi: float, *, prefill: bool) -> float:
    """FLOPs of the tokens that arrived in ``[lo, hi]``: the decode work
    of each later token, and with ``prefill`` the prefill of each
    request whose first token arrived there."""
    m, total = ctx.model, 0.0
    for r in ctx.requests:
        t = np.asarray(r.times)
        if not len(t):
            continue
        if prefill and lo <= t[0] <= hi:
            total += prefill_flops(m, r.prompt_len)
        later = np.nonzero((t >= lo) & (t <= hi))[0]
        later = later[later > 0]
        # token i (i >= 1) is decoded at position prompt_len + i - 1 and
        # attends over that many + 1 positions
        total += float(token_flops(m, r.prompt_len + later).sum())
    return total
