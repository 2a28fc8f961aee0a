"""Plain float32 reference of DeepSeek-V2-Lite (DeepSeek-V2 layout,
arXiv:2405.04434: RMSNorm; multi-head latent attention without q-LoRA,
materialised, not absorbed, with YaRN rotary scaling on the rope slice;
layer 0 a dense SwiGLU, every later layer a mixture of experts — softmax
gating over all experts, the top 6 not renormalised, times the routed
scaling factor, plus the shared experts; untied unembedding), written
from the published description in ``jax.numpy`` at "highest" matmul
precision.  It imports nothing of the system under test: the weights are
rebuilt from the seed by ``bench.lib.lm_weights``, one layer at a time,
so that they fit beside the activations.

Departure: the rotary embedding rotates halves of each rope slice (the
published model first permutes interleaved pairs into halves); under
random weights that is the same model up to a fixed permutation of the
rope weight columns, and the program does the same.

Each expert is computed over only the tokens routed to it (a loop over
the experts, ``lax.map``), and each token gathers its experts' outputs
back: index gathers, no scatter.  The smallest gap between the 6th and
7th routing probability of any token, the margin a rounding would have
to cross to swap an expert, and the seconds each stage took are logged
per replay.

``fp8=True`` is the control: every matmul operand, the experts' and the
router's, the attention scores' and values' too, rounded to float8 e4m3
(per-tensor scale for weights, per-row for activations), the step below
the bfloat16 activations the configuration states.
"""
from __future__ import annotations

import functools
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import lm_weights as lw

HI = jax.lax.Precision.HIGHEST
ATTN = ("q_proj", "kv_a_proj", "kv_b_proj", "o_proj", "norm1", "kv_a_norm")
ROW_BUCKET = 256          # experts' gathered rows: padded to a power of two
                          # of at least this, so a replay compiles one or two


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, fp8):
    if fp8:
        a, b = _fp8(a, -1), _fp8(b, None)
    return jnp.matmul(a, b, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn(c: dict):
    """``(inv_freq, cos/sin scale, softmax scale)`` of the rope slice,
    as DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding`` computes them."""
    d, base = c["qk_rope_head_dim"], float(c["rope_theta"])
    rs = dict(c["rope_scaling"])
    f = float(rs["factor"])
    extra = 1.0 / base ** (np.arange(0, d, 2) / d)
    inter = extra / f

    def corr(rot):
        return d * math.log(rs["original_max_position_embeddings"]
                            / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv_freq = inter * ramp + extra * (1 - ramp)
    cs = _mscale(f, rs["mscale"]) / _mscale(f, rs["mscale_all_dim"])
    qk = c["qk_nope_head_dim"] + d
    sm = qk ** -0.5 * _mscale(f, rs["mscale_all_dim"]) ** 2
    return inv_freq, cs, sm


def _rope(x, inv_freq, cs):
    """``x (P, H, d)`` rotated by position, halves form."""
    p = x.shape[0]
    ang = jnp.arange(p, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None] * cs, jnp.sin(ang)[:, None] * cs
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _proj(key, path, index, kk, n, bits):
    k = lw.leaf_key(key, path)
    tbl = lw.table(k, 1 << bits)
    return lw.decode(lw.words(k, index, kk, n, bits), tbl,
                     lw.scale(tbl, 1.0 / math.sqrt(kk)), bits)


def _dense(key, path, index, shape):
    return lw.dense(lw.leaf_key(key, path), index, shape, "norm")


@functools.partial(jax.jit, static_argnames=("c", "bits", "dense"))
def layer_weights(key, i, *, c, bits, dense):
    """A layer's dense float32 weights but the routed experts: the dense
    layer 0 (``prologue/0/`` in the served tree), or MoE layer ``i + 1``
    (index ``i`` of ``stack/b0/``)."""
    c = dict(c)
    d, h = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    kr = c["kv_lora_rank"]
    pre = "prologue/0/" if dense else "stack/b0/"
    w = {"q_proj": _proj(key, pre + "mixer/q_proj", i, d, h * (dn + dr),
                         bits),
         "kv_a_proj": _proj(key, pre + "mixer/kv_a_proj", i, d, kr + dr,
                            bits),
         "kv_b_proj": _proj(key, pre + "mixer/kv_b_proj", i, kr,
                            h * (dn + dv), bits),
         "o_proj": _proj(key, pre + "mixer/o_proj", i, h * dv, d, bits),
         "norm1": _dense(key, pre + "norm1/w", i, (d,)),
         "kv_a_norm": _dense(key, pre + "mixer/kv_a_norm/w", i, (kr,)),
         "norm2": _dense(key, pre + "norm2/w", i, (d,))}
    if dense:
        f, mlp = c["intermediate_size"], pre + "mlp/"
    else:
        f = c["moe_intermediate_size"] * c["n_shared_experts"]
        mlp = pre + "mlp/shared/"
        w["router"] = _proj(key, pre + "mlp/router", i, d,
                            c["n_routed_experts"], bits)
    w["gate_proj"] = _proj(key, mlp + "gate_proj", i, d, f, bits)
    w["up_proj"] = _proj(key, mlp + "up_proj", i, d, f, bits)
    w["down_proj"] = _proj(key, mlp + "down_proj", i, f, d, bits)
    return w


@functools.partial(jax.jit, static_argnames=("c", "bits"))
def expert_weights(key, i, *, c, bits):
    """MoE layer ``i + 1``'s routed experts: gate, up ``(E, d, f)`` and
    down ``(E, f, d)``, float32; expert ``e`` is matrix ``i * E + e`` of
    its leaf."""
    c = dict(c)
    d, f, e = (c["hidden_size"], c["moe_intermediate_size"],
               c["n_routed_experts"])
    out = []
    for name, (kk, n) in (("w_experts_gate", (d, f)),
                          ("w_experts_in", (d, f)),
                          ("w_experts_out", (f, d))):
        k = lw.leaf_key(key, "stack/b0/mlp/" + name)
        tbl = lw.table(k, 1 << bits)
        s = lw.scale(tbl, 1.0 / math.sqrt(kk))
        words = jax.vmap(lambda j: lw.words(k, i * e + j, kk, n, bits))(
            jnp.arange(e))
        # decoded as one 2-D stack: a decode under vmap lowers to a
        # batched gather that takes seconds a layer on the TPU
        out.append(lw.decode(words.reshape(e * kk, -1), tbl, s, bits)
                   .reshape(e, kk, n))
    return tuple(out)


def attn_apply(x, w, *, c, fp8):
    """``x + MLA(x)`` over one causal sequence ``x (P, d)``."""
    c = dict(c)
    p = x.shape[0]
    h = c["num_attention_heads"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    kr, eps = c["kv_lora_rank"], c["rms_norm_eps"]
    inv_freq, cs, sm = yarn(c)
    a = _rms(x, w["norm1"], eps)
    q = _mm(a, w["q_proj"], fp8).reshape(p, h, dn + dr)
    kva = _mm(a, w["kv_a_proj"], fp8)
    ckv = _rms(kva[:, :kr], w["kv_a_norm"], eps)
    k_rot = _rope(kva[:, None, kr:], inv_freq, cs)
    kv = _mm(ckv, w["kv_b_proj"], fp8).reshape(p, h, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], inv_freq, cs)], -1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_rot, (p, h, dr))], -1)
    v = kv[..., dn:]
    if fp8:
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, -1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * sm
    causal = jnp.arange(p)[:, None] >= jnp.arange(p)[None, :]
    att = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    if fp8:
        att = _fp8(att, -1)
    o = jnp.einsum("hqk,khd->qhd", att, v, precision=HI).reshape(p, h * dv)
    return x + _mm(o, w["o_proj"], fp8)


@functools.partial(jax.jit, static_argnames=("c", "fp8"))
def attention(x, w, *, c, fp8):
    """``attn_apply`` over each sequence of ``x (B, P, d)`` in turn."""
    return jax.lax.map(lambda xi: attn_apply(xi, w, c=c, fp8=fp8), x)


def _swiglu(x, wg, wu, wd, fp8):
    return _mm(jax.nn.silu(_mm(x, wg, fp8)) * _mm(x, wu, fp8), wd, fp8)


@functools.partial(jax.jit, static_argnames=("eps",))
def ffn_in(x, real, norm2, *, eps):
    """The FFN's input: rows ``real`` of the sequences ``x (B, P, d)``."""
    return _rms(x.reshape(-1, x.shape[-1])[real], norm2, eps)


@functools.partial(jax.jit, static_argnames=("fp8",))
def router_probs(a, router, *, fp8):
    """Softmax over the experts' router logits."""
    return jax.nn.softmax(_mm(a, router, fp8), axis=-1)


@functools.partial(jax.jit, static_argnames=("fp8",))
def dense_ffn(a, w, *, fp8):
    return _swiglu(a, w["gate_proj"], w["up_proj"], w["down_proj"], fp8)


@functools.partial(jax.jit, static_argnames=("fp8",))
def routed(a, rows, slots, gates, wg, wu, wd, *, fp8):
    """A loop over the experts: expert ``e`` gathers its own rows
    ``rows[e]`` of ``a (N, d)`` and runs its SwiGLU over them; then each
    token gathers its k experts' outputs, at ``slots (N, k)`` of the
    flattened ``(E * cap, d)`` outputs, and sums them weighted by
    ``gates (N, k)``.  Gathers only: a scatter-add of every expert's
    padded rows back into the tokens is slow on the TPU."""
    def expert(args):
        r, eg, eu, ed = args
        return _swiglu(jnp.take(a, r, axis=0), eg, eu, ed, fp8)
    ys = jax.lax.map(expert, (rows, wg, wu, wd)).reshape(-1, a.shape[1])
    return jnp.sum(gates[..., None] * jnp.take(ys, slots, axis=0), axis=1)


def moe_ffn(a, probs, ew, *, c, fp8, n=None) -> tuple[jax.Array, float]:
    """Routed experts of the tokens ``a (N, d)``, each expert over only
    its own tokens (padded to a common count, a power of two of at least
    ``ROW_BUCKET``); and the smallest gap between a token's k-th and
    (k+1)-th routing probability.  Only the first ``n`` rows are tokens
    (all by default); the rest come out 0."""
    k, e = c["num_experts_per_tok"], c["n_routed_experts"]
    p = np.asarray(probs)[:n]
    order = np.argsort(-p, axis=1, kind="stable")
    top = order[:, :k]
    srt = np.take_along_axis(p, order[:, :k + 1], axis=1)
    margin = float((srt[:, k - 1] - srt[:, k]).min())
    gates = np.take_along_axis(p, top, axis=1) \
        * float(c["routed_scaling_factor"])
    # pair n * k + j (token n's j-th expert) goes to row ``rank`` of its
    # expert's group, in token order
    flat = top.reshape(-1)
    by_expert = np.argsort(flat, kind="stable")
    counts = np.bincount(flat, minlength=e)
    starts = np.cumsum(counts) - counts
    rank = np.empty_like(flat)
    rank[by_expert] = np.arange(len(flat)) - starts[flat[by_expert]]
    cap = max(ROW_BUCKET, 1 << (int(counts.max()) - 1).bit_length())
    rows = np.zeros((e, cap), np.int32)
    rows[flat, rank] = np.arange(len(flat)) // k
    slots = np.zeros((a.shape[0], k), np.int32)
    slots[:len(top)] = (flat * cap + rank).reshape(top.shape)
    g = np.zeros((a.shape[0], k), np.float32)
    g[:len(top)] = gates
    return routed(a, jnp.asarray(rows), jnp.asarray(slots), jnp.asarray(g),
                  *ew, fp8=fp8), margin


@jax.jit
def add_rows(x, at, *ys):
    """``x`` plus row ``at[i]`` of the sum of ``ys`` at each row ``i`` of
    ``x`` flattened (index ``len(y)``: nothing), by a gather, not a
    scatter."""
    y = functools.reduce(jnp.add, ys)
    y = jnp.concatenate([y, jnp.zeros_like(y[:1])])
    return x + jnp.take(y, at, axis=0).reshape(x.shape)


@jax.jit
def embed(emb, tok):
    return jnp.take(emb, tok, axis=0)


@functools.partial(jax.jit, static_argnames=("c", "bits"))
def embeddings(key, *, c, bits):
    c = dict(c)
    v, d = c["vocab_size"], c["hidden_size"]
    out = []
    for path in ("embed", "out_embed"):
        k = lw.leaf_key(key, path)
        tbl = lw.table(k, 1 << bits)
        out.append(lw.decode(lw.words(k, 0, v, d, bits), tbl,
                             lw.scale(tbl, lw.EMBED_STD), bits))
    fnorm = lw.dense(lw.leaf_key(key, "final_norm/w"), 0, (d,), "norm")
    return out[0], out[1], fnorm


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def unembed(x, fnorm, emb, *, eps, fp8):
    return _mm(_rms(x, fnorm, eps), emb.T, fp8)


class _Laps:
    """Seconds spent in each stage of the replay, each stage waited for."""

    def __init__(self):
        self.spent, self.t = {}, time.monotonic()

    def lap(self, name: str, *arrays) -> None:
        jax.block_until_ready(arrays)
        now = time.monotonic()
        self.spent[name] = round(self.spent.get(name, 0.0) + now - self.t, 3)
        self.t = now


def _hashable(c: dict) -> tuple:
    return tuple(sorted((k, tuple(sorted(v.items())) if isinstance(v, dict)
                         else v) for k, v in c.items()
                        if isinstance(v, (int, float, str, bool, dict))))


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def logits_at(c: dict, seed: int, seqs, positions, *, pad_to: int,
              bits: int, fp8: bool = False) -> list[np.ndarray]:
    """For each token sequence ``seqs[i]``, the float32 logits that
    predict the token after each position in ``positions[i]``.  Every
    sequence is padded at its end to ``pad_to`` (causal attention leaves
    earlier positions untouched), and the counts of sequences, tokens
    and served rows are rounded up, so that one set of programs serves
    most replays: compiling dominated a replay."""
    ch = _hashable(c)
    eps = c["rms_norm_eps"]
    key = lw.seed_key(seed)
    emb, out_emb, fnorm = embeddings(key, c=ch, bits=bits)
    tok = np.zeros((_round_up(len(seqs), 8), pad_to), np.int32)
    real = []
    for i, s in enumerate(seqs):
        tok[i, :len(s)] = s
        real.append(i * pad_to + np.arange(len(s)))
    real = np.concatenate(real)
    n = len(real)
    at = np.full(tok.size, n, np.int32)
    at[real] = np.arange(n)
    real = jnp.asarray(np.pad(real, (0, _round_up(n, 1024) - n)))
    at = jnp.asarray(at)
    x = embed(emb, jnp.asarray(tok))
    del emb
    margins, laps = [], _Laps()
    for layer in range(c["num_hidden_layers"]):
        w = layer_weights(key, max(layer - 1, 0), c=ch, bits=bits,
                          dense=layer == 0)
        ew = expert_weights(key, layer - 1, c=ch, bits=bits) if layer \
            else None
        laps.lap("weights", w, ew)
        x = attention(x, {k: w[k] for k in ATTN}, c=ch, fp8=fp8)
        laps.lap("attention", x)
        a = ffn_in(x, real, w["norm2"], eps=eps)
        y = dense_ffn(a, w, fp8=fp8)
        if layer:
            probs = router_probs(a, w["router"], fp8=fp8)
            routed, margin = moe_ffn(a, probs, ew, c=c, fp8=fp8, n=n)
            margins.append(margin)
            x = add_rows(x, at, y, routed)
        else:
            x = add_rows(x, at, y)
        laps.lap("ffn", x)
        del w, ew, a, y
    print(f"[reference] smallest gap between the k-th and (k+1)-th routing "
          f"probability over {n} tokens and "
          f"{len(margins)} MoE layers: {min(margins)!r} (fp8 {fp8}); "
          f"per layer min {[float(f'{m:.3g}') for m in margins]}; "
          f"seconds {laps.spent}", file=sys.stderr, flush=True)
    rows = np.concatenate([i * pad_to + np.asarray(pos)
                           for i, pos in enumerate(positions)])
    m = len(rows)
    flat = x.reshape(-1, x.shape[-1])
    out = np.asarray(unembed(flat[jnp.asarray(np.pad(
        rows, (0, _round_up(m, 256) - m)))], fnorm, out_emb, eps=eps,
        fp8=fp8))[:m]
    return np.split(out, np.cumsum([len(p) for p in positions])[:-1])
