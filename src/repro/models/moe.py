"""Dense MLP and Mixture-of-Experts with expert parallelism.

MoE dispatch is sort-based + a grouped matmul (active-expert FLOPs
only — no one-hot dispatch einsum, keeping the roofline's useful-FLOPs
ratio honest): ``lax.ragged_dot`` on dense stacks, and on packed stacks
the backend's ``grouped_matmul`` (``codr_matmul``'s grouped kernel
decodes only the experts that received rows).  Under a mesh, experts
are sharded over the ``model`` axis via ``shard_map``: tokens (already
sharded over ``data``) are processed against the *local* expert slice
and partial outputs are ``psum``-combined over ``model`` — one
all-reduce per MoE layer, the same collective class as TP, with no
data-dependent all-to-all sizes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.models.common import (PackedLinear, act_fn, dense_init,
                                 dense_weight, grouped_linear, linear)
from repro.sharding import current_ctx

# the mesh lanes hand the router and expert stacks to shard_map as raw
# operands, so there a packed leaf is decoded once per forward
# (decode-on-dispatch, docs/DESIGN.md §2); one device keeps them packed
_PACKABLE_KEYS = ("router", "w_experts_gate", "w_experts_in",
                  "w_experts_out")


def _dense_moe_params(p):
    if not any(isinstance(p.get(k), PackedLinear) for k in _PACKABLE_KEYS):
        return p
    return {k: dense_weight(v) if k in _PACKABLE_KEYS else v
            for k, v in p.items()}


# ---------------------------------------------------------------------------
# dense MLP (SwiGLU-style gate/up/down or plain act(up)·down)
# ---------------------------------------------------------------------------

def mlp_init(key, d_model: int, d_ff: int, *, gated: bool = True) -> dict:
    ks = jax.random.split(key, 3)
    p = {"up_proj": dense_init(ks[0], d_model, d_ff),
         "down_proj": dense_init(ks[2], d_ff, d_model)}
    if gated:
        p["gate_proj"] = dense_init(ks[1], d_model, d_ff)
    return p


def mlp_forward(p, x, act: str = "silu"):
    up = linear(x, p["up_proj"])
    if "gate_proj" in p:
        up = act_fn(act)(linear(x, p["gate_proj"])) * up
    else:
        up = act_fn(act)(up)
    return linear(up, p["down_proj"])


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def moe_init(key, cfg) -> dict:
    ks = jax.random.split(key, 5)
    d, e, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    p = {
        "router": dense_init(ks[0], d, e, scale=0.02),
        "w_experts_gate": jax.vmap(lambda k: dense_init(k, d, f))(
            jax.random.split(ks[1], e)),
        "w_experts_in": jax.vmap(lambda k: dense_init(k, d, f))(
            jax.random.split(ks[2], e)),
        "w_experts_out": jax.vmap(lambda k: dense_init(k, f, d))(
            jax.random.split(ks[3], e)),
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(ks[4], d, cfg.moe_d_ff * cfg.n_shared_experts)
    return p


def _expert_compute(xs: jax.Array, group_sizes: jax.Array, wg, wi, wo,
                    act: str, max_group: int) -> jax.Array:
    """Grouped SwiGLU over sorted tokens: xs (T, d), experts (E, d, f)."""
    gate = grouped_linear(xs, wg, group_sizes, max_group=max_group)
    up = grouped_linear(xs, wi, group_sizes, max_group=max_group)
    h = act_fn(act)(gate) * up
    return grouped_linear(h, wo, group_sizes, max_group=max_group)


def route(logits: jax.Array, cfg) -> tuple[jax.Array, jax.Array]:
    """``(gates, expert ids)``, each ``(T, k)``, from f32 router logits
    ``(T, E)``, by ``cfg.moe_gating``: ``"topk_softmax"`` takes the top
    k logits and a softmax over them; ``"softmax_topk"`` a softmax over
    all E, its top k, not renormalised, times ``cfg.moe_routed_scale``."""
    k = cfg.moe_top_k
    if cfg.moe_gating == "softmax_topk":
        gates, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        return gates * cfg.moe_routed_scale, idx
    if cfg.moe_gating != "topk_softmax":
        raise ValueError(f"unknown moe_gating {cfg.moe_gating!r}")
    gates, idx = jax.lax.top_k(logits, k)
    return jax.nn.softmax(gates, axis=-1), idx


def _moe_local(x2d: jax.Array, p, cfg, n_local: int, expert_offset
               ) -> tuple[jax.Array, jax.Array]:
    """Token-choice top-k against ``n_local`` experts starting at
    ``expert_offset`` (traced).  x2d (T, d) → ((T, d) partial output,
    how many of the local experts received rows)."""
    t, d = x2d.shape
    k = cfg.moe_top_k
    logits = linear(x2d.astype(jnp.float32), p["router"])   # f32 (T, E)
    gates, idx = route(logits, cfg)                        # (T, k)

    flat_idx = idx.reshape(-1)                             # (T*k,)
    flat_gate = gates.reshape(-1)
    local_id = flat_idx - expert_offset
    is_local = (local_id >= 0) & (local_id < n_local)
    sort_key = jnp.where(is_local, local_id, n_local)      # remotes last
    order = jnp.argsort(sort_key)
    token_of = order // k                                  # source token
    xs = jnp.take(x2d, token_of, axis=0)                   # (T*k, d)
    group_sizes = jnp.bincount(jnp.where(is_local, local_id, n_local),
                               length=n_local + 1)[:n_local]
    # a token picks k distinct experts: no group holds more than T rows
    ys = _expert_compute(xs, group_sizes, p["w_experts_gate"],
                         p["w_experts_in"], p["w_experts_out"], cfg.act,
                         max_group=t)
    # zero contributions from remote/padding rows
    in_range = jnp.arange(t * k) < group_sizes.sum()
    ys = jnp.where(in_range[:, None], ys, 0.0)
    ys = ys * jnp.take(flat_gate, order).astype(ys.dtype)[:, None]
    out = jnp.zeros((t, d), ys.dtype).at[token_of].add(ys)
    return out, jnp.count_nonzero(group_sizes).astype(jnp.int32)


def _moe_2d(p, x, cfg, ctx):
    """Decode-time MoE with 2-D expert sharding (§Perf optimization).

    Experts shard over ``model`` (E/m each) and every expert's FFN
    hidden dim shards over ``data`` (TP-within-expert), so each chip
    holds E·3·d·f/(m·d_axis) weight bytes and reads ONLY those from HBM
    — zero per-step weight collectives.  The (tiny) decode token batch
    is all-gathered over ``data``; every shard computes its expert/f
    slice for all of its pod's tokens; one psum over (data, model)
    combines both the cross-expert and the f-partial sums (both are
    additive); each data shard keeps its own token rows."""
    b, s, d = x.shape
    mesh = ctx.mesh
    msize, dsize = ctx.axis_size("model"), ctx.axis_size("data")
    e = cfg.n_experts
    n_local = e // msize
    bspec = ctx.batch_spec
    rows = b * s

    def body(x2d, router, wg, wi, wo):
        xg = jax.lax.all_gather(x2d, "data", axis=0, tiled=True)
        offset = jax.lax.axis_index("model") * n_local
        pl_ = {"router": router, "w_experts_gate": wg,
               "w_experts_in": wi, "w_experts_out": wo}
        part, _ = _moe_local(xg, pl_, cfg, n_local, offset)
        full = jax.lax.psum(part, ("data", "model"))
        t_loc = x2d.shape[0]
        start = jax.lax.axis_index("data") * t_loc
        return jax.lax.dynamic_slice(full, (start, 0), (t_loc, d))

    out2d = shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, None), P(None, None),
                  P("model", None, "data"),      # gate (E, d, f{data})
                  P("model", None, "data"),      # up
                  P("model", "data", None)),     # down (E, f{data}, d)
        out_specs=P(bspec, None),
        check_vma=False,
    )(x.reshape(rows, d), p["router"], p["w_experts_gate"],
      p["w_experts_in"], p["w_experts_out"])
    return out2d.reshape(b, s, d).astype(x.dtype)


def _local_lane(cfg, ctx) -> bool:
    """Whether ``moe_forward`` runs every expert on this device."""
    return (ctx is None or ctx.axis_size("model") == 1
            or cfg.n_experts % ctx.axis_size("model") != 0)


def counts_experts(cfg) -> bool:
    """Whether ``moe_forward`` counts the experts given rows: on one
    device; the mesh lanes, which no caller reads a count from, return
    None."""
    return _local_lane(cfg, current_ctx())


def moe_forward(p, x, cfg, mode: str = "train"):
    """x (B, S, d) → ((B, S, d), how many experts received rows, None on
    the mesh lanes).  EP over 'model' when a mesh is active; 2-D expert
    sharding for decode when ``cfg.moe_decode_2d``."""
    b, s, d = x.shape
    ctx = current_ctx()
    e = cfg.n_experts

    if (cfg.moe_decode_2d and mode == "decode" and ctx is not None
            and ctx.axis_size("model") > 1 and ctx.axis_size("data") > 1
            and e % ctx.axis_size("model") == 0
            and cfg.moe_d_ff % ctx.axis_size("data") == 0):
        p = _dense_moe_params(p)
        out = _moe_2d(p, x, cfg, ctx)
        if "shared" in p:
            out = out + mlp_forward(p["shared"], x, cfg.act)
        return out, None

    if _local_lane(cfg, ctx):
        out, touched = _moe_local(x.reshape(-1, d), p, cfg, e, 0)
        out = out.reshape(b, s, d).astype(x.dtype)
    else:
        p = _dense_moe_params(p)
        mesh = ctx.mesh
        msize = ctx.axis_size("model")
        n_local = e // msize
        batch = ctx.batch_spec
        # token rows must divide the batch axes; otherwise replicate
        # (single-sequence decode: B·S == 1)
        if batch is not None:
            baxes = batch if isinstance(batch, tuple) else (batch,)
            total = 1
            for a in baxes:
                total *= ctx.axis_size(a)
            if (b * s) % total:
                batch = None

        def sharded(x2d, router, wg, wi, wo):
            my = jax.lax.axis_index("model")
            pl_ = {"router": router, "w_experts_gate": wg,
                   "w_experts_in": wi, "w_experts_out": wo}
            part, _ = _moe_local(x2d, pl_, cfg, n_local, my * n_local)
            return jax.lax.psum(part, "model")

        specs_w = (P(None, None), P("model", None, None),
                   P("model", None, None), P("model", None, None))
        out2d = shard_map(
            sharded, mesh=mesh,
            in_specs=(P(batch, None),) + specs_w,
            out_specs=P(batch, None),
            check_vma=False,
        )(x.reshape(-1, d), p["router"], p["w_experts_gate"],
          p["w_experts_in"], p["w_experts_out"])
        out = out2d.reshape(b, s, d).astype(x.dtype)
        touched = None

    if "shared" in p:
        out = out + mlp_forward(p["shared"], x, cfg.act)
    return out, touched
