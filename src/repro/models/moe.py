"""Mixture-of-Experts FFN with explicit expert parallelism (shard_map).

Design (DESIGN.md S5): experts are sharded over the *model* axis (EP
co-located with TP).  Activations enter the block replicated over 'model'
(they are batch-sharded over 'dp' only), so each model-rank routes ALL of its
data-shard's tokens to its own local experts -- **no all-to-all is required**;
the expert outputs are combined with the same psum a Megatron TP-FFN needs.
Expert weights are additionally FSDP-sharded over 'dp' and all-gathered on
entry (ZeRO-3); the gather transposes to a reduce-scatter in the backward.

Dispatch is sort-free (cumsum-position capacity dispatch):
  1. top-k routing (router logits; padded experts masked to -inf),
  2. per-expert positions via a one-hot cumsum (no argsort -> cheap grads),
  3. tokens beyond capacity C = ceil(T*k/E * cf) are dropped (standard),
  4. scatter into the (E_local, C, d) buffer, dense per-expert GEMMs on the
     MXU, gather back weighted by the routing probabilities.

The expert count is padded to a multiple of 16 so every mesh tp size in
{1,2,4,8,16} divides it (qwen2-moe: 60 -> 64; the 4 pads receive -inf router
logits and are never selected).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.launch import mesh as meshlib

from .common import ParamDef, act_fn

Array = jax.Array

EXPERT_PAD_MULTIPLE = 16


def padded_experts(n: int) -> int:
    return -(-n // EXPERT_PAD_MULTIPLE) * EXPERT_PAD_MULTIPLE


def moe_defs(cfg: ModelConfig) -> dict:
    d, fe = cfg.d_model, cfg.d_ff_expert
    e_pad = padded_experts(cfg.n_experts)
    defs = {
        "router": ParamDef((d, e_pad), (None, None)),
        "w_gate": ParamDef((e_pad, d, fe), ("expert", "fsdp", None)),
        "w_up": ParamDef((e_pad, d, fe), ("expert", "fsdp", None)),
        "w_down": ParamDef((e_pad, fe, d), ("expert", None, "fsdp")),
    }
    if cfg.d_ff_shared:
        fs = cfg.d_ff_shared
        defs["shared"] = {
            "gate": ParamDef((d, fs), ("fsdp", "tp")),
            "up": ParamDef((d, fs), ("fsdp", "tp")),
            "down": ParamDef((fs, d), ("tp", "fsdp")),
        }
        defs["shared_gate"] = ParamDef((d, 1), (None, None))  # qwen2-moe gate
    return defs


def moe_apply(p: dict, cfg: ModelConfig, x: Array) -> tuple[Array, Array]:
    """Returns (y, aux_loss).  x: (B, S, d) batch-sharded over dp."""
    mesh = meshlib.current_mesh()
    act = act_fn("silu")
    e_pad = padded_experts(cfg.n_experts)
    k = cfg.n_experts_per_tok

    if mesh is None or meshlib.in_manual_mode():
        # no-mesh debugging, or already inside a shard_map (pure-DP trainer):
        # run all experts locally -- correct semantics when 'model' axis is
        # not part of the enclosing manual region's sharding.
        return _moe_local(p, cfg, x, e_loc=e_pad, my_first=jnp.int32(0), act=act)

    dp = meshlib.dp_axes(mesh)
    dspec = meshlib.dp_spec_entry(mesh)
    tp = mesh.shape.get("model", 1)
    if e_pad % tp:
        raise ValueError(f"padded experts {e_pad} not divisible by tp={tp}")
    e_loc = e_pad // tp

    def local_fn(x_blk, router_w, w_gate, w_up, w_down, shared, shared_gate):
        # FSDP all-gather of the expert weights over the dp axes (ZeRO-3).
        w_gate = jax.lax.all_gather(w_gate, dp, axis=1, tiled=True)
        w_up = jax.lax.all_gather(w_up, dp, axis=1, tiled=True)
        w_down = jax.lax.all_gather(w_down, dp, axis=2, tiled=True)
        my_first = (jax.lax.axis_index("model") * e_loc).astype(jnp.int32)
        # Build the local param view from explicit shard_map args only (no
        # accidental closure capture of sharded arrays).
        pl = dict(router=router_w, w_gate=w_gate, w_up=w_up, w_down=w_down)
        if shared is not None:
            pl["shared"] = dict(
                gate=jax.lax.all_gather(shared["gate"], dp, axis=0, tiled=True),
                up=jax.lax.all_gather(shared["up"], dp, axis=0, tiled=True),
                down=jax.lax.all_gather(shared["down"], dp, axis=1, tiled=True),
            )
            pl["shared_gate"] = shared_gate
        y, aux = _moe_local(pl, cfg, x_blk, e_loc=e_loc, my_first=my_first, act=act)
        y = jax.lax.psum(y, "model")
        aux = jax.lax.pmean(aux, dp + ("model",))
        return y, aux

    shared = p.get("shared")
    shared_specs = (
        None
        if shared is None
        else dict(gate=P(dspec, "model"), up=P(dspec, "model"), down=P("model", dspec))
    )
    y, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            P(dspec, None, None),
            P(None, None),
            P("model", dspec, None),
            P("model", dspec, None),
            P("model", None, dspec),
            shared_specs,
            None if shared is None else P(None, None),
        ),
        out_specs=(P(dspec, None, None), P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"], shared, p.get("shared_gate"))
    return y, aux


def _moe_local(
    p: dict,
    cfg: ModelConfig,
    x: Array,
    *,
    e_loc: int,
    my_first: Array,
    act,
) -> tuple[Array, Array]:
    """Per-device MoE body.  x: (B_loc, S, d)."""
    b, s, d = x.shape
    t = b * s
    e_pad = padded_experts(cfg.n_experts)
    k = cfg.n_experts_per_tok
    cap = max(8, int(math.ceil(t * k / e_pad * cfg.capacity_factor)))
    dt = x.dtype
    xf = x.reshape(t, d)

    logits = (xf @ p["router"].astype(dt)).astype(jnp.float32)
    pad_mask = jnp.arange(e_pad) < cfg.n_experts
    logits = jnp.where(pad_mask[None, :], logits, -jnp.inf)
    top_vals, top_idx = jax.lax.top_k(logits, k)  # (T, k)
    probs = jax.nn.softmax(top_vals, axis=-1).astype(dt)

    # Within-expert positions over the flat (token-major) pair order -- one
    # cumsum over a (T*k, E_pad) int32 one-hot (small); everything (T*k, d)-
    # sized is avoided: dispatch/combine run per k-slice so the largest
    # dispatch intermediates are (T, d), not (T*k, d).
    pair_expert = top_idx.reshape(-1)  # (T*k,)
    onehot = (pair_expert[:, None] == jnp.arange(e_pad)[None, :]).astype(jnp.int32)
    pos_flat = jnp.take_along_axis(
        jnp.cumsum(onehot, 0) - 1, pair_expert[:, None], 1
    ).squeeze(-1)
    pos = pos_flat.reshape(t, k)
    local_e = top_idx - my_first  # (T, k)
    keep = (local_e >= 0) & (local_e < e_loc) & (pos < cap)
    slot = jnp.where(keep, local_e * cap + pos, e_loc * cap)  # (T, k)

    buf = jnp.zeros((e_loc * cap + 1, d), dt)
    for j in range(k):  # scatter each routing choice; slots are unique
        buf = buf.at[slot[:, j]].set(xf, mode="drop")
    buf3 = buf[: e_loc * cap].reshape(e_loc, cap, d)
    h = act(jnp.einsum("ecd,edf->ecf", buf3, p["w_gate"].astype(dt))) * jnp.einsum(
        "ecd,edf->ecf", buf3, p["w_up"].astype(dt)
    )
    y_exp = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(dt))
    y_flat = jnp.concatenate([y_exp.reshape(e_loc * cap, d), jnp.zeros((1, d), dt)], 0)
    out = jnp.zeros((t, d), dt)
    for j in range(k):  # combine: plain gathers, no scatter-add needed
        w_j = (probs[:, j] * keep[:, j].astype(dt))[:, None]
        out = out + y_flat[slot[:, j]] * w_j

    if "shared" in p and p["shared"] is not None:
        sh = p["shared"]
        hs = act(xf @ sh["gate"].astype(dt)) * (xf @ sh["up"].astype(dt))
        ys = hs @ sh["down"].astype(dt)
        gate = jax.nn.sigmoid((xf @ p["shared_gate"].astype(dt)).astype(jnp.float32))
        out = out + ys * gate.astype(dt)

    # Load-balance aux loss (Switch-style): E * sum_e f_e * p_e over real experts.
    probs_full = jax.nn.softmax(logits, axis=-1)  # fp32, pads ~ 0
    frac = jnp.mean(
        (onehot.reshape(t, k, e_pad).sum(1) > 0).astype(jnp.float32), axis=0
    )
    mean_p = jnp.mean(probs_full, axis=0)
    aux = cfg.n_experts * jnp.sum(frac * mean_p)
    return out.reshape(b, s, d), aux
