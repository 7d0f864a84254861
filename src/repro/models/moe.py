"""Mixture-of-Experts FFN: top-k routing with capacity, two implementations.

``dense``   one-hot dispatch einsum (GShard-style) — O(T*E*C) memory, used
            as the small-shape oracle in tests.
``sharded`` expert-parallel shard_map path: routing + capacity ranking are
            computed per data shard (no global sort), each model shard
            gathers only the slots of *its* experts (input is replicated
            across the model axis, so no all-to-all is needed on dispatch),
            and the combine is a single psum over the model axis — the same
            collective footprint as a Megatron TP FFN.

Dispatch uses index buffers (token ids scattered into [E_local, C] slots)
rather than [T*k, D] materialization, so peak memory is O(E_local * C * D).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig


def router_probs(x2d, w_router, jitter_key=None, jitter=0.0):
    logits = jnp.einsum("td,de->te", x2d, w_router,
                        preferred_element_type=jnp.float32)
    if jitter_key is not None and jitter > 0:
        logits += jax.random.uniform(jitter_key, logits.shape,
                                     minval=-jitter, maxval=jitter)
    return jax.nn.softmax(logits, axis=-1)


def _topk_gates(probs, top_k, norm_topk=True):
    gates, eidx = jax.lax.top_k(probs, top_k)           # [T,k]
    if norm_topk:
        gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    return gates, eidx


def load_balance_loss(probs, eidx, num_experts):
    """Switch-transformer aux loss: E * sum_e f_e * p_e."""
    T = probs.shape[0]
    counts = jnp.zeros((num_experts,), jnp.float32).at[eidx.reshape(-1)].add(1.0)
    f = counts / jnp.maximum(T * eidx.shape[-1], 1)
    p = jnp.mean(probs, axis=0)
    return num_experts * jnp.sum(f * p)


def _capacity(T, moe: MoEConfig) -> int:
    return max(1, int(T * moe.top_k * moe.capacity_factor / moe.num_experts))


def _rank_within_expert(eidx_flat, num_experts):
    """Position of each (token,k) pair within its expert's arrival order."""
    P = eidx_flat.shape[0]
    order = jnp.argsort(eidx_flat)                     # stable
    sorted_e = eidx_flat[order]
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(num_experts), side="left")
    rank_sorted = jnp.arange(P, dtype=jnp.int32) - seg_start[sorted_e].astype(jnp.int32)
    pos = jnp.zeros((P,), jnp.int32).at[order].set(rank_sorted)
    return pos


def moe_ffn_dense(x, params, moe: MoEConfig):
    """Oracle implementation. x: [B,S,D] -> ([B,S,D], aux_loss)."""
    B, S, D = x.shape
    T, E, k = B * S, moe.num_experts, moe.top_k
    xt = x.reshape(T, D)
    probs = router_probs(xt, params["router"])
    gates, eidx = _topk_gates(probs, k)
    aux = load_balance_loss(probs, eidx, E)
    C = _capacity(T, moe)

    pos = _rank_within_expert(eidx.reshape(-1), E).reshape(T, k)
    keep = pos < C
    # dispatch/combine tensors [T, k] -> [T, E, C]
    disp = (jax.nn.one_hot(eidx, E, dtype=xt.dtype)[..., None]
            * jax.nn.one_hot(jnp.where(keep, pos, C), C, dtype=xt.dtype)[..., None, :])
    disp = jnp.sum(disp, axis=1)                       # [T,E,C]
    buf = jnp.einsum("tec,td->ecd", disp, xt)
    h = _expert_swiglu(buf, params)
    # combine weights: gate per (t,e,c) slot
    gate_disp = jnp.sum(
        jax.nn.one_hot(eidx, E, dtype=jnp.float32)[..., None]
        * jax.nn.one_hot(jnp.where(keep, pos, C), C, dtype=jnp.float32)[..., None, :]
        * gates[..., None, None], axis=1)              # [T,E,C]
    y = jnp.einsum("tec,ecd->td", gate_disp.astype(h.dtype), h)
    return y.reshape(B, S, D), aux


def _expert_swiglu(buf, params):
    """buf: [E(,local), C, D] -> same shape through per-expert SwiGLU."""
    hg = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])
    hu = jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
    h = jax.nn.silu(hg.astype(jnp.float32)).astype(buf.dtype) * hu
    return jnp.einsum("ecf,efd->ecd", h, params["w_down"])


def moe_ffn_sharded(x, params, moe: MoEConfig, plan, gather_mode="auto"):
    """Expert-parallel path (see module docstring). x: [B,S,D].

    Under plan.fsdp the expert weights arrive sharded over the data axes on
    their embed dim and are all-gathered per layer inside the shard_map
    (ZeRO-3 semantics: transient full weights, persistent shards)."""
    info = plan.info
    mesh = info.mesh
    model_ax = info.model_axis
    d_axes = plan.spec("batch")[0]  # ("pod","data") / "data" / None
    # tiny decode batches (e.g. long_500k at batch=1) can't shard over the
    # data axes: replicate the tokens, keep expert parallelism over model
    if d_axes is not None and x.shape[0] % info.data_size != 0:
        d_axes = None
    P = jax.sharding.PartitionSpec
    fsdp = plan.fsdp and info.data_axes

    in_specs = (
        P(d_axes, None, None),                        # x: replicated over model
        P(None, None),                                # router: replicated (tiny,
                                                      # routing needs ALL experts)
        plan.spec("experts", "embed", "expert_mlp"),  # w_gate  [E,D,F]
        plan.spec("experts", "embed", "expert_mlp"),  # w_up
        plan.spec("experts", "expert_mlp", "embed"),  # w_down  [E,F,D]
    )
    out_specs = (P(d_axes, None, None), P())
    gather_axes = info.data_axes   # weights are data-sharded regardless of
                                   # how (or whether) the tokens shard
    # FSDP expert-weight strategy:
    #   "weights": all-gather the weights per layer (classic ZeRO-3; right
    #              when tokens >> weights, i.e. training/prefill)
    #   "partial": keep the weight shards; contract the token buffer against
    #              the local D-slice and psum/all-gather the *activations*
    #              (O(capacity) comm; right for decode where tokens << weights)
    mode = gather_mode
    if mode == "auto":
        mode = "weights"

    def local_fn(x_loc, w_router, w_gate, w_up, w_down):
        if fsdp and mode == "weights":
            w_gate = jax.lax.all_gather(w_gate, gather_axes, axis=1, tiled=True)
            w_up = jax.lax.all_gather(w_up, gather_axes, axis=1, tiled=True)
            w_down = jax.lax.all_gather(w_down, gather_axes, axis=2, tiled=True)
        B, S, D = x_loc.shape
        T = B * S
        E, k = moe.num_experts, moe.top_k
        E_loc = w_gate.shape[0]
        xt = x_loc.reshape(T, D)
        probs = router_probs(xt, w_router)
        gates, eidx = _topk_gates(probs, k)
        aux = load_balance_loss(probs, eidx, E)
        C = _capacity(T, moe)

        e_flat = eidx.reshape(-1)
        pos = _rank_within_expert(e_flat, E)
        tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
        g_flat = gates.reshape(-1)

        shard = jax.lax.axis_index(model_ax) if model_ax else 0
        e_lo = shard * E_loc
        mine = (e_flat >= e_lo) & (e_flat < e_lo + E_loc) & (pos < C)
        e_local = jnp.where(mine, e_flat - e_lo, E_loc)   # E_loc = drop row

        # index/gate buffers: [E_loc, C]; sentinel token id = T
        tok_buf = jnp.full((E_loc + 1, C), T, jnp.int32)
        tok_buf = tok_buf.at[e_local, jnp.minimum(pos, C - 1)].set(
            jnp.where(mine, tok, T))[:E_loc]
        gate_buf = jnp.zeros((E_loc + 1, C), jnp.float32)
        gate_buf = gate_buf.at[e_local, jnp.minimum(pos, C - 1)].set(
            jnp.where(mine, g_flat, 0.0))[:E_loc]

        xt_pad = jnp.concatenate([xt, jnp.zeros((1, D), xt.dtype)], axis=0)
        buf = xt_pad[tok_buf]                             # [E_loc, C, D]
        if fsdp and mode == "partial":
            # Activation-movement expert compute: weights stay sharded on D;
            # the (tiny) capacity buffers move instead.
            #   1. gather every data shard's slots (tokens differ per shard)
            #   2. contract the local D-slice, psum partials (same tokens
            #      everywhere now), 3. gather the D-sharded output and take
            #      this shard's slot segment back.
            dz = 1
            for a in gather_axes:
                dz *= mesh.shape[a]
            d_idx = jax.lax.axis_index(gather_axes)
            d_blk = D // dz
            tokens_sharded = d_axes is not None
            if tokens_sharded:
                buf_all = jax.lax.all_gather(buf, gather_axes, axis=1,
                                             tiled=True)   # [E, dz*C, D]
            else:
                buf_all = buf                               # replicated tokens
            buf_sl = jax.lax.dynamic_slice_in_dim(buf_all, d_idx * d_blk,
                                                  d_blk, axis=2)
            hg = jnp.einsum("ecd,edf->ecf", buf_sl, w_gate,
                            preferred_element_type=jnp.float32)
            hu = jnp.einsum("ecd,edf->ecf", buf_sl, w_up,
                            preferred_element_type=jnp.float32)
            hg, hu = jax.lax.psum((hg, hu), gather_axes)
            h = (jax.nn.silu(hg) * hu).astype(buf.dtype)
            out_part = jnp.einsum("ecf,efd->ecd", h, w_down)  # [E,*,D/dz]
            out_all = jax.lax.all_gather(out_part, gather_axes, axis=2,
                                         tiled=True)          # [E,*,D]
            if tokens_sharded:
                out_buf = jax.lax.dynamic_slice_in_dim(
                    out_all, d_idx * C, C, axis=1)            # this shard's
            else:
                out_buf = out_all
        else:
            out_buf = _expert_swiglu(buf, {"w_gate": w_gate, "w_up": w_up,
                                           "w_down": w_down})
        contrib = (out_buf.astype(jnp.float32)
                   * gate_buf[..., None]).astype(x_loc.dtype)
        y = jnp.zeros((T, D), x_loc.dtype)
        y = y.at[tok_buf.reshape(-1)].add(contrib.reshape(-1, D), mode="drop")
        if model_ax:
            y = jax.lax.psum(y, model_ax)
            aux = jax.lax.pmean(aux, model_ax)
        return y.reshape(B, S, D), aux

    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(x, params["router"], params["w_gate"], params["w_up"],
              params["w_down"])


def moe_ffn(x, params, moe: MoEConfig, plan, impl="auto", gather_mode="auto"):
    if impl == "auto":
        impl = "sharded"
    if impl == "dense":
        return moe_ffn_dense(x, params, moe)
    return moe_ffn_sharded(x, params, moe, plan, gather_mode=gather_mode)
