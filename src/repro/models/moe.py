"""Mixture-of-Experts FFN with sort-based static-capacity dispatch.

Design (TPU-native, DESIGN.md §5):

* token-choice top-k routing with a static per-expert capacity
  ``C = ceil(T * k / E * capacity_factor)`` (rounded up to a multiple of
  128 for MXU alignment) — static shapes keep the step jit-compatible;
* dispatch via **argsort by expert id** + rank-within-expert scatter into
  an ``[E, C, D]`` buffer (no ``[T, E, C]`` one-hot blow-up, which would
  be ~20 TB for the kimi-k2 train shape);
* expert FFNs run as one batched einsum over the expert dim;
* sharding: the buffer is constrained to ``P('model' on E, data on C)``,
  so GSPMD emits the expert-parallel all-to-all between token shards and
  expert shards — the same communication pattern as a hand-written EP
  dispatch;
* auxiliary losses: switch-style load-balance + router z-loss.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import ParamDef
from repro.sharding.rules import Rules


def moe_schema(cfg: ModelConfig) -> Dict[str, ParamDef]:
    d, e, f = cfg.d_model, cfg.num_experts, cfg.expert_d_ff
    return {
        "router": ParamDef((d, e), ("embed", None)),
        "w_gate": ParamDef((e, d, f), ("expert", "embed", "mlp")),
        "w_up": ParamDef((e, d, f), ("expert", "embed", "mlp")),
        "w_down": ParamDef((e, f, d), ("expert", "mlp", "embed")),
    }


def expert_capacity(tokens: int, cfg: ModelConfig) -> int:
    c = math.ceil(tokens * cfg.experts_per_token / cfg.num_experts * cfg.capacity_factor)
    if c >= 128:
        return ((c + 127) // 128) * 128  # MXU-aligned
    # serve-path (decode) capacities are tiny; a hard 128 floor inflated
    # the kimi-k2 decode dispatch buffer 16x (EXPERIMENTS.md §Perf).
    # Sublane-aligned (8) is enough when the tile is this small.
    return max(8, ((c + 7) // 8) * 8)


def moe_ffn(
    params: dict,
    x: jax.Array,
    cfg: ModelConfig,
    rules: Optional[Rules] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: [B, S, D] -> (y, aux_losses).

    Two dispatch paths:

    * ``_moe_ffn_global`` — single global argsort + scatter.  Correct
      everywhere, but under GSPMD the scatter's computed indices span the
      whole token space, so the partitioner **replicates** the [E*C, D]
      buffer per device and stitches it with giant all-reduces (measured:
      64 GB f32 buffers + 103 GB all-reduces per layer on the granite
      train_4k shape — EXPERIMENTS.md §Perf iteration 1).  Kept as the
      reference path for unsharded/test meshes.
    * ``_moe_ffn_sharded`` — dispatch and combine run *locally per data
      shard* inside :func:`jax.shard_map` (each shard scatters into its
      own capacity block of a [E, G*C_loc, D] buffer), then the expert
      einsums stay in GSPMD land: constraining the buffer to
      ``('expert','capacity')`` emits the expert-parallel all-to-all when
      E divides the model axis (kimi-k2), and falls back to TP on the
      FFN dim otherwise (granite's E=40).  This is the TPU-native
      adaptation: local VMEM-sized scatters, MXU-aligned capacity.
    """
    B, S, D = x.shape
    if rules is not None:
        G = rules.data_extent
        if G > 1 and B % G == 0:
            return _moe_ffn_sharded(params, x, cfg, rules)
    return _moe_ffn_global(params, x, cfg, rules)


def _moe_ffn_global(
    params: dict,
    x: jax.Array,
    cfg: ModelConfig,
    rules: Optional[Rules] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    B, S, D = x.shape
    T = B * S
    E, K = cfg.num_experts, cfg.experts_per_token
    C = expert_capacity(T, cfg)
    xt = x.reshape(T, D)

    # ---- routing -------------------------------------------------------
    logits = (xt @ params["router"]).astype(jnp.float32)  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)  # [T, K]
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)  # renormalize

    # ---- dispatch: sort (token, slot) pairs by expert ------------------
    flat_e = top_e.reshape(T * K)  # expert of each assignment
    flat_p = top_p.reshape(T * K)
    flat_tok = jnp.repeat(jnp.arange(T), K)
    order = jnp.argsort(flat_e)  # stable -> FCFS within expert
    sorted_e = flat_e[order]
    sorted_tok = flat_tok[order]
    sorted_p = flat_p[order]
    # rank of each assignment within its expert
    counts = jnp.bincount(flat_e, length=E)  # [E]

    # ---- aux losses (bincount-based: no [T,K,E] one-hot blow-up) --------
    density = counts.astype(jnp.float32) / T  # routed fraction per expert
    router_mean = jnp.mean(probs, axis=0)
    lb_loss = E * jnp.sum(density * router_mean) / K
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))

    starts = jnp.cumsum(counts) - counts  # exclusive prefix
    rank = jnp.arange(T * K) - starts[sorted_e]
    keep = rank < C  # capacity-dropped assignments contribute nothing
    slot = jnp.where(keep, sorted_e * C + rank, E * C)  # OOB -> dropped

    buf = jnp.zeros((E * C, D), x.dtype)
    buf = buf.at[slot].add(
        jnp.where(keep[:, None], xt[sorted_tok], 0).astype(x.dtype),
        mode="drop",
    )
    buf = buf.reshape(E, C, D)
    if rules is not None:
        buf = rules.constrain(buf, ("expert", "capacity", None))

    # ---- expert FFNs (batched over E) -----------------------------------
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, params["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
    if rules is not None:
        h = rules.constrain(h, ("expert", "capacity", "mlp"))
    out = jnp.einsum("ecf,efd->ecd", h, params["w_down"])
    if rules is not None:
        out = rules.constrain(out, ("expert", "capacity", None))

    # ---- combine: gather back and weight by router prob ----------------
    out_flat = out.reshape(E * C, D)
    gathered = jnp.where(
        keep[:, None], out_flat[jnp.minimum(slot, E * C - 1)], 0
    )  # [T*K, D] in sorted order
    contrib = gathered * sorted_p[:, None].astype(x.dtype)
    y_flat = jnp.zeros((T, D), x.dtype).at[sorted_tok].add(contrib)
    y = y_flat.reshape(B, S, D)
    if rules is not None:
        y = rules.constrain(y, ("batch", None, None))
    return y, {"load_balance": lb_loss, "router_z": z_loss}


# ---------------------------------------------------------------------------
# shard_map dispatch (TPU-native path; EXPERIMENTS.md §Perf iteration 1)
# ---------------------------------------------------------------------------


def _local_dispatch(xt, router, E, K, C_loc, E_buf=None, e_lo=None, n_slice=None):
    """Per-shard dispatch: xt [T_loc, D] -> buffer + combine metadata.

    Pure dense ops on local data — no cross-shard indices, so GSPMD never
    sees a global scatter.  ``E_buf >= E`` pads the buffer's expert dim
    (EP divisibility); tokens only ever route to the first E experts.
    ``[e_lo, e_lo + n_slice)`` restricts the built buffer to one expert
    slice (the caller's model rank); ``n_slice`` must be a static int
    (``e_lo`` may be a traced ``axis_index``).  Metadata keeps global
    expert coordinates.
    """
    E_buf = E if E_buf is None else E_buf
    if e_lo is None:
        e_lo, n_slice = 0, E_buf
    T_loc = xt.shape[0]
    logits = (xt @ router).astype(jnp.float32)  # [T_loc, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    flat_e = top_e.reshape(T_loc * K)
    flat_p = top_p.reshape(T_loc * K)
    flat_tok = jnp.repeat(jnp.arange(T_loc), K)
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    sorted_tok = flat_tok[order]
    sorted_p = flat_p[order]
    counts = jnp.bincount(flat_e, length=E)

    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(T_loc * K) - starts[sorted_e]
    keep = rank < C_loc
    # global slot coordinates (combine metadata)
    slot = jnp.where(keep, sorted_e * C_loc + rank, E_buf * C_loc)

    # this rank's expert slice only; out-of-slice assignments drop
    local = keep & (sorted_e >= e_lo) & (sorted_e < e_lo + n_slice)
    local_slot = jnp.where(
        local, (sorted_e - e_lo) * C_loc + rank, n_slice * C_loc
    )

    # slots are unique per (expert, rank), so a plain scatter-set suffices
    # — scatter-ADD on bf16 is what the CPU backend upcasts to f32, which
    # would double every boundary collective (§Perf iteration 4)
    buf = jnp.zeros((n_slice * C_loc, xt.shape[1]), xt.dtype)
    buf = buf.at[local_slot].set(
        jnp.where(local[:, None], xt[sorted_tok], 0).astype(xt.dtype),
        mode="drop",
    )
    # inverse sort permutation lets the combine run scatter-free
    inv = jnp.argsort(order)
    meta = (inv, sorted_p.astype(xt.dtype), slot, keep)
    aux = (counts, jnp.mean(probs, axis=0),
           jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))))
    return buf.reshape(n_slice, C_loc, xt.shape[1]), meta, aux


def _local_combine(out, inv, sorted_p, slot, keep, T_loc, slot_lo=0):
    """Per-shard combine: expert-slice output [E_l, C_loc, D] -> partial
    y [T_loc, D] (zeros for assignments outside this slice).

    Scatter-free: gather each assignment's expert output in sorted order,
    undo the sort with ``inv``, and sum the K contributions per token
    with a dense reshape — no scatter-add (CPU upcasts those to f32, and
    TPUs much prefer dense reductions).
    """
    E_l, C_loc, D = out.shape
    K = inv.shape[0] // T_loc
    n = E_l * C_loc
    out_flat = out.reshape(n, D)
    idx = slot - slot_lo
    mine = keep & (idx >= 0) & (idx < n)
    gathered = jnp.where(mine[:, None], out_flat[jnp.clip(idx, 0, n - 1)], 0)
    contrib = gathered * sorted_p[:, None].astype(out.dtype)
    return contrib[inv].reshape(T_loc, K, D).sum(axis=1, dtype=out.dtype)


def _moe_ffn_sharded(
    params: dict,
    x: jax.Array,
    cfg: ModelConfig,
    rules: Rules,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    from jax.sharding import PartitionSpec as P

    B, S, D = x.shape
    T = B * S
    E, K = cfg.num_experts, cfg.experts_per_token
    G = rules.data_extent
    T_loc = T // G
    C_loc = expert_capacity(T_loc, cfg)
    data = rules.data_axes
    data_ax = data if len(data) > 1 else data[0]

    # BEYOND-PAPER (EXPERIMENTS.md §Perf iteration 2): when E does not
    # divide the model axis (granite: 40 on 16) the expert dim cannot
    # shard, and the fallback TP-on-F contraction all-reduces the [E, C,
    # D] activations every layer (measured 710 GB/device per step).  Pad
    # the *dispatch buffer and weights* — never the router — to the next
    # multiple of the model axis: dead experts receive no tokens and no
    # gradient, and EP's all-to-alls replace the all-reduces.
    e_axes = rules.mapping.get("expert", ())
    e_extent = math.prod(rules.axis_sizes[a] for a in e_axes) if e_axes else 1
    E_pad = E if E % e_extent == 0 else ((E + e_extent - 1) // e_extent) * e_extent

    x = rules.constrain(x, ("batch", None, None))

    # BEYOND-PAPER (EXPERIMENTS.md §Perf iteration 5): the dispatch and
    # combine shard_maps run over the data AND model axes.  Each model
    # rank builds only its own expert slice of the buffer (routing is
    # recomputed per rank — a trivial [T_loc, E] matmul), so the dispatch
    # output is *born* EP-sharded: no replicated boundary, hence no
    # [E_pad, C_loc, D]-sized cotangent psum in the backward.  The
    # combine likewise reduces each rank's expert-slice contribution and
    # psums only the [T_loc, D] result — 24x less boundary traffic than
    # gathering full-E expert outputs per data shard.
    e_ax = (e_axes if len(e_axes) > 1 else e_axes[0]) if e_axes else None
    E_l = E_pad // e_extent

    def dispatch(xs, router):
        # xs: [B/G, S, D] local block; build only this rank's expert slice
        if e_ax is not None:
            m = jax.lax.axis_index(e_ax)
        else:
            m = 0
        buf, (inv, p, slot, keep), (counts, rmean, z) = _local_dispatch(
            xs.reshape(-1, D), router, E, K, C_loc,
            E_buf=E_pad, e_lo=m * E_l, n_slice=E_l,
        )
        # lead shard axes of extent 1 so out_specs can map them
        return (
            buf[None, None],  # [1, 1, E_l, C_loc, D] -> [G, M, E_pad/M...]
            inv[None],
            p[None],
            slot[None],
            keep[None],
            counts[None],
            rmean[None],
            z[None],
        )

    buf, inv, p, slot, keep, counts, rmean, z = jax.shard_map(
        dispatch,
        mesh=rules.mesh,
        in_specs=(P(data_ax, None, None), P(None, None)),
        out_specs=(
            P(data_ax, e_ax, None, None, None),  # [G, M, E_l, C_loc, D]
            P(data_ax, None),
            P(data_ax, None),
            P(data_ax, None),
            P(data_ax, None),
            P(data_ax, None),
            P(data_ax, None),
            P(data_ax),
        ),
        check_vma=False,
    )(x, params["router"])
    buf = buf.reshape(G, E_pad, C_loc, D)  # model-sharded dim stays in place

    # ---- aux losses from per-shard partials ------------------------------
    density = jnp.sum(counts, axis=0).astype(jnp.float32) / T
    router_mean = jnp.mean(rmean, axis=0)
    lb_loss = E * jnp.sum(density * router_mean) / K
    z_loss = jnp.mean(z)

    # ---- expert FFNs under GSPMD -----------------------------------------
    # The buffer keeps its [G, E_pad, C_loc, D] layout and only its
    # SHARDING changes: (data on G) -> (data on G, model on E).  A
    # dim-preserving respec is the pattern GSPMD lowers to a true
    # all-to-all; reshaping [G, E, C, D] -> [E, G*C, D] across the
    # sharded dims instead lowered to full all-gathers (measured 534
    # GB/device — EXPERIMENTS.md §Perf iteration 3).  With E padded to
    # the model-axis extent EP always engages.
    def _pad_e(w):
        if E_pad == E:
            return w
        return jnp.pad(w, ((0, E_pad - E),) + ((0, 0),) * (w.ndim - 1))

    buf = rules.constrain(buf, ("capacity", "expert", None, None))
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, _pad_e(params["w_gate"])))
    h = h * jnp.einsum("gecd,edf->gecf", buf, _pad_e(params["w_up"]))
    h = rules.constrain(h, ("capacity", "expert", None, "mlp"))
    out = jnp.einsum("gecf,efd->gecd", h, _pad_e(params["w_down"]))
    out = rules.constrain(out, ("capacity", "expert", None, None))

    # combine over BOTH axes: each model rank reduces its expert slice's
    # contribution and psums only the [T_loc, D] result (iteration 5)
    def combine(out_s, inv_s, p_s, slot_s, keep_s):
        if e_ax is not None:
            m = jax.lax.axis_index(e_ax)
        else:
            m = 0
        y = _local_combine(
            out_s[0, 0], inv_s[0], p_s[0], slot_s[0], keep_s[0], T_loc,
            slot_lo=m * E_l * C_loc,
        )
        if e_ax is not None:
            y = jax.lax.psum(y, e_ax)
        return y.reshape(1, B // G, S, D)

    y = jax.shard_map(
        combine,
        mesh=rules.mesh,
        in_specs=(
            P(data_ax, e_ax, None, None, None),
            P(data_ax, None),
            P(data_ax, None),
            P(data_ax, None),
            P(data_ax, None),
        ),
        out_specs=P(data_ax, None, None, None),
        check_vma=False,
    )(out.reshape(G, e_extent, E_l, C_loc, D), inv, p, slot, keep)
    y = y.reshape(B, S, D)
    y = rules.constrain(y, ("batch", None, None))
    return y, {"load_balance": lb_loss, "router_z": z_loss}
