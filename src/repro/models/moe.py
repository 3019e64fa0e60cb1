"""Mixture-of-Experts FFN.

TPU-native dispatch: instead of a CUDA-style scatter/gather of individual
tokens (or a dense (tokens × experts × capacity) one-hot, which blows memory
at pod batch sizes), we

  1. route with top-k over router logits,
  2. flatten (token, k) assignments and sort by expert id,
  3. build an (experts, capacity, d_model) dispatch tensor via one scatter of
     *indices* (rank-within-expert < capacity keeps the token, else dropped —
     standard capacity-factor semantics),
  4. run both FFN matmuls as a single batched einsum over experts (MXU
     friendly), and
  5. combine back with the top-k gate weights via one segment-sum scatter.

Sharding: expert weights are (E, D, F). The logical-axis resolver
(params.py) binds E→model when divisible (expert parallelism: granite's 32
experts on a 16-way model axis) and otherwise binds F→model (expert tensor
parallelism: qwen2-moe's 60 experts). Under pjit/GSPMD the einsum then
induces either an all-to-all-free EP pattern or a psum over the model axis.

An optional load-balancing aux loss (Switch-style) is returned for training.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .config import MoECfg
from .params import P
from . import layers


def moe_defs(d: int, mcfg: MoECfg) -> dict:
    e, f = mcfg.num_experts, mcfg.expert_d_ff
    defs = {
        "router": P((d, e), ("embed", None), dtype=jnp.float32),
        "w_gate": P((e, d, f), ("experts", "embed", "expert_mlp")),
        "w_up": P((e, d, f), ("experts", "embed", "expert_mlp")),
        "w_down": P((e, f, d), ("experts", "expert_mlp", "embed")),
    }
    if mcfg.num_shared:
        defs["shared"] = layers.mlp_defs(d, mcfg.shared_d_ff)
        defs["shared_gate"] = P((d, 1), ("embed", None), dtype=jnp.float32)
    return defs


def moe_block_sharded(mcfg: MoECfg, p: dict, x: jax.Array
                      ) -> tuple[jax.Array, jax.Array]:
    """Expert-tensor-parallel MoE under shard_map (§Perf lever).

    Routing, sort and dispatch run *locally* per data shard (the plain-pjit
    version's global token gather otherwise all-gathers every token to every
    device); each device holds all experts with a 1/TP slice of d_ff and the
    partial outputs psum over the model axis — one (N_local, D) bf16
    all-reduce per MoE layer, no dispatch traffic at all.

    Falls back to the einsum path when no mesh is active (CPU tests).
    """
    from jax.sharding import PartitionSpec as PS
    from ..sharding.activation import _active_mesh, batch_axes

    mesh = _active_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return moe_block(mcfg, p, x)
    sizes = dict(mesh.shape)
    bd = []
    prod = 1
    for a in batch_axes():
        if a in sizes and a != "model" \
                and x.shape[0] % (prod * sizes[a]) == 0:
            bd.append(a)
            prod *= sizes[a]
    bd = tuple(bd)   # axes the batch dim actually divides over (may be ())

    def local(x_l, p_l):
        out, aux = moe_block(mcfg, p_l, x_l, psum_axis="model")
        aux = jax.lax.pmean(aux, axis_name="model")
        for a in bd:
            aux = jax.lax.pmean(aux, axis_name=a)
        return out, aux

    p_specs = {"router": PS(None, None),
               "w_gate": PS(None, None, "model"),   # expert-TP on d_ff
               "w_up": PS(None, None, "model"),
               "w_down": PS(None, "model", None)}
    if mcfg.num_shared:
        p_specs["shared"] = {"w_gate": PS(None, "model"),
                             "w_up": PS(None, "model"),
                             "w_down": PS("model", None)}
        p_specs["shared_gate"] = PS(None, None)
    p_in = {k: p[k] for k in p_specs}
    out, aux = jax.shard_map(
        local, mesh=mesh,
        in_specs=(PS(bd, None, None), p_specs),
        out_specs=(PS(bd, None, None), PS()),
        check_vma=False,
    )(x, p_in)
    return out, aux


def moe_block_a2a(mcfg: MoECfg, p: dict, x: jax.Array
                  ) -> tuple[jax.Array, jax.Array]:
    """True expert parallelism with all-to-all dispatch (§Perf lever).

    Requires num_experts % model-axis-size == 0 (granite: 32 % 16). Each
    model shard owns E/16 experts with their FULL d_ff; tokens are routed
    locally, exchanged with one all-to-all (k·cf× activation bytes instead
    of expert-TP's full psum per layer), expert-computed, and a2a'd back.
    Falls back to expert-TP shard_map when indivisible / no mesh.
    """
    from jax.sharding import PartitionSpec as PS
    from ..sharding.activation import _active_mesh, batch_axes

    mesh = _active_mesh()
    if mesh is None or "model" not in mesh.axis_names \
            or mcfg.num_experts % dict(mesh.shape)["model"]:
        return moe_block_sharded(mcfg, p, x)
    sizes = dict(mesh.shape)
    n_shards = sizes["model"]
    e_local = mcfg.num_experts // n_shards
    bd = []
    prod = 1
    for a in batch_axes():
        if a in sizes and a != "model" \
                and x.shape[0] % (prod * sizes[a]) == 0:
            bd.append(a)
            prod *= sizes[a]
    bd = tuple(bd)

    def local(x_l, p_l):
        b, s, d = x_l.shape
        n = b * s
        e, k = mcfg.num_experts, mcfg.top_k
        xt = x_l.reshape(n, d)
        logits = xt.astype(jnp.float32) @ p_l["router"]
        probs = jax.nn.softmax(logits, axis=-1)
        gate, expert_idx = jax.lax.top_k(probs, k)
        gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)
        density = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], e), axis=0)
        aux = e * jnp.sum(density * jnp.mean(probs, axis=0))

        # ---- dispatch to (n_shards, cap) send buffer, sorted by expert --
        cap = int(max(1, round(n * k / e * mcfg.capacity_factor))) * e_local
        flat_e = expert_idx.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
        flat_g = gate.reshape(-1)
        tgt = flat_e // e_local                     # owning shard
        order = jnp.argsort(tgt * e + flat_e)       # group by shard, expert
        se, st, sg, stgt = (flat_e[order], flat_t[order], flat_g[order],
                            tgt[order])
        counts = jnp.bincount(stgt, length=n_shards)
        starts = jnp.concatenate([jnp.zeros(1, counts.dtype),
                                  jnp.cumsum(counts)[:-1]])
        rank = jnp.arange(n * k) - starts[stgt]
        keep = rank < cap
        slot = jnp.where(keep, stgt * cap + rank, n_shards * cap)  # OOB→drop
        send_x = jnp.zeros((n_shards * cap, d), x_l.dtype).at[slot].set(
            xt[st], mode="drop")
        send_e = jnp.full((n_shards * cap,), -1, jnp.int32).at[slot].set(
            se, mode="drop")
        send_x = send_x.reshape(n_shards, cap, d)
        send_e = send_e.reshape(n_shards, cap)

        # ---- exchange: every shard receives the tokens for its experts --
        recv_x = jax.lax.all_to_all(send_x, "model", split_axis=0,
                                    concat_axis=0, tiled=True)
        recv_e = jax.lax.all_to_all(send_e, "model", split_axis=0,
                                    concat_axis=0, tiled=True)
        rx = recv_x.reshape(n_shards * cap, d)
        shard_id = jax.lax.axis_index("model")
        re_local = recv_e.reshape(-1) - shard_id * e_local  # local expert id
        valid = (recv_e.reshape(-1) >= 0)

        # ---- second-level dispatch to the E_local experts --------------
        cap2 = n_shards * cap // e_local
        order2 = jnp.argsort(jnp.where(valid, re_local, e_local))
        se2 = re_local[order2]
        counts2 = jnp.bincount(jnp.where(valid[order2], se2, e_local),
                               length=e_local + 1)[:e_local]
        starts2 = jnp.concatenate([jnp.zeros(1, counts2.dtype),
                                   jnp.cumsum(counts2)[:-1]])
        rank2 = jnp.arange(n_shards * cap) - starts2[jnp.clip(se2, 0,
                                                              e_local - 1)]
        keep2 = (rank2 < cap2) & valid[order2]
        slot2 = jnp.where(
            keep2, jnp.clip(se2, 0, e_local - 1) * cap2 + rank2,
            e_local * cap2)                                    # OOB→drop
        xe = jnp.zeros((e_local * cap2, d), x_l.dtype).at[slot2].set(
            rx[order2], mode="drop")
        xe = xe.reshape(e_local, cap2, d)

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p_l["w_gate"])) \
            * jnp.einsum("ecd,edf->ecf", xe, p_l["w_up"])
        ye = jnp.einsum("ecf,efd->ecd", h, p_l["w_down"])

        # ---- undo second-level dispatch, a2a back, combine --------------
        y_sorted = ye.reshape(e_local * cap2, d)[
            jnp.clip(slot2, 0, e_local * cap2 - 1)] \
            * keep2[:, None].astype(ye.dtype)
        inv2 = jnp.zeros_like(order2).at[order2].set(
            jnp.arange(order2.shape[0]))
        y_recv_layout = y_sorted[inv2]              # matches recv_x layout
        back = jax.lax.all_to_all(
            y_recv_layout.reshape(n_shards, cap, d), "model",
            split_axis=0, concat_axis=0, tiled=True).reshape(-1, d)
        y_slots = back[jnp.clip(slot, 0, n_shards * cap - 1)] \
            * (sg * keep.astype(sg.dtype))[:, None].astype(back.dtype)
        out = jnp.zeros((n, d), y_slots.dtype).at[st].add(y_slots)
        if mcfg.num_shared:
            sgw = jax.nn.sigmoid(xt.astype(jnp.float32) @ p_l["shared_gate"])
            partial = layers.mlp_block(p_l["shared"], xt) * sgw.astype(out.dtype)
            out = out + jax.lax.psum(partial, "model")
        aux = jax.lax.pmean(aux, axis_name="model")
        for a in bd:
            aux = jax.lax.pmean(aux, axis_name=a)
        return out.reshape(b, s, d), aux

    p_specs = {"router": PS(None, None),
               "w_gate": PS("model", None, None),   # experts over model (EP)
               "w_up": PS("model", None, None),
               "w_down": PS("model", None, None)}
    if mcfg.num_shared:
        p_specs["shared"] = {"w_gate": PS(None, "model"),
                             "w_up": PS(None, "model"),
                             "w_down": PS("model", None)}
        p_specs["shared_gate"] = PS(None, None)
    p_in = {k: p[k] for k in p_specs}
    out, aux = jax.shard_map(
        local, mesh=mesh,
        in_specs=(PS(bd, None, None), p_specs),
        out_specs=(PS(bd, None, None), PS()),
        check_vma=False,
    )(x, p_in)
    return out, aux


def moe_block(mcfg: MoECfg, p: dict, x: jax.Array, psum_axis: str | None = None
              ) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, D) → (out (B, S, D), aux_loss scalar)."""
    b, s, d = x.shape
    n = b * s
    e, k = mcfg.num_experts, mcfg.top_k
    xt = x.reshape(n, d)

    logits = (xt.astype(jnp.float32) @ p["router"])          # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert_idx = jax.lax.top_k(probs, k)                # (N, k) each
    gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)

    # Switch-style load-balancing loss.
    density = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], e), axis=0)
    router_mean = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(density * router_mean)

    # ---- sort-based capacity dispatch --------------------------------------
    cap = int(max(1, round(n * k / e * mcfg.capacity_factor)))
    flat_e = expert_idx.reshape(-1)                           # (N*k,)
    flat_t = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)    # token of slot
    flat_g = gate.reshape(-1)
    order = jnp.argsort(flat_e)                               # stable
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    # rank of each sorted slot within its expert group
    offsets = jnp.cumsum(jnp.bincount(se, length=e))          # (E,)
    starts = jnp.concatenate([jnp.zeros(1, offsets.dtype), offsets[:-1]])
    rank = jnp.arange(n * k) - starts[se]
    keep = rank < cap
    # dropped rows write out-of-bounds (mode="drop" discards them) so they
    # can never collide with a valid rank-0 slot
    slot = jnp.where(keep, se * cap + rank, e * cap)

    # dispatch indices: which token fills each (expert, capacity) slot
    token_for_slot = jnp.zeros(e * cap, jnp.int32).at[slot].set(
        st, mode="drop")
    filled = jnp.zeros(e * cap, bool).at[slot].set(keep, mode="drop")
    xe = xt[token_for_slot].reshape(e, cap, d)
    xe = xe * filled.reshape(e, cap, 1).astype(xe.dtype)

    # ---- expert FFN as batched einsum ---------------------------------------
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])) \
        * jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_down"])            # (E, C, D)

    # ---- combine -------------------------------------------------------------
    # gather each kept slot's output and scatter-add into its token
    y_slots = ye.reshape(e * cap, d)[jnp.clip(slot, 0, e * cap - 1)]
    y_slots = y_slots * (sg * keep.astype(sg.dtype))[:, None].astype(y_slots.dtype)
    out = jnp.zeros((n, d), y_slots.dtype).at[st].add(y_slots)

    if mcfg.num_shared:
        sg_w = jax.nn.sigmoid(xt.astype(jnp.float32) @ p["shared_gate"])
        out = out + (layers.mlp_block(p["shared"], xt)
                     * sg_w.astype(out.dtype))
    if psum_axis is not None:
        # expert-TP: routed+shared outputs are partial over the d_ff shards
        out = jax.lax.psum(out, psum_axis)
    return out.reshape(b, s, d), aux
