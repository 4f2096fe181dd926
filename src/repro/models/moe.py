"""Mixture-of-Experts: top-k routing, capacity-based dispatch, shared experts.

Dispatch uses the scatter formulation (no (T, E, C) one-hot, no sort): per
routing choice, position-in-expert comes from a (T, E) cumsum; tokens scatter
into (E·C, d) slot buffers and gather back with their gate weights.  Expert
FFNs run as stacked einsums over the expert dimension, which shards over the
`model` mesh axis (expert parallelism) — under GSPMD the scatter/gather turn
into the MoE all-to-alls, visible in the roofline's collective term.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..sharding import with_logical_constraint as wlc
from .config import ModelConfig, MoEConfig
from .layers import Params, dense_init, mlp, mlp_init


def moe_init(key, cfg: ModelConfig, dtype):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 8)
    mult_names = ["wi", "wg", "wo"] if cfg.mlp == "swiglu" else ["wi", "wo"]
    p: Params = {}
    a: Params = {}
    p["router"], a["router"] = dense_init(ks[0], d, m.num_experts, None, None,
                                          dtype)
    # stacked expert weights: (E, d, ff) / (E, ff, d)
    std_in = 1.0 / math.sqrt(d)
    std_out = 1.0 / math.sqrt(m.d_ff)
    shapes = {"wi": (m.num_experts, d, m.d_ff),
              "wg": (m.num_experts, d, m.d_ff),
              "wo": (m.num_experts, m.d_ff, d)}
    axes = {"wi": ("experts", "fsdp", "expert_ffn"),
            "wg": ("experts", "fsdp", "expert_ffn"),
            "wo": ("experts", "expert_ffn", "fsdp")}
    for i, name in enumerate(mult_names):
        std = std_out if name == "wo" else std_in
        w = jax.random.normal(ks[1 + i], shapes[name], jnp.float32) * std
        p[name] = w.astype(dtype)
        a[name] = axes[name]
    if m.num_shared_experts:
        p["shared"], a["shared"] = mlp_init(
            ks[6], d, m.num_shared_experts * m.shared_d_ff, cfg.mlp, dtype)
    return p, a


def _expert_ffn(p: Params, x: jnp.ndarray, kind: str) -> jnp.ndarray:
    """x: (E, C, d) → (E, C, d) with per-expert weights."""
    if kind == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x, p["wg"].astype(x.dtype)))
        h = h * jnp.einsum("ecd,edf->ecf", x, p["wi"].astype(x.dtype))
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", x, p["wi"].astype(x.dtype)))
    h = wlc(h, ("experts", None, "expert_ffn"))
    return jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(x.dtype))


def moe_apply(p: Params, cfg: ModelConfig, x: jnp.ndarray
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) → (y, aux_loss).

    Two paths:

    * **on-mesh (production)**: explicit expert parallelism under shard_map.
      Experts shard over `model`; activations are replicated across `model`
      (d_model is unsharded), so each shard selects the tokens routed to its
      own experts locally — no all-to-all for dispatch — runs its expert
      FFNs, and a single `psum` over `model` combines expert contributions
      (it fuses with the TP output reduction).  Capacity is applied per
      (data-shard, expert).  This exists because both GSPMD-auto
      formulations failed at scale: scatter-of-activations replicated an
      (E·C, d) buffer (+311 GB/dev all-reduce), gather-from-sharded-source
      replicated the expert buffer (520 GB/dev temps) — EXPERIMENTS.md
      §Perf logs the progression.
    * **off-mesh (host tests)**: the same math, single shard.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.empty and "model" in mesh.axis_names:
        return _moe_sharded(p, cfg, x, mesh)
    return _moe_global(p, cfg, x)


def _moe_global(p: Params, cfg: ModelConfig, x: jnp.ndarray
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    xf = x.reshape(T, d)
    E, k = m.num_experts, m.top_k
    cap = max(1, int(m.capacity_factor * T * k / E))

    logits = xf.astype(jnp.float32) @ p["router"]["w"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                     # (T, E)
    gate_vals, choices = jax.lax.top_k(probs, k)                # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)       # renormalize

    # ---- sort-based slot assignment (indices only) ----------------------
    flat_e = choices.reshape(T * k)                             # expert ids
    flat_tok = jnp.arange(T * k, dtype=jnp.int32) // k          # token ids
    order = jnp.argsort(flat_e, stable=True)                    # group by e
    sorted_e = flat_e[order]
    counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)       # (E,)
    starts = jnp.cumsum(counts) - counts                        # exclusive
    pos_sorted = jnp.arange(T * k, dtype=jnp.int32) - starts[sorted_e]
    keep_sorted = pos_sorted < cap
    slot_sorted = sorted_e * cap + jnp.minimum(pos_sorted, cap - 1)
    # slot -> token map (pad slots point at the zero row T)
    slot_tok = jnp.full((E * cap,), T, jnp.int32)
    slot_tok = slot_tok.at[slot_sorted].set(
        jnp.where(keep_sorted, flat_tok[order], T))

    # ---- dispatch (gather), expert FFN, combine (gather) -----------------
    x_pad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
    expert_in = x_pad[slot_tok].reshape(E, cap, d)
    expert_in = wlc(expert_in, ("experts", "fsdp", None))
    expert_out = _expert_ffn(p, expert_in, cfg.mlp)
    expert_out = wlc(expert_out, ("experts", "fsdp", None))
    expert_out = expert_out.reshape(E * cap, d)

    # inverse permutation: flat entry -> its sorted position
    inv = jnp.zeros((T * k,), jnp.int32).at[order].set(
        jnp.arange(T * k, dtype=jnp.int32))
    pos = pos_sorted[inv]                                       # (T*k,)
    keep = (pos < cap).reshape(T, k)
    slot = (flat_e * cap + jnp.minimum(pos, cap - 1)).reshape(T, k)
    y = jnp.zeros_like(xf)
    for i in range(k):  # k gathers of (T, d), accumulated in place
        contrib = expert_out[slot[:, i]]
        w = (gate_vals[:, i] * keep[:, i]).astype(x.dtype)
        y = y + contrib * w[:, None]
    if m.num_shared_experts:
        y = y + mlp(p["shared"], xf, cfg.mlp)

    # load-balancing aux loss (Switch-style)
    frac_tokens = counts.astype(jnp.float32) / jnp.float32(T * k)
    frac_probs = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs) * m.router_aux_weight
    return y.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# explicit-EP path (shard_map)
# ---------------------------------------------------------------------------


def _local_expert_pass(p: Params, cfg: ModelConfig, xf: jnp.ndarray,
                       gate_vals: jnp.ndarray, choices: jnp.ndarray,
                       e_lo: jnp.ndarray, E_local: int) -> jnp.ndarray:
    """Dispatch the local tokens routed to experts [e_lo, e_lo+E_local),
    run the local expert FFNs, combine with gates.  All-local; the caller
    psums across the expert axis."""
    m = cfg.moe
    T, d = xf.shape
    k = m.top_k
    cap = max(1, int(m.capacity_factor * T * k / m.num_experts))

    flat_e = choices.reshape(T * k) - e_lo          # local expert ids
    local = (flat_e >= 0) & (flat_e < E_local)
    flat_e = jnp.where(local, flat_e, E_local)      # E_local = overflow bin
    flat_tok = jnp.arange(T * k, dtype=jnp.int32) // k
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.zeros((E_local + 1,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts
    pos_sorted = jnp.arange(T * k, dtype=jnp.int32) - starts[sorted_e]
    keep_sorted = (pos_sorted < cap) & (sorted_e < E_local)
    slot_sorted = jnp.where(
        keep_sorted, sorted_e * cap + jnp.minimum(pos_sorted, cap - 1),
        E_local * cap)                              # trash slot
    slot_tok = jnp.full((E_local * cap + 1,), T, jnp.int32)
    slot_tok = slot_tok.at[slot_sorted].set(
        jnp.where(keep_sorted, flat_tok[order], T))
    slot_tok = slot_tok[:E_local * cap]

    x_pad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
    expert_in = x_pad[slot_tok].reshape(E_local, cap, d)
    expert_out = _expert_ffn(p, expert_in, cfg.mlp).reshape(E_local * cap, d)
    expert_out = jnp.concatenate(
        [expert_out, jnp.zeros((1, d), expert_out.dtype)], axis=0)

    # combine: inverse permutation → slot per (token, choice)
    inv = jnp.zeros((T * k,), jnp.int32).at[order].set(
        jnp.arange(T * k, dtype=jnp.int32))
    pos = pos_sorted[inv]
    kept = (pos < cap) & local
    slot = jnp.where(kept,
                     flat_e * cap + jnp.minimum(pos, cap - 1),
                     E_local * cap)
    slot2 = slot.reshape(T, k)
    kept2 = kept.reshape(T, k)
    y = jnp.zeros_like(xf)
    for i in range(k):
        contrib = expert_out[slot2[:, i]]
        w = (gate_vals[:, i] * kept2[:, i]).astype(xf.dtype)
        y = y + contrib * w[:, None]
    return y


# Below this many global tokens (decode / small serving batches), moving
# weights is absurd: regathering fsdp-sharded expert weights costs GBs per
# layer while the token activations are MBs.  The decode path keeps weights
# stationary (E over `model`, d_model over `data`), replicates the tokens,
# contracts each device's d-slice and psums the partial hiddens over `data`
# (§Perf Track 1b).
_TOKEN_STATIONARY_MAX = 512


def _moe_sharded(p: Params, cfg: ModelConfig, x: jnp.ndarray, mesh
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    E = m.num_experts
    names = mesh.axis_names
    sizes = dict(mesh.shape)
    batch_axes = tuple(a for a in ("pod", "data") if a in names)
    bsz = 1
    for a in batch_axes:
        bsz *= sizes[a]
    B = x.shape[0]
    if B % bsz != 0:
        batch_axes = tuple(a for a in batch_axes
                           if B % sizes[a] == 0)[:1]  # degrade gracefully
    model_size = sizes["model"]
    if E % model_size != 0:
        return _moe_global(p, cfg, x)
    E_local = E // model_size

    T_global = B * x.shape[1]
    if (T_global <= _TOKEN_STATIONARY_MAX and cfg.mlp == "swiglu"
            and "data" in names and cfg.d_model % sizes["data"] == 0):
        return _moe_decode_stationary(p, cfg, x, mesh)

    # per-leaf param specs: expert weights sharded over `model`, rest repl.
    def pspec(path_leaf):
        name, leaf = path_leaf
        if name in ("wi", "wg", "wo"):
            return P("model", None, None)
        return P(*(None,) * leaf.ndim)

    p_specs = {}
    for name, sub in p.items():
        if name in ("wi", "wg", "wo"):
            p_specs[name] = P("model", None, None)
        elif isinstance(sub, dict):
            p_specs[name] = jax.tree.map(lambda l: P(*(None,) * l.ndim), sub)
        else:
            p_specs[name] = P(*(None,) * sub.ndim)

    x_spec = P(batch_axes if batch_axes else None, None, None)

    def body(p_local, x_local):
        Bl, S, d = x_local.shape
        xf = x_local.reshape(Bl * S, d)
        logits = xf.astype(jnp.float32) @ p_local["router"]["w"].astype(
            jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, choices = jax.lax.top_k(probs, m.top_k)
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

        midx = jax.lax.axis_index("model")
        e_lo = midx * E_local
        y = _local_expert_pass(p_local, cfg, xf, gate_vals, choices,
                               e_lo, E_local)
        # combine expert contributions living on other model shards
        y = jax.lax.psum(y, "model")
        if m.num_shared_experts:
            y = y + mlp(p_local["shared"], xf, cfg.mlp)

        counts = jnp.sum(jax.nn.one_hot(choices, E, dtype=jnp.float32),
                         axis=(0, 1))
        frac_tokens = counts / jnp.float32(xf.shape[0] * m.top_k)
        frac_probs = jnp.mean(probs, axis=0)
        aux = E * jnp.sum(frac_tokens * frac_probs) * m.router_aux_weight
        if batch_axes:
            aux = jax.lax.pmean(aux, batch_axes)
        return y.reshape(Bl, S, d), aux

    y, aux = jax.shard_map(
        body, mesh=mesh, in_specs=(p_specs, x_spec),
        out_specs=(x_spec, P()), check_vma=False,
    )(p, x)
    return y, aux


def _moe_decode_stationary(p: Params, cfg: ModelConfig, x: jnp.ndarray, mesh
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Weights-stationary decode MoE: tokens replicate (MBs), weights never
    move.  Each (data_i, model_j) device holds experts j·E_l..(j+1)·E_l with
    the d_model dim sharded over `data`; it contracts its d-slice for ALL
    tokens routed to its experts and the partial hiddens psum over `data`.
    wo runs d-sharded the other way and the output reduce-scatters back to
    the callers' batch sharding via a final psum over `model` + slice."""
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    E, k = m.num_experts, m.top_k
    sizes = dict(mesh.shape)
    model_size = sizes["model"]
    data_size = sizes["data"]
    E_local = E // model_size
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    p_specs = {}
    for name, sub in p.items():
        if name in ("wi", "wg"):
            p_specs[name] = P("model", "data", None)   # stationary: d over data
        elif name == "wo":
            p_specs[name] = P("model", None, "data")
        elif isinstance(sub, dict):
            p_specs[name] = jax.tree.map(lambda l: P(*(None,) * l.ndim), sub)
        else:
            p_specs[name] = P(*(None,) * sub.ndim)

    def body(p_local, x_full):
        Bf, S, d = x_full.shape            # tokens fully replicated
        T = Bf * S
        xf = x_full.reshape(T, d)
        logits = xf.astype(jnp.float32) @ p_local["router"]["w"].astype(
            jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, choices = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

        midx = jax.lax.axis_index("model")
        didx = jax.lax.axis_index("data")
        e_lo = midx * E_local
        d_sh = d // jax.lax.psum(1, "data") if False else d // data_size
        # dense per-expert token masks (T small): (E_local, T) gate weights
        w_et = jnp.zeros((E_local, T), jnp.float32)
        for i in range(k):
            onehot = jax.nn.one_hot(choices[:, i] - e_lo, E_local,
                                    dtype=jnp.float32)          # (T, E_l)
            w_et = w_et + onehot.T * gate_vals[:, i][None, :]
        # local d-slice of tokens
        x_slice = jax.lax.dynamic_slice_in_dim(xf, didx * d_sh, d_sh, 1)
        # partial hidden for every (expert, token): contract local d-slice
        hg = jnp.einsum("td,edf->etf", x_slice.astype(p_local["wg"].dtype),
                        p_local["wg"])                           # (E_l,T,f)
        hi = jnp.einsum("td,edf->etf", x_slice.astype(p_local["wi"].dtype),
                        p_local["wi"])
        hg = jax.lax.psum(hg, "data")      # complete the d contraction
        hi = jax.lax.psum(hi, "data")
        h = jax.nn.silu(hg) * hi
        # wo: back to a d-slice, weighted by gates; psum over model combines
        # experts, then gather d-slices across data
        y_slice = jnp.einsum("etf,efd,et->td", h, p_local["wo"],
                             w_et.astype(h.dtype))               # (T, d_sh)
        y_slice = jax.lax.psum(y_slice, "model")
        y = jax.lax.all_gather(y_slice, "data", axis=1, tiled=True)  # (T, d)
        if m.num_shared_experts:
            y = y + mlp(p_local["shared"], xf, cfg.mlp)
        counts = jnp.sum(jax.nn.one_hot(choices, E, dtype=jnp.float32),
                         axis=(0, 1))
        frac_tokens = counts / jnp.float32(T * k)
        frac_probs = jnp.mean(probs, axis=0)
        aux = E * jnp.sum(frac_tokens * frac_probs) * m.router_aux_weight
        # return only this shard's batch slice (out_specs re-shards)
        y = y.reshape(Bf, S, d)
        if batch_axes:
            n_b = 1
            for a in batch_axes:
                n_b *= sizes[a]
            if Bf % n_b == 0:
                bidx = jax.lax.axis_index(batch_axes[0]) if len(batch_axes) == 1                     else (jax.lax.axis_index(batch_axes[0]) * sizes[batch_axes[1]]
                          + jax.lax.axis_index(batch_axes[1]))
                y = jax.lax.dynamic_slice_in_dim(y, bidx * (Bf // n_b),
                                                 Bf // n_b, 0)
        return y, aux

    x_spec = P(batch_axes if batch_axes else None, None, None)
    B = x.shape[0]
    n_b = 1
    for a in batch_axes:
        n_b *= sizes[a]
    out_spec = x_spec if (batch_axes and B % n_b == 0) else P(None, None, None)
    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(p_specs, P(None, None, None)),   # tokens replicated
        out_specs=(out_spec, P()), check_vma=False,
    )(p, x)
    return y, aux
