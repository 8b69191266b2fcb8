"""Mixture-of-Experts layer with sort-based capacity dispatch (EP-shardable).

Router top-k: the *distributed* top-k over a sharded expert axis uses the
paper's local-Selection-Sort + global-merge scheme (core/topk.py — see
DESIGN.md §2). Inside a jit'd step, top-k over the replicated router logits is
mathematically identical, and GSPMD partitions it; tests/test_core_topk.py
proves the local+global merge equals the plain top-k bit-exactly.

Dispatch: megablocks-style sort-based placement with static capacity
(C = ceil(T·k/E·cf)) so the expert matmuls are true (E, C, d)×(E, d, f)
batched GEMMs — expert FLOPs ≈ 2·T·k·d·f, with no switch-style dense
dispatch einsum inflating the compute roofline term.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map

from repro.configs.base import ModelConfig
from repro.models.layers import dense_init, mlp_is_gated

CAPACITY_FACTOR = 1.25


def init_moe(key, cfg: ModelConfig):
    m = cfg.moe
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    E, d, f = m.num_experts, cfg.d_model, m.d_ff_expert

    def ew(k, a, b):
        keys = jax.random.split(k, E)
        return jnp.stack([dense_init(kk, a, b, dt) for kk in keys])

    params = {
        "router": dense_init(ks[0], d, E, jnp.float32, scale=0.02),
        "w_in": ew(ks[1], d, f),
        "w_out": ew(ks[2], f, d),
    }
    if mlp_is_gated(cfg.mlp_type):
        params["w_gate"] = ew(ks[3], d, f)
    return params


def moe_logical(cfg: ModelConfig):
    lg = {
        "router": ("embed", "experts"),
        "w_in": ("experts", "embed", "mlp"),
        "w_out": ("experts", "mlp", "embed"),
    }
    if mlp_is_gated(cfg.mlp_type):
        lg["w_gate"] = ("experts", "embed", "mlp")
    return lg


DROPLESS_THRESHOLD = 1024  # below this token count, run fully dropless


def capacity(tokens: int, cfg: ModelConfig) -> int:
    """Static per-expert capacity.

    Capacity-based dropping is not prefix-causal (a later token can displace
    an earlier token's slot), which would make prefill(S) disagree with
    forward(S+k) prefixes. Small token counts (decode steps, small-batch
    serving) therefore run DROPLESS (C = T*k covers the worst-case skew);
    large training/prefill batches use the standard capacity factor.
    """
    m = cfg.moe
    if tokens <= DROPLESS_THRESHOLD:
        return max(8, -(-tokens * m.top_k // 8) * 8)
    c = int(math.ceil(tokens * m.top_k / m.num_experts * CAPACITY_FACTOR))
    return max(8, -(-c // 8) * 8)  # round up to 8 for TPU lane alignment


def route(params, x, cfg: ModelConfig):
    """Router: logits -> (weights (T,k), expert_ids (T,k), aux_loss).

    The router matmul reads x in its storage dtype and accumulates in f32 —
    casting x itself to f32 would materialise an f32 copy of the whole token
    stream every MoE layer (measured: ~30% of step bytes, §Perf iter 3)."""
    m = cfg.moe
    logits = jnp.einsum("td,de->te", x, params["router"].astype(x.dtype),
                        preferred_element_type=jnp.float32)      # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, m.top_k)                 # (T, k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    # load-balance auxiliary loss (Switch-style): E * sum(f_e * p_e)
    T = x.shape[0]
    dispatch_frac = jnp.zeros((m.num_experts,), jnp.float32).at[
        ids.reshape(-1)].add(1.0) / (T * m.top_k)
    mean_prob = jnp.mean(probs, axis=0)
    aux = m.num_experts * jnp.sum(dispatch_frac * mean_prob)
    return weights, ids, aux


def _ranks_static(e_flat, num_experts: int):
    """Rank of each assignment within its expert, via one stable argsort.

    This is the paper's partial-sort insight at the framework level: we never
    need a full per-expert sort, only stable positions — O(A log A) total,
    all static shapes (jit-safe).
    """
    A = e_flat.shape[0]
    order = jnp.argsort(e_flat, stable=True)
    sorted_e = e_flat[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(num_experts), side="left")
    rank_sorted = jnp.arange(A) - starts[sorted_e]
    return jnp.zeros((A,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))


def _expert_ffn(params, xe, cfg: ModelConfig):
    """Batched expert GEMMs. xe: (E?, C, d) with matching weight slices."""
    h = jnp.einsum("ecd,edf->ecf", xe, params["w_in"])
    if cfg.mlp_type == "swiglu":
        g = jnp.einsum("ecd,edf->ecf", xe, params["w_gate"])
        h = jax.nn.silu(g) * h
    elif cfg.mlp_type == "geglu":
        g = jnp.einsum("ecd,edf->ecf", xe, params["w_gate"])
        h = jax.nn.gelu(g, approximate=True) * h
    elif cfg.mlp_type == "squared_relu":
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.gelu(h, approximate=True)
    return jnp.einsum("ecf,efd->ecd", h, params["w_out"])


def _dispatch_compute_combine(params, x, cfg: ModelConfig, *, e_base: int,
                              e_local: int, C: int):
    """Route + dispatch + expert FFN + weighted combine over the expert
    range [e_base, e_base + e_local). Pure function of LOCAL tokens — the
    paper's OP1 (each worker computes partial results for its slice).
    """
    m = cfg.moe
    T, d = x.shape
    k = m.top_k
    weights, ids, aux = route(params, x, cfg)
    e_flat = ids.reshape(-1)                                     # (T*k,)
    ranks = _ranks_static(e_flat, m.num_experts)                 # (T*k,)
    mine = (e_flat >= e_base) & (e_flat < e_base + e_local)
    keep = mine & (ranks < C)
    slot = jnp.where(keep, (e_flat - e_base) * C + ranks, e_local * C)

    # SLOT-SPACE dispatch/combine: all (token-count)-sized tensors here are
    # index/weight VECTORS; the only (.., d)-sized tensors are the expert
    # buffers (E_loc*C rows). Materialising x[tok_idx] per assignment would
    # stream T*k*d elements per layer (k=8 for qwen3) — measured as ~25% of
    # step bytes before this formulation (§Perf iter 4).
    n_slots = e_local * C
    tok_idx = jnp.repeat(jnp.arange(T), k)                       # (T*k,) i32
    inv_tok = jnp.full((n_slots + 1,), T, jnp.int32).at[slot].set(
        tok_idx, mode="drop")[:n_slots]                          # slot->token
    w_slot = jnp.zeros((n_slots + 1,), jnp.float32).at[slot].set(
        weights.reshape(-1), mode="drop")[:n_slots]              # slot->weight

    x_pad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])     # sentinel row
    buf = x_pad[inv_tok]                                         # (E_loc*C, d)
    ye = _expert_ffn(params, buf.reshape(e_local, C, d),
                     cfg).reshape(n_slots, d)

    contrib = ye * w_slot[:, None].astype(ye.dtype)
    y = jnp.zeros((T, d), ye.dtype).at[inv_tok].add(contrib, mode="drop")
    return y.astype(x.dtype), aux


def apply_moe(params, x, cfg: ModelConfig):
    """Dense-XLA path: x (T, d_model) -> (T, d_model), aux. T static."""
    C = capacity(x.shape[0], cfg)
    return _dispatch_compute_combine(params, x, cfg, e_base=0,
                                     e_local=cfg.moe.num_experts, C=C)


def apply_moe_two_phase(params, x, cfg: ModelConfig, plan):
    """The paper's two-phase scheme at production scale (DESIGN.md §2/§5).

    Activations are replicated over the model axis and experts are sharded
    over it, so each model shard can dispatch its LOCAL tokens to its LOCAL
    experts with zero collectives (OP1 = local dispatch+GEMM+combine into a
    partial y), and the only communication is the psum of the partial
    outputs (OP2) — the same single all-reduce a dense TP MLP pays. GSPMD
    cannot discover this schedule on its own (data-dependent scatter indices
    force it to all-gather the token buffer; see EXPERIMENTS.md §Perf).

    x: (T, d) with T sharded over plan.dp_axes. Router weights replicated.
    """
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    model_n = plan.mesh.shape[plan.model_axis]
    assert m.num_experts % model_n == 0, (m.num_experts, model_n)
    e_local = m.num_experts // model_n
    T = x.shape[0]
    # tiny/long-context batches (e.g. long_500k, T=1) can't shard over dp:
    # run token-replicated, experts still sharded
    dp_axes = plan.dp_axes if T % plan.dp_total == 0 else ()
    T_loc = T // plan.dp_total if dp_axes else T
    C = capacity(T_loc, cfg)
    gated = "w_gate" in params

    def local(x_loc, *weights):
        j = jax.lax.axis_index(plan.model_axis)
        if gated:
            router, w_in, w_gate, w_out = weights
            p = {"router": router, "w_in": w_in, "w_gate": w_gate,
                 "w_out": w_out}
        else:
            router, w_in, w_out = weights
            p = {"router": router, "w_in": w_in, "w_out": w_out}
        y_part, aux = _dispatch_compute_combine(
            p, x_loc, cfg, e_base=j * e_local, e_local=e_local, C=C)
        y = jax.lax.psum(y_part, plan.model_axis)        # OP2: global combine
        aux = jax.lax.pmean(aux, plan.model_axis)
        for a in dp_axes:
            aux = jax.lax.pmean(aux, a)
        return y, aux

    if dp_axes:
        dp = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    else:
        dp = None
    ax = plan.model_axis
    args = [x, params["router"], params["w_in"]]
    specs = [P(dp), P(), P(ax)]
    if gated:
        args.append(params["w_gate"])
        specs.append(P(ax))
    args.append(params["w_out"])
    specs.append(P(ax))
    fn = _shard_map(
        local,
        mesh=plan.mesh,
        in_specs=tuple(specs),
        out_specs=(P(dp), P()),
        check_vma=False,
    )
    return fn(*args)
