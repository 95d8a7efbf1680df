"""Mixture-of-Experts: top-k router + two dispatch implementations.

* ``impl="dense"`` — every expert runs on every token, outputs combined
  by gate weights. Exact (no token dropping), FLOP-inflated by E/k; used
  by the reduced smoke configs where E <= 4.

* ``impl="dropping"`` — GShard/Switch-style capacity-bounded dispatch,
  built with sort + scatter instead of the (tokens, E, C) one-hot einsum
  (which is memory-infeasible at qwen3's 128 experts). Tokens above an
  expert's capacity are dropped (their residual passes through — standard
  behaviour). The (E, C, d) dispatch buffer carries a sharding constraint
  so experts split over the 'model' mesh axis (expert parallelism) and
  XLA materializes the dispatch as the all-to-all the roofline pass then
  measures.

Router aux loss follows Switch Transformer: E * sum_e f_e * p_e, where
f_e is the fraction of tokens whose top-1 choice is e and p_e the mean
router probability of e.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, MoEConfig
from repro.models import layers


def init_moe(key, cfg: ArchConfig, dtype=jnp.float32) -> Dict:
    m = cfg.moe
    d = cfg.d_model
    k_router, k_experts = jax.random.split(key)
    ks = jax.random.split(k_experts, 3)
    return {
        "router": layers._dense_init(k_router, (d, m.num_experts), dtype),
        # experts stacked on a leading E axis -> shardable over 'model'
        "w_gate": layers._dense_init(ks[0], (m.num_experts, d, m.d_ff), dtype),
        "w_up": layers._dense_init(ks[1], (m.num_experts, d, m.d_ff), dtype),
        "w_down": layers._dense_init(ks[2], (m.num_experts, m.d_ff, d), dtype),
    }


def _router(params, m: MoEConfig, x2d: jnp.ndarray):
    """x2d (T, d) -> (gates (T, k), idx (T, k), aux_loss)."""
    logits = x2d.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    gates, idx = jax.lax.top_k(probs, m.experts_per_token)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    # Switch-style load balance loss
    e = m.num_experts
    top1 = idx[:, 0]
    f = jnp.mean(jax.nn.one_hot(top1, e, dtype=jnp.float32), axis=0)
    p = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f * p)
    return gates, idx, aux


def _expert_ffn(params, h: jnp.ndarray, kind: str) -> jnp.ndarray:
    """h (E, C, d) through per-expert gated MLPs -> (E, C, d)."""
    gate = jnp.einsum("ecd,edf->ecf", h, params["w_gate"])
    up = jnp.einsum("ecd,edf->ecf", h, params["w_up"])
    act = jax.nn.silu(gate) if kind == "swiglu" else jax.nn.gelu(gate, approximate=True)
    return jnp.einsum("ecf,efd->ecd", act * up, params["w_down"])


def moe_forward(
    params: Dict,
    cfg: ArchConfig,
    x: jnp.ndarray,  # (B, S, d)
    shard=None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y (B,S,d), aux_loss scalar). ``shard`` is the launcher's
    with_sharding_constraint hook — the dispatch buffer MUST be pinned to
    the batch sharding or GSPMD replicates it across the data axis
    (measured: +21 GiB/layer/device on mixtral train_4k)."""
    if shard is None:
        shard = lambda t, name: t
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    x2d = x.reshape(t, d)
    gates, idx, aux = _router(params, m, x2d)

    if m.impl == "dense":
        # (E, T, d) all-experts compute, exact combine
        h = jnp.einsum("td,edf->etf", x2d, params["w_gate"])
        up = jnp.einsum("td,edf->etf", x2d, params["w_up"])
        act = jax.nn.silu(h) if cfg.mlp == "swiglu" else jax.nn.gelu(h, approximate=True)
        y_all = jnp.einsum("etf,efd->etd", act * up, params["w_down"])  # (E,T,d)
        combine = jnp.zeros((t, m.num_experts), jnp.float32)
        combine = combine.at[
            jnp.arange(t)[:, None], idx
        ].add(gates)
        y = jnp.einsum("te,etd->td", combine.astype(x.dtype), y_all)
        return y.reshape(b, s, d), aux

    # ---- dropping / expert-parallel dispatch (batch-local) ----
    # §Perf iteration 2: the original implementation flattened (B, S) and
    # sorted GLOBALLY, which forced cross-data-shard sort/scatter
    # collectives (402 s of collective time per qwen3 train step). This
    # version keeps the batch dim leading and vmaps the sort/scatter per
    # row: with batch sharded over (pod, data), every dispatch index is
    # local to its shard; the only inter-shard traffic left is the
    # expert-output combine, which is O(B*S*d) instead of O(E*C*d*k).
    # Capacity is enforced per row (standard per-shard capacity
    # semantics; the smoke tests verify equality with `dense` whenever
    # the capacity factor is ample).
    k = m.experts_per_token
    e = m.num_experts
    sk = s * k
    capacity = max(1, int(-(-sk * m.capacity_factor // e)))  # ceil, static

    idx_rows = idx.reshape(b, sk)
    gate_rows = gates.reshape(b, sk)

    def dispatch_row(x_row, eid, gate):
        # x_row (S, d); eid/gate (S*k,)
        order = jnp.argsort(eid, stable=True)
        e_sorted = eid[order]
        tok_sorted = order // k
        gate_sorted = gate[order]
        counts = jnp.bincount(e_sorted, length=e)
        starts = jnp.concatenate(
            [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]]
        )
        pos = jnp.arange(sk) - starts[e_sorted]
        keep = pos < capacity
        safe_pos = jnp.where(keep, pos, 0)
        rows = x_row[tok_sorted] * keep[:, None].astype(x_row.dtype)
        buf = jnp.zeros((e, capacity, d), x_row.dtype)
        buf = buf.at[e_sorted, safe_pos].add(rows)
        return buf, (e_sorted, safe_pos, keep, tok_sorted, gate_sorted)

    buf, meta = jax.vmap(dispatch_row)(x, idx_rows, gate_rows)  # (B,E,C,d)
    buf = shard(buf, "moe_buf")

    mesh = getattr(shard, "mesh", None)
    model_size = 1
    if mesh is not None:
        model_size = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)

    if mesh is not None and model_size > 1 and e % model_size == 0:
        # §Perf iteration 2b: expert-parallel compute + combine under
        # shard_map. Without it, the combine gather from the E-sharded
        # y_buf makes GSPMD all-gather the full (E, C, d) buffer per row
        # (~385 GB/step on qwen3 train_4k). Inside shard_map each model
        # shard processes ONLY its local experts and scatter-adds their
        # token outputs; the combine becomes a psum of (B, S, d).
        y = _expert_combine_shardmap(params, cfg, mesh, buf, meta, s, d, capacity)
        return shard(y, "activation"), aux

    gate_w = jnp.einsum("becd,edf->becf", buf, params["w_gate"])
    up = jnp.einsum("becd,edf->becf", buf, params["w_up"])
    act = (
        jax.nn.silu(gate_w)
        if cfg.mlp == "swiglu"
        else jax.nn.gelu(gate_w, approximate=True)
    )
    y_buf = jnp.einsum("becf,efd->becd", act * up, params["w_down"])
    y_buf = shard(y_buf, "moe_buf")

    def combine_row(y_b, meta_row):
        e_sorted, safe_pos, keep, tok_sorted, gate_sorted = meta_row
        rows = y_b[e_sorted, safe_pos] * (
            gate_sorted * keep.astype(jnp.float32)
        ).astype(y_b.dtype)[:, None]
        return jnp.zeros((s, d), y_b.dtype).at[tok_sorted].add(rows)

    y = jax.vmap(combine_row)(y_buf, meta)  # (B, S, d)
    return shard(y, "activation"), aux


def _expert_combine_shardmap(params, cfg, mesh, buf, meta, s, d, capacity):
    """Expert FFN + combine with experts sharded over 'model'.

    buf  (B, E, C, d) — batch over (pod, data), E over model.
    meta — per-row dispatch indices (replicated over model).
    Returns y (B, S, d) batch-sharded, replicated over model.
    """
    from jax.sharding import PartitionSpec as P

    baxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    e = cfg.moe.num_experts
    model_size = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    e_local = e // model_size
    kind = cfg.mlp

    def body(w_gate, w_up, w_down, buf_l, e_sorted, safe_pos, keep, tok_sorted, gate_sorted):
        # w_* (E_local, ...); buf_l (B_l, E_local, C, d); meta (B_l, S*k)
        shard_idx = jax.lax.axis_index("model")
        gate_w = jnp.einsum("becd,edf->becf", buf_l, w_gate)
        up = jnp.einsum("becd,edf->becf", buf_l, w_up)
        act = (
            jax.nn.silu(gate_w) if kind == "swiglu"
            else jax.nn.gelu(gate_w, approximate=True)
        )
        y_buf = jnp.einsum("becf,efd->becd", act * up, w_down)  # (B_l,E_l,C,d)

        def combine_row(y_b, es, sp, kp, tok, gw):
            local_e = es - shard_idx * e_local
            mine = (local_e >= 0) & (local_e < e_local) & kp
            le = jnp.clip(local_e, 0, e_local - 1)
            rows = y_b[le, sp] * (
                gw * mine.astype(jnp.float32)
            ).astype(y_b.dtype)[:, None]
            return jnp.zeros((s, d), y_b.dtype).at[tok].add(rows)

        y_part = jax.vmap(combine_row)(
            y_buf, e_sorted, safe_pos, keep, tok_sorted, gate_sorted
        )
        return jax.lax.psum(y_part, "model")

    e_sorted, safe_pos, keep, tok_sorted, gate_sorted = meta
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P("model", None, None),  # w_gate
            P("model", None, None),  # w_up
            P("model", None, None),  # w_down
            P(baxes, "model", None, None),  # buf
            P(baxes, None),  # e_sorted
            P(baxes, None),  # safe_pos
            P(baxes, None),  # keep
            P(baxes, None),  # tok_sorted
            P(baxes, None),  # gate_sorted
        ),
        out_specs=P(baxes, None, None),
        check_vma=False,
    )(
        params["w_gate"], params["w_up"], params["w_down"], buf,
        e_sorted, safe_pos, keep, tok_sorted, gate_sorted,
    )
