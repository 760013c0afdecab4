"""Mixture-of-Experts FFN with grouped capacity dispatch (GShard style).

Tokens are split into fixed-size *groups* (``MoEConfig.group_tokens``); each
group routes independently with capacity ``C = ceil(g/E * top_k * cf)`` in
training.  Serving (prefill, chunked prefill, decode) routes *dropless*:
every expert has capacity for every row of its group, so a request's output
never depends on its batch-mates or on padding rows.
Dense one-hot dispatch/combine einsums keep every shape static (required for
SPMD lowering) while both FLOPs and peak memory stay linear in tokens —
O(tokens * E * C_g) with C_g fixed by the group size, NOT by the global
batch.  The group dim shards over "data" (it is aligned with the token
sharding) and the expert dim over "model" (expert parallelism); GSPMD turns
dispatch/combine into all-to-alls.

DeepSeek-V2-style *shared experts* (always-on) are a plain dense MLP added to
the routed output.  OBU transpose on a routed expert swaps its up/down
projections exactly like the dense MLP (see layers.apply_mlp).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.core.backend import resolve as resolve_backend
from repro.models.layers import _dense_init, apply_mlp, init_mlp


def init_moe(key, d_model: int, mcfg: MoEConfig):
    ks = jax.random.split(key, 5)
    E, f = mcfg.num_experts, mcfg.d_ff_expert
    Ep = mcfg.num_basic_experts or E    # PRM across experts (R_e physical)
    p = {"router": _dense_init(ks[0], (d_model, E), scale=0.02),
         "w_gate": _dense_init(ks[1], (Ep, d_model, f)),
         "w_up": _dense_init(ks[2], (Ep, d_model, f)),
         "w_down": _dense_init(ks[3], (Ep, f, d_model))}
    s = {"router": ("embed", "experts_r"),
         "w_gate": ("experts", "embed", "mlp"),
         "w_up": ("experts", "embed", "mlp"),
         "w_down": ("experts", "mlp", "embed")}
    if mcfg.num_shared:
        sp, ss = init_mlp(ks[4], d_model,
                          mcfg.d_ff_shared or f * mcfg.num_shared)
        p["shared"] = sp
        s["shared"] = ss
    return p, s


def _group_shape(n_tokens: int, mcfg: MoEConfig):
    g = min(mcfg.group_tokens, n_tokens)
    while n_tokens % g != 0:          # static search: g divides tokens
        g -= 1
    return n_tokens // g, g


def _capacity(g: int, mcfg: MoEConfig) -> int:
    cap = -(-g // mcfg.num_experts) * mcfg.top_k
    cap = int(cap * mcfg.capacity_factor)
    return max(min(cap, g), mcfg.top_k)


def route(p, xg, mcfg: MoEConfig, dropless: bool = False):
    """Per-group routing.  xg: (G, g, d).

    Returns dispatch (G,g,E,C), combine (G,g,E,C), aux: the losses and
    ``topk`` (G,g,K), each row's experts.  Gates are the top-k softmax
    probabilities, renormalised to sum to 1 unless ``norm_topk_prob`` is
    off.  Tokens beyond an expert's capacity are dropped (standard GShard
    semantics); ``dropless`` sets the capacity to the group's rows, so none
    is."""
    G, g, d = xg.shape
    E, K = mcfg.num_experts, mcfg.top_k
    C = g if dropless else _capacity(g, mcfg)
    logits = jnp.einsum("ngd,de->nge", xg.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)          # (G,g,K)
    if mcfg.norm_topk_prob:
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
    sel = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)   # (G,g,K,E)
    mask = jnp.max(sel, axis=2)                            # (G,g,E) in {0,1}
    pos_in_e = jnp.cumsum(mask, axis=1) - 1.0              # (G,g,E)
    keep = (pos_in_e < C) * mask
    weight_ge = jnp.einsum("ngke,ngk->nge", sel, gate_vals) * keep
    # the (G,g,E,C) one-hots are the MoE path's largest buffers — keep them
    # bf16 (they hold exact 0/1 and softmax weights; §Perf granite iteration)
    pos_oh = jax.nn.one_hot(pos_in_e.astype(jnp.int32), C,
                            dtype=jnp.bfloat16)            # (G,g,E,C)
    dispatch = pos_oh * keep.astype(jnp.bfloat16)[..., None]
    combine = pos_oh * weight_ge.astype(jnp.bfloat16)[..., None]
    frac_tokens = jnp.mean(mask, axis=(0, 1))
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = {"load_balance": E * jnp.sum(frac_tokens * frac_probs),
           "dropped_frac": 1.0 - jnp.sum(keep) / (G * g * K),
           "topk": gate_idx}
    return dispatch, combine, aux


def num_moe_layers(cfg) -> int:
    """MoE layers one forward pass runs through (logical layers)."""
    from repro.models.transformer import build_segments
    return sum(spec.num_groups * spec.ffn_kinds.count("moe")
               for spec in build_segments(cfg))


def route_record(cfg, rows: int) -> dict:
    """An aux accumulator that also keeps every MoE layer's routing of
    ``rows`` rows: ``route`` (MoE layers, rows, top_k) expert ids, filled
    in layer order (``accumulate_aux``)."""
    return {"load_balance": jnp.float32(0.0),
            "route": jnp.zeros((num_moe_layers(cfg), rows, cfg.moe.top_k),
                               jnp.int32),
            "layer": jnp.int32(0)}


def accumulate_aux(aux, moe_aux):
    """Add one MoE layer's aux to the stack's accumulator: a scalar (the
    load-balance loss) or a ``route_record``."""
    if not isinstance(aux, dict):
        return aux + moe_aux["load_balance"]
    K = moe_aux["topk"].shape[-1]
    idx = moe_aux["topk"].reshape(1, -1, K).astype(jnp.int32)
    return {"load_balance": aux["load_balance"] + moe_aux["load_balance"],
            "route": jax.lax.dynamic_update_slice(
                aux["route"], idx, (aux["layer"], 0, 0)),
            "layer": aux["layer"] + 1}


def _expert_weights(p, mcfg: MoEConfig, dtype):
    """Effective (E, ...) expert banks.  With ``num_basic_experts`` set,
    the E logical experts are *blended* from R_e basic experts (PRM across
    the expert dimension): expert e reuses basic e % R_e, diversified by a
    static OBU group-shuffle of its gate activations (applied in
    apply_moe) — one physical programming serves E/R_e experts."""
    wg, wu, wd = (p["w_gate"].astype(dtype), p["w_up"].astype(dtype),
                  p["w_down"].astype(dtype))
    E = mcfg.num_experts
    if mcfg.num_basic_experts and mcfg.num_basic_experts < E:
        idx = jnp.arange(E) % mcfg.num_basic_experts
        wg, wu, wd = wg[idx], wu[idx], wd[idx]
    return wg, wu, wd


def _expert_gate_perms(mcfg: MoEConfig):
    """(E, f) static permutation table for the blended experts' gate
    activations; identity for basic (first-use) experts."""
    import numpy as np
    from repro.core.obu import group_shuffle_permutation
    E, f = mcfg.num_experts, mcfg.d_ff_expert
    Rp = mcfg.num_basic_experts
    table = np.tile(np.arange(f), (E, 1))
    for e in range(E):
        t = e // Rp                    # reuse index of this expert
        if t > 0:
            g = min(4 * t, max(2, f // 2))
            if f % g:
                g = 2
            table[e] = group_shuffle_permutation(f, g)
    return jnp.asarray(table)


def _photonic_expert_ffn(bk, p, xe, mcfg: MoEConfig, dtype, transpose):
    """Expert FFN on the photonic backend: per-expert Pallas W8A8 matmuls.

    With PRM-blended experts (``num_basic_experts`` = R_e < E) the E logical
    experts of a bank share R_e physical weights — exactly the write-once /
    reuse-T-times situation, with *independent* activation streams (each
    logical expert's capacity buffer).  Those stream through the
    reuse-resident kernel: the basic bank is programmed once and the
    E/R_e buffers pass through the VMEM-resident tile."""
    G, E, C, d = xe.shape
    rows = xe.transpose(1, 0, 2, 3).reshape(E, G * C, d)
    wg, wu, wd = (p["w_gate"].astype(dtype), p["w_up"].astype(dtype),
                  p["w_down"].astype(dtype))
    nb = wg.shape[0]                       # R_e physical banks (== E if none)
    blended = nb < E

    def bank_dot(h, w_bank, transpose_w=False, activation=None):
        if blended and not transpose_w and E % nb == 0:
            outs = [None] * E
            for r in range(nb):            # logical experts e ≡ r (mod R_e)
                y = bk.reuse_dot(h[r::nb], w_bank[r])
                for j, e in enumerate(range(r, E, nb)):
                    outs[e] = y[j]
            y = jnp.stack(outs)
            return _apply_act(y, activation)
        return jnp.stack([bk.dot(h[e], w_bank[e % nb], transpose=transpose_w,
                                 activation=activation)
                          for e in range(E)])

    def _apply_act(y, activation):
        if activation in (None, "none"):
            return y
        if activation == "silu":
            return jax.nn.silu(y)
        raise ValueError(f"unsupported activation {activation!r} on the "
                         f"reuse-resident expert path")

    if transpose:
        # the gate silu fuses into the per-expert megakernel's blend
        # epilogue (per-call dot path); the reuse-resident branch applies
        # it post-kernel — same elementwise math either way
        gate = bank_dot(rows, wd, transpose_w=True,  # W_down.T as up-proj
                        activation="silu")
        up = bank_dot(rows, wu)
        out = bank_dot(gate * up, wg, transpose_w=True)  # W_gate.T: down-proj
    else:
        if blended:
            # blended experts diversify the gate by a static fine-grained
            # shuffle; silu commutes with the gather but the literal order
            # (gather then silu) is kept for bit-stability with history
            gate = bank_dot(rows, wg)
            perms = _expert_gate_perms(mcfg)         # (E, f) static
            gate = jnp.take_along_axis(gate, perms[:, None, :], axis=-1)
            gate = jax.nn.silu(gate)
        else:
            gate = bank_dot(rows, wg, activation="silu")
        up = bank_dot(rows, wu)
        out = bank_dot(gate * up, wd)
    return out.reshape(E, G, C, d).transpose(1, 0, 2, 3)


def apply_moe(p, x, mcfg: MoEConfig, transpose: bool = False, backend=None,
              dropless: bool = False):
    """x: (B, S, d) -> (B, S, d) plus aux (``route``'s).

    Routing stays electronic/fp32 on every backend (the router is a tiny
    matmul and top-k wants full precision); only the expert FFN banks route
    through the photonic kernels.  ``dropless`` (every serving path) gives
    each expert capacity for its whole routing group; training keeps the
    GShard capacity.  The one-hot dispatch then computes every expert at
    the group's rows."""
    bk = resolve_backend(backend)
    B, S, d = x.shape
    G, g = _group_shape(B * S, mcfg)
    xg = x.reshape(G, g, d)
    with jax.named_scope("moe.route"):
        dispatch, combine, aux = route(p, xg, mcfg, dropless)
    with jax.named_scope("moe.dispatch"):
        xe = jnp.einsum("ngec,ngd->necd", dispatch.astype(x.dtype), xg)
    with jax.named_scope("moe.experts"):
        ye = _expert_ffn(bk, p, xe, mcfg, x.dtype, transpose)
    with jax.named_scope("moe.combine"):
        yg = jnp.einsum("ngec,necd->ngd", combine.astype(x.dtype), ye)
    y = yg.reshape(B, S, d)
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            y = y + apply_mlp(p["shared"], x, act="swiglu",
                              transpose=transpose, backend=bk)
    return y, aux


def _expert_ffn(bk, p, xe, mcfg: MoEConfig, dtype, transpose):
    """Every expert's SwiGLU on its capacity buffer xe (G, E, C, d)."""
    if bk.is_photonic:
        return _photonic_expert_ffn(bk, p, xe, mcfg, dtype, transpose)
    wg, wu, wd = _expert_weights(p, mcfg, dtype)
    if transpose:
        gate = jnp.einsum("necd,efd->necf", xe, wd)  # W_down.T as up-proj
        up = jnp.einsum("necd,edf->necf", xe, wu)
        h = jax.nn.silu(gate) * up
        return jnp.einsum("necf,edf->necd", h, wg)   # W_gate.T as down-proj
    gate = jnp.einsum("necd,edf->necf", xe, wg)
    if mcfg.num_basic_experts and mcfg.num_basic_experts < mcfg.num_experts:
        perms = _expert_gate_perms(mcfg)             # (E, f) static
        gate = jnp.take_along_axis(gate, perms[None, :, None, :], axis=-1)
    up = jnp.einsum("necd,edf->necf", xe, wu)
    h = jax.nn.silu(gate) * up
    return jnp.einsum("necf,efd->necd", h, wd)
