"""Mixture-of-Experts block: a top-k router over all experts, and two
dispatches of the tokens to the experts this chip holds.

* ``moe_block`` (the training loss): GShard-style capacity dispatch
  (one-hot dispatch/combine einsums with per-group token capacity), so that
  compiled FLOPs reflect the *routed* compute (top-k / E of dense); tokens
  over an expert's capacity get no routed contribution.
* ``moe_serve`` (prefill and decode): dropless — every (token, expert)
  assignment is sorted by expert and the held experts run as grouped
  matmuls (``jax.lax.ragged_dot``), so no token is ever dropped.

Expert parallelism: the layer is told which experts it holds, ids
``[expert_offset, expert_offset + experts_held)`` of the router's
``num_experts``.  It routes over all of them and adds only its own experts'
share (plus the shared experts, which every chip computes alike); the
absent experts' share is another chip's.  The capacity dispatch needs every
expert held.

Sharding note (DESIGN.md §5): expert weights are (E, d, d_ff) arrays; the
baseline shards d_ff over the ``model`` axis (tensor-parallel experts) since
the assigned expert counts (60, 8) do not divide the 16-way model axis.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers.mlp import init_mlp, mlp


def init_moe(key, cfg: ModelConfig, dtype=jnp.float32):
    """Router over all ``num_experts``; weights of the held experts."""
    m = cfg.moe
    d, f, e = cfg.d_model, m.expert_d_ff or cfg.d_ff, m.held
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    s_in, s_ff = d ** -0.5, f ** -0.5
    p = {
        "router": {"w": (jax.random.normal(kr, (d, m.num_experts))
                         * s_in).astype(jnp.float32)},
        # routed experts: stacked (E, d, f) / (E, f, d)
        "gate_proj": (jax.random.normal(kg, (e, d, f)) * s_in).astype(dtype),
        "up_proj": (jax.random.normal(ku, (e, d, f)) * s_in).astype(dtype),
        "down_proj": (jax.random.normal(kd, (e, f, d)) * s_ff).astype(dtype),
    }
    if m.num_shared_experts > 0:
        # shared experts are always-on; fuse into one wide MLP
        p["shared"] = init_mlp(ks, d, m.num_shared_experts * f,
                               cfg.activation, dtype)
    return p


def _capacity(group: int, top_k: int, num_experts: int, cf: float) -> int:
    c = int(group * top_k / num_experts * cf) + 1
    return max(4, -(-c // 4) * 4)        # round up to multiple of 4


def moe_block(params, cfg: ModelConfig, x: jnp.ndarray, *,
              group_size: int = 512):
    """x: (B, S, d) -> (y, aux_loss). Capacity-dropped tokens fall through
    with zero routed contribution (shared experts / residual still apply)."""
    m = cfg.moe
    if m.held != m.num_experts:
        raise ValueError("capacity dispatch needs every expert held; "
                         "serving takes moe_serve")
    B, S, d = x.shape
    T = B * S
    g = min(group_size, T)
    assert T % g == 0, (T, g)
    nG = T // g
    C = _capacity(g, m.top_k, m.num_experts, m.capacity_factor)

    xt = x.reshape(nG, g, d)
    logits = jnp.einsum("Ggd,de->Gge", xt.astype(jnp.float32),
                        params["router"]["w"])
    probs = jax.nn.softmax(logits, axis=-1)                 # (G,g,E)
    gate_vals, expert_idx = jax.lax.top_k(probs, m.top_k)   # (G,g,k)
    if m.norm_topk_prob:
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)

    # position of each (token, k) assignment inside its expert's buffer
    onehot = jax.nn.one_hot(expert_idx, m.num_experts, dtype=jnp.int32)  # (G,g,k,E)
    flat = onehot.reshape(nG, g * m.top_k, m.num_experts)
    pos = jnp.cumsum(flat, axis=1) - 1                      # (G,g*k,E)
    pos = (pos * flat).sum(-1).reshape(nG, g, m.top_k)      # (G,g,k)
    in_cap = pos < C

    # dispatch: (G,g,E,C) binary; combine: same with gate weights.
    # Built per-k (python loop, k<=4) to avoid the (G,g,k,E,C) tensor.
    dispatch = jnp.zeros((nG, g, m.num_experts, C), x.dtype)
    combine = jnp.zeros((nG, g, m.num_experts, C), x.dtype)
    for kk in range(m.top_k):
        oe = jax.nn.one_hot(expert_idx[..., kk], m.num_experts,
                            dtype=x.dtype)                  # (G,g,E)
        oc = jax.nn.one_hot(jnp.where(in_cap[..., kk], pos[..., kk], C),
                            C + 1, dtype=x.dtype)[..., :C]  # (G,g,C)
        d_k = oe[..., :, None] * oc[..., None, :]           # (G,g,E,C)
        dispatch = dispatch + d_k
        combine = combine + d_k * gate_vals[..., kk, None, None].astype(x.dtype)

    def expert_compute(disp, comb, xg):
        """(G',g,E,C) x (G',g,d) -> (G',g,d) routed output."""
        expert_in = jnp.einsum("Ggec,Ggd->Gecd", disp, xg)   # (G',E,C,d)
        if cfg.activation == "swiglu":
            h = jax.nn.silu(jnp.einsum("Gecd,edf->Gecf", expert_in,
                                       params["gate_proj"].astype(x.dtype)))
            h = h * jnp.einsum("Gecd,edf->Gecf", expert_in,
                               params["up_proj"].astype(x.dtype))
        elif cfg.activation == "geglu":
            h = jax.nn.gelu(jnp.einsum("Gecd,edf->Gecf", expert_in,
                                       params["gate_proj"].astype(x.dtype)),
                            approximate=True)
            h = h * jnp.einsum("Gecd,edf->Gecf", expert_in,
                               params["up_proj"].astype(x.dtype))
        else:
            h = jax.nn.gelu(jnp.einsum("Gecd,edf->Gecf", expert_in,
                                       params["up_proj"].astype(x.dtype)),
                            approximate=True)
        expert_out = jnp.einsum("Gecf,efd->Gecd", h,
                                params["down_proj"].astype(x.dtype))
        return jnp.einsum("Ggec,Gecd->Ggd", comb, expert_out)

    y = expert_compute(dispatch, combine, xt)
    y = y.reshape(B, S, d)

    if "shared" in params:
        y = y + mlp(params["shared"], x, cfg.activation)

    # load-balance auxiliary loss (Switch-style)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(expert_idx[..., 0], m.num_experts, dtype=jnp.float32),
        axis=(0, 1))
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = m.num_experts * jnp.sum(frac_tokens * frac_probs) \
        * m.router_aux_loss_coef
    return y, aux


def _route(params, cfg: ModelConfig, x):
    """Softmax over all experts' logits, greedy top-k: (gates, expert
    ids), each (T, k); gates renormalised when ``norm_topk_prob``.  The
    router runs in float32 at full precision (a default-precision float32
    matmul on the MXU rounds its operands to bf16, which reorders close
    experts)."""
    m = cfg.moe
    logits = jnp.dot(x.astype(jnp.float32), params["router"]["w"],
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, idx


def moe_serve(params, cfg: ModelConfig, x):
    """Dropless MoE for prefill and decode: x (B, S, d) -> y (B, S, d).

    The (token, expert) assignments are sorted by expert; those of the held
    experts come first, grouped, and run through the experts' SwiGLU as
    grouped matmuls over exactly their rows; the rest (other chips'
    experts) sort last and add nothing.  Each output row is weighted by
    its gate and summed back to its token, then the shared experts add."""
    if cfg.activation != "swiglu":
        raise ValueError("moe_serve runs SwiGLU experts")
    with jax.named_scope("obs.moe"):
        m = cfg.moe
        B, S, d = x.shape
        xt = x.reshape(B * S, d)
        gates, idx = _route(params, cfg, xt)
        n, k = m.held, m.top_k
        local = idx - m.expert_offset
        mine = (local >= 0) & (local < n)
        e = jnp.where(mine, local, n).reshape(-1)       # n: not held here
        order = jnp.argsort(e, stable=True)
        sizes = jnp.bincount(e, length=n + 1)[:n].astype(jnp.int32)
        rows = xt[order // k]                           # (T*k, d)

        def grouped(h, w):
            return jax.lax.ragged_dot(h, w.astype(x.dtype), sizes)

        h = (jax.nn.silu(grouped(rows, params["gate_proj"]))
             * grouped(rows, params["up_proj"]))
        out = grouped(h, params["down_proj"])           # (T*k, d) by expert
        held_row = jnp.arange(out.shape[0]) < sizes.sum()
        w = jnp.where(held_row, gates.reshape(-1)[order], 0.0)
        out = jnp.where(held_row[:, None], out, 0).astype(jnp.float32) \
            * w[:, None]
        inv = jnp.argsort(order)                        # back to (token, k)
        y = out[inv].reshape(B * S, k, d).sum(1).astype(x.dtype)
        y = y.reshape(B, S, d)
        if "shared" in params:
            y = y + mlp(params["shared"], x, cfg.activation)
        return y
