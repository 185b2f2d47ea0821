"""Mixture-of-Experts layer with expert parallelism in its capacity form:
routing is over a fixed expert capacity and tokens past it are dropped
(``capacity_factor``), dispatch and combine are one-hot einsums ``[T, E,
C]``.  That is what it is: a static-shape layer whose exchange GSPMD can
place, which cannot agree with a reference (a dropped token's logits
differ).  The dropless layer that is told which experts it holds, for
serving and, with a backward pass over its sorted tiles, for training that
agrees with a reference, is
:func:`horovod_tpu.models.latent_moe.held_experts`
(:mod:`horovod_tpu.models.moe_decoder` trains through it).

No reference equivalent (the reference is a data-parallel-only framework,
SURVEY.md §2.3); this supplies the EP axis of the framework's parallelism
matrix, TPU-first:

* **Dense dispatch**: routing is one-hot einsums over a fixed expert
  capacity — static shapes, MXU-friendly batched matmuls, no scatter/sort
  (the standard TPU MoE formulation; GPU implementations sort tokens
  instead, which XLA:TPU would handle poorly).
* **Top-k router** (top-2 default) with softmax gates renormalized over
  the selected experts and the Switch-Transformer load-balancing
  auxiliary loss: ``E · sum_e(frac_tokens_e · mean_router_prob_e)``,
  where frac_tokens counts first-choice assignments (no top-k factor).
* **Expert parallelism**: expert-stacked weights ``[E, ...]`` shard over
  the ``ep`` mesh axis via :func:`param_partition_specs`; under ``jit``
  GSPMD turns the dispatch/combine einsums into all-to-alls over ICI.
  :func:`expert_parallel_mlp` is the explicit ``shard_map`` form (manual
  ``lax.all_to_all``) for the hand-scheduled path.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    dim: int = 512
    ffn_dim: int = 1024
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    # Router z-loss (ST-MoE): penalizes large router logit norms —
    # log²(Σe^logit) per token — which keeps the softmax out of its
    # saturated region and stabilizes bf16 training.  0 disables.
    z_loss_weight: float = 0.0
    # Multiplicative jitter on router inputs during training (Switch
    # Transformer's input noise): x · U[1−ε, 1+ε].  0 disables.
    router_jitter: float = 0.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32


def init_params(cfg: MoEConfig, key: jax.Array) -> dict:
    ks = jax.random.split(key, 3)
    e, d, f = cfg.n_experts, cfg.dim, cfg.ffn_dim
    dt = cfg.param_dtype

    def dense(k, shape, fan_in):
        return jax.random.normal(k, shape, dt) / jnp.sqrt(fan_in)

    return {
        "router": dense(ks[0], (d, e), d),
        "w_in": dense(ks[1], (e, d, f), d),
        "w_out": dense(ks[2], (e, f, d), f),
    }


def param_partition_specs(*, ep_axis: str = "ep") -> dict:
    """Expert-stacked weights shard over the expert axis; the router is
    replicated (every token scores every expert)."""
    return {
        "router": P(None, None),
        "w_in": P(ep_axis, None, None),
        "w_out": P(ep_axis, None, None),
    }


def _capacity(n_tokens: int, cfg: MoEConfig) -> int:
    cap = int(cfg.capacity_factor * n_tokens * cfg.top_k / cfg.n_experts)
    return max(cap, cfg.top_k)


def route(cfg: MoEConfig, logits: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k routing with per-expert capacity.

    logits: [T, E] → (dispatch [T, E, C] one-hot, combine [T, E, C] gated,
    aux loss scalar).  All static shapes; position-in-expert computed with
    a cumulative sum over the token axis (deterministic tie-break by token
    order, the standard TPU formulation).
    """
    t = logits.shape[0]
    e = cfg.n_experts
    cap = _capacity(t, cfg)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # [T, E]

    gate_vals, expert_idx = lax.top_k(probs, cfg.top_k)          # [T, k]
    gate_vals = gate_vals / jnp.clip(
        gate_vals.sum(-1, keepdims=True), 1e-9
    )  # renormalize over the selected experts

    # One-hot per choice: [k, T, E]
    choice_oh = jax.nn.one_hot(expert_idx.T, e, dtype=jnp.float32)
    # Position of each (choice, token) within its expert queue, counting
    # first-choice tokens before second-choice tokens (priority to top-1).
    flat = choice_oh.reshape(cfg.top_k * t, e)                    # [k*T, E]
    pos = jnp.cumsum(flat, axis=0) - flat                         # [k*T, E]
    pos = (pos * flat).sum(-1).reshape(cfg.top_k, t)              # [k, T]
    keep = pos < cap
    pos_oh = jax.nn.one_hot(
        pos.astype(jnp.int32), cap, dtype=jnp.float32
    ) * keep[..., None]

    # dispatch[t, e, c] = 1 iff token t occupies slot c of expert e.
    dispatch = jnp.einsum("kte,ktc->tec", choice_oh, pos_oh)
    combine = jnp.einsum(
        "kte,ktc,tk->tec", choice_oh, pos_oh, gate_vals.astype(jnp.float32)
    )

    # Load-balancing aux loss (Switch): E · sum_e(frac_tokens_e · mean_prob_e).
    frac_tokens = choice_oh[0].mean(0)          # first-choice assignment share
    mean_prob = probs.mean(0)
    aux = cfg.n_experts * jnp.sum(frac_tokens * mean_prob)
    return dispatch.astype(jnp.float32), combine.astype(jnp.float32), aux


def router_logits(
    params: dict, x: jax.Array, cfg: MoEConfig,
    *, noise_key: jax.Array | None = None,
) -> jax.Array:
    """Router scores [T, E], with optional training-time jitter: the Switch
    Transformer's multiplicative input noise ``x · U[1−ε, 1+ε]``
    (``cfg.router_jitter``), applied only when a ``noise_key`` is given."""
    xf = x.astype(jnp.float32)
    if noise_key is not None and cfg.router_jitter > 0.0:
        eps = cfg.router_jitter
        xf = xf * jax.random.uniform(
            noise_key, xf.shape, jnp.float32, 1.0 - eps, 1.0 + eps
        )
    return xf @ params["router"].astype(jnp.float32)


def weighted_aux(cfg: MoEConfig, aux: jax.Array,
                 logits: jax.Array) -> jax.Array:
    """Combine the Switch balance loss with the ST-MoE router z-loss —
    ``mean(log²Σ_e e^logit)``, which keeps router logits small and the
    softmax out of its saturated region (bf16 stability)."""
    total = cfg.aux_loss_weight * aux
    if cfg.z_loss_weight:
        z = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        total = total + cfg.z_loss_weight * jnp.mean(z ** 2)
    return total


def forward(
    params: dict, x: jax.Array, cfg: MoEConfig,
    *, noise_key: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """MoE MLP: x [T, D] → (y [T, D], aux_loss).  ``noise_key`` enables
    the training-time router jitter (see :func:`router_logits`).

    The GSPMD path: with ``w_in``/``w_out`` sharded over ``ep`` and the
    einsums below, XLA inserts the token all-to-alls — same comm pattern a
    hand-written EP implementation issues, derived from the sharding.
    """
    dt = cfg.dtype
    logits = router_logits(params, x, cfg, noise_key=noise_key)
    dispatch, combine, aux = route(cfg, logits)
    # Tokens → expert buffers: [E, C, D]
    expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(dt), x.astype(dt))
    h = jax.nn.silu(
        jnp.einsum("ecd,edf->ecf", expert_in, params["w_in"].astype(dt))
    )
    expert_out = jnp.einsum("ecf,efd->ecd", h, params["w_out"].astype(dt))
    y = jnp.einsum("tec,ecd->td", combine.astype(dt), expert_out)
    return y.astype(x.dtype), weighted_aux(cfg, aux, logits)


def expert_parallel_mlp(
    params: dict, x: jax.Array, cfg: MoEConfig, *, axis_name: str = "ep",
    noise_key: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Explicit shard_map form: each device holds E/n experts and its own
    token shard; tokens move via ``lax.all_to_all`` (the MoE dispatch
    collective), compute runs on local experts, and a second all-to-all
    brings results home.

    x: per-device token shard [T_loc, D]; params: per-device expert shard
    (``w_in``/``w_out`` leading dim E/n, router replicated).
    """
    n = lax.axis_size(axis_name)
    e_loc = params["w_in"].shape[0]
    dt = cfg.dtype
    full_cfg = dataclasses.replace(cfg, n_experts=e_loc * n)

    if noise_key is not None:
        # Per-shard decorrelation: inside shard_map every device sees the
        # same replicated key and the same local shape, so without the
        # fold-in each token shard would draw IDENTICAL jitter.
        noise_key = jax.random.fold_in(noise_key, lax.axis_index(axis_name))
    logits = router_logits(params, x, cfg, noise_key=noise_key)
    dispatch, combine, aux = route(full_cfg, logits)

    # Local dispatch to ALL experts' buffers, then all-to-all exchanges
    # buffer ownership: [E, C, D] -> [E/n, n·C, D] on each device (expert
    # index is group-major: expert e = g·e_loc + j lives on device g, so a
    # tiled split over axis 0 routes chunk g to device g; received chunks
    # stack along the slot axis).
    expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(dt), x.astype(dt))
    expert_in = lax.all_to_all(expert_in, axis_name, 0, 1, tiled=True)

    h = jax.nn.silu(
        jnp.einsum("ecd,edf->ecf", expert_in, params["w_in"].astype(dt))
    )
    out = jnp.einsum("ecf,efd->ecd", h, params["w_out"].astype(dt))

    # Inverse exchange: slot chunk s came from device s; send results home
    # and restack along the expert axis -> [E, C, D] per device.
    out = lax.all_to_all(out, axis_name, 1, 0, tiled=True)
    y = jnp.einsum("tec,ecd->td", combine.astype(dt), out)
    # aux is computed from the local token shard; mean over devices.
    total = lax.pmean(weighted_aux(full_cfg, aux, logits), axis_name)
    return y.astype(x.dtype), total
