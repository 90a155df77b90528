"""Mixture-of-experts MLP with expert parallelism.

Not in the reference (its model surface is a single attention op); this
is the expert-parallel capability a complete framework needs, built the
TPU way: **static-shape one-hot dispatch** — no gather/scatter, no
data-dependent shapes anywhere, so the whole layer jits and shards.

Dispatch math (mesh-tensorflow / flaxformer lineage):
    router probs (T, E) -> top-k experts per token, renormalized
    capacity C = ceil(k * T / E * capacity_factor)
    dispatch (T, E, C) one-hot   : token t -> slot c of expert e
    combine  (T, E, C) weighted  : same support, carries router weight
    expert_in  = einsum('tec,td->ecd', dispatch, x)      [all_to_all]
    expert_out = per-expert MLP on (E, C, D)             [expert-sharded]
    y          = einsum('tec,ecd->td', combine, expert_out)

Expert parallelism is declarative: expert-major params (E, ...) and the
(E, C, D) activations carry a PartitionSpec on ``ep_axis``; XLA turns
the dispatch/return einsums into all-to-alls over ICI.  Tokens over
capacity are DROPPED (their combine weights are zero -> they pass
through the residual unchanged), the standard switch-transformer
contract.

Load balancing: the switch-style aux loss E * sum_e(f_e * P_e) is sown
into the ``losses`` collection; `train.loss_fn` picks it up.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _active_mesh_axes() -> tuple | None:
    """Axis names of the mesh context the caller entered (via
    ``jax.sharding.set_mesh``), or None when no mesh is active."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else tuple(mesh.axis_names)


def _maybe_constrain(x, spec: P | None):
    if spec is None:
        return x
    mesh_axes = _active_mesh_axes()
    if mesh_axes is None:
        # no mesh context: single-device and test runs go unsharded
        return x
    axes = [a for a in spec if a is not None]
    missing = [a for a in axes if a not in mesh_axes]
    if missing:
        # a named-but-absent axis is a misconfiguration, not a
        # fall-through: silently replicating would claim EP while
        # spending full expert memory on every device
        raise ValueError(
            f"ep_axis {missing} not in the current mesh "
            f"(axes {mesh_axes}); enter the mesh with "
            "jax.sharding.set_mesh or fix the axis name"
        )
    return jax.lax.with_sharding_constraint(x, spec)


class MoEMLP(nn.Module):
    """Token-choice top-k MoE MLP: (B, S, D) -> (B, S, D).

    ``ep_axis`` names the mesh axis experts shard over (None = no
    constraint).  ``capacity_factor`` scales the per-expert buffer; at
    1.0 a perfectly balanced router drops nothing.
    """

    num_experts: int
    top_k: int = 2
    hidden_mult: int = 4
    capacity_factor: float = 1.25
    ep_axis: str | None = None
    dtype: jnp.dtype = jnp.bfloat16
    aux_loss_weight: float = 0.01

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, s, d = x.shape
        e = self.num_experts
        k = self.top_k
        if not (1 <= k <= e):
            raise ValueError(f"top_k {k} must be in [1, num_experts={e}]")
        t = b * s
        h = d * self.hidden_mult
        cap = max(int(-(-k * t * self.capacity_factor // e)), 1)

        xt = x.reshape(t, d)
        # router in fp32: small tensor, and expert choice is
        # precision-sensitive (argmax ties flip under bf16 rounding)
        gate_w = self.param(
            "router", nn.initializers.lecun_normal(), (d, e), jnp.float32
        )
        logits = xt.astype(jnp.float32) @ gate_w  # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)

        topv, tope = jax.lax.top_k(probs, k)  # (T, k)
        topv = topv / jnp.sum(topv, axis=-1, keepdims=True)

        # slot assignment: position of each (token, choice) in its
        # expert's buffer = how many earlier (token, choice) pairs chose
        # the same expert.  Priority is choice-major (all first choices
        # before any second choice), the switch-transformer order.
        choice_onehot = jax.nn.one_hot(tope.T.reshape(-1), e,
                                       dtype=jnp.int32)  # (k*T, E)
        pos_in_expert = jnp.cumsum(choice_onehot, axis=0) - 1  # (k*T, E)
        slot = jnp.sum(pos_in_expert * choice_onehot, axis=-1)  # (k*T,)
        keep = slot < cap

        ids = tope.T.reshape(-1)            # (k*T,) expert per pair
        w = topv.T.reshape(-1) * keep       # zero weight for dropped

        # (k*T, E, C) one-hot per (choice, token) pair; pairs are
        # choice-major so a (k, T, E, C) reshape + sum over choices
        # yields the (T, E, C) dispatch directly — no (k*T, T) scatter
        pair_onehot = (
            jax.nn.one_hot(ids, e, dtype=x.dtype)[:, :, None]
            * jax.nn.one_hot(jnp.where(keep, slot, 0), cap,
                             dtype=x.dtype)[:, None, :]
            * keep[:, None, None].astype(x.dtype)
        )
        dispatch = jnp.sum(pair_onehot.reshape(k, t, e, cap), axis=0)
        combine = jnp.sum(
            (pair_onehot * w[:, None, None].astype(x.dtype))
            .reshape(k, t, e, cap), axis=0,
        )

        ep_spec = P(self.ep_axis, None, None) if self.ep_axis else None
        w_up = self.param(
            "experts_up", nn.initializers.lecun_normal(), (e, d, h),
            jnp.float32,
        ).astype(self.dtype)
        w_down = self.param(
            "experts_down", nn.initializers.lecun_normal(), (e, h, d),
            jnp.float32,
        ).astype(self.dtype)
        w_up = _maybe_constrain(w_up, ep_spec)
        w_down = _maybe_constrain(w_down, ep_spec)

        xin = jnp.einsum("tec,td->ecd", dispatch, xt.astype(self.dtype))
        xin = _maybe_constrain(xin, ep_spec)
        hmid = nn.gelu(jnp.einsum("ecd,edh->ech", xin, w_up))
        xout = jnp.einsum("ech,ehd->ecd", hmid, w_down)
        xout = _maybe_constrain(xout, ep_spec)
        y = jnp.einsum("tec,ecd->td", combine, xout.astype(x.dtype))

        # switch aux loss: E * sum_e( frac_tokens_e * mean_prob_e ),
        # computed over FIRST choices (the balancing target)
        first = jax.nn.one_hot(tope[:, 0], e, dtype=jnp.float32)
        f_e = jnp.mean(first, axis=0)
        p_e = jnp.mean(probs, axis=0)
        aux = self.aux_loss_weight * e * jnp.sum(f_e * p_e)
        self.sow("losses", "moe_aux", aux,
                 reduce_fn=lambda a, b_: a + b_, init_fn=lambda: 0.0)

        return y.reshape(b, s, d).astype(x.dtype)
