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

from typing import NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from attention_tpu.ops.experts import (
    ExpertLayout,
    expert_layout,
    grouped_experts,
    grouped_gated_experts,
    row_tile,
)


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


# -- the served expert layer ---------------------------------------------------

class PackedTokens(NamedTuple):
    """What a packed engine step tells a layer that keeps no cache:
    ``token_slot`` (T,) int32, -1 for the pad tokens of the step."""

    token_slot: jax.Array


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


class ReluSquaredMLP(nn.Module):
    """``down(relu(up x)^2)`` at a free width: the shared expert of
    `LatentExperts`."""

    hidden: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        h = nn.Dense(self.hidden, use_bias=False, dtype=self.dtype,
                     name="up_proj")(x)
        return nn.Dense(x.shape[-1], use_bias=False, dtype=self.dtype,
                        name="down_proj")(_relu2(h))


def sigmoid_top_k(x, router, bias, *, top_k: int, scale: float,
                  groups: int = 1, top_groups: int = 1):
    """The router of `LatentExperts`, in float32 whatever ``x`` is:
    scores ``sigmoid(x W_r)``, the ``top_k`` experts by ``score +
    bias``, weighted by their scores normalised over the chosen, times
    ``scale``.  The bias chooses and never weighs.  With ``groups`` > 1
    the choice is GROUP-LIMITED: the columns are ``groups`` equal runs,
    a group's mark is the sum of its two largest ``score + bias``, and
    only the ``top_groups`` groups of largest mark (ties to the lower
    group) can be chosen from.  Returns ``(chosen (T, k) int32, weight
    (T, k) float32)``."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    choice = scores + bias
    if groups > 1:
        by_group = choice.reshape(choice.shape[0], groups, -1)
        mark = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
        _, best = jax.lax.top_k(mark, top_groups)
        open_ = jnp.any(best[..., None] == jnp.arange(groups), axis=-2)
        choice = jnp.where(open_[..., None], by_group, -jnp.inf).reshape(
            choice.shape)
    _, chosen = jax.lax.top_k(choice, top_k)
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    return chosen.astype(jnp.int32), weight * scale


def packed_rows(x, cache: PackedTokens | None):
    """``x`` (B, S, D) as rows (T, D), and which of them are real
    tokens: all without a cache, a packed step's non-pad tokens with
    one."""
    tokens = x.shape[0] * x.shape[1]
    valid = (jnp.ones((tokens,), bool) if cache is None
             else jnp.asarray(cache.token_slot).reshape(tokens) >= 0)
    return x.reshape(tokens, x.shape[-1]), valid


def weighted_pairs(y, layout: ExpertLayout, weight):
    """Each token's weighted sum over its pairs held here: ``y`` is the
    grouped product's (R, width) result, ``weight`` (T, k).  Rows that
    hold no pair were never written: select, not scale."""
    here = layout.dest < y.shape[0]
    picked = jnp.where(here[..., None],
                       y[jnp.minimum(layout.dest, y.shape[0] - 1)], 0.0)
    return jnp.sum(picked * weight[..., None], axis=1)


def pair_counts(layout: ExpertLayout, absent, *more):
    """What an expert layer sows: the pairs of each held expert, the
    pairs of experts held elsewhere, the held experts that received a
    pair, then ``more``."""
    return jnp.concatenate([layout.counts, jnp.stack(
        [absent, jnp.sum(layout.counts > 0), *more])]).astype(jnp.int32)


class LatentExperts(nn.Module):
    """Sparse experts in a latent space, as ONE CHIP'S SHARE of an
    expert-parallel deployment: (B, S, D) -> (B, S, D).

        s = sigmoid(W_r x)                    float32, all ``num_experts``
        chosen = top_k of (s + bias)          the bias selects, s weighs
        g_i = scale * s_i / sum_chosen s
        u = W_dn x                            D -> latent
        r = sum_{i chosen, HELD HERE} g_i W2_i relu(W1_i u)^2
        out = W_up r + shared(x)              the shared expert at D

    The layer holds experts ``[held * share, held * (share + 1))`` of
    ``num_experts``: it routes over all of them and computes its own
    experts' part of ``r``; what the experts held elsewhere would add
    is left out (the exchange that brings it belongs to the
    deployment, not to this chip).  Pairs are counted by held expert
    and run through one grouped product (`ops.experts`): no token is
    dropped whatever the imbalance, and no shape depends on the
    routing.  ``PackedTokens`` marks a step's pad tokens, which take no
    expert.

    Sows into the ``expert_stats`` collection ``pairs`` (held + 2,)
    int32: the pairs of each held expert, the pairs of experts held
    elsewhere, and the held experts that received a pair."""

    num_experts: int
    held: int
    share: int = 0
    top_k: int = 2
    latent: int = 64
    hidden: int = 128
    shared_hidden: int = 0
    scale: float = 1.0
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array, cache: PackedTokens | None = None):
        if not (0 < self.held and self.held * (self.share + 1)
                <= self.num_experts and 1 <= self.top_k <= self.num_experts):
            raise ValueError(
                f"share {self.share} of {self.held} experts, top "
                f"{self.top_k}, does not fit {self.num_experts} experts")
        batch, seq, dim = x.shape
        tokens = batch * seq
        xt, valid = packed_rows(x, cache)
        chosen, weight = sigmoid_top_k(
            xt, self.param("router", nn.initializers.lecun_normal(),
                           (dim, self.num_experts), jnp.float32),
            self.param("router_bias", nn.initializers.zeros,
                       (self.num_experts,), jnp.float32),
            top_k=self.top_k, scale=self.scale)
        w1 = self.param("experts_up", nn.initializers.lecun_normal(),
                        (self.held, self.latent, self.hidden), jnp.float32)
        w2 = self.param("experts_down", nn.initializers.lecun_normal(),
                        (self.held, self.hidden, self.latent), jnp.float32)
        u = nn.Dense(self.latent, use_bias=False, dtype=self.dtype,
                     name="latent_down")(xt)

        tile = row_tile(tokens)
        layout = expert_layout(chosen - self.held * self.share, valid,
                               held=self.held, tile=tile)
        y = grouped_experts(u[layout.row_token], w1, w2, layout, tile=tile)
        r = weighted_pairs(y, layout, weight)
        out = nn.Dense(dim, use_bias=False, dtype=self.dtype,
                       name="latent_up")(r.astype(self.dtype))
        if self.shared_hidden:
            out = out + ReluSquaredMLP(self.shared_hidden, dtype=self.dtype,
                                       name="shared_expert")(xt)
        absent = jnp.sum(valid) * self.top_k - jnp.sum(layout.counts)
        self.sow("expert_stats", "pairs", pair_counts(layout, absent))
        return out.reshape(batch, seq, dim)


def softmax_top_k(x, router, bias, *, top_k: int, scale: float):
    """The router of `GatedExperts`, in float32 whatever ``x`` is:
    scores ``softmax(x W_r)`` over every column, the ``top_k`` by
    ``score + bias``, weighted by ``scale`` times their scores as they
    are, NOT normalised over the chosen.  Returns ``(chosen (T, k)
    int32, weight (T, k) float32)``."""
    scores = jax.nn.softmax(jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    _, chosen = jax.lax.top_k(scores + bias, top_k)
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen.astype(jnp.int32), weight * scale


class GatedExperts(nn.Module):
    """Sparse gated (SwiGLU) experts beside ZERO-COMPUTE experts, as
    ONE CHIP'S SHARE of an expert-parallel deployment: (B, S, D) ->
    (B, S, D).  ``router`` = ``"softmax"``, as below, or ``"sigmoid"``:
    `sigmoid_top_k`'s scores, normalised weights and, with ``groups``
    > 1, group-limited choice (no zero experts behind that one).

        s = softmax(W_r y)                  float32, ``num_experts + zero_experts`` columns, real first
        chosen = top_k of (s + bias)        the bias selects, s weighs
        g_i = scale * s_i                   not normalised over the chosen
        m = sum_{i chosen, real, HELD HERE} g_i Wd_i (silu(Wg_i y) * (Wu_i y))
          + (sum_{i chosen, zero} g_i) * y  a zero expert returns its input

    The share is `LatentExperts`'s: the layer holds real experts
    ``[held * share, held * (share + 1))``, routes over every column,
    computes its own experts' part through one grouped product
    (`ops.experts.grouped_gated_experts`: no token dropped, no shape
    from the routing) and leaves out what the real experts held
    elsewhere would add.  The zero experts' part is computed where the
    token is, on every chip alike, so it is whole here.  A token takes
    0 to ``top_k`` real experts.  Pad tokens of a packed step
    (`PackedTokens`) take none.

    Sows into ``expert_stats`` ``pairs`` (held + 3,) int32: the pairs
    of each held expert, the pairs of real experts held elsewhere, the
    held experts that received a pair, and the pairs that went to zero
    experts."""

    num_experts: int
    held: int
    share: int = 0
    zero_experts: int = 0
    top_k: int = 2
    hidden: int = 128
    scale: float = 1.0
    dtype: jnp.dtype = jnp.bfloat16
    router: str = "softmax"
    groups: int = 1
    top_groups: int = 1

    @nn.compact
    def __call__(self, x: jax.Array, cache: PackedTokens | None = None):
        columns = self.num_experts + self.zero_experts
        if not (0 < self.held and self.held * (self.share + 1)
                <= self.num_experts and 1 <= self.top_k <= columns):
            raise ValueError(
                f"share {self.share} of {self.held} experts, top "
                f"{self.top_k}, does not fit {self.num_experts} experts "
                f"and {self.zero_experts} zero experts")
        limited = {}
        if self.router == "sigmoid":
            if self.zero_experts or columns % self.groups or not (
                    1 <= self.top_groups <= self.groups
                    and self.top_k <= self.top_groups
                    * (columns // self.groups)):
                raise ValueError(
                    f"a sigmoid router over {columns} columns in "
                    f"{self.groups} groups of which {self.top_groups}, "
                    f"top {self.top_k}, {self.zero_experts} zero experts")
            limited = dict(groups=self.groups, top_groups=self.top_groups)
        elif self.router != "softmax" or self.groups != 1:
            raise ValueError(f"router {self.router!r} in {self.groups} "
                             "groups: softmax (one group) or sigmoid")
        batch, seq, dim = x.shape
        tokens = batch * seq
        xt, valid = packed_rows(x, cache)
        chosen, weight = (sigmoid_top_k if limited else softmax_top_k)(
            xt, self.param("router", nn.initializers.lecun_normal(),
                           (dim, columns), jnp.float32),
            self.param("router_bias", nn.initializers.zeros,
                       (columns,), jnp.float32),
            top_k=self.top_k, scale=self.scale, **limited)

        def experts(name, shape):
            return self.param(name, nn.initializers.lecun_normal(
                in_axis=-2, out_axis=-1, batch_axis=(0,)), shape,
                jnp.float32)

        wg = experts("experts_gate", (self.held, dim, self.hidden))
        wu = experts("experts_up", (self.held, dim, self.hidden))
        wd = experts("experts_down", (self.held, self.hidden, dim))
        tile = row_tile(tokens)
        layout = expert_layout(chosen - self.held * self.share, valid,
                               held=self.held, tile=tile)
        y = grouped_gated_experts(xt.astype(self.dtype)[layout.row_token],
                                  wg, wu, wd, layout, tile=tile)
        to_zero = chosen >= self.num_experts
        out = (weighted_pairs(y, layout, weight)
               + jnp.sum(jnp.where(to_zero, weight, 0.0), axis=1,
                         keepdims=True) * xt.astype(jnp.float32))
        zero = jnp.sum(to_zero & valid[:, None])
        absent = (jnp.sum(valid) * self.top_k - jnp.sum(layout.counts)
                  - zero)
        self.sow("expert_stats", "pairs", pair_counts(layout, absent, zero))
        return out.astype(self.dtype).reshape(batch, seq, dim)
