"""MoE tests: one-hot dispatch correctness vs a per-token reference,
capacity/drop semantics, expert-parallel sharding equivalence, and the
end-to-end MoE decoder (train step + cached decode).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from attention_tpu.models import MoEMLP, TinyDecoder
from attention_tpu.models.train import (
    init_sharded,
    make_mesh_3d,
    make_train_step,
)


def _moe(e=4, k=2, cf=8.0, **kw):
    # generous capacity by default: no drops -> exact reference compare
    return MoEMLP(num_experts=e, top_k=k, capacity_factor=cf,
                  dtype=jnp.float32, **kw)


def _reference_moe(params, x, e, k):
    """Per-token loop: route to top-k experts, weighted sum (no drops)."""
    t, d = x.shape
    gate = np.asarray(params["router"], np.float64)
    up = np.asarray(params["experts_up"], np.float64)
    down = np.asarray(params["experts_down"], np.float64)

    def gelu(v):
        return 0.5 * v * (1 + np.tanh(np.sqrt(2 / np.pi) * (v + 0.044715 * v**3)))

    logits = x @ gate
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    out = np.zeros_like(x)
    for ti in range(t):
        order = np.argsort(-probs[ti])[:k]
        w = probs[ti][order]
        w = w / w.sum()
        for ei, wi in zip(order, w):
            h = gelu(x[ti] @ up[ei])
            out[ti] += wi * (h @ down[ei])
    return out


def test_moe_matches_per_token_reference(rng):
    mod = _moe()
    x = jnp.asarray(rng.standard_normal((2, 8, 32)), jnp.float32)
    params = mod.init(jax.random.PRNGKey(0), x)["params"]
    got = np.asarray(mod.apply({"params": params}, x))
    want = _reference_moe(params, np.asarray(x, np.float64).reshape(16, 32),
                          4, 2).reshape(2, 8, 32)
    # gelu approximations differ (exact erf vs tanh) -> loose-ish tol
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=1e-2)


def test_moe_top1_matches_reference(rng):
    mod = _moe(k=1)
    x = jnp.asarray(rng.standard_normal((1, 12, 16)), jnp.float32)
    params = mod.init(jax.random.PRNGKey(1), x)["params"]
    got = np.asarray(mod.apply({"params": params}, x))
    want = _reference_moe(params, np.asarray(x, np.float64).reshape(12, 16),
                          4, 1).reshape(1, 12, 16)
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=1e-2)


def test_moe_zero_capacity_drops_all_tokens(rng):
    """capacity_factor ~ 0 -> every token dropped -> output is zero
    (tokens ride the residual unchanged in the block)."""
    mod = MoEMLP(num_experts=4, top_k=1, capacity_factor=1e-9,
                 dtype=jnp.float32)
    x = jnp.asarray(rng.standard_normal((1, 8, 16)), jnp.float32)
    params = mod.init(jax.random.PRNGKey(0), x)["params"]
    out = mod.apply({"params": params}, x)
    # cap = max(..., 1): one slot per expert -> at most E tokens kept;
    # with 8 tokens and 4 experts at least half must be exact zeros
    zero_rows = np.sum(np.all(np.asarray(out[0]) == 0.0, axis=-1))
    assert zero_rows >= 4


def test_moe_aux_loss_sown(rng):
    mod = _moe()
    x = jnp.asarray(rng.standard_normal((2, 8, 32)), jnp.float32)
    params = mod.init(jax.random.PRNGKey(0), x)["params"]
    _, mods = mod.apply({"params": params}, x, mutable=["losses"])
    aux = jax.tree_util.tree_leaves(mods["losses"])
    assert len(aux) == 1
    # switch aux loss is >= aux_weight * 1.0 at perfect balance
    assert float(aux[0]) >= mod.aux_loss_weight * 0.99


def test_moe_ep_sharded_matches_unsharded(rng):
    """Experts sharded over an 8-device 'ep' mesh == single-device."""
    mod = _moe(e=8)
    x = jnp.asarray(rng.standard_normal((2, 16, 32)), jnp.float32)
    params = mod.init(jax.random.PRNGKey(0), x)["params"]
    want = np.asarray(mod.apply({"params": params}, x))

    mesh = Mesh(np.asarray(jax.devices()[:8]), ("ep",))
    ep_mod = _moe(e=8, ep_axis="ep")
    spec = {
        "router": P(),
        "experts_up": P("ep", None, None),
        "experts_down": P("ep", None, None),
    }
    sharded = {
        kk: jax.device_put(v, NamedSharding(mesh, spec[kk]))
        for kk, v in params.items()
    }
    with jax.sharding.set_mesh(mesh):
        got = np.asarray(
            jax.jit(lambda p, xx: ep_mod.apply({"params": p}, xx))(sharded, x)
        )
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_moe_decoder_forward_and_cached_decode(rng):
    """MoE blocks compose with the KV-cache serving path."""
    model = TinyDecoder(vocab=31, dim=32, depth=2, num_q_heads=4,
                        num_kv_heads=2, impl="flash", dtype=jnp.float32,
                        moe_experts=4, moe_capacity_factor=8.0)
    tokens = jnp.asarray(rng.integers(0, 31, (2, 9)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    full = model.apply({"params": params}, tokens)

    caches = model.init_caches(batch=2, capacity=128)
    stepwise = []
    for t in range(tokens.shape[1]):
        logits, caches = model.apply(
            {"params": params}, tokens[:, t : t + 1], caches
        )
        stepwise.append(logits[:, 0])
    got = jnp.stack(stepwise, axis=1)
    # decode routes each token alone (capacity >= 1 per expert): no
    # drops, so logits match the full forward only when the full
    # forward also drops nothing -> generous capacity_factor above
    np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                               atol=2e-4, rtol=1e-3)


def test_moe_train_step_decreases_loss(rng):
    """Sharded train step on the dp/sp/tp mesh with MoE blocks (experts
    ride the tp axis): loss finite and decreasing, aux loss included."""
    mesh = make_mesh_3d(8)
    model = TinyDecoder(vocab=64, dim=32, depth=1, num_q_heads=4,
                        num_kv_heads=2, impl="xla", dtype=jnp.float32,
                        moe_experts=4, ep_axis="tp")
    batch = max(4, mesh.shape["dp"])
    seq = 32 * mesh.shape["sp"]
    with jax.sharding.set_mesh(mesh):
        params, optimizer, opt_state = init_sharded(
            model, mesh, batch=batch, seq=seq
        )
        step = make_train_step(model, optimizer, mesh)
        tokens = jnp.asarray(
            rng.integers(0, 64, (batch, seq + 1)), jnp.int32
        )
        losses = []
        for _ in range(5):
            params, opt_state, loss = step(params, opt_state, tokens)
            losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_moe_rejects_bad_top_k(rng):
    x = jnp.zeros((1, 4, 16), jnp.float32)
    mod = MoEMLP(num_experts=2, top_k=3, dtype=jnp.float32)
    with pytest.raises(ValueError, match="top_k"):
        mod.init(jax.random.PRNGKey(0), x)


def test_moe_bad_ep_axis_raises_under_mesh(rng):
    """A named-but-absent ep_axis under a real mesh is a
    misconfiguration and must raise, not silently replicate."""
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("ep",))
    mod = MoEMLP(num_experts=8, top_k=2, ep_axis="exp", dtype=jnp.float32)
    x = jnp.zeros((1, 8, 16), jnp.float32)
    with jax.sharding.set_mesh(mesh):
        with pytest.raises(ValueError, match="not in the current mesh"):
            mod.init(jax.random.PRNGKey(0), x)


# -- the served expert layer (`LatentExperts`, `ops.experts`) ------------------

E, HELD, TOP_K, DIM, LATENT, HIDDEN = 16, 4, 4, 64, 32, 48


def _served_layer(share, held=HELD):
    from attention_tpu.models.moe import LatentExperts

    return LatentExperts(num_experts=E, held=held, share=share, top_k=TOP_K,
                         latent=LATENT, hidden=HIDDEN, shared_hidden=96,
                         scale=5.0, dtype=jnp.float32)


@pytest.fixture(scope="module")
def whole_layer():
    """The uncut layer: every expert held (one share of one), weights
    from the benchmark reference's initialiser, a selection bias that
    is not zero."""
    from benchmark import harness

    reference = harness.load_module("configs",
                                    "nemotron-3-super-120b_reference")
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 24, DIM), jnp.float32)
    layer = _served_layer(0, held=E)
    shapes = jax.eval_shape(layer.init, jax.random.PRNGKey(1), x)["params"]
    params = reference.init_params(shapes, jax.random.PRNGKey(2))
    params["router_bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(3),
                                                    (E,))
    return reference, x, params


def _dense(reference, params, x, share=0):
    with jax.default_matmul_precision("highest"):
        return reference._experts(params, x[0], share=share, top_k=TOP_K,
                                  scale=5.0, quant=lambda t: t)[0]


def _share_of(params, share):
    cut = slice(share * HELD, (share + 1) * HELD)
    return dict(params, experts_up=params["experts_up"][cut],
                experts_down=params["experts_down"][cut])


def test_the_shares_add_up_to_the_uncut_layer(whole_layer):
    """THE SHARE TEST: the routed parts of all 4 shares of 4 experts,
    with what every chip computes alike (the shared expert; ``W_up`` is
    linear) counted once, are the uncut reference's layer."""
    reference, x, params = whole_layer
    with jax.default_matmul_precision("highest"):
        whole = _dense(reference, params, x)
        shared = reference._feed_forward(params["shared_expert"], x[0],
                                         quant=lambda t: t)
        total, counted = 0.0, 0
        for share in range(E // HELD):
            out, sown = _served_layer(share).apply(
                {"params": _share_of(params, share)}, x,
                mutable=["expert_stats"])
            # each share against the reference given the same share
            np.testing.assert_allclose(
                out[0], _dense(reference, _share_of(params, share), x,
                               share), atol=2e-5)
            total = total + (out[0] - shared)
            (pairs,) = sown["expert_stats"]["pairs"]
            assert int(pairs[:HELD].sum() + pairs[HELD]) == 24 * TOP_K
            counted += int(pairs[:HELD].sum())
        assert counted == 24 * TOP_K     # every pair is some share's
        np.testing.assert_allclose(total + shared, whole, atol=5e-5)
        # the whole layer through the program, all 16 held
        out = _served_layer(0, held=E).apply({"params": params}, x)
        np.testing.assert_allclose(out[0], whole, atol=2e-5)


def test_a_skewed_router_drops_nothing(whole_layer):
    """One held expert takes a pair of EVERY token (its selection bias
    lifts it over all others): 24 rows in tiles of 8 where an even
    load gives 6; nothing is dropped and the counts add up."""
    from attention_tpu.models.moe import PackedTokens

    reference, x, params = whole_layer
    skew = dict(params, router_bias=params["router_bias"].at[5].set(9.0))
    layer = _served_layer(1)                     # holds experts 4-7
    with jax.default_matmul_precision("highest"):
        out, sown = layer.apply({"params": _share_of(skew, 1)}, x,
                                mutable=["expert_stats"])
        np.testing.assert_allclose(
            out[0], _dense(reference, _share_of(skew, 1), x, 1), atol=2e-5)
        (pairs,) = sown["expert_stats"]["pairs"]
        assert int(pairs[1]) == 24 == int(pairs[:HELD].max())
        assert int(pairs[:HELD].sum() + pairs[HELD]) == 24 * TOP_K
        assert int(pairs[HELD + 1]) == int((pairs[:HELD] > 0).sum())
        # pad tokens of a packed step take no expert and move no row
        slot = jnp.where(jnp.arange(24) < 20, 0, -1)
        padded, sown = layer.apply({"params": _share_of(skew, 1)}, x,
                                   PackedTokens(slot),
                                   mutable=["expert_stats"])
        (pairs,) = sown["expert_stats"]["pairs"]
        assert int(pairs[1]) == 20
        assert int(pairs[:HELD].sum() + pairs[HELD]) == 20 * TOP_K
        np.testing.assert_allclose(padded[0, :20], out[0, :20], atol=1e-6)


def test_the_router_decides_in_float32(whole_layer):
    """bf16 activations feed a float32 router: the scores are those of
    the float32 product, not of a bf16 one."""
    from attention_tpu.models.moe import sigmoid_top_k

    _, x, params = whole_layer
    xb = x.astype(jnp.bfloat16)
    chosen, weight = sigmoid_top_k(
        xb[0], params["router"], params["router_bias"], top_k=TOP_K,
        scale=5.0)
    scores = jax.nn.sigmoid(jnp.dot(xb[0].astype(jnp.float32),
                                    params["router"], precision="highest"))
    want = jax.lax.top_k(scores + params["router_bias"], TOP_K)[1]
    assert weight.dtype == jnp.float32
    assert np.array_equal(np.sort(chosen, -1), np.sort(want, -1))
    np.testing.assert_allclose(weight.sum(-1), 5.0, rtol=1e-6)


@pytest.mark.parametrize("tokens, top_k, held, tile", [
    (24, 4, 4, 8), (7, 3, 2, 8), (40, 22, 64, 8), (200, 4, 4, 32)])
def test_the_layout_puts_every_held_pair_on_a_row_of_its_expert(
        tokens, top_k, held, tile):
    from attention_tpu.ops.experts import (
        expert_layout, layout_rows, row_tile)

    rng = np.random.default_rng(tokens)
    local = np.stack([rng.permutation(3 * held)[:top_k] - held
                      for _ in range(tokens)]).astype(np.int32)
    valid = rng.random(tokens) < 0.8
    lay = expert_layout(jnp.asarray(local), jnp.asarray(valid), held=held,
                        tile=tile)
    rows = layout_rows(tokens, top_k, held, tile)
    here = (local >= 0) & (local < held) & valid[:, None]
    dest = np.asarray(lay.dest)
    assert (dest[~here] == rows).all() and (dest[here] < rows).all()
    assert len(set(dest[here])) == here.sum()           # a row a pair
    assert np.array_equal(np.asarray(lay.counts),
                          np.bincount(local[here], minlength=held))
    tile_expert = np.asarray(lay.tile_expert)
    assert (tile_expert[dest[here] // tile] == local[here]).all()
    token = np.broadcast_to(np.arange(tokens)[:, None], local.shape)
    assert (np.asarray(lay.row_token)[dest[here]] == token[here]).all()
    assert int(lay.num_tiles) == sum(-(-c // tile)
                                     for c in np.asarray(lay.counts))
    assert int(lay.num_tiles) * tile <= rows
    assert row_tile(8) == 8 and row_tile(64) == 8 and row_tile(384) == 32


# -- gated experts behind a softmax router ---------------------------------

def test_the_softmax_router_weighs_without_normalising():
    """Scores are a softmax over EVERY column, the bias selects and
    does not weigh, and the chosen weights are scale x score as they
    are: they do not add up to the scale."""
    from attention_tpu.models.moe import softmax_top_k

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((6, 16)), jnp.bfloat16)
    router = jnp.asarray(rng.standard_normal((16, 12)) / 8, jnp.float32)
    bias = jnp.zeros((12,)).at[7].set(5.0)
    chosen, weight = softmax_top_k(x, router, bias, top_k=3, scale=6.0)
    scores = jax.nn.softmax(jnp.dot(
        x.astype(jnp.float32), router,
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    assert chosen.dtype == jnp.int32 and weight.dtype == jnp.float32
    assert (np.asarray(chosen)[:, 0] == 7).all()       # the bias selects
    np.testing.assert_allclose(
        weight, 6.0 * jnp.take_along_axis(scores, chosen, axis=-1),
        rtol=1e-6)
    assert (np.asarray(weight).sum(axis=1) < 6.0 - 1e-3).all()


@pytest.mark.parametrize("dtype, stored, tol", [
    (jnp.float32, jnp.float32, 2e-5), (jnp.bfloat16, jnp.bfloat16, 3e-2),
    (jnp.bfloat16, jnp.float32, 3e-2)])
def test_the_gated_grouped_product_is_each_rows_own_expert(dtype, stored,
                                                           tol):
    """silu(x Wg) * (x Wu) then Wd, a row tile an expert, the hidden
    width in two tiles; weights cast where they are read."""
    from attention_tpu.ops.experts import (
        expert_layout,
        gated_hidden_tile,
        grouped_gated_experts,
    )

    rng = np.random.default_rng(1)
    held, width, hidden, tokens, tile = 3, 128, 256, 20, 8
    assert gated_hidden_tile(width, hidden, 4) == 128
    assert gated_hidden_tile(6144, 2048, 2) == 256
    local = jnp.asarray(rng.integers(-1, held + 1, size=(tokens, 1)),
                        jnp.int32)
    layout = expert_layout(local, jnp.ones((tokens,), bool), held=held,
                           tile=tile)
    x = jnp.asarray(rng.standard_normal((tokens, width)), dtype)

    def w(*shape):
        return jnp.asarray(rng.standard_normal(shape) / 8, dtype).astype(
            stored)

    wg, wu, wd = w(held, width, hidden), w(held, width, hidden), w(
        held, hidden, width)
    y = grouped_gated_experts(x[layout.row_token], wg, wu, wd, layout,
                              tile=tile)
    assert y.dtype == jnp.float32
    f = lambda a: np.asarray(a, np.float32)  # noqa: E731
    for t in range(tokens):
        e = int(local[t, 0])
        if not 0 <= e < held:
            assert int(layout.dest[t, 0]) == y.shape[0]
            continue
        gate, up = f(x[t]) @ f(wg[e]), f(x[t]) @ f(wu[e])
        want = (gate / (1 + np.exp(-gate)) * up) @ f(wd[e])
        np.testing.assert_allclose(y[int(layout.dest[t, 0])], want,
                                   atol=tol * np.abs(want).max())
