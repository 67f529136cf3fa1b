"""The dropless routed FFN against its plain oracle (ISSUE 26, tests (a)):
``moe_ffn`` (pairs ordered by expert, grouped matmuls) equals
``moe_ffn_reference`` (every expert over every row, masked) under uniform
routing, under routing forced onto one expert set, and with dead rows
interleaved; the routing summary counts only live pairs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.moe_ffn import STATS, moe_ffn, moe_ffn_reference

T, H, E, I, K = 37, 64, 8, 32, 2


def _weights(seed, router_scale=1.0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(k[0], (T, H)),
            jax.random.normal(k[1], (H, E)) * router_scale,
            jax.random.normal(k[2], (E, H, I)) * 0.1,
            jax.random.normal(k[3], (E, H, I)) * 0.1,
            jax.random.normal(k[4], (E, I, H)) * 0.1)


def _forced_router(experts):
    """A router that sends every token to ``experts``, whatever it holds:
    zero weights would tie, so a constant column bias is folded in through
    a hidden unit that every test input shares."""
    r = np.zeros((H, E), np.float32)
    r[0, list(experts)] = 50.0
    return jnp.asarray(r)


def _live(kind):
    if kind == "all":
        return None
    if kind == "interleaved":
        return jnp.arange(T) % 3 != 1
    if kind == "one":
        return jnp.arange(T) == 5
    return jnp.zeros(T, bool)               # "none"


@pytest.mark.parametrize("live_kind", ["all", "interleaved", "one", "none"])
@pytest.mark.parametrize("routing", ["uniform", "forced"])
def test_moe_ffn_equals_reference(routing, live_kind):
    h, r, wg, wu, wd = _weights(0)
    if routing == "forced":
        # every token on the same K experts: the skew a dropless FFN must
        # take without a capacity
        h = h.at[:, 0].set(1.0)
        r = _forced_router((2, 5))
    live = _live(live_kind)
    out, stats = jax.jit(lambda *a: moe_ffn(*a, top_k=K, live=live))(
        h, r, wg, wu, wd)
    ref, ref_stats = jax.jit(
        lambda *a: moe_ffn_reference(*a, top_k=K, live=live))(
        h, r, wg, wu, wd)
    scale = max(float(jnp.max(jnp.abs(ref))), 1e-6)
    assert float(jnp.max(jnp.abs(out - ref))) <= 1e-5 * scale
    n_live = T if live is None else int(jnp.sum(live))
    stats, ref_stats = np.asarray(stats), np.asarray(ref_stats)
    assert (stats == ref_stats).all()
    pairs, touched, fullest, picks = (int(stats[STATS.index(n)])
                                      for n in STATS)
    # dropless: K per live row; every expert held, so every pick a pair
    assert pairs == picks == n_live * K
    if routing == "forced" and n_live:
        assert touched == K and fullest == n_live
    if not n_live:
        assert touched == 0 and float(jnp.max(jnp.abs(out))) == 0.0
    if live is not None:                        # a dead row gets no FFN
        assert float(jnp.max(jnp.abs(jnp.where(live[:, None], 0, out)))) == 0


def test_renormalize_divides_the_picked_weights_by_their_sum():
    h, r, wg, wu, wd = _weights(1, router_scale=0.05)
    raw, _ = moe_ffn(h, r, wg, wu, wd, top_k=K)
    ren, _ = moe_ffn(h, r, wg, wu, wd, top_k=K, renormalize=True)
    p = jax.nn.softmax(h @ r, -1)
    top = jnp.sum(jax.lax.top_k(p, K)[0], -1, keepdims=True)
    assert float(jnp.max(jnp.abs(ren * top - raw))) <= 1e-5
    assert float(jnp.max(jnp.abs(ren - raw))) > 1e-2   # and it matters


def test_leading_dims_and_one_program_for_every_routing():
    h, r, wg, wu, wd = _weights(2)
    f = jax.jit(lambda h, r, live: moe_ffn(h, r, wg, wu, wd, top_k=K,
                                           live=live))
    h3 = h[:36].reshape(3, 12, H)
    live = jnp.ones((3, 12), bool)
    out, _ = f(h3, r, live)
    assert out.shape == h3.shape
    f(h3, _forced_router((0, 1)), live.at[0].set(False))
    assert f._cache_size() == 1


def test_a_layer_of_a_stack_is_read_in_place():
    """What a layer scan hands the FFN: the expert weights stacked over
    layers and the layer's index; the result is that layer's."""
    h, r, wg, wu, wd = _weights(3)
    stacks = [jnp.stack([0 * w, w, 2 * w]) for w in (wg, wu, wd)]
    f = jax.jit(lambda layer: moe_ffn(h, r, *stacks, top_k=K, layer=layer))
    want, stats = moe_ffn(h, r, wg, wu, wd, top_k=K)
    got, got_stats = f(jnp.int32(1))
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-6
    assert (np.asarray(stats) == np.asarray(got_stats)).all()
    assert float(jnp.max(jnp.abs(f(jnp.int32(0))[0]))) == 0.0
    assert f._cache_size() == 1
