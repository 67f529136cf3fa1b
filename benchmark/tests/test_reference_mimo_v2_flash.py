"""``reference_mimo_v2_flash``: each particular of the model on a small case
computed by hand in numpy (the partial rotation, the KV groups, keys wider
than values, the value scale, the window's edge and the sink as one more
column; the router's rule, the held share and nothing beside it; the dense
layer in blocks), the layer plan from the two lists, causality of the whole
forward, the model's ``forward`` against it, and that the file imports nothing
from ``paddle_tpu``."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_mimo_v2_flash as ref

from conftest import BENCH


def test_imports_nothing_from_the_program():
    with open(os.path.join(BENCH, "reference_mimo_v2_flash.py")) as f:
        text = f.read()
    assert "import paddle_tpu" not in text and "from paddle_tpu" not in text


def sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def silu(x):
    return x * sigmoid(x)


HY = {"num_heads": 4, "head_dim": 6, "v_head_dim": 4, "rotary": 2,
      "theta": 100.0, "swa_theta": 10.0, "window": 3, "v_scale": 0.5,
      "eps": 1e-5}


def _attn_weights(nkv, hid=8, seed=0):
    rng = np.random.default_rng(seed)

    def rand(*s):
        return jnp.asarray(rng.standard_normal(s), jnp.float32)

    return {"wq": rand(hid, 4 * 6), "wk": rand(hid, nkv * 6),
            "wv": rand(hid, nkv * 4), "wo": rand(4 * 4, hid),
            "sink": jnp.asarray([0.5, -1.0, 2.0, 0.0], jnp.float32)}


def _by_hand(u, w, nkv, window):
    """The attention of section 1 in numpy loops: a head and a query at a
    time."""
    S = u.shape[0]
    q = (u @ np.asarray(w["wq"])).reshape(S, 4, 6)
    k = (u @ np.asarray(w["wk"])).reshape(S, nkv, 6)
    v = 0.5 * (u @ np.asarray(w["wv"])).reshape(S, nkv, 4)
    theta = 10.0 if window else 100.0

    def rot(x):
        out = x.copy()
        for s in range(S):
            # the FIRST 2 values of a head: one pair, half-split (a, b)
            c, n = np.cos(s * theta ** 0.0), np.sin(s * theta ** 0.0)
            a, b = x[s, :, 0].copy(), x[s, :, 1].copy()
            out[s, :, 0], out[s, :, 1] = a * c - b * n, b * c + a * n
        return out

    q, k = rot(q), rot(k)
    o = np.zeros((S, 4, 4))
    for h in range(4):
        kv = h // (4 // nkv)
        for i in range(S):
            seen = [j for j in range(i + 1) if not window or j > i - 3]
            s = np.array([q[i, h] @ k[j, kv] for j in seen]) * 6 ** -0.5
            e = np.exp(s)
            den = e.sum() + (np.exp(float(w["sink"][h])) if window else 0.0)
            o[i, h] = sum(e[n] / den * v[j, kv] for n, j in enumerate(seen))
    return o.reshape(S, 16) @ np.asarray(w["wo"])


@pytest.mark.parametrize("nkv,window", [(2, False), (4, True), (1, True)])
def test_attention_by_hand(nkv, window):
    """Keys of 6 under values of 4, 4 query heads on ``nkv`` KV heads, the
    first pair of a head rotated, values scaled by 0.5; a full layer (causal,
    plain softmax, theta 100) and a window layer (the last 3 keys, the sink
    one more column with no value, theta 10)."""
    rng = np.random.default_rng(1)
    u = rng.standard_normal((7, 8)).astype(np.float32)
    w = _attn_weights(nkv)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.attention(jnp.asarray(u), w, HY, window))
    assert np.allclose(got, _by_hand(u, w, nkv, window), atol=2e-4)


def test_rotation_leaves_the_rest_of_a_head():
    x = jnp.asarray(np.arange(2 * 1 * 6, dtype=np.float32).reshape(2, 1, 6))
    got = np.asarray(ref.rotate(x, 4, 100.0))
    assert np.allclose(got[0], np.asarray(x)[0])        # position 0
    assert np.allclose(got[1, 0, 4:], [10.0, 11.0])     # past the first 4
    # half-split inside the 4: pairs (0, 2) and (1, 3)
    a, b = np.array([6.0, 7.0]), np.array([8.0, 9.0])
    inv = 100.0 ** (-np.arange(2) * 2.0 / 4)
    assert np.allclose(got[1, 0, :2], a * np.cos(inv) - b * np.sin(inv))
    assert np.allclose(got[1, 0, 2:4], b * np.cos(inv) + a * np.sin(inv))


def test_router_rule_share_and_nothing_beside_it():
    rng = np.random.default_rng(2)
    S, hid, E, wid = 5, 6, 8, 4
    u = rng.standard_normal((S, hid)).astype(np.float32)
    router = rng.standard_normal((hid, E)).astype(np.float32)
    bias = (rng.standard_normal(E) * 0.5).astype(np.float32)
    held = slice(2, 5)                                  # experts 2, 3, 4
    w = {n: rng.standard_normal(s).astype(np.float32) * 0.3
         for n, s in (("w_gate", (E, hid, wid)), ("w_up", (E, hid, wid)),
                      ("w_down", (E, wid, hid)))}
    hy = {"top_k": 3, "norm_topk_prob": True, "routed_scale": 1.0,
          "first_held": 2}
    with jax.default_matmul_precision("highest"):
        got, probs = ref.routed_ffn(
            jnp.asarray(u), dict(
                router=jnp.asarray(router), router_bias=jnp.asarray(bias),
                **{n: jnp.asarray(a[held]) for n, a in w.items()}),
            jnp.full((S, 3), -1), hy)
    s = sigmoid(u @ router)
    want = np.zeros((S, hid))
    for t in range(S):
        picked = np.argsort(s[t] + bias)[-3:]           # by s + c
        total = s[t, picked].sum()                      # of s, not of s + c
        for e in picked:
            if 2 <= e < 5:                              # the held share
                want[t] += s[t, e] / total * (
                    (silu(u[t] @ w["w_gate"][e]) * (u[t] @ w["w_up"][e]))
                    @ w["w_down"][e])
    assert np.allclose(np.asarray(got), want, atol=1e-5)
    assert np.allclose(np.asarray(probs), s + bias, atol=1e-6)
    # told the picks, it follows them
    told = jnp.asarray(np.tile([2, 3, 4], (S, 1)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        forced, _ = ref.routed_ffn(
            jnp.asarray(u), dict(
                router=jnp.asarray(router), router_bias=jnp.asarray(bias),
                **{n: jnp.asarray(a[held]) for n, a in w.items()}), told, hy)
    assert not np.allclose(np.asarray(forced), want, atol=1e-3)


def test_the_dense_layer_in_blocks_is_the_whole():
    rng = np.random.default_rng(3)
    u = rng.standard_normal((4, 6)).astype(np.float32)
    w = {"w_gate": rng.standard_normal((6, 4096)).astype(np.float32) * 0.1,
         "w_up": rng.standard_normal((6, 4096)).astype(np.float32) * 0.1,
         "w_down": rng.standard_normal((4096, 6)).astype(np.float32) * 0.1}
    assert ref.FFN_BLOCK == 2048                # two blocks
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.dense_ffn(
            jnp.asarray(u), {n: jnp.asarray(a) for n, a in w.items()}))
        # a width that is no whole number of blocks is an error, not a cut
        with pytest.raises(ValueError):
            ref.dense_ffn(jnp.asarray(u), {
                n: jnp.asarray(a[:3000] if n == "w_down" else a[:, :3000])
                for n, a in w.items()})
    want = (silu(u @ w["w_gate"]) * (u @ w["w_up"])) @ w["w_down"]
    assert np.allclose(got, want, atol=1e-4)


def test_the_layer_plan_follows_the_two_lists():
    plan = ref.layer_plan({"pattern": (0, 1, 1, 1, 1, 1, 0),
                           "moe": (0, 1, 1, 1, 1, 1, 1)})
    assert plan[0] == ("dense", None, 0, False, False)
    assert plan[1:6] == [("window", j, 0, True, True) for j in range(5)]
    assert plan[6] == ("full", None, 0, False, True)
    two = ref.layer_plan({"pattern": (0, 1, 1, 0, 1, 1, 0),
                          "moe": (0, 1, 1, 1, 1, 1, 1)})
    assert [(p[0], p[1], p[2]) for p in two[1:]] == [
        ("window", 0, 0), ("window", 1, 0), ("full", None, 0),
        ("window", 0, 1), ("window", 1, 1), ("full", None, 1)]


def test_forward_is_causal_and_the_models_forward_agrees():
    import paddle_tpu as paddle
    from paddle_tpu.models.mimo_v2_flash import (MiMoV2FlashForCausalLM,
                                                 mimo_v2_flash_tiny)
    paddle.seed(5)
    model = MiMoV2FlashForCausalLM(mimo_v2_flash_tiny(
        decode_attention="jnp"))
    weights, hyper = ref.weights_of(model), ref.hyper_of(model.config)
    rng = np.random.default_rng(6)
    ids = rng.integers(1, 256, (1, 40)).astype(np.int32)
    at = np.arange(40)[None]
    base, probs = ref.logits_at(weights, hyper, ids, at, with_router=True)
    assert base.shape == (1, 40, 256) and probs.shape == (6, 1, 40, 8)
    changed = ids.copy()
    changed[0, 30:] = rng.integers(1, 256, 10)
    moved = np.asarray(ref.logits_at(weights, hyper, changed, at))
    assert np.allclose(moved[0, :30], np.asarray(base)[0, :30], atol=1e-6)
    assert not np.allclose(moved[0, 30:], np.asarray(base)[0, 30:],
                           atol=1e-3)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.forward(ids).value)
    assert np.abs(got - np.asarray(base)).max() \
        <= 1e-4 * np.abs(np.asarray(base)).max()
