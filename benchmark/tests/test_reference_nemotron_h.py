"""``reference_nemotron_h``: the Mamba-2 recurrence on a two-token case
computed by hand (one head, the gate before the grouped norm, the skip), the
convolution's bias and start, attention without a rotary embedding and its
KV groups, the router's rule (bias in the selection only, renormalised, times
the scale, the held share), causality of the whole forward, and that the file
imports nothing from ``paddle_tpu``."""
import os

import jax
import jax.numpy as jnp
import numpy as np

import reference_nemotron_h as ref

from conftest import BENCH

HY = {"ssd_heads": 1, "ssd_head_dim": 2, "ssd_groups": 1, "ssd_state": 2,
      "eps": 1e-5}


def test_imports_nothing_from_the_program():
    with open(os.path.join(BENCH, "reference_nemotron_h.py")) as f:
        text = f.read()
    assert "import paddle_tpu" not in text and "from paddle_tpu" not in text


def silu(x):
    return x / (1.0 + np.exp(-x))


def _mamba_weights(hid=6):
    # u = [z (2) | x (2) | B (1.. | C] laid out by an identity projection:
    # hidden 6 = z 2 + x 2 + B 2 ... C reads B's rows too (a second identity)
    w_in = np.zeros((hid, 2 + 2 + 2 + 2), np.float32)
    for i in range(6):
        w_in[i, i] = 1.0
    w_in[4, 6] = w_in[5, 7] = 1.0       # C = B
    return {
        "ssd_in": jnp.asarray(w_in), "ssd_dt": jnp.zeros((hid, 1)),
        "ssd_conv": jnp.asarray(np.array([[0.0] * 6] * 3 + [[1.0] * 6],
                                         np.float32)),
        "ssd_conv_b": jnp.zeros((6,)),
        "ssd_dt_b": jnp.asarray([0.5]), "ssd_A_log": jnp.asarray([np.log(2.0)]),
        "ssd_D": jnp.asarray([0.5]), "ssd_norm": jnp.asarray([1.0, 2.0]),
        "ssd_out": jnp.eye(2),
    }


def test_mamba2_two_tokens_by_hand():
    """One head of 2 channels, state 2, the convolution the identity tap, dt
    = softplus of its bias alone."""
    w = _mamba_weights()
    u = np.array([[1.0, -2.0, 0.3, 0.7, -1.0, 0.5],
                  [0.5, 3.0, -0.4, 1.1, 0.8, -0.6]], np.float32)
    dt = np.log1p(np.exp(0.5))
    decay = np.exp(-2.0 * dt)
    state = np.zeros((2, 2))
    want = []
    for t in range(2):
        z, x, b = u[t, :2], silu(u[t, 2:4]), silu(u[t, 4:6])
        state = decay * state + dt * x[:, None] * b[None, :]
        y = state @ b + 0.5 * x            # C = B
        g = y * silu(z)                    # the gate BEFORE the norm
        g = g / np.sqrt(np.mean(g * g) + 1e-5) * np.array([1.0, 2.0])
        want.append(g)
    got = ref.mamba2(jnp.asarray(u), w, HY)
    assert np.allclose(np.asarray(got), np.stack(want), atol=1e-5)


def test_convolution_bias_and_start():
    w = dict(_mamba_weights())
    conv = np.zeros((4, 6), np.float32)
    conv[:, 0] = [0.1, 0.2, 0.3, 0.4]       # x[0]'s channel: four taps
    conv[3, 1] = 1.0                        # x[1]'s: the identity tap
    bias = np.zeros((6,), np.float32)
    bias[0] = 1.0
    w.update(ssd_conv=jnp.asarray(conv), ssd_conv_b=jnp.asarray(bias),
             ssd_D=jnp.asarray([1.0]), ssd_norm=jnp.ones((2,)))
    # B = C = silu(0) = 0: y = D x alone; z the same on both channels, so the
    # norm keeps the channels' ratio, and x[1] = silu(1) is known
    u = np.zeros((4, 6), np.float32)
    u[:, 2] = [1.0, 2.0, 3.0, 4.0]
    u[:, 3] = 1.0
    u[:, 0] = u[:, 1] = 3.0
    x0 = silu(1.0 + np.array([0.4 * 1, 0.3 * 1 + 0.4 * 2,
                              0.2 * 1 + 0.3 * 2 + 0.4 * 3,
                              0.1 * 1 + 0.2 * 2 + 0.3 * 3 + 0.4 * 4]))
    got = np.asarray(ref.mamba2(jnp.asarray(u), w, HY))
    assert np.allclose(got[:, 0] / got[:, 1], x0 / silu(1.0), atol=1e-4)
    assert np.allclose((got ** 2).mean(-1), 1.0, atol=1e-3)


def test_attention_groups_and_no_rotation():
    rng = np.random.default_rng(0)
    hy = {"num_heads": 4, "num_kv_heads": 2, "head_dim": 4}
    w = {"wq": jnp.asarray(rng.standard_normal((16, 8)), jnp.float32),
         "wk": jnp.asarray(rng.standard_normal((8, 8)), jnp.float32),
         "wv": jnp.asarray(rng.standard_normal((8, 8)), jnp.float32),
         "wo": jnp.eye(16)}
    u = rng.standard_normal((5, 8)).astype(np.float32)
    got = np.asarray(ref.attention(jnp.asarray(u), w, hy))
    q = (u @ np.asarray(w["wq"]).T).reshape(5, 4, 4)
    k = (u @ np.asarray(w["wk"])).reshape(5, 2, 4)
    v = (u @ np.asarray(w["wv"])).reshape(5, 2, 4)
    want = np.zeros((5, 4, 4))
    for h in range(4):
        g = h // 2                          # two query heads a KV head
        for t in range(5):
            s = q[t, h] @ k[:t + 1, g].T / 2.0
            p = np.exp(s - s.max())
            want[t, h] = (p / p.sum()) @ v[:t + 1, g]
    assert np.allclose(got, want.reshape(5, 16), atol=1e-5)
    # no position enters: the same tokens later in a sequence whose earlier
    # rows are masked out by a permutation-invariant softmax: row 0 is v[0]
    assert np.allclose(got[0].reshape(4, 4), v[0][[0, 0, 1, 1]], atol=1e-5)


def test_router_rule_and_share():
    hy = {"top_k": 2, "norm_topk_prob": True, "first_held": 2,
          "routed_scale": 2.5}
    scores = jnp.asarray([[0.50, 0.49, 0.10, 0.30]])
    bias = jnp.asarray([0.0, 0.02, 0.0, 0.0])
    none = jnp.full((1, 2), -1)
    top_e, top_s = ref.route(scores, bias, none, hy)
    # the bias decides the near-tie (1 before 0) and is in no weight
    assert np.asarray(top_e).tolist() == [[1, 0]]
    assert np.allclose(np.asarray(top_s), 2.5 * np.array([0.49, 0.50]) / 0.99)
    forced = jnp.asarray([[3, 0]])
    top_e, top_s = ref.route(scores, bias, forced, hy)
    assert np.asarray(top_e).tolist() == [[3, 0]]
    assert np.allclose(np.asarray(top_s), 2.5 * np.array([0.30, 0.50]) / 0.80)
    # the held share: experts 2 and 3 of four; a pick on 0 adds nothing here
    rng = np.random.default_rng(1)
    u = jnp.asarray(rng.standard_normal((3, 4)), jnp.float32)
    w = {"router": jnp.asarray(rng.standard_normal((4, 4)), jnp.float32),
         "router_bias": jnp.zeros((4,)),
         "w_up": jnp.asarray(rng.standard_normal((2, 6, 4)), jnp.float32),
         "w_down": jnp.asarray(rng.standard_normal((2, 6, 4)), jnp.float32),
         "ws_up": jnp.zeros((4, 8)), "ws_down": jnp.zeros((8, 4))}
    out, sel = ref.routed_ffn(u, w, jnp.full((3, 2), -1), hy)
    s = np.asarray(jax.nn.sigmoid(u @ w["router"]))
    assert np.allclose(np.asarray(sel), s, atol=1e-6)
    want = np.zeros((3, 4))
    for t in range(3):
        picked = np.argsort(s[t])[-2:]
        for e in picked:
            if e >= 2:
                h = np.maximum(np.asarray(u[t]) @ np.asarray(w["w_up"][e - 2]).T,
                               0) ** 2
                want[t] += 2.5 * s[t, e] / s[t, picked].sum() \
                    * (h @ np.asarray(w["w_down"][e - 2]))
    assert np.allclose(np.asarray(out), want, atol=1e-4)


def test_the_forward_is_causal():
    """A later token changes no earlier logit, through every kind of block."""
    import sys
    sys.path.insert(0, os.path.dirname(BENCH))
    import paddle_tpu as paddle
    from paddle_tpu.models.nemotron_h import (NemotronHForCausalLM,
                                              nemotron_h_tiny)
    paddle.seed(5)
    model = NemotronHForCausalLM(nemotron_h_tiny())
    w, hy = ref.weights_of(model), ref.hyper_of(model.config)
    assert hy["pattern"] == "MEM*EME" and hy["first_held"] == 0
    ids = np.random.RandomState(0).randint(0, 256, (1, 24)).astype(np.int32)
    other = ids.copy()
    other[0, 16:] = (other[0, 16:] + 7) % 256
    at = np.arange(24)[None]
    a, sa = ref.logits_at(w, hy, ids, at, with_router=True)
    b, _ = ref.logits_at(w, hy, other, at, with_router=True)
    assert np.allclose(np.asarray(a)[:, :16], np.asarray(b)[:, :16],
                       atol=1e-5)
    assert np.abs(np.asarray(a)[:, 16:] - np.asarray(b)[:, 16:]).max() > 1e-3
    assert np.asarray(sa).shape == (3, 1, 24, 8)
