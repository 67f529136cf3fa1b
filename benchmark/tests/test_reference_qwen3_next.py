"""``reference_qwen3_next``: each particular of the model on a small case
computed by hand in numpy (the zero-centred norm, the delta rule on two tokens
with two value heads on one key head, the convolution's start, the attention's
gate, head norm, partial rotation and KV groups, the router's rule and the
held share, the shared expert's gate), causality of the whole forward, the
model's ``forward`` against it, and that the file imports nothing from
``paddle_tpu``."""
import os

import jax
import jax.numpy as jnp
import numpy as np

import reference_qwen3_next as ref

from conftest import BENCH


def test_imports_nothing_from_the_program():
    with open(os.path.join(BENCH, "reference_qwen3_next.py")) as f:
        text = f.read()
    assert "import paddle_tpu" not in text and "from paddle_tpu" not in text


def silu(x):
    return x / (1.0 + np.exp(-x))


def sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def test_the_norm_weight_is_zero_centred():
    x = np.array([[3.0, -4.0]], np.float32)
    got = np.asarray(ref.norm(jnp.asarray(x), jnp.asarray([0.5, -0.25]),
                              1e-6))
    rms = np.sqrt(12.5 + 1e-6)
    assert np.allclose(got, x / rms * np.array([1.5, 0.75]), atol=1e-6)
    # a weight of 0 is the plain norm, not a zero
    assert np.allclose(np.asarray(ref.norm(jnp.asarray(x), jnp.zeros(2),
                                           1e-6)), x / rms, atol=1e-6)


HY_GDN = {"key_heads": 1, "value_heads": 2, "dk": 2, "dv": 2, "eps": 1e-6}


def _gdn_weights(hid=8):
    """hidden 8 -> u = [q 2 | k 2 | v 4] by an identity, z and [a | b] from
    small dense matrices; the convolution the identity tap."""
    rng = np.random.default_rng(0)
    wqkv = np.zeros((hid, 8), np.float32)
    wqkv[np.arange(8), np.arange(8)] = 1.0
    conv = np.zeros((4, 8), np.float32)
    conv[3] = 1.0
    return {"gdn_wqkv": jnp.asarray(wqkv),
            "gdn_wz": jnp.asarray(rng.standard_normal((hid, 4)), jnp.float32),
            "gdn_wab": jnp.asarray(rng.standard_normal((hid, 4)) * 0.5,
                                   jnp.float32),
            "gdn_conv": jnp.asarray(conv),
            "gdn_A_log": jnp.asarray(np.log([0.5, 2.0]), jnp.float32),
            "gdn_dt_bias": jnp.asarray([0.1, -0.2], jnp.float32),
            "gdn_o_norm": jnp.asarray([1.0, 2.0], jnp.float32),
            "gdn_wo": jnp.eye(4, dtype=jnp.float32)}


def test_delta_rule_two_tokens_two_value_heads_on_one_key_head():
    w = _gdn_weights()
    u = np.random.default_rng(1).standard_normal((2, 8)).astype(np.float32)
    a_log, dt_b = np.log([0.5, 2.0]), np.array([0.1, -0.2])
    ab = u @ np.asarray(w["gdn_wab"])
    z = (u @ np.asarray(w["gdn_wz"])).reshape(2, 2, 2)
    state = np.zeros((2, 2, 2))
    want = []
    for t in range(2):
        x = silu(u[t])
        q = x[0:2] / np.sqrt((x[0:2] ** 2).sum() + 1e-6) * 2 ** -0.5
        k = x[2:4] / np.sqrt((x[2:4] ** 2).sum() + 1e-6)
        v = x[4:].reshape(2, 2)
        g = -np.exp(a_log) * np.log1p(np.exp(ab[t, :2] + dt_b))
        beta = sigmoid(ab[t, 2:])           # never doubled
        out = []
        for h in range(2):                  # BOTH value heads on key head 0
            s = np.exp(g[h]) * state[h]
            r = v[h] - s.T @ k
            s = s + np.outer(k, beta[h] * r)
            state[h] = s
            o = s.T @ q
            o = o / np.sqrt(np.mean(o * o) + 1e-6) * np.array([1.0, 2.0])
            out.append(o * silu(z[t, h]))   # w_o as it is, no 1 +
        want.append(np.concatenate(out))
    got = np.asarray(ref.delta_net(jnp.asarray(u), w, HY_GDN))
    assert np.allclose(got, np.stack(want), atol=1e-5)


def test_convolution_start_is_zero_and_has_no_bias():
    w = dict(_gdn_weights())
    conv = np.zeros((4, 8), np.float32)
    conv[:, 4] = [0.1, 0.2, 0.3, 0.4]       # v[0, 0]'s channel: four taps
    w["gdn_conv"] = jnp.asarray(conv)
    u = np.zeros((4, 8), np.float32)
    u[:, 4] = [1.0, 2.0, 3.0, 4.0]
    x = u @ np.asarray(w["gdn_wqkv"])
    ext = np.pad(x, ((3, 0), (0, 0)))
    want = silu(sum(conv[j] * ext[j:j + 4] for j in range(4)))[:, 4]
    assert np.allclose(want, silu(np.array([0.4, 1.1, 2.0, 3.0])))
    # (through the layer: q = k = 0 after the norm's eps, so o = 0; the
    # convolution is the file's own few lines, checked above by hand)
    assert np.isfinite(np.asarray(ref.delta_net(jnp.asarray(u), w,
                                                HY_GDN))).all()


def test_attention_gate_head_norm_partial_rotation_and_groups():
    rng = np.random.default_rng(0)
    hy = {"num_heads": 4, "num_kv_heads": 2, "head_dim": 8, "rotary": 4,
          "theta": 100.0, "eps": 1e-6}
    w = {"wq": jnp.asarray(rng.standard_normal((6, 64)), jnp.float32),
         "wk": jnp.asarray(rng.standard_normal((6, 16)), jnp.float32),
         "wv": jnp.asarray(rng.standard_normal((6, 16)), jnp.float32),
         "q_norm": jnp.asarray(rng.standard_normal(8) * 0.1, jnp.float32),
         "k_norm": jnp.asarray(rng.standard_normal(8) * 0.1, jnp.float32),
         "wo": jnp.eye(32, dtype=jnp.float32)}
    u = rng.standard_normal((5, 6)).astype(np.float32)
    got = np.asarray(ref.attention(jnp.asarray(u), w, hy))

    def head_norm(x, wn):
        return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + 1e-6) \
            * (1.0 + np.asarray(wn))

    def rot(x):                             # x [S, heads, 8]: first 4 rotated
        out = x.copy()
        for t in range(x.shape[0]):
            for i in range(2):
                ang = t * 100.0 ** (-2.0 * i / 4)
                a, b = x[t, :, i], x[t, :, 2 + i]
                out[t, :, i] = a * np.cos(ang) - b * np.sin(ang)
                out[t, :, 2 + i] = b * np.cos(ang) + a * np.sin(ang)
        return out

    qg = (u @ np.asarray(w["wq"])).reshape(5, 4, 16)
    q, gate = qg[..., :8], qg[..., 8:]      # a head: its query, then its gate
    k = (u @ np.asarray(w["wk"])).reshape(5, 2, 8)
    v = (u @ np.asarray(w["wv"])).reshape(5, 2, 8)
    q, k = rot(head_norm(q, w["q_norm"])), rot(head_norm(k, w["k_norm"]))
    want = np.zeros((5, 4, 8))
    for h in range(4):
        g = h // 2                          # two query heads a KV head
        for t in range(5):
            s = q[t, h] @ k[:t + 1, g].T * 8 ** -0.5
            p = np.exp(s - s.max())
            want[t, h] = (p / p.sum()) @ v[:t + 1, g] * sigmoid(gate[t, h])
    assert np.allclose(got, want.reshape(5, 32), atol=1e-5)
    # the last 4 values of a head are not rotated: position 3's key is
    assert np.allclose(rot(k)[3, :, 4:], k[3, :, 4:])


def test_router_rule_share_and_the_shared_experts_gate():
    hy = {"top_k": 2, "norm_topk_prob": True, "first_held": 2}
    rng = np.random.default_rng(2)
    u = rng.standard_normal((3, 4)).astype(np.float32)
    w = {"router": jnp.asarray(rng.standard_normal((4, 6)) * 2, jnp.float32),
         # experts 2 and 3 of the router's 6 are held
         "w_gate": jnp.asarray(rng.standard_normal((2, 4, 3)), jnp.float32),
         "w_up": jnp.asarray(rng.standard_normal((2, 4, 3)), jnp.float32),
         "w_down": jnp.asarray(rng.standard_normal((2, 3, 4)), jnp.float32),
         "ws_gate": jnp.asarray(rng.standard_normal((4, 3)), jnp.float32),
         "ws_up": jnp.asarray(rng.standard_normal((4, 3)), jnp.float32),
         "ws_down": jnp.asarray(rng.standard_normal((3, 4)), jnp.float32),
         "ws_sgate": jnp.asarray(rng.standard_normal((4, 1)), jnp.float32)}
    forced = jnp.full((3, 2), -1)
    got, probs = ref.routed_ffn(jnp.asarray(u), w, forced, hy)
    logits = u @ np.asarray(w["router"])
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    assert np.allclose(np.asarray(probs), p, atol=1e-6)

    def swiglu(x, g, up, d):
        return (silu(x @ np.asarray(g)) * (x @ np.asarray(up))) @ np.asarray(d)

    want = sigmoid(u @ np.asarray(w["ws_sgate"])) * swiglu(
        u, w["ws_gate"], w["ws_up"], w["ws_down"])
    for t in range(3):
        top = np.argsort(p[t])[-2:]
        weight = p[t, top] / p[t, top].sum()        # renormalised
        for e, wt in zip(top, weight):
            if 2 <= e < 4:                          # held here
                want[t] += wt * swiglu(u[t], w["w_gate"][e - 2],
                                       w["w_up"][e - 2], w["w_down"][e - 2])
    assert np.allclose(np.asarray(got), want, atol=1e-5)
    # teacher-forced picks replace the router's own, at its probabilities
    told = jnp.asarray([[2, 3]] * 3)
    forced_out, _ = ref.routed_ffn(jnp.asarray(u), w, told, hy)
    want2 = sigmoid(u @ np.asarray(w["ws_sgate"])) * swiglu(
        u, w["ws_gate"], w["ws_up"], w["ws_down"])
    for t in range(3):
        wt = p[t, [2, 3]] / p[t, [2, 3]].sum()
        for j in range(2):
            want2[t] += wt[j] * swiglu(u[t], w["w_gate"][j], w["w_up"][j],
                                       w["w_down"][j])
    assert np.allclose(np.asarray(forced_out), want2, atol=1e-5)


def _tiny():
    import paddle_tpu as paddle
    from paddle_tpu.models.qwen3_next import (Qwen3NextForCausalLM,
                                              qwen3_next_tiny)
    paddle.seed(3)
    return Qwen3NextForCausalLM(qwen3_next_tiny(num_hidden_layers=4,
                                                decode_attention="jnp"))


def test_forward_is_causal_and_the_models_forward_agrees():
    model = _tiny()
    weights, hyper = ref.weights_of(model), ref.hyper_of(model.config)
    weights["served_picks"] = None
    rng = np.random.default_rng(5)
    ids = rng.integers(1, 256, (1, 24)).astype(np.int32)
    at = np.arange(24)[None]
    with jax.default_matmul_precision("highest"):
        base, probs = ref.logits_at(weights, hyper, ids, at, with_router=True)
        changed = ids.copy()
        changed[0, 16:] = rng.integers(1, 256, 8)
        other = ref.logits_at(weights, hyper, changed, at)
        got = model.forward(ids).value
    base, other = np.asarray(base), np.asarray(other)
    assert np.allclose(base[0, :16], other[0, :16], atol=1e-5)
    assert np.abs(base[0, 16:] - other[0, 16:]).max() > 1e-3
    assert probs.shape == (4, 1, 24, 8)
    assert np.allclose(np.asarray(probs).sum(-1), 1.0, atol=1e-5)
    assert np.abs(np.asarray(got)[0] - base[0]).max() \
        <= 1e-4 * np.abs(base).max()
