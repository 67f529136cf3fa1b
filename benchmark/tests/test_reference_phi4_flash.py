"""``reference_phi4_flash``: the selective scan on a two-token case computed
by hand, differential attention on a case where the two softmaxes are known,
the window, the convolution's bias and start, causality of the whole forward,
and that the file imports nothing from ``paddle_tpu``."""
import os

import jax.numpy as jnp
import numpy as np

import reference_phi4_flash as ref

from conftest import BENCH


def test_imports_nothing_from_the_program():
    with open(os.path.join(BENCH, "reference_phi4_flash.py")) as f:
        text = f.read()
    assert "import paddle_tpu" not in text and "from paddle_tpu" not in text


def _mamba_weights(c=2, n=2, hid=2, rank=1):
    eye = np.eye(hid, dtype=np.float32)
    return {
        # a = h, z = h: in_proj is [I | I]
        "ssm_in": jnp.asarray(np.concatenate([eye, eye], 1)),
        "ssm_conv": jnp.asarray([[0.0, 0.0], [0.0, 0.0], [0.0, 0.0],
                                 [1.0, 1.0]], jnp.float32),
        "ssm_conv_b": jnp.zeros((c,), jnp.float32),
        # r = c[0]; B = (c[0], c[1]); C = (1, 1) needs a constant: use c[1]
        "ssm_x": jnp.asarray([[1.0, 1.0, 0.0, 0.0, 0.0],
                              [0.0, 0.0, 1.0, 1.0, 1.0]], jnp.float32),
        "ssm_dt": jnp.zeros((rank, c), jnp.float32),
        "ssm_dt_b": jnp.asarray([0.5, -0.2], jnp.float32),
        "ssm_A_log": jnp.asarray(np.log([[1.0, 2.0], [3.0, 4.0]]),
                                 jnp.float32),
        "ssm_D": jnp.asarray([0.5, 2.0], jnp.float32),
        "ssm_out": jnp.asarray(eye),
    }


def test_selective_scan_two_tokens_by_hand():
    """d_inner = d_state = 2, the convolution the identity tap, dt = softplus
    of its bias alone."""
    w = _mamba_weights()
    h = np.array([[1.0, -2.0], [0.5, 3.0]], np.float32)

    def silu(x):
        return x / (1.0 + np.exp(-x))

    c = silu(h)                                    # conv: last tap 1, bias 0
    dt = np.log1p(np.exp(np.array([0.5, -0.2])))   # softplus(b_dt), r W_dt = 0
    a = -np.array([[1.0, 2.0], [3.0, 4.0]])        # [N, C]
    state = np.zeros((2, 2))
    want = []
    for t in range(2):
        b = np.array([c[t, 0], c[t, 1]])
        cm = np.array([c[t, 1], c[t, 1]])
        state = np.exp(dt[None, :] * a) * state \
            + b[:, None] * (dt * c[t])[None, :]
        y = cm @ state + np.array([0.5, 2.0]) * c[t]
        want.append(y * silu(h[t]))
    out, y = ref.mamba(jnp.asarray(h), w)
    assert np.allclose(np.asarray(out), np.stack(want), atol=1e-6)
    # the memory is y BEFORE the gate
    assert np.allclose(np.asarray(y) * silu(h), np.stack(want), atol=1e-6)


def test_convolution_bias_and_start():
    w = dict(_mamba_weights())
    w["ssm_conv"] = jnp.asarray([[0.1, 0.0], [0.2, 0.0], [0.3, 0.0],
                                 [0.4, 1.0]], jnp.float32)
    w["ssm_conv_b"] = jnp.asarray([1.0, -1.0], jnp.float32)
    # read c back through B: y is linear in it only with care, so test the
    # convolution through a one-channel probe: D * c with the state's part
    # removed (C = 0)
    w["ssm_x"] = jnp.zeros((2, 5), jnp.float32)
    w["ssm_D"] = jnp.ones((2,), jnp.float32)
    h = jnp.asarray([[1.0, 10.0], [2.0, 20.0], [3.0, 30.0], [4.0, 40.0]])
    _, y = ref.mamba(h, w)

    def silu(x):
        return x / (1.0 + np.exp(-x))

    got = np.asarray(y)
    assert np.allclose(got[0], silu(np.array([1.0 + 0.4 * 1.0, -1.0 + 10.0])))
    assert np.allclose(got[3], silu(np.array(
        [1.0 + 0.1 * 1 + 0.2 * 2 + 0.3 * 3 + 0.4 * 4, -1.0 + 40.0])))


def _attn_weights(hd=2):
    return {"lam": jnp.zeros((4, hd), jnp.float32),
            "subln": jnp.ones((2 * hd,), jnp.float32)}


def test_differential_attention_by_hand():
    """One differential head (2 query heads, 2 KV heads of 2), two tokens,
    zero queries: both softmaxes are uniform over the visible keys, lambda =
    exp(0) - exp(0) + lambda_init, so token 1 reads (1 - lambda_init) x the
    mean of V, then the RMSNorm and the (1 - lambda_init) outside."""
    q = jnp.zeros((2, 2, 2), jnp.float32)
    k = jnp.ones((2, 2, 2), jnp.float32)
    v = jnp.asarray([[[1.0, 2.0], [3.0, 4.0]], [[5.0, 6.0], [7.0, 8.0]]])
    mask = jnp.asarray([[True, False], [True, True]])
    init = ref.lambda_init(3)
    got = np.asarray(ref.diff_attention(q, k, v, _attn_weights(), mask,
                                        layer=3, eps=0.0))
    for t, mean in ((0, np.array([1.0, 2.0, 3.0, 4.0])),
                    (1, np.array([3.0, 4.0, 5.0, 6.0]))):
        a = (1.0 - init) * mean
        a = a / np.sqrt(np.mean(a * a)) * (1.0 - init)
        assert np.allclose(got[t], a, atol=1e-5)
    assert abs(init - (0.8 - 0.6 * np.exp(-0.9))) < 1e-12


def test_lambda_subtracts_the_second_map():
    """q2 picks key 0, q1 is uniform: with lambda_init alone the output is
    P1 V - lambda P2 V, which a nonzero learned lambda moves."""
    q = jnp.asarray([[[0.0, 0.0], [9.0, 0.0]], [[0.0, 0.0], [9.0, 0.0]]])
    k = jnp.asarray([[[1.0, 0.0], [5.0, 0.0]], [[1.0, 0.0], [-5.0, 0.0]]])
    v = jnp.asarray([[[1.0, 0.0], [0.0, 0.0]], [[0.0, 1.0], [0.0, 0.0]]])
    mask = jnp.asarray([[True, False], [True, True]])
    w = _attn_weights()
    base = np.asarray(ref.diff_attention(q, k, v, w, mask, layer=1, eps=1e-5))
    w2 = dict(w, lam=jnp.asarray([[1.0, 0.0], [0.7, 0.0], [0.0, 0.0],
                                  [0.0, 0.0]]))
    moved = np.asarray(ref.diff_attention(q, k, v, w2, mask, layer=1,
                                          eps=1e-5))
    assert np.abs(base - moved).max() > 1e-2


def _tiny(window):
    rng = np.random.RandomState(0)
    hid, nh, nkv, hd, inter, vocab, c, n, rank = 8, 4, 2, 2, 6, 11, 16, 4, 1

    def w(*shape):
        return jnp.asarray(0.3 * rng.randn(*shape), jnp.float32)

    def block(lead):
        return {"ln1_w": jnp.ones(lead + (hid,)), "ln1_b": w(*lead, hid),
                "ln2_w": jnp.ones(lead + (hid,)), "ln2_b": w(*lead, hid),
                "w_gate": w(*lead, hid, inter), "w_up": w(*lead, hid, inter),
                "w_down": w(*lead, inter, hid)}

    def mamba(lead):
        return {**block(lead), "ssm_in": w(*lead, hid, 2 * c),
                "ssm_conv": w(*lead, 4, c), "ssm_conv_b": w(*lead, c),
                "ssm_x": w(*lead, c, rank + 2 * n),
                "ssm_dt": w(*lead, rank, c), "ssm_dt_b": w(*lead, c),
                "ssm_A_log": w(*lead, n, c), "ssm_D": w(*lead, c),
                "ssm_out": w(*lead, c, hid)}

    def attn(lead, cross=False):
        out = {**block(lead), "wo": w(*lead, nh * hd, hid),
               "bo": w(*lead, hid), "lam": w(*lead, 4, hd),
               "subln": jnp.ones(lead + (2 * hd,))}
        if cross:
            out.update(wq=w(*lead, hid, nh * hd), bq=w(*lead, nh * hd))
        else:
            width = (nh + 2 * nkv) * hd
            out.update(wqkv=w(*lead, hid, width), bqkv=w(*lead, width))
        return out

    gmu = {**block((1,)), "gmu_in": w(1, hid, c), "gmu_out": w(1, c, hid)}
    weights = {"embed": w(vocab, hid), "final_norm": jnp.ones((hid,)),
               "final_norm_b": w(hid),
               "self_layers": (mamba((2,)), attn((2,))),
               "mid_layers": (mamba(()), attn(())),
               "cross_layers": (gmu, attn((1,), cross=True))}
    hyper = {"num_heads": nh, "num_kv_heads": nkv, "head_dim": hd,
             "window": window, "eps": 1e-5}
    return weights, hyper


def test_the_window_moves_the_logits_only_past_it():
    ids = np.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], np.int32)
    w, narrow = _tiny(3)
    _, wide = _tiny(100)
    at = np.asarray([[2, 7]], np.int32)
    a = np.asarray(ref.logits_at(w, narrow, ids, at))
    b = np.asarray(ref.logits_at(w, wide, ids, at))
    assert a.shape == (1, 2, 11) and np.isfinite(a).all()
    assert np.allclose(a[0, 0], b[0, 0], atol=1e-6)    # position 2: inside
    assert np.abs(a[0, 1] - b[0, 1]).max() > 1e-4      # position 7: past it


def test_causality_of_the_whole_forward():
    """A later token does not move an earlier position's logits: through the
    scans, the window layers, the full layer and the cross layers."""
    w, hyper = _tiny(3)
    a = np.asarray(ref.logits_at(w, hyper, np.asarray([[1, 2, 3, 4, 5]]),
                                 np.asarray([[2]])))
    b = np.asarray(ref.logits_at(w, hyper, np.asarray([[1, 2, 3, 9, 7]]),
                                 np.asarray([[2]])))
    assert np.allclose(a, b, atol=1e-6)
