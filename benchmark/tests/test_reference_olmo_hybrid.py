"""``reference_olmo_hybrid``: the delta rule on a two-token case computed by
hand, the 2 in ``beta``, the convolution at a sequence's start, and that the
file imports nothing from ``paddle_tpu``."""
import os

import jax.numpy as jnp
import numpy as np

import reference_olmo_hybrid as ref

from conftest import BENCH


def test_imports_nothing_from_the_program():
    with open(os.path.join(BENCH, "reference_olmo_hybrid.py")) as f:
        text = f.read()
    assert "import paddle_tpu" not in text and "from paddle_tpu" not in text


def test_delta_rule_two_tokens_by_hand():
    """One head, dk = dv = 2. Token 1: S0 = 0, so r = v1 and S = k1 (b1
    v1)^T. Token 2: S decays by e^g2, the read S^T k2 is subtracted from v2,
    the rest is written on k2."""
    k1, k2 = np.array([1.0, 0.0]), np.array([0.6, 0.8])
    v1, v2 = np.array([2.0, -1.0]), np.array([0.5, 3.0])
    q1, q2 = np.array([1.0, 1.0]), np.array([0.0, 2.0])
    b1, b2, g1, g2 = 0.5, 1.5, -0.1, -0.7
    s1 = np.outer(k1, b1 * v1)                     # e^g1 * 0 + k1 (b1 r1)^T
    o1 = s1.T @ q1
    s2 = np.exp(g2) * s1
    r2 = v2 - s2.T @ k2
    s2 = s2 + np.outer(k2, b2 * r2)
    o2 = s2.T @ q2
    assert np.allclose(o1, [1.0, -0.5])
    got = ref.delta_rule(
        jnp.asarray([[q1], [q2]], jnp.float32),
        jnp.asarray([[k1], [k2]], jnp.float32),
        jnp.asarray([[v1], [v2]], jnp.float32),
        jnp.asarray([[g1], [g2]], jnp.float32),
        jnp.asarray([[b1], [b2]], jnp.float32))
    assert np.allclose(np.asarray(got)[:, 0], [o1, o2], atol=1e-6)


def test_beta_2_on_a_unit_key_reflects():
    """beta = 2 on a key the state already holds: I - 2 k k^T reflects, so
    what was stored along k comes back negated (the negative eigenvalue)."""
    k = jnp.asarray([[[1.0, 0.0]], [[1.0, 0.0]]], jnp.float32)
    v = jnp.asarray([[[3.0, 4.0]], [[0.0, 0.0]]], jnp.float32)
    q = jnp.asarray([[[1.0, 0.0]], [[1.0, 0.0]]], jnp.float32)
    beta = jnp.asarray([[1.0], [2.0]], jnp.float32)
    out = np.asarray(ref.delta_rule(q, k, v, jnp.zeros((2, 1)), beta))
    assert np.allclose(out[0, 0], [3.0, 4.0])
    assert np.allclose(out[1, 0], [-3.0, -4.0])


def _tiny(neg_eigval):
    rng = np.random.RandomState(0)
    hid, heads, dk, dv, inter, vocab = 8, 2, 2, 4, 6, 11
    c = 2 * heads * dk + heads * dv

    def w(*shape):
        return jnp.asarray(0.3 * rng.randn(*shape), jnp.float32)

    lin = {"gdn_wqkv": w(1, hid, c), "gdn_wz": w(1, hid, heads * dv),
           "gdn_wab": w(1, hid, 2 * heads), "gdn_conv": w(1, 4, c),
           "gdn_A_log": w(1, heads), "gdn_dt_bias": w(1, heads),
           "gdn_o_norm": jnp.ones((1, dv)),
           "gdn_wo": w(1, heads * dv, hid), "w_gate": w(1, hid, inter),
           "w_up": w(1, hid, inter), "w_down": w(1, inter, hid),
           "attn_out_ln": jnp.ones((1, hid)),
           "ffn_out_ln": jnp.ones((1, hid))}
    weights = {"linear": (lin,), "embed": w(vocab, hid),
               "final_norm": jnp.ones((hid,)), "lm_head": w(hid, vocab),
               "wq": w(1, hid, hid), "wk": w(1, hid, hid),
               "wv": w(1, hid, hid), "wo": w(1, hid, hid),
               "q_norm": jnp.ones((1, hid)), "k_norm": jnp.ones((1, hid)),
               "w_gate": w(1, hid, inter), "w_up": w(1, hid, inter),
               "w_down": w(1, inter, hid), "attn_out_ln": jnp.ones((1, hid)),
               "ffn_out_ln": jnp.ones((1, hid))}
    hyper = {"num_heads": 2, "head_dim": 4, "eps": 1e-6, "lin_heads": heads,
             "dk": dk, "dv": dv, "neg_eigval": neg_eigval}
    return weights, hyper


def test_the_2_in_beta_moves_the_logits():
    ids = np.asarray([[1, 2, 3, 4, 5, 6]], np.int32)
    at = np.asarray([[5]], np.int32)
    w, with_2 = _tiny(True)
    _, without = _tiny(False)
    a = np.asarray(ref.logits_at(w, with_2, ids, at))
    b = np.asarray(ref.logits_at(w, without, ids, at))
    assert a.shape == (1, 1, 11) and np.isfinite(a).all()
    assert np.abs(a - b).max() > 1e-3


def test_convolution_at_a_sequences_start():
    """Rows before the start are zero: token 0 sees its own input times the
    LAST tap alone, token 3 all four."""
    u = jnp.asarray([[1.0, 10.0], [2.0, 20.0], [3.0, 30.0], [4.0, 40.0]])
    w = jnp.asarray([[0.1, 0.0], [0.2, 0.0], [0.3, 0.0], [0.4, 1.0]])
    got = np.asarray(ref.conv_silu(u, w))

    def silu(x):
        return x / (1.0 + np.exp(-x))

    assert np.allclose(got[0], silu(np.array([0.4 * 1.0, 10.0])))
    assert np.allclose(got[1], silu(np.array([0.3 * 1 + 0.4 * 2, 20.0])))
    assert np.allclose(
        got[3], silu(np.array([0.1 * 1 + 0.2 * 2 + 0.3 * 3 + 0.4 * 4, 40.0])))


def test_causality_of_the_whole_forward():
    """A later token does not move an earlier position's logits."""
    w, hyper = _tiny(True)
    a = np.asarray(ref.logits_at(w, hyper, np.asarray([[1, 2, 3, 4, 5]]),
                                 np.asarray([[2]])))
    b = np.asarray(ref.logits_at(w, hyper, np.asarray([[1, 2, 3, 9, 7]]),
                                 np.asarray([[2]])))
    assert np.allclose(a, b, atol=1e-6)
