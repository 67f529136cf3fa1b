"""``reference_jamba``: the whole forward against a token-by-token loop written
out by hand in numpy at a tiny size (the inner norms, the convolution's bias
and start, one KV head under several query heads, the attention layer's place
in the period), causality, the blocked attention against the unblocked one,
that the file imports nothing from ``paddle_tpu``, and the parameter count of
the published configuration from the program's own shapes."""
import json
import os

import jax.numpy as jnp
import numpy as np

import reference_jamba as ref

from conftest import BENCH


def test_imports_nothing_from_the_program():
    with open(os.path.join(BENCH, "reference_jamba.py")) as f:
        text = f.read()
    assert "import paddle_tpu" not in text and "from paddle_tpu" not in text


HID, NH, NKV, HD, INTER, VOCAB, C, N, RANK = 8, 4, 1, 2, 6, 11, 16, 4, 3
HYPER = {"num_heads": NH, "num_kv_heads": NKV, "head_dim": HD, "eps": 1e-6}


def _tiny(periods=2, before=2, after=1):
    rng = np.random.RandomState(0)

    def w(*shape):
        return jnp.asarray(0.3 * rng.randn(*shape), jnp.float32)

    def block():
        return {"ln1": 1.0 + w(periods, HID), "ln2": 1.0 + w(periods, HID),
                "w_gate": w(periods, HID, INTER),
                "w_up": w(periods, HID, INTER),
                "w_down": w(periods, INTER, HID)}

    def mamba():
        return {**block(), "ssm_in": w(periods, HID, 2 * C),
                "ssm_conv": w(periods, 4, C), "ssm_conv_b": w(periods, C),
                "ssm_x": w(periods, C, RANK + 2 * N),
                "ssm_dt_ln": 1.0 + w(periods, RANK),
                "ssm_b_ln": 1.0 + w(periods, N),
                "ssm_c_ln": 1.0 + w(periods, N),
                "ssm_dt": w(periods, RANK, C), "ssm_dt_b": w(periods, C),
                "ssm_A_log": w(periods, N, C), "ssm_D": w(periods, C),
                "ssm_out": w(periods, C, HID)}

    attn = {**block(), "wq": w(periods, HID, NH * HD),
            "wk": w(periods, HID, NKV * HD), "wv": w(periods, HID, NKV * HD),
            "wo": w(periods, NH * HD, HID)}
    return {"embed": w(VOCAB, HID), "final_norm": 1.0 + w(HID),
            "mamba_layers": (tuple(mamba() for _ in range(before)),
                             tuple(mamba() for _ in range(after))),
            "attn_layers": attn}


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _rms(x, w, eps=1e-6):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * w


def _by_hand(weights, ids):
    """The equations of the module docstring, a token at a time, in numpy
    float64: every Mamba layer keeps its state and its last three inputs,
    the attention layer every earlier token's key and value."""
    wt = {k: np.asarray(v, np.float64) for k, v in weights.items()
          if k in ("embed", "final_norm")}
    before, after = weights["mamba_layers"]
    periods = weights["attn_layers"]["wo"].shape[0]
    layers = []
    for p in range(periods):
        for kind, trees in (("m", before), ("a", [weights["attn_layers"]]),
                            ("m", after)):
            layers += [(kind, {k: np.asarray(v[p], np.float64)
                               for k, v in t.items()}) for t in trees]
    held = [dict(s=np.zeros((N, C)), tail=np.zeros((3, C)), k=[], v=[])
            for _ in layers]
    out = []
    for tok in ids:
        x = wt["embed"][tok]
        for (kind, w), h in zip(layers, held):
            hn = _rms(x, w["ln1"])
            if kind == "m":
                az = hn @ w["ssm_in"]
                a, z = az[:C], az[C:]
                window = np.concatenate([h["tail"], a[None]])
                h["tail"] = window[1:]
                c = _silu(w["ssm_conv_b"] + (w["ssm_conv"] * window).sum(0))
                xdb = c @ w["ssm_x"]
                r = _rms(xdb[:RANK], w["ssm_dt_ln"])
                b = _rms(xdb[RANK:RANK + N], w["ssm_b_ln"])
                cm = _rms(xdb[RANK + N:], w["ssm_c_ln"])
                dt = np.log1p(np.exp(r @ w["ssm_dt"] + w["ssm_dt_b"]))
                h["s"] = np.exp(dt[None] * -np.exp(w["ssm_A_log"])) * h["s"] \
                    + b[:, None] * (dt * c)[None]
                y = cm @ h["s"] + w["ssm_D"] * c
                x = x + (y * _silu(z)) @ w["ssm_out"]
            else:
                h["k"].append(hn @ w["wk"])
                h["v"].append(hn @ w["wv"])
                q = (hn @ w["wq"]).reshape(NH, HD)
                k, v = np.stack(h["k"]), np.stack(h["v"])      # one KV head
                o = []
                for n in range(NH):
                    s = k @ q[n] / np.sqrt(HD)
                    pr = np.exp(s - s.max())
                    o.append((pr / pr.sum()) @ v)
                x = x + np.concatenate(o) @ w["wo"]
            hn = _rms(x, w["ln2"])
            x = x + (_silu(hn @ w["w_gate"]) * (hn @ w["w_up"])) @ w["w_down"]
        out.append(_rms(x, wt["final_norm"]) @ wt["embed"].T)
    return np.stack(out)


def test_the_forward_equals_a_token_by_token_loop():
    weights = _tiny()
    ids = [1, 7, 3, 3, 9, 2, 10, 5, 4]
    got = np.asarray(ref.logits_at(weights, HYPER, np.asarray([ids]),
                                   np.arange(len(ids))[None]))[0]
    want = _by_hand(weights, ids)
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()


def test_the_attention_layers_place_is_the_trees():
    """The same weights with the attention layer one place early give other
    logits: the order is read from ``mamba_layers``' two runs."""
    weights = _tiny()
    before, after = weights["mamba_layers"]
    moved = dict(weights, mamba_layers=(before[:1], before[1:] + after))
    ids, at = np.asarray([[1, 7, 3, 3, 9]]), np.asarray([[4]])
    a = np.asarray(ref.logits_at(weights, HYPER, ids, at))
    b = np.asarray(ref.logits_at(moved, HYPER, ids, at))
    assert np.abs(a - b).max() > 1e-3 * np.abs(a).max()


def test_blocked_attention_equals_unblocked(monkeypatch):
    rng = np.random.RandomState(1)
    q, k, v = (jnp.asarray(rng.randn(11, h, HD), jnp.float32)
               for h in (NH, NKV, NKV))
    whole = np.asarray(ref.attention(q, k, v))
    monkeypatch.setattr(ref, "QUERY_BLOCK", 4)     # 11 = 2 blocks and 3 rows
    assert np.allclose(np.asarray(ref.attention(q, k, v)), whole, atol=1e-6)


def test_causality_of_the_whole_forward():
    weights = _tiny()
    a = np.asarray(ref.logits_at(weights, HYPER, np.asarray([[1, 2, 3, 4, 5]]),
                                 np.asarray([[2]])))
    b = np.asarray(ref.logits_at(weights, HYPER, np.asarray([[1, 2, 3, 9, 7]]),
                                 np.asarray([[2]])))
    assert np.allclose(a, b, atol=1e-6)


def _layer_params(jamba, c):
    """Parameters of one layer of each kind, from the program's own shapes
    (``_shapes``) and Mamba's float32 entries, which it builds beside them."""
    own = {"mamba": {"ssm_conv_b": (c.d_inner,), "ssm_dt_b": (c.d_inner,),
                     "ssm_A_log": (c.mamba_d_state, c.d_inner),
                     "ssm_D": (c.d_inner,)}, "attn": {}}
    shapes = jamba._shapes(c)
    return {kind: sum(int(np.prod(s)) for group in shapes[kind] + (own[kind],)
                      for s in group.values()) for kind in shapes}


def _total_params(c, layer):
    return c.num_ssm_layers * layer["mamba"] \
        + c.num_kv_layers * layer["attn"] \
        + c.vocab_size * c.hidden_size + c.hidden_size


def test_the_published_configuration_counts_3_029_337_472_parameters():
    """From the program's own shapes at the published keys (no allocation):
    the check on what the configuration file assumes."""
    from kinds import common
    from paddle_tpu.models import jamba
    with open(os.path.join(BENCH, "configs",
                           "jamba2-3b-serve-28L.json")) as f:
        cfg = json.load(f)
    c = jamba.JambaConfig(**common.model_keys(cfg))
    layer = _layer_params(jamba, c)
    assert layer == {"mamba": 104161472, "attn": 76682240}
    assert (c.num_ssm_layers, c.num_kv_layers) == (26, 2)
    assert _total_params(c, layer) == 3029337472
    # the tiny model's real parameters count the same way
    tiny = jamba.jamba_tiny()
    assert jamba.JambaForCausalLM(tiny).num_params() == _total_params(
        tiny, _layer_params(jamba, tiny))
