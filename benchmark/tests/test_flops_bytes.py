"""Counts worked by hand for one Mistral-7B-v0.3 layer."""
import flops_bytes as fb

MISTRAL = dict(hidden_size=4096, num_hidden_layers=32,
               num_attention_heads=32, num_key_value_heads=8,
               intermediate_size=14336, vocab_size=32768)


def test_one_layer_by_hand():
    # wq 4096x4096, wk and wv 4096x1024, wo 4096x4096, three 4096x14336
    by_hand = 16_777_216 + 2 * 4_194_304 + 16_777_216 + 3 * 58_720_256
    assert by_hand == 218_103_808
    assert fb.layer_matmul_params(MISTRAL) == by_hand
    assert fb.head_dim(MISTRAL) == 128


def test_whole_model_is_7_25_billion():
    # 32 x (218,103,808 + 8,192) + 2 x 134,217,728 + 4,096
    assert fb.param_count(MISTRAL) == 7_248_023_552


def test_training_flops_per_token_at_s4096():
    two = dict(MISTRAL, num_hidden_layers=2)
    dense = 2 * (2 * 218_103_808 + 4096 * 32768)          # 1,140,850,688
    attn = 2 * 4 * 4096 * 4097 / 2                         # 67,125,248
    assert fb.forward_flops_per_token(two, 4096) == dense + attn
    assert fb.train_flops_per_token(two, 4096) == 3 * (dense + attn)
    # causal attention is 7.1 % of a layer's required FLOPs at 4k (it
    # would be 13.3 % counted at the full square)
    per_layer_attn = 4 * 4096 * 4097 / 2
    share = per_layer_attn / (per_layer_attn + 2 * 218_103_808)
    assert 0.070 < share < 0.072


def test_flash_calls():
    # 64 folded heads (B=2 x 32), S=4096, D=128
    pairs = 64 * 4096 * 4097 / 2
    f, b = fb.flash_call("fwd", 64, 4096, 128)
    assert f == 4 * pairs * 128
    assert b == 4 * 64 * 4096 * 128 * 2 + 64 * 4096 * 4
    assert fb.flash_call("dkv", 64, 4096, 128)[0] == 8 * pairs * 128
    assert fb.flash_call("dq", 64, 4096, 128)[0] == 6 * pairs * 128


def test_classify_by_signature():
    q = "bf16[64,4096,128]"
    assert fb.classify_flash(f"{q}|f32[64,4096,1] <- {q},{q},{q}") == \
        ("fwd", 64, 4096, 128)
    assert fb.classify_flash(f"{q}|{q} <- {q},{q},{q},{q},f32[64,4096,1],"
                             "f32[64,4096,1]")[0] == "dkv"
    assert fb.classify_flash(f"{q} <- {q},{q},{q},{q},f32[64,4096,1],"
                             "f32[64,4096,1]")[0] == "dq"
    assert fb.classify_flash("f32[8] <- f32[8]") is None


def test_ragged_work_and_bounds():
    c = dict(MISTRAL, num_hidden_layers=16)
    # one decoded token over a context of 1000: memory-bound
    f, b = fb.ragged_work(c, [1000], [])
    assert f == 16 * 4 * 4096 * 1000
    assert b == 16 * (2 * 8 * 128 * 2) * 1000
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert fb.least_seconds(f, b, peaks)[1] == "memory"
    # one 2048-token prompt: compute-bound
    f, b = fb.ragged_work(c, [], [2048])
    assert f == 16 * 4 * 4096 * 2048 * 2049 / 2
    assert fb.least_seconds(f, b, peaks)[1] == "compute"
