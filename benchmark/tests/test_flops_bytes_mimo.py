"""``flops_bytes_mimo`` against counts made by hand at the published widths
(the cell's 7 layers)."""
import flops_bytes_mimo as fb

CELL = {"hybrid_layer_pattern": [0, 1, 1, 1, 1, 1, 0], "hidden_size": 4096,
        "num_attention_heads": 64, "num_key_value_heads": 4,
        "swa_num_key_value_heads": 8, "head_dim": 192, "v_head_dim": 128,
        "moe_intermediate_size": 2048}


def test_layers_by_the_pattern():
    assert (fb.full_layers(CELL), fb.window_layers(CELL)) == (2, 5)
    whole = dict(CELL, hybrid_layer_pattern=[0, 1, 1, 1, 1, 0]
                 + [1, 1, 1, 1, 1, 0] * 7)
    assert (fb.full_layers(whole), fb.window_layers(whole)) == (9, 39)


def test_a_cached_token_costs_the_models_bytes():
    # 4 x (192 + 128) x 2 B in a full layer, 8 x 320 x 2 B in a window layer
    assert fb.kv_row_bytes(CELL, window=False) == 2560
    assert fb.kv_row_bytes(CELL, window=True) == 5120


def test_a_full_layers_decode_rows_are_bound_by_their_cache():
    # 32 decode rows at a mean context of 9,800
    pairs = kv = 32 * 9800
    flops, nbytes = fb.attention_work(CELL, pairs, kv, 32, window=False)
    assert flops == 2 * 2 * 64 * 320 * pairs
    assert nbytes == 2 * (kv * 2560 + 32 * 64 * 320 * 2)
    # ISSUE 56's arithmetic: 1.60 GB of KV a step, read ONCE for the 16
    # query heads a KV head serves; bound by the memory
    assert 1.60e9 < 2 * kv * 2560 < 1.61e9
    assert flops / 197e12 < 0.1 * nbytes / 819e9


def test_a_window_layer_needs_the_window_only():
    # 32 decode rows, every context past the window: 128 keys a row
    pairs = kv = 32 * 128
    flops, nbytes = fb.attention_work(CELL, pairs, kv, 32, window=True)
    assert flops == 5 * 2 * 64 * 320 * pairs
    assert nbytes == 5 * (kv * 5120 + 32 * 64 * 320 * 2)
    # ISSUE 56's arithmetic: 0.10 GB of rings a step
    assert 0.10e9 < 5 * kv * 5120 < 0.11e9


def test_a_chunk_is_bound_by_the_mxu():
    # one 512-token chunk 8,192 tokens into its prompt, a full layer
    pairs = 512 * 8192 + 512 * 513 // 2
    flops, nbytes = fb.attention_work(CELL, pairs, 8704, 512, window=False)
    assert flops / 197e12 > nbytes / 819e9
