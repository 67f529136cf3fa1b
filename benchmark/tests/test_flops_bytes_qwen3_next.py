"""``flops_bytes_qwen3_next`` against counts made by hand at the published
widths (the cell's 12 layers)."""
import flops_bytes_qwen3_next as fb

CELL = {"num_hidden_layers": 12, "full_attention_interval": 4,
        "hidden_size": 2048, "num_attention_heads": 16,
        "num_key_value_heads": 2, "head_dim": 256,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32,
        "linear_key_head_dim": 128, "linear_value_head_dim": 128,
        "moe_intermediate_size": 512}


def test_layers_by_the_interval():
    assert (fb.linear_layers(CELL), fb.full_layers(CELL)) == (9, 3)
    whole = dict(CELL, num_hidden_layers=48)
    assert (fb.linear_layers(whole), fb.full_layers(whole)) == (36, 12)


def test_a_decode_row_is_bound_by_its_state():
    ops, nbytes = fb.update_work(CELL, 128)
    # 32 x 128 x 128 float32 = 2 MiB a layer a row, in and out
    assert fb.state_bytes(CELL) == 2 * 2 ** 20
    # q, k ONCE at 16 heads of 128, v and o at 32 heads of 128, two gates a
    # value head; float32
    assert fb.token_bytes(CELL) == 4 * (2 * 16 * 128 + 2 * 32 * 128 + 64)
    assert nbytes == 9 * 128 * (2 * 2 * 2 ** 20 + 49408)
    assert ops == 9 * 128 * 32 * 7 * 128 * 128
    # ISSUE 54's arithmetic: 4.83 GB of state a step at 128 rows
    assert 4.82e9 < 9 * 128 * 2 * fb.state_bytes(CELL) < 4.84e9
    # q and k repeated to 32 heads would add 32 KiB a row a layer
    repeated = 4 * (2 * 32 * 128 + 2 * 32 * 128 + 64)
    assert repeated - fb.token_bytes(CELL) == 4 * 2 * 16 * 128
    # bound by the memory: 5.9 ms at 819 GB/s, the vector operations at the
    # MXU's peak a tenth of a percent of that
    assert 5.9e-3 < nbytes / 819e9 < 6.0e-3
    assert ops / 197e12 < 0.01 * nbytes / 819e9


def test_a_chunk_reads_its_state_once():
    ops, nbytes = fb.recurrence_work(CELL, 512, 1)
    assert nbytes == 9 * (2 * 2 * 2 ** 20 + 512 * 49408)
    assert ops == 9 * 512 * 32 * 114688
    # 0.27 GB: 0.32 ms of the memory, 0.086 ms of the MXU's peak
    assert 0.31e-3 < nbytes / 819e9 < 0.33e-3
    assert ops / 197e12 < nbytes / 819e9


def test_attention_counts_a_kv_heads_bytes_once_for_its_eight_query_heads():
    assert fb.kv_row_bytes(CELL) == 2048
    # 128 decode rows at a context of 2,200
    pairs = kv = 128 * 2200
    flops, nbytes = fb.attention_work(CELL, pairs, kv, 128)
    assert flops == 3 * 4 * 4096 * pairs
    assert nbytes == 3 * (kv * 2048 + 2 * 128 * 4096 * 2)
    # ISSUE 54's arithmetic: 1.73 GB of KV a step; bound by the memory
    assert 1.72e9 < 3 * kv * 2048 < 1.74e9
    assert flops / 197e12 < 0.05 * nbytes / 819e9


def test_the_touched_experts_are_the_stream():
    assert fb.expert_params(CELL) == 3 * 2048 * 512
    # 128 rows: 160 pairs over 64 held experts, 59 touched, 12 layers
    flops, nbytes = fb.experts_work(CELL, 12 * 160, 12 * 59)
    assert flops == 2 * 3145728 * 1920
    assert nbytes == 2 * (708 * 3145728 + 1920 * (4096 + 1024))
    # ISSUE 54's arithmetic: 4.44 GB of experts a step; the rows 0.4 % of it
    assert 4.44e9 < 2 * 708 * 3145728 < 4.46e9
    assert flops / 197e12 < 0.02 * nbytes / 819e9
