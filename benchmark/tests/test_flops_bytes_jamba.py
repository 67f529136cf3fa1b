"""``flops_bytes_jamba`` against counts made by hand at the published
widths."""
import flops_bytes_jamba as fb

PUBLISHED = {"num_hidden_layers": 28, "attn_layer_period": 14,
             "attn_layer_offset": 7, "hidden_size": 2560, "mamba_expand": 2,
             "mamba_d_state": 16, "num_attention_heads": 20,
             "num_key_value_heads": 1, "head_dim": 128}


def test_layers_by_period_and_offset():
    assert (fb.ssm_layers(PUBLISHED), fb.attn_layers(PUBLISHED)) == (26, 2)
    tiny = dict(PUBLISHED, num_hidden_layers=8, attn_layer_period=4,
                attn_layer_offset=2)
    assert (fb.ssm_layers(tiny), fb.attn_layers(tiny)) == (6, 2)


def test_a_chunk_reads_its_state_once():
    ops, nbytes = fb.recurrence_work(PUBLISHED, 512, 1)
    assert ops == 26 * 512 * 7 * 16 * 5120
    # the state [16, 5120] float32 read and written; dt, u, y rows and B, C
    assert nbytes == 26 * (2 * 327680 + 512 * 4 * (3 * 5120 + 32))
    # a 512-token chunk of 26 layers: 0.84 GB, 1.02 ms at 819 GB/s; its
    # vector operations at the MXU's peak a twentieth of that
    assert 1.01e-3 < nbytes / 819e9 < 1.03e-3
    assert ops / 197e12 < 0.05 * nbytes / 819e9


def test_a_decode_row_is_bound_by_its_state():
    ops, nbytes = fb.update_work(PUBLISHED, 8)
    assert nbytes == 26 * 8 * (2 * 327680 + 4 * (3 * 5120 + 32))
    assert ops == 26 * 8 * 7 * 16 * 5120
    # 8.125 MiB of state a slot over the 26 layers, read and written
    assert 26 * 327680 == 8.125 * 2 ** 20


def test_attention_counts_every_query_head_on_one_kv_heads_bytes():
    assert fb.kv_row_bytes(PUBLISHED) == 512
    # a 512-token chunk 16k into its prompt beside 8 decode rows at 16k
    pairs = 512 * 16384 + 512 * 513 // 2 + 8 * 16384
    kv = 16384 + 512 + 8 * 16384
    flops, nbytes = fb.attention_work(PUBLISHED, pairs, kv, 520)
    assert flops == 2 * 4 * 2560 * pairs
    assert nbytes == 2 * (kv * 512 + 2 * 520 * 2560 * 2)
    # the chunk is the MXU's (0.9 ms of 197 TFLOP/s over both layers), the
    # bytes a tenth of that
    assert 0.85e-3 < flops / 197e12 < 0.95e-3
    assert nbytes / 819e9 < 0.25 * flops / 197e12
    # no head_dim in the file: hidden / heads
    bare = {k: v for k, v in PUBLISHED.items() if k != "head_dim"}
    assert fb.attention_work(bare, pairs, kv, 520) == (flops, nbytes)
