"""``flops_bytes_ssm`` against counts made by hand at the published widths
and against the program's own geometry at a tiny size."""
import os
import sys

import flops_bytes_ssm as fb

from conftest import BENCH

PUBLISHED = {"hidden_size": 2560, "num_hidden_layers": 32,
             "num_attention_heads": 40, "num_key_value_heads": 20,
             "sliding_window": 512}


def test_published_sizes():
    assert fb.d_inner(PUBLISHED) == 5120 and fb.d_state(PUBLISHED) == 16
    assert fb.ssm_layers(PUBLISHED) == 9
    assert fb.window_layers(PUBLISHED) == 8
    assert fb.token_ops(PUBLISHED) == 573440
    assert fb.state_bytes(PUBLISHED) == 327680
    assert fb.kv_row_bytes(PUBLISHED) == 5120


def test_a_decode_row_is_bound_by_its_state():
    ops, nbytes = fb.update_work(PUBLISHED, 48)
    assert ops == 9 * 48 * 573440
    # state read and written, three rows of d_inner and two of d_state
    assert nbytes == 9 * 48 * (2 * 327680 + 4 * (3 * 5120 + 32))
    # 0.28 GB a step: ISSUE 37's count
    assert 0.28e9 < nbytes < 0.32e9


def test_a_chunk_reads_its_state_once():
    ops, nbytes = fb.recurrence_work(PUBLISHED, 512, 1)
    assert ops == 9 * 512 * 573440
    assert nbytes == 9 * (2 * 327680 + 512 * 4 * (3 * 5120 + 32))


def test_window_rows():
    # 48 decode rows see 512 keys each in eight layers: ISSUE 37's 1.0 GB
    assert fb.window_bytes(PUBLISHED, 48 * 512) == 8 * 48 * 512 * 5120


def test_the_one_cache_is_read_by_eight_layers():
    # 48 rows of 3.1k tokens: ISSUE 37's 6.1 GB a decode step
    assert fb.cache_bytes(PUBLISHED, 48 * 3100) == 8 * 48 * 3100 * 5120
    assert 6.0e9 < fb.cache_bytes(PUBLISHED, 48 * 3100) < 6.2e9


def test_the_programs_own_geometry():
    sys.path.insert(0, os.path.dirname(BENCH))
    from paddle_tpu.models.phi4_flash import phi4_flash_tiny
    c = phi4_flash_tiny()
    doc = {"hidden_size": c.hidden_size,
           "num_hidden_layers": c.num_hidden_layers,
           "num_attention_heads": c.num_attention_heads,
           "num_key_value_heads": c.num_key_value_heads}
    assert fb.d_inner(doc) == c.d_inner
    assert fb.ssm_layers(doc) == c.num_ssm_layers
    assert fb.window_layers(doc) == c.num_window_layers
    assert fb.cache_readers(doc) == 1 + c.num_hidden_layers // 4 - 1
    assert fb.kv_row_bytes(doc, 4) == 2 * c.num_key_value_heads * c.head_dim * 4
