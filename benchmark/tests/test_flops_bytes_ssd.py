"""``flops_bytes_ssd`` against counts made by hand at the published widths."""
import flops_bytes_ssd as fb

PUBLISHED = {"mamba_num_heads": 64, "mamba_head_dim": 64, "n_groups": 8,
             "ssm_state_size": 128, "chunk_size": 128, "hidden_size": 2688,
             "moe_intermediate_size": 1856}


def test_a_decode_row_is_bound_by_its_state():
    assert fb.state_bytes(PUBLISHED) == 2 * 2 ** 20
    ops, nbytes = fb.update_work(PUBLISHED, 32 * 23)
    assert ops == 32 * 23 * 5 * 64 * 64 * 128
    # state read and written; x and y (64 x 64), B and C (8 x 128), dt (64)
    assert nbytes == 32 * 23 * (4 * 2 ** 20 + 4 * (8192 + 2048 + 64))
    # ISSUE 47's count: 3.1 GB a decode step, 3.8 ms at 819 GB/s
    assert 3.08e9 < nbytes < 3.13e9
    assert 3.7e-3 < nbytes / 819e9 < 3.9e-3
    assert ops / 197e12 < 0.01 * nbytes / 819e9


def test_a_chunk_reads_its_state_once_and_is_matrix_products():
    flops, nbytes = fb.scan_work(PUBLISHED, 23 * 512, 23)
    # a token a head: (C B^T * L) X 2 Q P, the state read out and added to
    # 2 N P each; C B^T once a group, 2 Q N
    assert flops == 23 * 512 * (64 * (2 * 128 * 64 + 4 * 64 * 128)
                                + 8 * 2 * 128 * 128)
    assert nbytes == 23 * (512 * 4 * (8192 + 2048 + 64) + 4 * 2 ** 20)
    # the bytes bound it (0.6 ms a 512-token chunk against 0.4)
    assert flops / 197e12 < nbytes / 819e9


def test_an_expert_is_two_matrices():
    assert fb.expert_params(PUBLISHED) == 2 * 2688 * 1856 == 9977856
    flops, nbytes = fb.mlp_experts_work(PUBLISHED, 24 * 23, 13 * 23)
    assert flops == 2 * 9977856 * 24 * 23
    assert nbytes == 2 * (13 * 23 * 9977856 + 24 * 23 * 2 * (2688 + 1856))
    # ISSUE 47's count: about 5.8 GB of expert weights a step at 12.6 read
    assert 5.5e9 < 2 * 12.6 * 23 * 9977856 < 6.0e9
    # a SwiGLU's three matrices would read half as high again
    import flops_bytes_mla
    assert flops_bytes_mla.expert_params(PUBLISHED) * 2 == 3 * 9977856
