"""The operations and bytes a routed (mixture-of-experts) SwiGLU FFN
*requires*, from what the router decided: the number of live (token, expert)
pairs and the number of experts some pair touched, both counted by the
program per layer call and summed over the calls.

Conventions as in ``flops_bytes.py``: a multiply-add is 2 FLOPs, only matrix
multiplications are counted. A pair is multiplied by its expert's gate, up
and down matrices (3 x hidden x width multiply-adds). A touched expert's
three matrices are read once per layer call, whatever the number of pairs
on it; an expert nobody picked is not read. The rows of the pairs are read
and written three times (hidden in, 2 x width between, hidden out), which
at 8 pairs a token is under 2 % of the weight bytes of a decode step and is
counted too. The router matmul (hidden x experts a token) is 0.4 % of a
token's expert FLOPs and is left out, so a share is a slight under-estimate.
"""


def expert_params(c):
    """Weights of ONE expert (gate, up, down)."""
    return 3 * c["hidden_size"] * c["intermediate_size"]


def routed_ffn_work(c, pairs, experts_touched, bytes_per_el=2):
    """(FLOPs, bytes) of the grouped matmuls for ``pairs`` live (token,
    expert) pairs over ``experts_touched`` experts read (both summed over
    layer calls)."""
    flops = 2 * expert_params(c) * pairs
    rows = pairs * (2 * c["hidden_size"] + 2 * c["intermediate_size"])
    nbytes = (experts_touched * expert_params(c) + rows) * bytes_per_el
    return flops, nbytes


def expected_experts_touched(num_experts, picks):
    """Experts at least one of ``picks`` uniform picks lands on: the
    sanity figure of PERF.md (192 picks over 64 experts touch about 61)."""
    return num_experts * (1.0 - (1.0 - 1.0 / num_experts) ** picks)
