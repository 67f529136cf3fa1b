"""Trainer: model FLOP/s utilisation: ``train_tokens_per_s`` x required
FLOPs per token (``flops_bytes.train_flops_per_token``: forward + backward,
causal half, no recomputation) over chips x peak bf16 FLOP/s."""
import flops_bytes
import readers

tokens_per_s = readers.same_as("train_tokens_per_s")


def reduce(src):
    rate = tokens_per_s(src)
    if rate is None or "peaks" not in src:
        return None
    per_token = flops_bytes.train_flops_per_token(
        src["model"], src["mix"]["seq_len"])
    chips = src["config"]["chips"]
    return 100.0 * rate * per_token \
        / (chips * src["peaks"]["bf16_flops_per_s"])
