"""Model FLOP/s utilization of training, percent: the operations the forward
and backward passes need per token (6 per parameter in a matrix product, plus
causal attention's 6 * n_layer * seq * n_embd; recomputed operations do not
count) times tokens per second per chip, over the chip's bf16 peak."""

from perfbench import arith


def read(ctx):
    rate, _ = arith.train_rate(ctx.step_ends, ctx.tokens_per_step, *ctx.window)
    if rate is None:
        return None
    c = ctx.config
    E, L, V, S = c["n_embd"], c["n_layer"], c["vocab_size"], c["seq"]
    matmul_params = L * 12 * E * E + V * E          # blocks' four products and the tied head
    flops_per_token = 6 * matmul_params + 6 * L * S * E   # causal attention: half of 12*L*S*E
    return 100.0 * flops_per_token * rate / ctx.chips / ctx.peak.flops_bf16
