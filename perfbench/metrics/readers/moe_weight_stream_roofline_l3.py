"""``moe_weight_stream_roofline`` for a configuration whose keys are the
``bailing_hybrid`` ones (Ling-3.0-flash: ``num_experts`` held, every layer
after ``first_k_dense_replace`` an expert layer, 10 of the 12 kept, 8 picks a
token limited to 4 of 8 groups): ``moe_weight_stream_roofline_ms4.read`` as it
is, the held count under the name it reads
(perfbench/kernel_costs_ling3.with_mistral4_keys). What was hit and the pairs
held are the program's counts, whatever the routing's limit made them."""

from perfbench import kernel_costs_ling3 as kl
from perfbench.metrics.readers import moe_weight_stream_roofline_ms4


def read(ctx, pattern):
    return moe_weight_stream_roofline_ms4.read(kl.with_mistral4_keys(ctx), pattern)
