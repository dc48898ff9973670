"""``moe_weight_stream_roofline`` for a configuration whose keys are the
``longcat_flash`` ones: ``moe_weight_stream_roofline_ms4.read`` as it is (the
least time to stream the weights of the held experts that were HIT and the
tokens' activations, and to do the held pairs' products, over the device time
of the operations matching ``pattern``), over ``num_layers`` expert layers of
``expert_ffn_hidden_size`` wide experts. At 12 of 768 with 16 held a held
expert is hit in about two decode steps in three, so the least is well below
streaming every held expert; the identity experts' pairs cost no matrix and
are not in it. A program without the attributes gives nothing."""

from perfbench import kernel_costs_longcat_flash as kl
from perfbench.metrics.readers import moe_weight_stream_roofline_ms4


def read(ctx, pattern):
    return moe_weight_stream_roofline_ms4.read(kl.with_mistral4_keys(ctx, kl.sparse_layers(ctx.config)), pattern)
