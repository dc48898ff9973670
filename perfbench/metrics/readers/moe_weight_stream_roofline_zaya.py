"""``moe_weight_stream_roofline`` for a configuration whose keys are the
``zaya`` ones: ``moe_weight_stream_roofline_ms4.read`` as it is (the least time
to stream the weights of the experts that were HIT and the tokens' activations,
and to do the pairs' products, over the device time of the operations matching
``pattern``), over ``num_hidden_layers`` expert layers of ``num_experts`` held
experts at ONE pick a token. At 64 rows a decode step and 16 experts nearly
every expert is hit in every layer ((15/16)^64 = 1.6% that one is not), so the
least is close to streaming them all. A program without the attributes gives
nothing. Costs: perfbench/kernel_costs_zaya.py."""

from perfbench import kernel_costs_zaya as kz
from perfbench.metrics.readers import moe_weight_stream_roofline_ms4


def read(ctx, pattern):
    return moe_weight_stream_roofline_ms4.read(kz.with_mistral4_keys(ctx), pattern)
