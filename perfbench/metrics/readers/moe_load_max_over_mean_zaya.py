"""``moe_load_max_over_mean`` for a configuration whose keys are the ``zaya``
ones (``num_experts`` held, every layer an expert layer, one pick a token):
``moe_load_max_over_mean_ms4.read`` as it is. At one pick of 16 and 64 tokens a
step the mean is 4 tokens an expert, so the fullest of 16 stands well above it.
A program without the attributes gives nothing."""

from perfbench import kernel_costs_zaya as kz
from perfbench.metrics.readers import moe_load_max_over_mean_ms4


def read(ctx):
    return moe_load_max_over_mean_ms4.read(kz.with_mistral4_keys(ctx))
