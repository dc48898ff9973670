"""``moe_load_max_over_mean`` for a configuration whose keys are the
``longcat_flash`` ones (``n_routed_experts`` held, one expert layer a double
layer): ``moe_load_max_over_mean_ms4.read`` as it is, over ``num_layers``
expert layers. A program without the attributes gives nothing."""

from perfbench import kernel_costs_longcat_flash as kl
from perfbench.metrics.readers import moe_load_max_over_mean_ms4


def read(ctx):
    return moe_load_max_over_mean_ms4.read(kl.with_mistral4_keys(ctx, kl.sparse_layers(ctx.config)))
