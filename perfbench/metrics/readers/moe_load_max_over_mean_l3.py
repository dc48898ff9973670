"""``moe_load_max_over_mean`` for a configuration whose keys are the
``bailing_hybrid`` ones (Ling-3.0-flash): ``moe_load_max_over_mean_ms4.read`` as
it is, the held count under the name it reads: among the ``num_experts`` held
(one whole routing group), the fullest expert of a layer over the mean."""

from perfbench import kernel_costs_ling3 as kl
from perfbench.metrics.readers import moe_load_max_over_mean_ms4


def read(ctx):
    return moe_load_max_over_mean_ms4.read(kl.with_mistral4_keys(ctx))
