"""``mla_roofline`` for a configuration whose keys are the ``bailing_hybrid``
ones (Ling-3.0-flash): a latent attention kernel's share of its roofline over
the traced part of the window. The program's counts (``ds.serve.decode.
dispatch``'s / ``ds.serve.chunk``'s ``attended``) are ONE latent layer's, and
only one layer in ``layer_group_size`` is latent (2 of the 12 kept; the others
hold a matrix state and attend no row): ``mla_roofline.read`` as it is, over
that many layers at this file's widths (576 / 512 x 32 heads;
perfbench/kernel_costs_ling3.py). A program without the span or the attribute
gives nothing."""

from perfbench import kernel_costs_ling3 as kl
from perfbench.metrics.readers import mla_roofline


def read(ctx, pattern, kind):
    return mla_roofline.read(kl.with_mistral4_keys(ctx, kl.kinds(ctx.config).count("attn")), pattern, kind)
