"""``mla_roofline`` for a configuration whose keys are the ``longcat_flash``
ones (``num_layers`` double layers, each of TWO cached sub-blocks): a latent
attention kernel's share of its roofline over the traced part of the window.
The program's counts (``ds.serve.decode.dispatch``'s / ``ds.serve.chunk``'s
``attended``) are a sub-block's, and there are ``2 x num_layers`` of those:
``mla_roofline.read`` as it is, over that many layers at this file's widths
(576 / 512 x 64 heads; perfbench/kernel_costs_longcat_flash.py). A program
without the span or the attribute gives nothing."""

from perfbench import kernel_costs_longcat_flash as kl
from perfbench.metrics.readers import mla_roofline


def read(ctx, pattern, kind):
    return mla_roofline.read(kl.with_mistral4_keys(ctx, kl.sub_blocks(ctx.config)), pattern, kind)
