"""``tokens_per_forward``: tokens committed over row-forwards of either kind
(``diffusion.commit_forwards`` x 4 over ``denoise_forwards +
commit_forwards``), over the window: 4 / 3 at the floor of 2 denoise steps and
a commit a block, which random weights never leave."""

from benchmark.sdar_stats import tokens_per_forward as read  # noqa: F401
