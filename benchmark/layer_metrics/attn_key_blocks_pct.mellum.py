"""``attn_key_blocks_pct``: ``attn.key_blocks_visited`` over
``attn.key_blocks_causal``, summed over the layers: about 33 in a sliding
layer at 8,192 positions and blocks of 512, about 50 over a period of three
sliding layers and a full one.  The size of the grids the kernels were
launched with (``flash_attention.key_blocks_visited``, which sizes them), not
a count the kernels keep: it says what the band was asked to save, and would
read the same if a kernel fetched every block.  ``flash_band_mfu_pct.mellum``,
the band kernels' counted operations over their traced time, is the metric
that a lost band moves."""

from benchmark.mellum_stats import attn_key_blocks_pct as read  # noqa: F401
