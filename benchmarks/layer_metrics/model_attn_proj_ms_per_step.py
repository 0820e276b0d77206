"""``model.attn_proj_ms_per_step``: device self time a step under the scope
``attn.proj``: every product of an attention sub-layer's normed input, or of a
latent's normed compression, with a weight that feeds the kernel or a gate
(``wq``, ``wk``, ``wv``; ``wq_a``, ``wq_b``, ``wkv_a``, ``wkv_b``; ``wf``,
``wb``; ``wg``), the weight's cast with it: the time in fusions rooted in such a
product, with whatever element-wise work XLA fused into them.

Any phase (forward, backward and replay together), every attention kind of
the cell together; a fusion is booked to its root's ``op_name``
(``benchmarks/trace/parts.py``).  The prediction module's block and the dense
layer 0 of Laguna, Xing and Ling run the same sub-layers, so their parts are
in this sum: a cut across ``model.mtp_ms_per_step``, as that metric is across
``head`` + ``loss``."""

from benchmarks.trace import parts

NAME = "model.attn_proj_ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "model"
MOVES = "tokens_per_chip_s"
APPLIES = {}       # every cell that reports the metric it moves


def read(reading):
    return parts.part_ms_per_step(reading, "attn.proj")
