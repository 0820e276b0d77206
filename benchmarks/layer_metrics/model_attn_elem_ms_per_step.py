"""``model.attn_elem_ms_per_step``: device self time a step under the scope
``attn.elem``: what in an attention sub-layer is neither a weight product nor a
kernel (norms, rotary, reshapes, transposes, padding, slices, the causal
convolutions, the decay and the gates' activations, and what a kernel's
wrapper does around its call): the time in fusions that stand alone,
memory-bound passes and the layout copies that keep an ``op_name``.

Any phase (forward, backward and replay together), every attention kind of
the cell together; a fusion is booked to its root's ``op_name``
(``benchmarks/trace/parts.py``).  The prediction module's block and the dense
layer 0 of Laguna, Xing and Ling run the same sub-layers, so their parts are
in this sum: a cut across ``model.mtp_ms_per_step``, as that metric is across
``head`` + ``loss``."""

from benchmarks.trace import parts

NAME = "model.attn_elem_ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "model"
MOVES = "tokens_per_chip_s"
APPLIES = {}       # every cell that reports the metric it moves


def read(reading):
    return parts.part_ms_per_step(reading, "attn.elem")
