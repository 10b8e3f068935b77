"""Hand-written Pallas kernels — the repo's kernel library.

XLA schedules most device compute well (elementwise chains fuse into
the surrounding program for free), so a kernel exists only where a
VMEM-resident formulation avoids HBM traffic the default lowering pays:

* :mod:`~mmlspark_tpu.ops.pallas.attention` — flash-style fused
  attention (online-softmax tiling): the serving-path attention of
  ``models/vit.py``, the local block of ``parallel/ring_attention.py``
  and the q_len=1 decode step of ``serve/generate.py``, replacing three
  HBM materializations of the ``[B, H, Tq, Tk]`` score matrix.

* :mod:`~mmlspark_tpu.ops.pallas.selective_scan` — the selective
  state-space recurrence of ``models/lm_ssm.py`` in chunks of the
  sequence, its ``[channels, states]`` state carried in VMEM instead of
  two ``[L, channels, states]`` tensors in HBM (imported from its module:
  the function has the module's name).

* :mod:`~mmlspark_tpu.ops.pallas.ssd_scan` — the Mamba-2 recurrence of
  ``models/lm_mamba2.py`` (one scalar decay a head a position) in its
  chunked matrix-product form: ``[x | B | C]`` read where they lie, a
  group's ``[state, heads x head_dim]`` state carried in VMEM, the decay
  matrices never in HBM. Its entry is one jitted function too.

* :mod:`~mmlspark_tpu.ops.pallas.causal_conv` — the short depthwise
  causal convolution of ``models/lm_conv.py``, ``models/lm_ssm.py`` and
  ``models/lm_mamba2.py`` with the gates, bias and activation around it:
  one read of each input position, taken from the wide float32 product
  where it lies, instead of a cut-out copy and one HBM read a tap. Its entry is one jitted
  function, so a model's call sites share a trace and a lowering.

* :mod:`~mmlspark_tpu.ops.pallas.moe_combine` — the combine of
  ``parallel/moe.moe_dropless``: each token's held picks read from the
  sorted expert buffer where it lies (the 8-row tiles a block of tokens'
  rows lie in, staged by DMA), weighted and summed in float32 to the bit
  of the array form, where that form is ``k`` row gathers over the whole
  buffer. One jitted entry.

(The fused GroupNorm kernel lives next to its reference in
``ops/group_norm.py``.)

Every kernel keeps one discipline: ONE shared body = Pallas kernel = XLA
reference = numpy oracle, the kernel pinned against the reference UNDER
JIT, compiled by Mosaic on the chip and interpreted only where a caller
asks for it (``pltpu.force_tpu_interpret_mode()`` — tier-1's
``pallas_interpret`` fixture), and a VMEM-budget miss that is logged and
counted (:mod:`~mmlspark_tpu.ops.pallas.budget`), never silent.
``chip_smoke.py`` compiles each of them at one deployment shape.
"""

from mmlspark_tpu.ops.pallas.attention import (
    attention_block_update, decode_attention, flash_attention,
    flash_attention_host, flash_attention_reference,
)

__all__ = [
    "attention_block_update", "decode_attention", "flash_attention",
    "flash_attention_host", "flash_attention_reference",
]
