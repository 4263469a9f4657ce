"""Leading-batch-axis grid transformation, shared by every Pallas kernel.

A stack of problems (operands carrying a leading batch axis) executes as
ONE pallas_call whose leading grid dimension is the batch width: every
BlockSpec gains a length-1 leading block indexed by the batch coordinate,
the grid/out_shape are prefixed with the width, and the new dimension is
``parallel`` (items are independent).  Kernels detect the extra axis via
their ``off`` parameter (grid-axis indices shift by one) and read/write
``ref[0]`` instead of ``ref[...]``.

Operands *shared* across the stack (a 2-D weight against a batched
activation) keep their original index map and block via the per-operand
``broadcast`` flags: every batch grid step reads the same weight tile, so
the stack executes without materialising a broadcast copy of the weight.

One implementation — gemm, symm, syrk/syr2k, and trmm all apply the same
transformation, and a divergent copy would compile but mis-index.
"""

from __future__ import annotations

__all__ = ["with_batch_axis"]


def with_batch_axis(batch, grid, in_maps, in_blocks, out_map, out_block,
                    semantics, out_shape, broadcast=None):
    """Prefix a leading batch grid dimension; identity when ``batch`` is
    None.  ``broadcast`` optionally flags, per input, operands shared
    (unbatched) across the stack — their maps/blocks pass through
    untouched.  Returns the transformed ``(grid, in_maps, in_blocks,
    out_map, out_block, semantics, out_shape)`` tuple."""
    if batch is None:
        return (grid, in_maps, in_blocks, out_map, out_block, semantics,
                out_shape)
    if broadcast is None:
        broadcast = (False,) * len(in_maps)
    in_maps = [(lambda bt, *gi, f=f: tuple(f(*gi))) if bc
               else (lambda bt, *gi, f=f: (bt,) + tuple(f(*gi)))
               for f, bc in zip(in_maps, broadcast)]
    in_blocks = [tuple(blk) if bc else (1,) + tuple(blk)
                 for blk, bc in zip(in_blocks, broadcast)]
    inner_out = out_map

    def batched_out(bt, *gi):
        return (bt,) + tuple(inner_out(*gi))

    return ((batch,) + tuple(grid), in_maps, in_blocks, batched_out,
            (1,) + tuple(out_block), ("parallel",) + tuple(semantics),
            (batch,) + tuple(out_shape))
