"""Where the benchmark finds the program's kernels: one place, so a change
in the program is mended here once.

In the device trace a kernel is found by the array it reads (see
``trace.op_seconds``): a fold kernel (``block_sketch``, ``plan``, whatever
impl runs it on the device) reads one block, ``f32[block_rows,columns]``;
the shuffle (``rsp_shuffle``) reads one original block,
``f32[shuffle_rows,columns]``.  The permutation the shuffle draws first
reads neither and is not the shuffle's work.  Dispatches are counted by the
program's ``rsp_kernel_runs_total{kernel,impl}``.
"""

FOLD_KERNELS = ("block_sketch", "plan")
DEVICE_IMPLS = ("pallas", "jax")
SHUFFLE_KERNEL = "rsp_shuffle"


def operand(rows: int, columns: int) -> str:
    """The HLO type of a float32 ``[rows, columns]`` array."""
    return f"f32[{rows},{columns}]"
