"""Where the benchmark finds the typed fold, and the bytes it must move.

The typed fold (``repro.kernels.plan.plan_totals``: predicates on a typed
table's int32 codes, expression sums per group) must read its block once:
``rows x columns x 4`` bytes; its output, a few totals per group, is
negligible beside it.  In the device trace it is found by the array it
reads, ``s32[block_rows,columns]`` (see ``trace.op_seconds``), whatever
impl runs it on the device; its dispatches are counted by the program's
``rsp_kernel_runs_total{kernel="plan"}`` (in a typed cell, every plan call
is a typed fold)."""

KERNEL = "plan"


def fold_bytes(rows: int, columns: int) -> int:
    return rows * columns * 4


def operand(rows: int, columns: int) -> str:
    """The HLO type of an int32 ``[rows, columns]`` array."""
    return f"s32[{rows},{columns}]"
