"""How the per-layer readers find the pattern kernels in a device trace.

The program's compact FFN kernels are Pallas calls; on a TPU each runs as
one ``tpu_custom_call`` operation whose instruction is named after its
Pallas function with XLA's numeric suffix (``rdp_matmul_cols.40``).  A
kernel counts only by those names, so a Pallas kernel added elsewhere
(attention, say) never enters the FFN kernels' time; ``unknown_kernels``
names any that match none, for the tests and for a reader of the trace.
"""
from __future__ import annotations

MARK = "tpu_custom_call"
# kernels/rdp_matmul.py (forward) and kernels/rdp_matmul_bwd.py (backward)
PATTERN_KERNELS = frozenset({
    "rdp_matmul_cols", "rdp_matmul_rows",
    "rdp_cols_dgrad", "rdp_cols_wgrad", "rdp_rows_dgrad", "rdp_rows_wgrad"})


def base_name(name: str) -> str:
    """An instruction's name without XLA's ``.<n>`` suffix."""
    head, dot, tail = name.rpartition(".")
    return head if dot and tail.isdigit() else name


def is_pattern_kernel(event: dict) -> bool:
    """Whether a device operation is one of the compact FFN kernels."""
    return (event.get("target") == MARK
            and base_name(event.get("name", "")) in PATTERN_KERNELS)


def unknown_kernels(events: list) -> set:
    """Names of the Pallas kernels in a trace that are not pattern kernels."""
    return {base_name(e["name"]) for e in events
            if e.get("kind") == "op" and e.get("target") == MARK
            and not is_pattern_kernel(e)}


def kernel_seconds(events: list) -> float:
    """Device seconds of the pattern kernels in the traced window."""
    import trace_reduce as TR
    return sum(TR.op_seconds(events, is_pattern_kernel).values())
