"""The aggregation kernel's share of its roofline (%): the least time the
chip needs for the pass's aggregations (``bench/work.py``, from V, E and
the widths alone) over the kernel's summed device time in the trace."""

from bench import work

# the kernel's device operations carry this in their name
KERNEL = "spmm"


def kernel_ns(ops: dict) -> int:
    return sum(ns for name, ns in ops.items() if KERNEL in name)


def read(record):
    ns = kernel_ns(record["trace"]["ops"])
    if ns <= 0:
        return None
    cfg, peaks = record["config"], record["peaks"]
    least = work.aggregation_least_seconds(
        cfg["kind"], record["num_vertices"], record["num_edges"],
        cfg["dims"], peaks["flops_per_s"], peaks["bytes_per_s"])
    return 100.0 * least["seconds"] / (ns / 1e9)
