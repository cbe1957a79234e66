"""Work of one application, counted from the graph and the operator.

A once-through lower bound that any implementation must pay, whatever
its kernels, block shape or layout: read the (B, N) signals and L's
values and column indices once, write the (B, eta, N) result once, and
do the recurrence's arithmetic (one multiply-add per non-zero of L per
order and signal, and one per order, multiplier and vertex for the
running sums).  Block-ELL padding, lane padding and re-reads of the
iterates are waste measured against this, never counted into it.
"""
import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def apply_bytes(n, nnz, batch, eta, itemsize=4):
    return n * batch * itemsize + nnz * 2 * 4 + eta * n * batch * itemsize


def apply_flops(n, nnz, batch, K, eta):
    return batch * (K * 2 * nnz + (K + 1) * eta * 2 * n)


def peaks(device_kind):
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def least_seconds(n, nnz, batch, K, eta, chips, device_kind):
    """The shortest time one apply could take on `chips` chips: the
    larger of its bytes over their HBM bandwidth and its operations over
    their peak rate."""
    p = peaks(device_kind)
    return max(apply_bytes(n, nnz, batch, eta) / (chips * p["hbm_bytes_per_s"]),
               apply_flops(n, nnz, batch, K, eta) / (chips * p["flops_per_s"]))
