"""The work one Newton iteration needs, counted from shapes alone.

These counts are the yardstick of the roofline metrics: the least work the
algorithm needs, whichever path computes it, so a kernel that does more
than this (re-reads, recomputed encodes, materialised intermediates) reads
a lower share, never a higher one.  Operations count multiply-adds as two
and adds as one; bytes count float32 (4 bytes) reads and writes of HBM.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Tuple

F32 = 4
PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


@dataclasses.dataclass(frozen=True)
class Work:
    ops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.ops + other.ops, self.bytes + other.bytes)


def sketch_blocks(sketch: dict) -> Tuple[int, int, int]:
    """(K total blocks, N blocks that survive, b rows per block) of the
    configuration's OverSketch: N = sketch_dim / b, K = N + ceil(zeta N)."""
    b = int(sketch["block_size"])
    num = int(sketch["sketch_dim"]) // b
    return num + math.ceil(sketch["straggler_tolerance"] * num), num, b


def hessian(n: int, d: int, total_blocks: int, num_blocks: int,
            block_size: int) -> Work:
    """The sketched Hessian A^T S S^T A + lam I with A = sqrt(Lam/n) X.

    A is read once (4 n d bytes) and the d x d result written once.  The
    operations are hess_sqrt's (the margins X w, 2 n d, and the row scale,
    n d), the count-sketch scatter of every row of A into each of the K
    blocks (K n d adds), and the Gram of the N surviving blocks of b rows
    (2 N b d^2)."""
    ops = (3.0 * n * d + float(total_blocks) * n * d
           + 2.0 * num_blocks * block_size * d * d)
    return Work(ops=ops, bytes=F32 * (float(n) * d + float(d) * d))


def gradient(n: int, d: int) -> Work:
    """X w and X^T r, uncoded: two reads of X."""
    return Work(ops=4.0 * n * d, bytes=2.0 * F32 * n * d)


def line_search(n: int, d: int) -> Work:
    """X p for the trial points w + a p, with X w known: one read of X."""
    return Work(ops=2.0 * n * d, bytes=F32 * float(n) * d)


def direction(d: int) -> Work:
    """Cholesky of the d x d Hessian (d^3 / 3) and two triangular solves."""
    return Work(ops=d ** 3 / 3.0 + 2.0 * d * d, bytes=F32 * float(d) * d)


def iteration(n: int, d: int, total_blocks: int, num_blocks: int,
              block_size: int) -> Work:
    return (hessian(n, d, total_blocks, num_blocks, block_size)
            + gradient(n, d) + line_search(n, d) + direction(d))


def peaks(device_kind: str) -> dict:
    """The device's peaks from ``peaks.json``; an unknown device raises."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def least_time(work: Work, peak: dict) -> Tuple[float, str]:
    """(seconds, bound): the larger of ops over peak FLOP/s and bytes over
    peak bytes/s, and which of the two it was."""
    t_ops = work.ops / peak["flops_per_s"]
    t_bytes = work.bytes / peak["bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
